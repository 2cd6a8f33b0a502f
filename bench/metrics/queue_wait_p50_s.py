"""Median queue wait (arrival to first reservation, plus a preempted
request's wait to its next) over every request that arrived in the window;
one that was never reserved counts as infinitely late. None where the
program records no queue wait."""

import math

from ._window import in_window, nearest_rank


def read(name, rec):
    reqs = in_window(rec)
    if not any("queue_s" in r for r in reqs):
        return None
    return nearest_rank([r.get("queue_s", math.inf) for r in reqs], 50)
