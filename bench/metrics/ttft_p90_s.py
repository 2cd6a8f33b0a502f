"""90th percentile of time to first token over every request that arrived
in the window, from its scheduled arrival; one that never got a first
token counts as infinitely late."""

import math

from ._window import in_window, nearest_rank


def read(name, rec):
    return nearest_rank([r.get("ttft_s", math.inf) for r in in_window(rec)],
                        90)
