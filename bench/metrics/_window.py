"""Helpers the readers share: what happened inside the measured window."""

from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The q-th percentile by nearest rank (a value that was observed);
    missing values count as infinite."""
    xs = sorted(values)
    if not xs:
        return math.nan
    return xs[max(math.ceil(q / 100 * len(xs)) - 1, 0)]


def in_window(rec: dict) -> list[dict]:
    """Requests that arrived inside the window."""
    return [r for r in rec["requests"] if r["arrival_s"] < rec["seconds"]]
