"""90th percentile (nearest rank) of the gaps between consecutive tokens
reaching the host, over the completed requests of the window with at least
2 tokens, in milliseconds. None where the program stamps no tokens."""

from ._window import in_window, nearest_rank


def read(name, rec):
    gaps = [b - a for r in in_window(rec) if r.get("tokens_out", 0) >= 2
            for a, b in zip(r.get("token_s", ()), r.get("token_s", ())[1:])]
    return 1e3 * nearest_rank(gaps, 90) if gaps else None
