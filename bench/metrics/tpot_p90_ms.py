"""90th percentile, over the completed requests that arrived in the window,
of the mean time per output token after the first:
(latency - ttft) / (tokens out - 1), in milliseconds."""

from ._window import in_window, nearest_rank


def read(name, rec):
    xs = [1e3 * (r["latency_s"] - r["ttft_s"]) / (r["tokens_out"] - 1)
          for r in in_window(rec) if r.get("tokens_out", 0) >= 2]
    return nearest_rank(xs, 90) if xs else None
