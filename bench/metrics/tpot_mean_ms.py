"""Mean time per output token after the first, over all the work of the
window: the decode time of every completed request that arrived in the
window, summed, over the tokens it served after its first, in
milliseconds."""

from ._window import in_window


def read(name, rec):
    done = [r for r in in_window(rec) if r.get("tokens_out", 0) >= 2]
    if not done:
        return None
    return 1e3 * (sum(r["latency_s"] - r["ttft_s"] for r in done)
                  / sum(r["tokens_out"] - 1 for r in done))
