"""Share of the decode time that ``tpot_mean_ms`` divides which requests
spent parked (their first token held, no lane free to decode in): 100 x
sum of park_s / sum of (latency - ttft), over the completed requests of the
window with at least 2 tokens, in percent. None where the program records
no parked time."""

from ._window import in_window


def read(name, rec):
    done = [r for r in in_window(rec)
            if r.get("tokens_out", 0) >= 2 and "park_s" in r]
    decode_s = sum(r["latency_s"] - r["ttft_s"] for r in done)
    if not done or decode_s <= 0:
        return None
    return 100.0 * sum(r["park_s"] for r in done) / decode_s
