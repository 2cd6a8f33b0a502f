"""The comparisons that decide ``correct``: what the timed path produced
against the plain float32 reference, run after the window has closed.

For a sample of the served requests, drawn from the seed and holding the
longest context and the most served tokens, the reference runs once over
each prompt followed by its served tokens. At every position where the engine served a token, the
gap is how far that token's reference logit lies below the reference's
best. Two numbers are compared: the widest gap, and the mean gap over
every served position. Greedy decoding serves the argmax of the engine's
own logits, so a gap is 0 except at near ties, where it stays within the
engine's rounding error. The widest gap bounds one position; the mean gap
grows about as the square of the rounding error (how often a tie flips
times by how much), so it tells one precision from the next where the
widest gap alone does not. The control runs the same reference in a lower
precision and reads the gaps of the tokens that precision puts first.
"""

from __future__ import annotations

import numpy as np

from .loadgen import rng_for

MIN_REQUESTS = 3


def sample_served(served: list[dict], seed: int, size: dict) -> list[dict]:
    """The request with the longest context (prompt and served tokens: the
    most pages in the decode kernel) and the one that served the most
    tokens, then others in an order drawn from the seed, until
    ``size["min_tokens"]`` served tokens and MIN_REQUESTS requests are
    covered, and at most ``size["max_requests"]``."""
    if not served:
        return []

    def longest(key):
        return max(served, key=lambda r: (key(r), r["rid"]))

    out = [longest(lambda r: len(r["prompt"]) + len(r["served"]))]
    most = longest(lambda r: len(r["served"]))
    if most is not out[0]:
        out.append(most)
    rest = [r for r in served if not any(r is o for o in out)]
    rest = [rest[i] for i in rng_for(seed, 4).permutation(len(rest))]
    n = sum(len(r["served"]) for r in out)
    for r in rest:
        if (n >= size["min_tokens"] and len(out) >= MIN_REQUESTS) \
                or len(out) >= size["max_requests"]:
            break
        out.append(r)
        n += len(r["served"])
    return out


def check_served(params, dims, sample: list[dict], quant=None) -> dict:
    """Run the reference over each sampled request. Returns the widest and
    the mean gap of the served tokens and, with ``quant``, of the control's
    picks."""
    import jax.numpy as jnp

    from .reference import dense_lm

    widest, widest_ctl, total, total_ctl, tokens = 0.0, 0.0, 0.0, 0.0, 0
    for r in sample:
        served = np.asarray(r["served"], np.int32)
        seq = np.concatenate([r["prompt"], served[:-1]]).astype(np.int32)
        # row prompt_len - 1 + j predicts served token j; every array keeps
        # the padded length, so one program serves every request
        rows = slice(len(r["prompt"]) - 1, len(seq))
        h = dense_lm.hidden(params, seq, dims)
        ids = np.zeros(h.shape[0], np.int32)
        ids[rows] = served
        best, _ = dense_lm.head_stats(h, params, dims)
        got = dense_lm.token_logits(h, params, jnp.asarray(ids), dims)
        gap = np.asarray(best - got)[rows]
        widest, total = max(widest, float(gap.max())), total + float(gap.sum())
        tokens += len(served)
        if quant is not None:
            low = dense_lm.hidden(params, seq, dims, quant)
            _, pick = dense_lm.head_stats(low, params, dims, quant)
            ctl = dense_lm.token_logits(h, params, pick, dims)
            gap = np.asarray(best - ctl)[rows]
            widest_ctl = max(widest_ctl, float(gap.max()))
            total_ctl += float(gap.sum())
    n = max(tokens, 1)
    out = {"served_logit_gap": widest, "served_logit_gap_mean": total / n,
           "tokens": tokens, "requests": len(sample)}
    if quant is not None:
        out["control_logit_gap"] = widest_ctl
        out["control_logit_gap_mean"] = total_ctl / n
    return out
