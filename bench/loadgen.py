"""Seeded load: open-loop request schedules.

Every seed gets the same work, in another order. The requests are a fixed
set of (prompt length, output length) pairs: the quantiles of the mix's two
length distributions, paired once by a fixed stream that no seed changes.
The arrivals are a fixed set of periods, a fixed share of them bursts, each
with a fixed count of arrivals spaced evenly inside it. The seed decides
only which period bursts, which request arrives when, and the token ids.
So two seeds differ in order, not in load or in the set of gaps between
arrivals.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


# the stream that pairs prompt lengths with output lengths, the same for
# every seed
PAIRING_SEED = 0


@dataclass(frozen=True)
class Planned:
    """One request of a schedule: arrival on the engine clock (s), prompt
    token ids, output length."""

    rid: str
    arrival_s: float
    prompt: np.ndarray
    gen_len: int


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def lognormal_sizes(n: int, dist: dict) -> np.ndarray:
    """``n`` sizes at the quantiles (i + 0.5) / n of a lognormal with the
    given median and sigma, rounded and clipped to [min, max]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def arrival_times(rng: np.random.Generator, seconds: float, a: dict
                  ) -> np.ndarray:
    """Two-state modulated arrivals over [0, seconds): the window is cut
    into equal periods, a fixed share of them run at ``burst_factor`` times
    the base rate, the rest at the base rate, with mean ``rate_per_s``;
    a period's arrivals are spaced evenly inside it.
    ``backlog`` requests arrive at 0 on top."""
    n_per = max(int(round(seconds / a["period_s"])), 1)
    per = seconds / n_per
    n_burst = int(round(a["burst_share"] * n_per))
    base = a["rate_per_s"] / (1 - a["burst_share"]
                              + a["burst_share"] * a["burst_factor"])
    is_burst = np.zeros(n_per, bool)
    is_burst[rng.permutation(n_per)[:n_burst]] = True
    # fixed totals per state, spread over its periods as evenly as integers
    # allow; the seed decides which periods take the remainder
    counts = np.zeros(n_per, np.int64)
    for state, rate in ((True, base * a["burst_factor"]), (False, base)):
        idx = np.flatnonzero(is_burst == state)
        if not len(idx):
            continue
        total = int(round(rate * per * len(idx)))
        counts[idx] = total // len(idx)
        counts[rng.permutation(idx)[:total % len(idx)]] += 1
    times = [np.zeros(int(a.get("backlog", 0)))]
    for i, c in enumerate(counts):
        times.append(i * per + per * (np.arange(c) + 0.5) / c)
    return np.concatenate(times)


def open_loop(seed: int, seconds: float, mix: dict, vocab: int
              ) -> list[Planned]:
    """The cell's requests for one run: arrivals over the window, sizes
    from the mix, prompt ids uniform over the vocabulary."""
    rng = rng_for(seed, 1)
    t = arrival_times(rng, seconds, mix["arrivals"])
    n = len(t)
    pairs = np.stack([lognormal_sizes(n, mix["prompt"]),
                      lognormal_sizes(n, mix["output"])[
                          rng_for(PAIRING_SEED).permutation(n)]], 1)
    prompts, outputs = pairs[rng.permutation(n)].T
    tok = rng_for(seed, 2)
    return [Planned(rid=f"r{i:05d}", arrival_s=float(t[i]),
                    prompt=tok.integers(0, vocab, int(prompts[i])
                                        ).astype(np.int32),
                    gen_len=int(outputs[i]))
            for i in range(n)]
