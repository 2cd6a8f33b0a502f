"""The benchmark's load is a function of the seed: the same seed gives the
same requests, and every seed gives the same amount of work."""

import numpy as np
import pytest

from bench import loadgen, spec

BENCH = spec.benchmark()
SERVE_CELLS = [w for w in BENCH["workloads"]
               if spec.traffic_file(w["traffic"])["driver"]
               == "serve_open_loop"]
BIG_SEED = 2**32 + 12345


@pytest.mark.parametrize("cell", [w["name"] for w in SERVE_CELLS])
def test_open_loop_is_deterministic_in_the_seed(cell):
    w = spec.cell(BENCH, cell)
    mix = spec.traffic_file(w["traffic"])
    vocab = spec.config_file(BENCH, w["config"])["vocab_size"]
    a = loadgen.open_loop(BIG_SEED, 30.0, mix, vocab)
    b = loadgen.open_loop(BIG_SEED, 30.0, mix, vocab)
    c = loadgen.open_loop(BIG_SEED + 1, 30.0, mix, vocab)
    key = [(p.rid, p.arrival_s, p.gen_len, p.prompt.tobytes()) for p in a]
    assert key == [(p.rid, p.arrival_s, p.gen_len, p.prompt.tobytes())
                   for p in b]
    # another seed: other order and ids, the same work: the same pairs of
    # sizes and the same gaps between arrivals inside each period
    assert len(c) == len(a)
    assert sorted((len(p.prompt), p.gen_len) for p in c) == \
        sorted((len(p.prompt), p.gen_len) for p in a)
    assert [(len(p.prompt), p.gen_len) for p in c] != \
        [(len(p.prompt), p.gen_len) for p in a]
    period = mix["arrivals"]["period_s"]
    assert sorted(p.arrival_s % period for p in c) == \
        pytest.approx(sorted(p.arrival_s % period for p in a))
    for p in a:
        assert 0 <= p.arrival_s < 30.0
        assert mix["prompt"]["min"] <= len(p.prompt) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= p.gen_len <= mix["output"]["max"]
        assert p.prompt.min() >= 0 and p.prompt.max() < vocab


def test_arrivals_keep_the_mean_rate_and_the_bursts():
    a = {"rate_per_s": 5.0, "period_s": 1.0, "burst_share": 0.1,
         "burst_factor": 4.0, "backlog": 3}
    t = loadgen.arrival_times(loadgen.rng_for(1), 40.0, a)
    assert abs(len(t) - 3 - 5.0 * 40) <= 1
    assert (t[:3] == 0).all()
    per_second = np.bincount(t[3:].astype(int), minlength=40)
    base = 5.0 / (0.9 + 0.4)
    assert per_second.max() >= round(4 * base) - 1     # 4 bursty periods
    assert np.median(per_second) <= base + 1


def test_lognormal_sizes_are_quantiles():
    s = loadgen.lognormal_sizes(101, {"median": 192, "sigma": 0.75,
                                      "min": 16, "max": 1024})
    assert s[50] == 192 and (np.diff(s) >= 0).all()
    assert s.min() >= 16 and s.max() <= 1024
