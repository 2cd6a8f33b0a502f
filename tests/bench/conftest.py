"""A tiny cell for the benchmark's CPU rehearsals: the chat configuration
and mix at reduced widths, in float32 (the program's CPU test dtype)."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# the comparison's limit for the float32 rehearsal: the program and the
# reference agree to float32 rounding (about 1e-6 of a logit) where nothing
# is wrong, while a bfloat16 path or a broken step misses by 1e-3 or more
TINY_GAP_LIMIT = 1e-4


def tiny_config(dtype="float32"):
    from bench import spec

    cfg = dict(spec.load_json(spec.BENCH_DIR / "configs" / "qwen1.5-0.5b.json"))
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4, head_dim=16,
               vocab_size=250, torch_dtype=dtype)
    cfg["serve"] = {"slots": 4, "hbm_budget_bytes": 2_000_000,
                    "max_inflight_prefills": 2}
    cfg["limits"] = {"served_logit_gap": TINY_GAP_LIMIT,
                     "served_logit_gap_mean": TINY_GAP_LIMIT}
    return cfg


def tiny_qwen3_config(dtype="float32", mesh=None):
    """The tiny configuration with Qwen3's shape of block: GQA (8 query
    heads on 4 KV heads), per-head q/k RMSNorm, no biases, an untied head;
    ``mesh`` = (data, model) adds a serving mesh."""
    cfg = tiny_config(dtype)
    cfg.update(arch="qwen3-14b", num_attention_heads=8,
               num_key_value_heads=4, head_dim=16, qkv_bias=False,
               qk_norm=True, tie_word_embeddings=False)
    if mesh is not None:
        cfg["serve"]["mesh"] = {"data": mesh[0], "model": mesh[1]}
    return cfg


def tiny_mix():
    from bench import spec

    mix = dict(spec.traffic_file("chat"))
    mix.update(arrivals={"rate_per_s": 4.0, "period_s": 0.5,
                         "burst_share": 0.5, "burst_factor": 2.0,
                         "backlog": 2},
               prompt={"median": 24, "sigma": 0.7, "min": 4, "max": 64},
               output={"median": 12, "sigma": 0.5, "min": 4, "max": 24},
               trace={"start_s": 0.2, "seconds": 0.3},
               check={"min_tokens": 40, "max_requests": 8})
    return mix


@pytest.fixture
def tiny_ctx():
    """A Ctx factory for the tiny chat cell (seconds, seed, dtype, trace)."""
    import time

    from bench import harness, spec

    def make(seconds=1.5, seed=2**33 + 7, dtype="float32", trace=False):
        bench = spec.benchmark()
        return harness.Ctx(bench=bench,
                           cell=spec.cell(bench, "qwen1.5-0.5b.chat"),
                           config=tiny_config(dtype), mix=tiny_mix(),
                           seed=seed, seconds=seconds, trace=trace,
                           t_start=time.perf_counter())
    return make
