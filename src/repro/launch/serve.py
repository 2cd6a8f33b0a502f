"""Serving launcher: thin CLI over repro.serve.ServeEngine (per-step
continuous batching with chunked prefill — prompts are padded to
UPD-declared length buckets, prefill advances one fixed-size chunk per
unified step alongside decode, admission is cost-model gated, and sampling
is configurable). Slot memory is paged, and KV-family decode reads the page
pools directly (fused paged attention).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
        --batch 4 --prompt-len 32 --gen-len 32 --requests 8 \
        --temperature 0.8 --top-k 40 --sla-ms 500 --prefill-chunk 8
"""

from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.serve import (BucketPolicy, PagedConfig, Request, SamplingConfig,
                         ServeEngine)


def build_engine(cfg, *, batch: int, prompt_len: int, gen_len: int,
                 sampling: SamplingConfig, seed: int = 0,
                 prefill_chunk: int | None = None, mesh=None) -> ServeEngine:
    """The launcher's engine: paged fused slot memory, a slot table sized
    for ``prompt_len`` + ``gen_len``, optionally sharded over ``mesh``."""
    # budget the slot table for the decode prefix (vlm vision rows) AND the
    # length bucket the prompt pads to, or admission would refuse every
    # request by construction; a prompt beyond the largest declared bucket
    # extends the bucket set (rounded to whole chunks) instead of refusing
    policy = BucketPolicy.from_upd(chunk=prefill_chunk)
    bucket = policy.assign(prompt_len)
    buckets = None
    if bucket is None:
        bucket = BucketPolicy.round_up(prompt_len, policy.chunk)
        buckets = policy.buckets + (bucket,)
    return ServeEngine(
        cfg, batch=batch, max_len=cfg.decode_prefix + bucket + gen_len,
        sampling=sampling, seed=seed, prefill_chunk=prefill_chunk,
        buckets=buckets, paged=PagedConfig(), mesh=mesh,
        enc_len=prompt_len if cfg.family == "audio" else None)


def make_requests(cfg, n: int, *, prompt_len: int, gen_len: int,
                  seed: int = 0, sla_s: float | None = None) -> list[Request]:
    """``n`` requests of random prompt tokens drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return [
        Request(rid=f"req{i}",
                tokens=rng.integers(0, cfg.vocab, prompt_len).astype(np.int32),
                gen_len=gen_len, sla_s=sla_s)
        for i in range(n)
    ]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="slot-table size (decode batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy argmax")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation (0 = full distribution)")
    ap.add_argument("--sla-ms", type=float, default=None,
                    help="per-request end-to-end deadline; feeds both "
                         "cost-model admission and the hit-rate report")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill tokens per unified step (default: the "
                         "UPD-declared serve chunk; declared buckets round "
                         "up to whole chunks)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # serving different archs in one process: drop jit caches so recycled
    # function ids from a previous model cannot alias stale executables
    jax.clear_caches()

    engine = build_engine(
        cfg, batch=args.batch, prompt_len=args.prompt_len,
        gen_len=args.gen_len,
        sampling=SamplingConfig(temperature=args.temperature,
                                top_k=args.top_k),
        seed=args.seed, prefill_chunk=args.prefill_chunk)
    sla_s = args.sla_ms / 1e3 if args.sla_ms is not None else None
    requests = make_requests(cfg, args.requests, prompt_len=args.prompt_len,
                             gen_len=args.gen_len, seed=args.seed,
                             sla_s=sla_s)

    report = engine.run(requests)
    first = report["outputs"].get("req0", [])
    result = {
        "arch": cfg.name,
        "requests": report["requests"],
        "decode_tokens_per_s": report["decode_tokens_per_s"],
        "ttft_s_mean": report["ttft_s_mean"],
        "sla_hit_rate": report["sla_hit_rate"],
        "padded_slot_steps_steady": report["padded_slot_steps_steady"],
        "prefill_chunk": report["prefill_chunk"],
        "buckets": report["buckets"],
        "ttft_by_bucket": report["ttft_by_bucket"],
        "refused": report["refused"],
        "sample_output": first[:8],
    }
    print("[serve] done:", json.dumps(result))
    return result


if __name__ == "__main__":
    main()
