"""Benchmark-driven adaptive variant selection GPO — BEYOND PAPER.

Paper §4.2: *"we recommend benchmarking all variants within the generation
process and choosing the best-performing one [...] benchmarking alongside
adaptive variant selection should be integrated as an ongoing process."*
The paper leaves this as future work; we implement it.

For every primitive with >1 valid candidate and a ``bench`` setup in its UPD,
each candidate body is stage-1 rendered, exec'd into a scratch namespace,
jit-compiled, and timed on the live host. Measured winners override the flag
heuristic (``Selection.reason == "bench"``). Winners live in the unified
artifact cache, content-addressed by (UPD fingerprint, target, probed
hardware flags, generator version) — moving the cache to different hardware
or editing the corpus re-benchmarks automatically, editing nothing makes
repeated generation free ("ongoing process").
"""

from __future__ import annotations

import time

from . import engine, hwprobe
from .cache import ArtifactCache
from .model import GenerationResult, Selection
from .select import hardware_flags, score, valid_candidates

_PRELUDE = (
    "import jax\nimport jax.numpy as jnp\nimport numpy as np\nfrom jax import lax\n"
)


def _bench_store(ctx: GenerationResult) -> ArtifactCache:
    from .library import DEFAULT_BUILD_ROOT, artifact_key, resolve_store

    key = artifact_key(ctx.config, ctx.meta.get("fingerprint", "x"),
                       ctx.corpus)
    store, _ = resolve_store(ctx.config, key,
                             ctx.config.build_root or DEFAULT_BUILD_ROOT)
    return store


def _compile_candidate(ctx: GenerationResult, prim, impl, ctype: str):
    """exec a candidate implementation into a scratch module namespace."""
    sru = ctx.targets[impl.target_extension].as_render_dict()
    body = engine.render_stage1(impl.implementation, sru=sru, ctype=ctype,
                                primitive=prim.name, params=prim.arg_names())
    helpers = ""
    if impl.helpers.strip():
        helpers = engine.render_stage1(impl.helpers, sru=sru, ctype=ctype,
                                       primitive=prim.name, params=prim.arg_names())
    sig = prim.signature()
    indented = "\n".join("    " + ln if ln.strip() else ln
                         for ln in body.splitlines())
    src = f"{_PRELUDE}\n{helpers}\n\ndef __cand__({sig}):\n{indented}\n"

    class _Tgt:  # minimal TARGET stand-in for helper code
        pass

    for k, v in sru.items():
        setattr(_Tgt, k, v)
    ns: dict = {"TARGET": _Tgt}
    exec(src, ns)  # noqa: S102 — trusted UPD, same trust domain as the repo
    return ns["__cand__"]


def _time_candidate(fn, args: dict, n_iter: int) -> float:
    import jax

    jfn = jax.jit(fn)
    out = jfn(**args)
    jax.block_until_ready(out)  # compile + warmup
    t0 = time.perf_counter()
    for _ in range(n_iter):
        out = jfn(**args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n_iter


class BenchSelectGPO:
    name = "bench-select"

    def run(self, ctx: GenerationResult) -> GenerationResult:
        if ctx.errors:
            return ctx
        tgt = ctx.targets[ctx.config.target]
        if not hwprobe.runnable(tgt):
            ctx.warn("bench-select: target does not run on this host; skipped")
            return ctx
        # on the live device's own target a candidate that fails is a fault
        # in the library that device runs, not a slow candidate
        live = tgt.name == hwprobe.live_target()
        hw = hardware_flags(ctx)
        from .library import artifact_key

        store = _bench_store(ctx)
        store_key = artifact_key(ctx.config, ctx.meta.get("fingerprint", "x"),
                                 ctx.corpus)
        cache = store.bench_load(store_key)

        for name, sels in ctx.selection.items():
            prim = ctx.primitives[name]
            if prim.bench is None:
                continue
            for ctype in list(sels):
                cands = valid_candidates(prim, ctx.config.target, ctype, hw)
                if len(cands) < 2:
                    continue
                key = f"{name}/{ctype}"
                # smoke mode: one timed iteration — exercises the full
                # compile+measure path without the measurement cost (CI)
                n_iter = 1 if ctx.config.bench_smoke else prim.bench["n_iter"]
                cached = cache.get(key)
                # a cached winner measured with FEWER iterations than requested
                # is stale (a smoke sweep must never pin real selection)
                if cached is not None and cached.get("n_iter", 0) >= n_iter:
                    winner_idx = cached["winner"]
                else:
                    # build sample inputs from the UPD bench setup
                    sru = tgt.as_render_dict()
                    setup_src = engine.render_stage1(
                        prim.bench["setup"], sru=sru, ctype=ctype,
                        primitive=name, params=prim.arg_names())
                    ns: dict = {}
                    exec(_PRELUDE + "\n" + setup_src, ns)  # noqa: S102
                    args = ns["args"]
                    times = []
                    for impl in cands:
                        try:
                            fn = _compile_candidate(ctx, prim, impl, ctype)
                            t = _time_candidate(fn, args, n_iter)
                        except Exception as e:
                            msg = (f"bench-select {key}: candidate "
                                   f"def[{prim.definitions.index(impl)}] "
                                   f"failed ({e})")
                            if live:
                                ctx.fail(msg)
                            else:   # an emulated dialect: rank it last
                                ctx.warn(msg)
                            t = float("inf")
                        times.append(t)
                    winner_idx = prim.definitions.index(
                        cands[times.index(min(times))])
                    cache[key] = {
                        "winner": winner_idx,
                        "times_us": [t * 1e6 for t in times],
                        "candidates": [prim.definitions.index(c) for c in cands],
                        "n_iter": n_iter,
                    }
                impl = prim.definitions[winner_idx]
                if sels[ctype].impl is not impl:
                    sels[ctype] = Selection(
                        primitive=name, target=ctx.config.target, ctype=ctype,
                        impl=impl, score=score(impl, hw),
                        candidates=len(cands), reason="bench",
                    )
                else:
                    sels[ctype].reason = "bench"
        ctx.meta["bench_cache"] = str(store.bench_store(store_key, cache))
        return ctx
