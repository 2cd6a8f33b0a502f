"""Open-loop serving: requests arrive on a schedule drawn from the seed,
whether or not earlier ones have finished, and the engine serves them.

Set-up builds the engine the serving launcher builds (paged, fused, the
bound library, greedy sampling), over the configuration's mesh where it
names one, hands it weights drawn from the seed (on a mesh, into the
shardings of the engine's own parameters, so the engine must build those
as it serves them), and warms up every shape the window will run. The
window is the engine's own ``run`` over the schedule: arrivals cover
``--seconds`` seconds from the engine's clock start, and the engine serves
every arrival to its end (a tail counts every request). Then the engine is
freed and the reference checks a sample of what it served, on weights laid
out as the engine's were (on a mesh, no chip ever holds the whole model).
"""

from __future__ import annotations

import contextlib
import gc
import json
import threading
import time

import jax
import numpy as np

from .. import check, loadgen, spans, weights
from ..harness import Ctx, check_entry, device_info
from ..spec import Dims, arch_config
from ..trace import Tracer


class CompileCounter:
    """Counts, while armed (the window), XLA compilations and programs
    loaded from the persistent cache (either is a program the warm-up did
    not cover), and JAX traces (re-tracing a primitive that is already
    compiled, which costs host time but compiles nothing)."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.armed = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if self.armed:
            self.traces += event == self.TRACE
            self.compiles += event == self.COMPILE

    def _on_event(self, event, **kw):
        if self.armed:
            self.compiles += event == self.CACHE_HIT

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_dur)
        jax.monitoring.unregister_event_listener(self._on_event)


def build_engine(cfg, serve: dict, mix: dict, seed: int, mesh=None):
    """The launcher's engine (``launch/serve.build_engine``) with the
    configuration's page budget and prefill concurrency, over ``mesh``
    where one is given: the slot table is sized for the mix's longest
    prompt, padded to its bucket, plus its longest output."""
    from repro.serve import (BucketPolicy, PagedConfig, SamplingConfig,
                             ServeEngine)

    policy = BucketPolicy.from_upd()
    max_prompt, max_gen = mix["prompt"]["max"], mix["output"]["max"]
    bucket = policy.assign(max_prompt)
    buckets = None
    if bucket is None:
        bucket = BucketPolicy.round_up(max_prompt, policy.chunk)
        buckets = policy.buckets + (bucket,)
    return ServeEngine(
        cfg, batch=serve["slots"],
        max_len=cfg.decode_prefix + bucket + max_gen,
        sampling=SamplingConfig(), seed=seed, buckets=buckets,
        paged=PagedConfig(
            hbm_budget_bytes=serve.get("hbm_budget_bytes"),
            max_inflight_prefills=serve.get("max_inflight_prefills")),
        mesh=mesh)


def served_requests(plan, served: dict) -> list[dict]:
    """Every request that served a token, with its prompt and the tokens it
    served."""
    return [{"rid": p.rid, "prompt": p.prompt,
             "served": np.asarray(served[p.rid], np.int64)}
            for p in plan if served.get(p.rid)]


def warm_up(engine, plan) -> None:
    """Compile every shape the window runs: the engine's steps through a
    short run, then the page-store copies for every prompt length of the
    schedule (the store slices a finished prefill by its length)."""
    from repro.serve import Request

    lens = sorted({len(p.prompt) for p in plan})
    picks = [lens[0], lens[len(lens) // 2], lens[-1]]
    engine.run([Request(rid=f"warm{i}", tokens=plan[0].prompt[:1].repeat(n),
                        gen_len=3) for i, n in enumerate(picks)])
    store = engine._store
    # the page-store writes run under the mesh, as the engine's loop runs
    # them, so they compile the programs the window uses
    with (jax.set_mesh(engine.mesh) if engine.mesh is not None
          else contextlib.nullcontext()):
        donor = engine._new_donor()
        for n in lens:
            rid = f"warm-store-{n}"
            store.attach(rid, prompt_rows=engine.cfg.decode_prefix + n)
            store.store_donor(rid, donor, fill=engine.cfg.decode_prefix + n)
            store.free(rid)
    jax.block_until_ready(store.device_pools())


def run(ctx: Ctx) -> dict:
    from repro.serve import Request

    dims = Dims.from_config(ctx.config)
    cfg = arch_config(ctx.config)
    serve, mix = ctx.config["serve"], ctx.mix
    plan = loadgen.open_loop(ctx.seed, ctx.seconds, mix, dims.vocab)

    mesh = None
    if ctx.mesh_shape is not None:
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(*ctx.mesh_shape)
    engine = build_engine(cfg, serve, mix, ctx.seed, mesh)
    weights.check_layout(dims, jax.eval_shape(engine.model.init,
                                              jax.random.PRNGKey(0)))
    # on a mesh, the weights go where the program put its own: the
    # benchmark never restates the program's sharding rules, so the engine
    # has to build its own parameters, laid out on the mesh as it serves
    # them, before they are replaced. On one device the draw stays
    # uncommitted, as the program's own init is: weights committed to the
    # device would make the engine's programs compile again in the window
    shardings = None
    if mesh is not None:
        shardings = jax.tree.map(lambda a: a.sharding, engine.params)
    engine.params = None                 # the program's own init, freed
    engine.params = weights.make_params(dims, ctx.seed, shardings=shardings)
    warm_up(engine, plan)
    jit_before = engine.jit_cache_sizes()
    counter = CompileCounter()
    requests = [Request(rid=p.rid, tokens=p.prompt, gen_len=p.gen_len,
                        arrival_s=p.arrival_s) for p in plan]
    setup_s = time.perf_counter() - ctx.t_start
    ctx.log(f"set-up {setup_s:.3f} s; {len(requests)} requests over "
            f"{ctx.seconds} s; jit caches {jit_before}")

    tracer, timer = None, None
    if ctx.trace:
        tracer = Tracer(ctx.work_dir / "trace")
        t = mix["trace"]
        timer = _TraceTimer(tracer, t["start_s"], t["seconds"])
    counter.armed = True
    try:
        if timer:
            timer.start()
        t_run = time.perf_counter()
        report = engine.run(requests)
        run_s = time.perf_counter() - t_run
    finally:
        counter.close()
        if timer:
            timer.join()
    jit_after = engine.jit_cache_sizes()
    device = device_info(ctx.cell["chips"])
    summary, step_spans = None, None
    if timer:
        step_spans = spans.reduce_spans(timer.path)
        summary = tracer.collect(timer.path, ctx.keep_trace)

    finished = {m["rid"]: m for m in report["per_request"]}
    served = report["outputs"]
    refused = report["refused"]
    counts = {k: report[k] for k in
              ("phases", "decode_steps", "decode_slot_steps",
               "decode_live_pages", "decode_table_pages")}
    mesh_report = report.get("mesh")
    del engine, report
    gc.collect()

    # -- the check: the reference over a sample of what was served ---------
    sample = check.sample_served(served_requests(plan, served), ctx.seed,
                                 mix["check"])
    params = weights.make_params(dims, ctx.seed, shardings=shardings)
    t_ref = time.perf_counter()
    got = check.check_served(params, dims, sample)
    ref_s = time.perf_counter() - t_ref
    del params
    limits = ctx.config["limits"]
    # every request is served, each with its whole output
    incomplete = [p.rid for p in plan if p.rid not in finished
                  or len(served[p.rid]) != p.gen_len]
    checks = {
        "served_logit_gap": check_entry(got["served_logit_gap"],
                                        limits["served_logit_gap"]),
        "served_logit_gap_mean": check_entry(
            got["served_logit_gap_mean"], limits["served_logit_gap_mean"]),
        "checked_tokens": check_entry(got["tokens"],
                                      mix["check"]["min_tokens"],
                                      at_least=True),
        "checked_requests": check_entry(got["requests"], check.MIN_REQUESTS,
                                        at_least=True),
        "requests_incomplete": check_entry(len(incomplete), 0),
        "requests_refused": check_entry(len(refused), 0),
    }
    counters = {
        "requests": len(plan), "finished": len(finished),
        "checked_longest": max((len(r["served"]) for r in sample), default=0),
        "run_s": run_s, "reference_s": ref_s,
        "window_traces": counter.traces, "window_compiles": counter.compiles,
        "jit_cache_before": jit_before, "jit_cache_after": jit_after,
    }
    if mesh_report is not None:
        counters["mesh"] = {
            k: mesh_report[k]
            for k in ("axes", "reshard_events", "param_bytes_per_shard")}
    if summary is not None:
        counters["collective_s"] = summary.collective_s
    ctx.log(f"counters {counters}")
    # each request as (arrival s, prompt, output, ttft s, latency s), so a
    # run's statistics can be read again from its log
    ctx.log("requests " + json.dumps(
        [[round(p.arrival_s, 3), len(p.prompt), p.gen_len,
          round(finished[p.rid]["ttft_s"], 4),
          round(finished[p.rid]["latency_s"], 4)]
         for p in plan if p.rid in finished]))
    return {
        "attempted": len(plan),
        "failed": len(set(incomplete) | {r["rid"] for r in refused}),
        "device": device, "check": checks, "counters": counters,
        "trace": summary, "spans": step_spans, "setup_s": setup_s,
        "seconds": ctx.seconds, "slots": serve["slots"], **counts,
        "requests": [{"rid": p.rid, "arrival_s": p.arrival_s,
                      "prompt_len": len(p.prompt), "gen_len": p.gen_len,
                      **({k: finished[p.rid][k] for k in
                          ("ttft_s", "latency_s", "tokens_out", "queue_s",
                           "park_s", "token_s")}
                         if p.rid in finished else {})}
                     for p in plan],
    }


class _TraceTimer(threading.Thread):
    """Starts the profiler ``start_s`` after the engine clock starts and
    stops it ``seconds`` later, from a thread of its own (the engine's loop
    runs undisturbed in the main thread)."""

    def __init__(self, tracer: Tracer, start_s: float, seconds: float):
        super().__init__(daemon=True)
        self.tracer, self.start_s, self.seconds = tracer, start_s, seconds
        self.path = None

    def run(self):
        time.sleep(self.start_s)
        self.tracer.start()
        time.sleep(self.seconds)
        self.path = self.tracer.stop()
