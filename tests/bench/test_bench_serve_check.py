"""The serving cells' check, rehearsed on the CPU at a tiny size: the plain
reference agrees with the engine, a lower precision or a broken timed path
fails the same comparison, and a whole run comes out correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import TINY_GAP_LIMIT, tiny_config, tiny_mix

from bench import check, harness, loadgen, spec, weights
from bench.drivers import serve_open_loop as drv
from bench.reference import dense_lm


def _engine(dtype="float32", seed=3):
    cfg = tiny_config(dtype)
    dims = spec.Dims.from_config(cfg)
    engine = drv.build_engine(spec.arch_config(cfg), cfg["serve"], tiny_mix(),
                              seed)
    weights.check_layout(dims, jax.eval_shape(engine.model.init,
                                              jax.random.PRNGKey(0)))
    engine.params = weights.make_params(dims, seed)
    return engine, dims


def _serve(engine, seed=5, n=4, gen=16):
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    reqs = [Request(rid=f"q{i}", tokens=rng.integers(0, 250, 20 + 7 * i
                                                     ).astype(np.int32),
                    gen_len=gen) for i in range(n)]
    out = engine.run(reqs)["outputs"]
    return [{"rid": r.rid, "prompt": np.asarray(r.tokens),
             "served": np.asarray(out[r.rid])} for r in reqs]


def test_reference_matches_the_engine_on_pallas_interpret(bind_library):
    """Prefill logits of the program's forward and served greedy tokens of
    the paged engine (chunked prefill, fused paged decode, the Pallas
    kernels in interpret mode) agree with the float32 reference."""
    bind_library("pallas_interpret")
    engine, dims = _engine()
    toks = np.random.default_rng(0).integers(0, 250, 40).astype(np.int32)
    prog = engine.model.forward_logits(engine.params,
                                       {"tokens": jnp.asarray(toks)[None]})
    ref = dense_lm.logits(weights.make_params(dims, 3), toks, dims)
    err = np.abs(np.asarray(prog[0, :, :dims.vocab]) - np.asarray(ref)).max()
    assert err < 1e-4
    got = check.check_served(weights.make_params(dims, 3), dims,
                             _serve(engine))
    assert got["served_logit_gap"] <= TINY_GAP_LIMIT
    assert got["tokens"] == 4 * 16


def test_a_lower_precision_fails_the_comparison(bind_library):
    """The engine run in bfloat16 where the configuration states float32,
    and the reference's own bfloat16 control, both miss the limit."""
    bind_library("cpu_xla")
    engine, dims = _engine("bfloat16")
    sample = _serve(engine, n=6, gen=24)
    ref_params = weights.make_params(dims, 3, dtype=jnp.float32)
    low = check.check_served(ref_params, dims, sample)
    assert low["served_logit_gap"] > TINY_GAP_LIMIT
    engine, dims = _engine("float32")
    ctl = check.check_served(weights.make_params(dims, 3),
                             dims, _serve(engine, n=6, gen=24),
                             quant="bfloat16")
    assert ctl["served_logit_gap"] <= TINY_GAP_LIMIT
    assert ctl["control_logit_gap"] > TINY_GAP_LIMIT


def test_a_whole_run_is_correct_and_reports_its_metrics(tiny_ctx,
                                                         bind_library):
    """A run serves every request whole and reads every metric."""
    bind_library("cpu_xla")
    res = harness.execute(tiny_ctx())
    assert res["correct"] and res["failed"] == 0
    listed = spec.cell_metrics(spec.benchmark(), "qwen1.5-0.5b.chat",
                               "end_to_end")
    assert set(res["metrics"]) == {m["name"] for m in listed}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "check"
    assert res["counters"]["window_compiles"] == 0
    # the configuration names no mesh: the engine runs unmeshed
    assert "mesh" not in res["counters"]


def _altered_sampler(orig):
    def build(self):
        sample = orig(self)
        vocab = self.cfg.vocab

        def altered(logits, key, temps):
            tok = sample(logits, key, temps)
            return tok if logits.shape[0] == 1 else (tok + 1) % vocab
        return altered
    return build


def _stale_decode(orig):
    def step(params, state, pools, tables, tokens, pos, cfg):
        logits, state, _ = orig(params, state, pools, tables, tokens, pos,
                                cfg)
        return logits, state, pools
    return step


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged"])
def test_a_broken_timed_path_comes_out_incorrect(fault, tiny_ctx,
                                                 bind_library, monkeypatch):
    """Every fault a serving cell can have: a served token altered where it
    is produced (the sampler), and a decode step that returns its page
    pools unchanged."""
    from repro.nn import lm
    from repro.serve.engine import ServeEngine

    bind_library("cpu_xla")
    if fault == "token_altered":
        monkeypatch.setattr(ServeEngine, "_build_sampler",
                            _altered_sampler(ServeEngine._build_sampler))
    else:
        monkeypatch.setattr(lm, "lm_decode_step_paged",
                            _stale_decode(lm.lm_decode_step_paged))
    res = harness.execute(tiny_ctx())
    assert not res["correct"]
    assert not res["check"]["served_logit_gap"]["ok"]


def test_a_traced_run_reports_the_trace_window(tiny_ctx, bind_library,
                                               tmp_path):
    """--trace 1: the profiler runs inside the window from a thread of its
    own; the result carries the traced window and a breakdown, and the raw
    trace is kept where asked. The CPU has no TPU plane, so the cell's
    device-trace metrics read nothing (its metrics from the requests, the
    program's counters and its spans read), and a listed per-layer metric
    that reads nothing makes the run not correct."""
    from bench import trace

    bind_library("cpu_xla")
    ctx = tiny_ctx(trace=True)
    ctx.keep_trace = tmp_path / "kept" / "t.xplane.pb.gz"
    res = harness.execute(ctx)
    kept = trace.reduce(ctx.keep_trace, res["device"]["window_s"])
    assert kept.devices == 0 and kept.op_s == {}
    assert res["device"]["window_s"] > 0 and res["device"]["busy_s"] == 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    listed = spec.cell_metrics(spec.benchmark(), "qwen1.5-0.5b.chat",
                               "per_layer")
    on_device = {m["name"] for m in listed if m["source"] == "device_trace"}
    assert {"device_idle.chat", "host_gap_ms.chat",
            "decode_step_ms.chat"} <= on_device
    assert set(res["metrics"]) == {m["name"] for m in listed} - on_device
    assert res["check"]["metrics_missing"]["value"] == len(on_device)
    assert not res["correct"]
    assert all(c["ok"] for n, c in res["check"].items()
               if n != "metrics_missing")
    assert not (harness.WORK_DIR / "trace").exists()


def test_a_thin_sample_comes_out_incorrect(tiny_ctx, bind_library):
    """A run whose check covers fewer served tokens than the mix asks for
    is not correct, whatever the gaps read."""
    bind_library("cpu_xla")
    ctx = tiny_ctx()
    ctx.mix["check"] = {"min_tokens": 10**6, "max_requests": 8}
    res = harness.execute(ctx)
    assert not res["correct"]
    assert not res["check"]["checked_tokens"]["ok"]
    assert res["check"]["served_logit_gap"]["ok"]


def test_the_sample_holds_the_longest_context_and_the_most_served():
    """The sample starts with the request of the longest context and the
    one that served the most tokens, then fills up to its size; a request
    that served nothing is not in it."""
    plan = [loadgen.Planned(rid=f"r{i}", arrival_s=0.0,
                            prompt=np.arange(5 + 100 * (i == 3),
                                             dtype=np.int32), gen_len=50)
            for i in range(5)]
    served = {"r0": list(range(50)), "r1": list(range(30)), "r2": [],
              "r3": list(range(10)), "r4": list(range(20))}
    got = drv.served_requests(plan, served)
    assert [r["rid"] for r in got] == ["r0", "r1", "r3", "r4"]
    sample = check.sample_served(got, 7, {"min_tokens": 60,
                                          "max_requests": 8})
    assert [r["rid"] for r in sample[:2]] == ["r3", "r0"]
    assert len(sample) == 3
    assert sum(len(r["served"]) for r in sample) >= 60
