"""The serving loop's phases and stamps: the ``phases`` table agrees with
the steps, chunks and decode steps the loop ran; a request that finishes
its prefill with no free lane records the time it spent parked; and in a
profiler session the phases are named host spans beside the engine's named
programs."""

from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.serve import PagedConfig, Request, ServeEngine
from test_serve_chunked import assert_measured_stamps


def _requests(cfg, gen_lens, arrivals, prompt_len=12, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}", gen_len=g, arrival_s=t,
                    tokens=rng.integers(0, cfg.vocab, prompt_len
                                        ).astype(np.int32))
            for i, (g, t) in enumerate(zip(gen_lens, arrivals))]


def test_phase_counts_match_the_loop():
    """One admit, one complete and one step phase per device step, one
    prefill_chunk phase per chunk, one decode phase per decode step; the
    idle wait for a late arrival is a phase of its own."""
    jax.clear_caches()
    cfg = get_config("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, batch=2, max_len=48, paged=PagedConfig())
    eng.run(_requests(cfg, [6, 4], [0.0, 0.0]))        # compile first
    rep = eng.run(_requests(cfg, [6, 4, 3], [0.0, 0.0, 1.5]))
    ph, log = rep["phases"], rep["step_log"]
    assert set(ph) == {"step", "admit", "prefill_chunk", "decode",
                       "complete", "wait_arrival"}
    assert ph["step"]["n"] == rep["steps"] == len(log)
    assert ph["admit"]["n"] == ph["complete"]["n"] == rep["steps"]
    assert ph["prefill_chunk"]["n"] == sum(e["chunks"] for e in log)
    assert ph["decode"]["n"] == rep["decode_steps"] \
        == sum(e["decoded"] > 0 for e in log)
    assert rep["decode_slot_steps"] == sum(e["decoded"] for e in log)
    assert ph["wait_arrival"]["n"] > 0
    assert all(v["s"] > 0 for v in ph.values())
    # the phases inside a step take no more than the step
    inner = sum(ph[n]["s"] for n in ("admit", "prefill_chunk", "decode",
                                     "complete"))
    assert inner <= ph["step"]["s"]


def test_a_request_with_no_free_lane_records_its_park():
    """One lane, two prefills at once: the second to finish its prefill
    holds its first token, parked, until the first request leaves the lane;
    its park_s covers that wait and leaves its decode rate to the lane."""
    jax.clear_caches()
    cfg = get_config("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, batch=1, max_len=48,
                      paged=PagedConfig(page_size=8, max_inflight_prefills=2))
    rep = eng.run(_requests(cfg, [8, 4], [0.0, 0.0]))
    per = {m["rid"]: m for m in rep["per_request"]}
    for m in per.values():
        assert_measured_stamps(m)
    parked = [m for m in per.values() if m["park_s"] > 0]
    assert len(parked) == 1
    m = parked[0]
    lane_s = m["latency_s"] - m["ttft_s"] - m["park_s"]
    assert m["decode_tokens_per_s"] == pytest.approx(
        (m["tokens_out"] - 1) / lane_s)
    # it parked until the other request's last token, and decoded after
    other = next(o for o in per.values() if o is not m)
    assert m["token_s"][1] >= other["token_s"][-1]
    assert m["token_s"][1] - m["token_s"][0] >= m["park_s"]


def _host_events(log_dir: Path) -> list:
    found = sorted(log_dir.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_serialized_xspace(
        found[-1].read_bytes())
    return [(ev.name, dict(ev.stats)) for plane in data.planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def test_a_profiler_session_shows_the_spans_and_program_names(tmp_path):
    """Around a tiny engine run, a profiler session holds the serve.* spans
    with their arguments, and the engine's programs by their fixed names
    (none is a lambda)."""
    jax.clear_caches()
    cfg = get_config("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, batch=2, max_len=48, paged=PagedConfig())
    reqs = _requests(cfg, [4, 3], [0.0, 0.0])
    eng.run([Request(**vars(r)) for r in reqs])        # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        rep = eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    names = {n for n, _ in events}
    assert {"serve.step", "serve.admit", "serve.prefill_chunk",
            "serve.decode", "serve.complete"} <= names
    steps = [a for n, a in events if n == "serve.step"]
    assert len(steps) == rep["steps"]
    assert sorted(a["step"] for a in steps) == list(range(rep["steps"]))
    assert sum(a["chunks"] for a in steps) == \
        sum(e["chunks"] for e in rep["step_log"])
    chunks = [a for n, a in events if n == "serve.prefill_chunk"]
    assert {a["rid"] for a in chunks} == {"r0", "r1"}
    assert min(a["chunk"] for a in chunks) == 0
    programs = {n for n in names if n.startswith("PjitFunction(")}
    for want in ("serve_chunk", "serve_decode_paged", "serve_sample",
                 "serve_insert", "serve_reset", "serve_page_write"):
        assert f"PjitFunction({want})" in programs
    assert "PjitFunction(<lambda>)" not in programs
