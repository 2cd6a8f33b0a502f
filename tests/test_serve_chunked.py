"""Chunked-prefill slot engine tests (ISSUE 5).

Covers: token-for-token equivalence of chunked continuation prefill against
whole-prompt prefill across all four decode families (every chunk size shape:
chunk=1, ragged final chunk, chunk >= prompt with bucket padding); the
no-decode-stall acceptance property under a mixed trace with a long prompt
arriving mid-run; the compiled-shape bound (len(buckets) + 1 per family);
async arrival gating; the measured per-request stamps (queue wait, prefill,
parked time, one host time per token); and hypothesis property tests for the
length-bucketing policy.
"""

import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from repro.configs import get_config
from repro.serve import (BucketPolicy, CostModelAdmission, Request,
                         Scheduler, ServeEngine, upd_serve_defaults)


def _requests(cfg, gen_lens, prompt_len=8, seed=0, sla_s=None):
    rng = np.random.default_rng(seed)
    return [
        Request(rid=f"r{i}",
                tokens=rng.integers(0, cfg.vocab, prompt_len).astype(np.int32),
                gen_len=g, sla_s=sla_s)
        for i, g in enumerate(gen_lens)
    ]


# -- chunked continuation == whole-prompt prefill, all four families -----------


@pytest.mark.parametrize("arch,enc_len", [("qwen1.5-0.5b", None),
                                          ("rwkv6-7b", None),
                                          ("zamba2-7b", None),
                                          ("whisper-tiny", 8),
                                          ("internvl2-2b", None)])
def test_prefill_chunk_matches_whole_prompt(arch, enc_len):
    """For every family: running the prompt through prefill_chunk — at
    chunk=1, a ragged final chunk (prompt 9, chunk 3 -> 3 chunks; chunk 4 ->
    n_real=1 tail), and chunk >= prompt (one padded bucket-style chunk) —
    must reproduce whole-prompt prefill exactly: same last-token logits AND a
    decode step from the resulting state agrees."""
    import jax
    import jax.numpy as jnp

    jax.clear_caches()
    cfg = get_config(arch).reduced()
    from repro.nn.model import build_model

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt_len, max_len = 9, 24
    toks = rng.integers(0, cfg.vocab, (1, prompt_len)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)}
    embeds = None
    if cfg.family == "vlm":
        embeds = jnp.ones((1, cfg.vision_prefix, cfg.d_model), cfg.dtype)
        batch["vision_embeds"] = embeds
    if cfg.family == "audio":
        embeds = jnp.ones((1, enc_len, cfg.d_model), cfg.dtype)
        batch["audio_embeds"] = embeds
    want_logits, want_state = model.prefill(params, batch, max_len)
    prefix = cfg.decode_prefix
    greedy = int(np.asarray(want_logits)[..., :cfg.vocab].argmax(-1)[0])
    next_tok = jnp.asarray([[greedy]], jnp.int32)
    want_dec, _ = model.decode_step(
        params, jax.tree.map(jnp.array, want_state), next_tok,
        jnp.int32(prompt_len + prefix))

    # (chunk, padded_len): minimal whole-chunk padding for chunk 1/3/4/16,
    # plus a bucket-style schedule (chunk 4, bucket 16) whose last TWO chunks
    # are all padding (n_real == 0) — the recurrent carries must survive them
    for chunk, padded_len in ((1, None), (3, None), (4, None), (16, None),
                              (4, 16)):
        st_c = model.init_decode_state(1, max_len, enc_len=enc_len)
        if padded_len is None:
            padded_len = ((prompt_len + chunk - 1) // chunk) * chunk
        padded = np.zeros((1, padded_len), np.int32)
        padded[:, :prompt_len] = toks
        fill, last = 0, None
        for ci in range(padded_len // chunk):
            seg = jnp.asarray(padded[:, ci * chunk:(ci + 1) * chunk])
            n_real = max(0, min(prompt_len - ci * chunk, chunk))
            logits, st_c = model.prefill_chunk(
                params, st_c, seg, jnp.int32(fill), jnp.int32(fill),
                n_real=jnp.int32(n_real),
                embeds=embeds if ci == 0 else None)
            pr = logits.shape[1] - seg.shape[1]     # vlm/audio prefix rows
            if ci == 0:
                fill += pr
            if n_real:
                last = np.asarray(logits)[:, pr + n_real - 1]
                fill += n_real
        assert fill == prompt_len + prefix
        np.testing.assert_allclose(last, np.asarray(want_logits),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"{arch} chunk={chunk}")
        # the state is equivalent too: one decode step agrees bit-for-bit up
        # to f32 accumulation — this exercises the padded cache rows beyond
        # the real fill (they must stay masked/ignored)
        got_dec, _ = model.decode_step(
            params, jax.tree.map(jnp.array, st_c), next_tok,
            jnp.int32(prompt_len + prefix))
        np.testing.assert_allclose(np.asarray(got_dec), np.asarray(want_dec),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"{arch} chunk={chunk} decode")


@pytest.mark.parametrize("arch,prompt_len", [("qwen1.5-0.5b", 5),
                                             ("qwen1.5-0.5b", 17),
                                             ("rwkv6-7b", 17),
                                             ("zamba2-7b", 17)])
def test_engine_bucket_padding_is_exact(arch, prompt_len):
    """End-to-end: a prompt shorter than its bucket served through the
    chunked engine emits the SAME tokens as an unbucketed, unchunked solo
    reference — bucket padding must never leak into the math. prompt 5 ->
    bucket 8 (partial final chunk); prompt 17 -> bucket 32 (4-chunk
    schedule whose LAST chunk is pure padding — the recurrent families'
    carries must pass through it untouched)."""
    import jax
    import jax.numpy as jnp

    jax.clear_caches()
    cfg = get_config(arch).reduced()
    from repro.nn.model import build_model

    max_len, gen = 40, 6
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))   # same seed as the engine
    rng = np.random.default_rng(0)
    target = rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)

    def pick(logits):
        return int(np.asarray(logits, np.float64)[..., :cfg.vocab].argmax(-1)[0])

    logits, st_solo = model.prefill(
        params, {"tokens": jnp.asarray(target[None])}, max_len)
    want = [pick(logits)]
    pos = prompt_len
    for _ in range(gen - 1):
        tok = jnp.asarray([[want[-1]]], jnp.int32)
        logits, st_solo = model.decode_step(params, st_solo, tok,
                                            jnp.int32(pos))
        want.append(pick(logits))
        pos += 1

    eng = ServeEngine(cfg, batch=2, max_len=max_len, seed=0)
    rep = eng.run([Request(rid="t", tokens=target, gen_len=gen)])
    want_bucket = 8 if prompt_len <= 8 else 32
    assert rep["per_request"][0]["bucket"] == want_bucket   # genuinely padded
    assert rep["outputs"]["t"] == want


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "rwkv6-7b"])
def test_concurrent_prefill_is_exact(arch):
    """A multi-chunk prompt prefilled WHILE a neighbour decodes must emit
    exactly the tokens it emits when served alone: decode steps running
    between its chunk steps must not touch the in-flight prefill (the donor
    lives outside the slot table until grafted). Covers both a KV-cache
    family (stale-position scatter corruption) and a recurrent family
    (state advanced by garbage tokens) — greedy sampling, so outputs are a
    pure function of the logits."""
    import jax

    jax.clear_caches()
    cfg = get_config(arch).reduced()
    rng = np.random.default_rng(0)
    long_tokens = rng.integers(0, cfg.vocab, 32).astype(np.int32)
    long_gen = 5

    # solo reference: same engine config, the long request alone
    eng = ServeEngine(cfg, batch=2, max_len=48, seed=0)
    want = eng.run([Request(rid="long", tokens=long_tokens,
                            gen_len=long_gen)])["outputs"]["long"]
    assert len(want) == long_gen

    # concurrent: a neighbour decodes throughout the long prompt's 4-chunk
    # prefill (arrival gating makes the overlap deterministic)
    jax.clear_caches()
    eng = ServeEngine(cfg, batch=2, max_len=48, seed=0)
    runner = Request(rid="runner",
                     tokens=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                     gen_len=40)
    late = Request(rid="long", tokens=long_tokens, gen_len=long_gen,
                   arrival_s=0.3)
    rep = eng.run([runner, late])
    assert rep["requests"] == 2
    long_steps = [e for e in rep["step_log"] if "long" in e["prefill_rids"]]
    assert long_steps and all(e["decoded"] >= 1 for e in long_steps), \
        "setup failed to overlap prefill with decode"
    assert rep["outputs"]["long"] == want


# -- acceptance: no decode stall + bounded compiled shapes ---------------------


def test_long_prompt_prefill_never_stalls_decode():
    """ISSUE 5 acceptance: with a >= 4x-bucket-length prompt arriving
    mid-run, every engine step that advances its prefill chunks also decodes
    one token for every running slot; padded_slot_steps_steady stays 0; and
    the engine's compiled shapes stay bounded by len(buckets) + 1."""
    import jax

    jax.clear_caches()
    cfg = get_config("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, batch=3, max_len=48)
    assert eng.policy.buckets == (8, 16, 32)    # filtered to the slot table
    rng = np.random.default_rng(0)
    # neighbours generate long enough to still be running through the whole
    # of the long prompt's chunk schedule (batch 3: the mid-run arrival takes
    # the free third slot, so its chunks genuinely share steps with decode)
    short = _requests(cfg, [30, 34], prompt_len=6, sla_s=600.0)
    # the long prompt: 4x the smallest bucket, arriving once decode is going
    long_req = Request(rid="long",
                       tokens=rng.integers(0, cfg.vocab, 32).astype(np.int32),
                       gen_len=4, sla_s=600.0, arrival_s=0.5)
    rep = eng.run(short + [long_req])

    assert rep["requests"] == 3
    assert rep["padded_slot_steps_steady"] == 0
    steps_by_rid = {e["rid"]: e["step"] for e in rep["admission_log"]}
    assert steps_by_rid["long"] > 0                     # arrived mid-run
    long_steps = [e for e in rep["step_log"]
                  if "long" in e["prefill_rids"]]
    assert len(long_steps) == 32 // rep["prefill_chunk"]
    # NO DECODE STALL: the running slot kept emitting in every chunk step
    assert all(e["decoded"] >= 1 for e in long_steps), long_steps
    # the long request's TTFT is measured from ITS arrival, not run start
    long_m = [m for m in rep["per_request"] if m["rid"] == "long"][0]
    assert long_m["bucket"] == 32
    assert long_m["ttft_s"] <= rep["wall_s"] - 0.5 + 1e-6
    # compiled-shape bound: one prefill-chunk shape + one decode shape,
    # <= len(buckets) + 1 (the jit-cache probe behind "the engine never runs
    # a shape it hasn't compiled")
    jc = rep["jit_cache"]
    assert jc["prefill_chunk"] + jc["decode"] <= len(rep["buckets"]) + 1, jc


def test_async_arrivals_gate_admission():
    """Requests with future arrival_s stay invisible to admission until the
    engine clock reaches them; the scheduler releases them in arrival
    order."""
    # scheduler-level: pending -> queue at release time
    sched = Scheduler(2)
    early = Request(rid="e", tokens=np.arange(4), gen_len=2)
    late = Request(rid="l", tokens=np.arange(4), gen_len=2, arrival_s=5.0)
    sched.submit(late, 0.0)
    sched.submit(early, 0.0)
    assert [r.rid for r in sched.queue] == ["e"]
    assert sched.next_arrival_s() == 5.0
    assert sched.release(1.0) == 0
    assert sched.release(5.0) == 1
    assert [r.rid for r in sched.queue] == ["e", "l"]
    assert sched.has_work()

    # engine-level: the late request is admitted at a later step
    import jax

    jax.clear_caches()
    cfg = get_config("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, batch=2, max_len=24)
    reqs = _requests(cfg, [10], prompt_len=6)
    reqs.append(Request(rid="late", tokens=np.zeros(6, np.int32), gen_len=3,
                        arrival_s=0.4))
    rep = eng.run(reqs)
    assert rep["requests"] == 2
    steps_by_rid = {e["rid"]: e["step"] for e in rep["admission_log"]}
    assert steps_by_rid["late"] > steps_by_rid["r0"]
    late_m = [m for m in rep["per_request"] if m["rid"] == "late"][0]
    # latency measured from arrival: strictly less than the run's wall clock
    assert late_m["latency_s"] < rep["wall_s"]


# -- measured per-request stamps -------------------------------------------------


def assert_measured_stamps(m):
    """What the stamps of one finished request must satisfy: the queue wait
    and the prefill make up TTFT, the parked time fits between the first
    token and the last, and each token has its host time, in order."""
    assert m["queue_s"] + m["prefill_s"] == pytest.approx(m["ttft_s"],
                                                          abs=1e-6)
    assert m["ttft_s"] + m["park_s"] <= m["latency_s"] + 1e-9
    assert len(m["token_s"]) == m["tokens_out"]
    assert all(b >= a for a, b in zip(m["token_s"], m["token_s"][1:]))


def test_step_time_attribution_split():
    """The scheduler's stamps, on a lane and parked: queue wait to the
    reservation, prefill to the first token, the time parked, one host time
    per token (a verify round's tokens share theirs), and decode tokens/s
    over the time the request held a lane."""
    from dataclasses import asdict

    sched = Scheduler(2)
    a = Request(rid="a", tokens=np.arange(4), gen_len=5, arrival_s=0.1)
    b = Request(rid="b", tokens=np.arange(4), gen_len=2, arrival_s=0.1)
    sched.submit(a, 0.0)
    sched.submit(b, 0.0)
    sched.release(0.2)
    req = sched.next_admissible(0.5)
    sched.reserve(0, req, step=0, now_s=0.5)
    sched.place(req, 0)
    sched.first_token(0, 0.9)
    sched.step_done(0, now_s=1.0)
    sched.step_done(0, n=2, now_s=1.2)
    sched.step_done(0, now_s=1.4)
    ma = sched.finish(0, 1.5)
    assert ma.queue_s == pytest.approx(0.4)
    assert ma.prefill_s == pytest.approx(0.4)
    assert ma.ttft_s == pytest.approx(0.8)
    assert ma.park_s == 0.0
    assert ma.token_s == [0.9, 1.0, 1.2, 1.2, 1.4]
    assert ma.decode_tokens_per_s == pytest.approx(4 / (1.4 - 0.8))

    # paged: reserved with no lane, parked from its first token to a lane
    req = sched.next_admissible(0.3)
    sched.reserve_unplaced(req, step=1, now_s=0.3)
    sched.first_token_unplaced("b", 0.7)
    sched.park("b", 0.7)
    sched.place_parked("b", 1, 1.2)
    sched.step_done(1, now_s=1.3)
    mb = sched.finish(1, 1.3)
    assert mb.park_s == pytest.approx(0.5)
    assert mb.decode_tokens_per_s == pytest.approx(1 / (1.2 - 0.6 - 0.5))
    for m in (ma, mb):
        assert_measured_stamps(asdict(m))


def test_engine_attributes_prefill_and_decode_time():
    """Engine-level: every finished request carries measured stamps, the
    long prompt's prefill is timed from its reservation, and decode tokens/s
    is taken over the time each request held a lane."""
    import jax

    jax.clear_caches()
    cfg = get_config("qwen1.5-0.5b").reduced()
    eng = ServeEngine(cfg, batch=2, max_len=48)
    rng = np.random.default_rng(0)
    reqs = _requests(cfg, [20], prompt_len=6)
    reqs.append(Request(rid="long",
                        tokens=rng.integers(0, cfg.vocab, 32).astype(np.int32),
                        gen_len=3, arrival_s=0.2))
    rep = eng.run(reqs)
    assert rep["requests"] == 2
    per = {m["rid"]: m for m in rep["per_request"]}
    for m in per.values():
        assert_measured_stamps(m)
        assert m["park_s"] == 0.0                  # lanes never park
        lane_s = m["latency_s"] - m["ttft_s"]
        assert m["decode_tokens_per_s"] == pytest.approx(
            (m["tokens_out"] - 1) / lane_s)
    # the long prompt ran 32 / chunk chunks between reservation and its
    # first token; r0 decoded beside them, one stamp per step
    assert per["long"]["prefill_s"] > 0
    assert len(set(per["r0"]["token_s"])) == per["r0"]["tokens_out"]


# -- length-bucketing policy property tests ------------------------------------


def test_bucket_policy_validation_and_upd_defaults():
    with pytest.raises(ValueError, match="multiples"):
        BucketPolicy((8, 12), 8)
    with pytest.raises(ValueError, match="sorted"):
        BucketPolicy((16, 8), 8)
    d = upd_serve_defaults()
    pol = BucketPolicy.from_upd()
    assert pol.buckets == tuple(d["buckets"])
    assert pol.chunk == d["chunk"]
    assert all(b % pol.chunk == 0 for b in pol.buckets)


@settings(max_examples=100, deadline=None)
@given(st.frozensets(st.integers(1, 64), min_size=1, max_size=6),
       st.integers(1, 600), st.integers(1, 600))
def test_bucket_assignment_monotone_and_minimal(mults, p1, p2):
    """Monotone: longer prompts never get smaller buckets. Minimal: nobody
    is padded past the next bucket — the assigned bucket is the smallest
    declared size that fits."""
    chunk = 4
    pol = BucketPolicy(sorted(m * chunk for m in mults), chunk)
    b1, b2 = pol.assign(p1), pol.assign(p2)
    if p1 <= p2 and b1 is not None and b2 is not None:
        pass  # ordering asserted below via minimality
    if p1 <= p2 and b2 is not None and b1 is None:
        raise AssertionError("shorter prompt refused while longer admitted")
    if b1 is not None:
        assert b1 >= p1
        smaller = [b for b in pol.buckets if b < b1]
        assert all(b < p1 for b in smaller)     # no smaller bucket fits
        assert pol.n_chunks(b1) * chunk == b1
    if p1 <= p2 and b1 is not None and b2 is not None:
        assert b1 <= b2                          # monotone


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 80), st.integers(1, 8)),
                min_size=2, max_size=8),
       st.integers(0, 2 ** 31))
def test_refusal_reasons_stable_under_arrival_permutation(specs, shuffle_seed):
    """Admission at a fixed clock is a pure function of the request: the SET
    of refused rids and their reasons must not depend on arrival order."""
    import random

    cfg = get_config("qwen1.5-0.5b").reduced()
    pol = BucketPolicy((8, 16), 8)
    adm = CostModelAdmission(cfg, batch=2, max_len=20, policy=pol)
    reqs = [Request(rid=f"q{i}", tokens=np.zeros(p, np.int32), gen_len=g)
            for i, (p, g) in enumerate(specs)]

    def refusals(order):
        sched = Scheduler(len(order), admission=adm)
        for r in order:
            r.bucket = 0
            sched.submit(r, 0.0)
        while sched.next_admissible(0.0) is not None:
            pass
        return {r.rid: r.reason for r in sched.refused}

    base = refusals(list(reqs))
    shuffled = list(reqs)
    random.Random(shuffle_seed).shuffle(shuffled)
    assert refusals(shuffled) == base
