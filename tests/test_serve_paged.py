"""Paged slot memory tests (ISSUE 8).

Covers: hypothesis property tests for the page allocator (alloc/retain/
release round trips, refcounts never negative, double-free rejected) and the
prefix store (store-held pages stay referenced, eviction only without live
sharers); copy-on-write isolation (a sharer can never mutate a shared page);
full-precision store round trips bit-exactly and int8 honours the absmax
error bound; page-count admission (``PagedAdmission`` against a fake budget,
defer-not-refuse requeue semantics — the satellite-1 scheduler unit test);
and the tentpole pin: paged vs. contiguous decode is token-for-token
identical on all four decode families, greedy AND sampled, including
mid-stream slot reuse (more requests than lanes -> park + reactivate),
prefix sharing with the prefill-once chunk count, and preemption under a
tiny page budget. Plus the zero-core-diff structural proof for the two
cache_page primitives.
"""

import hypothesis.strategies as st
import numpy as np
import pytest
from hypothesis import given, settings

from repro.configs import get_config
from repro.serve import (PagedAdmission, PagedConfig, PagedKVStore,
                         PageAllocator, PagesExhausted, Request, SamplingConfig,
                         Scheduler, ServeEngine, prefix_key)


def _requests(cfg, gen_lens, prompt_len=8, seed=0, stagger=0.0, prefix=None):
    rng = np.random.default_rng(seed)
    out = []
    for i, g in enumerate(gen_lens):
        toks = rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
        if prefix is not None:
            toks[:len(prefix)] = prefix
        out.append(Request(rid=f"r{i}", tokens=toks, gen_len=g,
                           arrival_s=i * stagger,
                           shared_prefix_len=len(prefix) if prefix is not None
                           else None))
    return out


# -- page allocator properties -------------------------------------------------


@settings(max_examples=100, deadline=None)
@given(st.integers(1, 12),
       st.lists(st.tuples(st.integers(0, 2), st.integers(0, 10**6)),
                max_size=120))
def test_allocator_refcounts_and_round_trip(n_pages, ops):
    """Under any alloc/retain/release interleaving the allocator agrees with
    a shadow refcount model: free + held partitions the pool, refcounts
    match exactly (so they can never go negative), exhaustion raises, and
    releasing every reference returns the pool to fully free."""
    alloc = PageAllocator(n_pages)
    held: dict[int, int] = {}              # page -> our refcount
    for op, pick in ops:
        if op == 0:                         # alloc
            if alloc.free_pages == 0:
                with pytest.raises(PagesExhausted):
                    alloc.alloc()
            else:
                p = alloc.alloc()
                assert p not in held
                held[p] = 1
        elif op == 1 and held:              # retain a held page
            p = sorted(held)[pick % len(held)]
            alloc.retain(p)
            held[p] += 1
        elif op == 2 and held:              # release one reference
            p = sorted(held)[pick % len(held)]
            alloc.release(p)
            held[p] -= 1
            if held[p] == 0:
                del held[p]
        assert alloc.free_pages == n_pages - len(held)
        assert alloc.used_pages == len(held)
        for p, c in held.items():
            assert alloc.refcount(p) == c
    for p in sorted(held):
        for _ in range(held[p]):
            alloc.release(p)
    assert alloc.free_pages == n_pages


def test_allocator_double_free_and_stale_retain_raise():
    alloc = PageAllocator(2)
    p = alloc.alloc()
    alloc.release(p)
    with pytest.raises(ValueError):
        alloc.release(p)                    # double free
    with pytest.raises(ValueError):
        alloc.retain(p)                     # retain after free
    with pytest.raises(ValueError):
        alloc.release(99)                   # never allocated


# -- prefix store properties ---------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 4)),
                max_size=60))
def test_prefix_store_refcount_invariants(ops):
    """Random publish/lookup/release/evict sequences: every store entry's
    pages stay referenced (refcount >= 1), eviction only removes entries
    with no live sharer, and sharer releases never underflow — mirrored
    against a shadow model of outstanding lookup references."""
    from repro.serve import PrefixStore

    alloc = PageAllocator(16)
    store = PrefixStore(alloc)
    sharer_refs: list[tuple[str, list[int]]] = []   # outstanding lookups
    n_published = 0
    for op, pick in ops:
        if op == 0 and alloc.free_pages >= 2:       # publish a fresh entry
            pages = [alloc.alloc(), alloc.alloc()]
            key = f"k{n_published}"
            n_published += 1
            assert store.publish(key, pages, n_rows=2, tail=None)
            # the publisher's own working references are dropped on free
            for p in pages:
                alloc.release(p)
        elif op == 1 and store.entries:             # lookup retains
            key = sorted(store.entries)[pick % len(store.entries)]
            entry = store.lookup(key)
            assert entry is not None
            sharer_refs.append((key, list(entry.pages)))
        elif op == 2 and sharer_refs:               # a sharer finishes
            _, pages = sharer_refs.pop(pick % len(sharer_refs))
            for p in pages:
                alloc.release(p)
        elif op == 3:                               # evict LRU if possible
            live = {k for k, _ in sharer_refs}
            evictable = set(store.evictable())
            assert not (evictable & live)
            store.evict_one()
        for e in store.entries.values():
            for p in e.pages:
                assert alloc.refcount(p) >= 1
    # drain: every sharer done + every entry evicted -> pool fully free
    for _, pages in sharer_refs:
        for p in pages:
            alloc.release(p)
    while store.evict_one():
        pass
    assert not store.entries and alloc.free_pages == 16


def test_publish_is_idempotent_prefill_once():
    from repro.serve import PrefixStore

    alloc = PageAllocator(4)
    store = PrefixStore(alloc)
    p = [alloc.alloc()]
    assert store.publish("k", p, n_rows=1, tail=None)
    assert not store.publish("k", p, n_rows=1, tail=None)   # no double retain
    assert alloc.refcount(p[0]) == 2


# -- the paged store: round trip, CoW, int8 ------------------------------------


def _mini_store(**kw):
    import jax

    shapes = {"k": jax.ShapeDtypeStruct((1, 32, 4), np.float32)}
    return PagedKVStore(shapes, {"k": 1}, **kw)


def _donor(rows=32, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return {"k": jnp.asarray(rng.standard_normal((1, rows, 4)), jnp.float32)}


def test_store_round_trip_is_bit_exact():
    store = _mini_store(page_size=8, n_pages=8)
    donor = _donor(seed=1)
    store.attach("r", prompt_rows=20)
    store.store_donor("r", donor, fill=20)
    out = store.load_donor("r", {"k": np.zeros((1, 32, 4), np.float32)})
    np.testing.assert_array_equal(np.asarray(out["k"])[:, :20],
                                  np.asarray(donor["k"])[:, :20])
    store.free("r")
    assert store.allocator.free_pages == store.n_pages


def test_cow_never_mutates_a_shared_page():
    """r2 shares r1's published prefix, then writes INTO the shared page
    range: the write must land on a fresh copy (cow_copies == 1) and r1's
    view of the prefix must be byte-identical before and after."""
    store = _mini_store(page_size=8, n_pages=12)
    d1 = _donor(seed=1)
    store.attach("r1", prompt_rows=16)
    store.store_donor("r1", d1, fill=16)
    key = "shared"
    store.publish_prefix("r1", key, n_rows=16, tail=None)

    shared = store.attach("r2", prompt_rows=16, share_key=key)
    assert shared == 16
    sp1, sp2 = store.requests["r1"], store.requests["r2"]
    assert sp1.pages[:2] == sp2.pages[:2]            # physically shared

    import jax.numpy as jnp

    store.write_rows("r2", 8, 16,
                     {"k": jnp.full((8, 1, 4), 7.0, jnp.float32)})
    assert store.cow_copies == 1
    assert sp1.pages[1] != store.requests["r2"].pages[1]   # diverged
    r1 = store.load_donor("r1", {"k": np.zeros((1, 32, 4), np.float32)})
    np.testing.assert_array_equal(np.asarray(r1["k"])[:, :16],
                                  np.asarray(d1["k"])[:, :16])
    r2 = store.load_donor("r2", {"k": np.zeros((1, 32, 4), np.float32)})
    np.testing.assert_array_equal(np.asarray(r2["k"])[:, 8:16],
                                  np.full((1, 8, 4), 7.0, np.float32))


def test_attach_rollback_on_exhaustion():
    store = _mini_store(page_size=8, n_pages=2)
    store.attach("r1", prompt_rows=16)               # takes both pages
    free_before = store.allocator.free_pages
    with pytest.raises(PagesExhausted):
        store.attach("r2", prompt_rows=8)
    assert store.allocator.free_pages == free_before
    assert "r2" not in store.requests


def test_int8_pages_honour_the_absmax_bound():
    """int8 pages round-trip within the wire format's bound: per last-axis
    row, |x - deq(q)| <= absmax / 254 (+ float slack)."""
    store = _mini_store(page_size=8, n_pages=8, int8=True)
    donor = _donor(seed=3)
    store.attach("r", prompt_rows=24)
    store.store_donor("r", donor, fill=24)
    out = store.load_donor("r", {"k": np.zeros((1, 32, 4), np.float32)})
    x = np.asarray(donor["k"])[:, :24]
    y = np.asarray(out["k"])[:, :24]
    bound = np.abs(x).max(axis=-1, keepdims=True) / 254 + 1e-6
    assert (np.abs(x - y) <= bound).all()


# -- page-count admission (satellite 1) ----------------------------------------


class FakeBudget:
    def __init__(self, free):
        self.free = free

    def pages_for_rows(self, rows):
        return -(-rows // 8) + 1            # data pages + tail reservation

    def pages_free(self):
        return self.free


def test_paged_admission_defers_on_page_shortage():
    cfg = get_config("qwen1.5-0.5b").reduced()
    budget = FakeBudget(free=100)
    adm = PagedAdmission(cfg, batch=2, max_len=64, budget=budget)
    req = Request(rid="a", tokens=np.zeros(16, np.int32), gen_len=4)
    ok, _ = adm.admit(req, 0.0)
    assert ok
    budget.free = 1                          # 16 rows need 2+1 pages
    req2 = Request(rid="b", tokens=np.zeros(16, np.int32), gen_len=4)
    ok, reason = adm.admit(req2, 0.0)
    assert not ok and reason.startswith("defer")

    # defer requeues at the FRONT; permanent refusals do not
    sched = Scheduler(2, adm)
    sched.submit(req2, 0.0)
    assert sched.next_admissible(0.0) is None
    assert sched.queue and sched.queue[0].rid == "b"   # still queued, front
    assert not sched.refused


def test_paged_admission_continuation_skips_sla_but_pays_pages():
    cfg = get_config("qwen1.5-0.5b").reduced()
    budget = FakeBudget(free=100)
    adm = PagedAdmission(cfg, batch=2, max_len=64, budget=budget)
    # an SLA no fresh request could meet: the continuation skips that check
    cont = Request(rid="c", tokens=np.zeros(24, np.int32), gen_len=4,
                   sla_s=1e-9, resume_token=7)
    ok, _ = adm.admit(cont, 0.0)
    assert ok and cont.bucket >= 24
    budget.free = 0                          # ...but never the page check
    cont2 = Request(rid="d", tokens=np.zeros(24, np.int32), gen_len=4,
                    resume_token=7)
    ok, reason = adm.admit(cont2, 0.0)
    assert not ok and reason.startswith("defer")
    # a continuation that cannot re-prefill within max_len is refused for real
    huge = Request(rid="e", tokens=np.zeros(64, np.int32), gen_len=4,
                   resume_token=7)
    ok, reason = adm.admit(huge, 0.0)
    assert not ok and reason.startswith("over_budget")


# -- tentpole pin: paged == contiguous, all four families ----------------------


@pytest.mark.parametrize("arch,enc_len", [("qwen1.5-0.5b", None),
                                          ("rwkv6-7b", None),
                                          ("zamba2-7b", None),
                                          ("whisper-tiny", 8),
                                          ("internvl2-2b", None)])
def test_paged_decode_matches_contiguous_all_families(arch, enc_len):
    """4 staggered requests on 2 lanes, greedy: the paged engine (park +
    reactivate through the page pools, mid-stream slot reuse) must emit
    exactly the contiguous engine's tokens, while holding more requests
    resident than it has lanes."""
    import jax

    jax.clear_caches()
    cfg = get_config(arch).reduced()
    max_len = 24 + (cfg.vision_prefix if cfg.family == "vlm" else 0)
    reqs = _requests(cfg, [5, 4, 4, 3], stagger=0.05)
    if cfg.family == "vlm":
        for r in reqs:
            r.embeds = np.ones((cfg.vision_prefix, cfg.d_model), np.float32)
    if cfg.family == "audio":
        for r in reqs:
            r.embeds = np.ones((enc_len, cfg.d_model), np.float32)
    want = ServeEngine(cfg, batch=2, max_len=max_len, seed=0,
                       enc_len=enc_len).run(
        [Request(**vars(r)) for r in reqs])

    jax.clear_caches()
    got = ServeEngine(cfg, batch=2, max_len=max_len, seed=0, enc_len=enc_len,
                      paged=PagedConfig()).run(
        [Request(**vars(r)) for r in reqs])
    assert got["outputs"] == want["outputs"]
    assert got["paged"]["resident_requests_peak"] > 2   # exceeded the lanes
    assert got["paged"]["hbm_bytes_resident"] == 0      # all freed at the end


def test_paged_sampled_matches_contiguous():
    """Sampled decoding (temperature + top-k) draws from the SAME per-step
    key sequence when every request fits a lane and prefix sharing is off —
    paged residency must not change a single draw."""
    import jax

    cfg = get_config("qwen1.5-0.5b").reduced()
    samp = SamplingConfig(temperature=0.8, top_k=16)
    reqs = _requests(cfg, [6, 5], seed=2)
    jax.clear_caches()
    want = ServeEngine(cfg, batch=2, max_len=24, seed=0, sampling=samp).run(
        [Request(**vars(r)) for r in reqs])
    jax.clear_caches()
    got = ServeEngine(cfg, batch=2, max_len=24, seed=0, sampling=samp,
                      paged=PagedConfig(prefix_sharing=False)).run(
        [Request(**vars(r)) for r in reqs])
    assert got["outputs"] == want["outputs"]


def test_prefix_sharing_prefills_shared_prompt_once():
    """4 requests sharing a 16-token system prompt, page_size 16: one miss,
    three hits, and the chunk count proves the prefix ran ONCE — 16/4 = 4
    chunks for the publisher plus (24-16)/4 = 2 per sharer. Outputs still
    match the contiguous engine exactly."""
    import jax

    cfg = get_config("qwen1.5-0.5b").reduced()
    rng = np.random.default_rng(7)
    system = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    reqs = _requests(cfg, [3, 3, 3, 3], prompt_len=24, seed=8,
                     stagger=0.05, prefix=system)
    jax.clear_caches()
    want = ServeEngine(cfg, batch=2, max_len=48, seed=0).run(
        [Request(**vars(r)) for r in reqs])
    jax.clear_caches()
    eng = ServeEngine(cfg, batch=2, max_len=48, seed=0,
                      paged=PagedConfig(page_size=16))
    rep = eng.run([Request(**vars(r)) for r in reqs])
    assert rep["outputs"] == want["outputs"]
    assert rep["paged"]["prefix_hits"] == 3
    assert rep["paged"]["prefix_misses"] == 1
    chunk = eng.policy.chunk
    bucket = rep["per_request"][0]["bucket"]      # same prompt len -> same
    chunks = sum(e["chunks"] for e in rep["step_log"])
    # publisher runs its whole bucket; each sharer skips the 16 shared rows
    assert chunks == bucket // chunk + 3 * ((bucket - 16) // chunk)


def test_preemption_returns_exact_tokens():
    """A page pool too small for three concurrent requests forces at least
    one preemption; the preempted request re-prefills its history as a
    continuation and must still emit exactly the contiguous tokens."""
    import jax

    cfg = get_config("qwen1.5-0.5b").reduced()
    # gen 10 on prompt 8 grows each request from 1 page to 3 (page 8):
    # admission prices the PROMPT, so growth is what exhausts the 5-page
    # pool and triggers preemption
    reqs = _requests(cfg, [10, 10, 10], stagger=0.05, seed=5)
    jax.clear_caches()
    want = ServeEngine(cfg, batch=2, max_len=24, seed=0).run(
        [Request(**vars(r)) for r in reqs])

    jax.clear_caches()
    probe = ServeEngine(cfg, batch=2, max_len=24, seed=0,
                        paged=PagedConfig(page_size=8))
    budget = 5 * probe._store.page_bytes
    jax.clear_caches()
    eng = ServeEngine(cfg, batch=2, max_len=24, seed=0,
                      paged=PagedConfig(page_size=8,
                                        hbm_budget_bytes=budget))
    rep = eng.run([Request(**vars(r)) for r in reqs])
    assert rep["outputs"] == want["outputs"]
    assert rep["paged"]["preemptions"] >= 1
    assert any(e["preemptions"] >= 1 for e in rep["per_request"])


def test_int8_paged_engine_smoke():
    """int8 pages change numerics (documented), so no exactness pin — but
    every request must finish with the right token count and the report
    must flag the precision."""
    import jax

    cfg = get_config("qwen1.5-0.5b").reduced()
    reqs = _requests(cfg, [4, 4, 4], stagger=0.05)
    jax.clear_caches()
    rep = ServeEngine(cfg, batch=2, max_len=24, seed=0,
                      paged=PagedConfig(int8=True)).run(reqs)
    assert rep["paged"]["int8"]
    assert {r.rid: len(rep["outputs"][r.rid]) for r in reqs} == \
        {r.rid: r.gen_len for r in reqs}


# -- structural: the primitives are pure UPD data ------------------------------


def test_cache_page_primitives_zero_core_diff():
    """No file under core/ knows the paged-memory primitives exist — they
    are data (tsl_data/primitives/memory.yaml), same proof as gpu_pallas."""
    from pathlib import Path

    import repro.core

    core_dir = Path(repro.core.__file__).parent
    offenders = [f.name for f in sorted(core_dir.rglob("*"))
                 if f.suffix in (".py", ".j2") and f.is_file()
                 and "cache_page" in f.read_text()]
    assert not offenders, offenders


def test_cache_page_primitives_cover_every_target():
    from repro.core import load_corpus

    corpus = load_corpus()
    for name in ("cache_page_read", "cache_page_write"):
        prim = corpus.primitives[name]
        covered = {d.target_extension for d in prim.definitions}
        assert covered == set(corpus.targets), (name, covered)
        assert prim.tests, name


def test_prefix_key_is_content_addressed():
    base = dict(arch="qwen", page_size=16, int8=False, seed=0,
                prefix_rows=0, tokens=[1, 2, 3])
    k = prefix_key(**base)
    assert k == prefix_key(**base)
    assert k != prefix_key(**{**base, "tokens": [1, 2, 4]})
    assert k != prefix_key(**{**base, "int8": True})
    assert k != prefix_key(**{**base, "seed": 1})


@pytest.mark.parametrize("fault,error", [("missing", AttributeError),
                                         ("raises", RuntimeError)])
def test_selected_page_size_propagates_library_faults(monkeypatch, fault,
                                                      error):
    """The pool geometry comes from the generated library or nowhere: a
    library without cache_page_read, or whose definition raises, is an
    error, never a default page size."""
    import types

    from repro import tsl_api
    from repro.serve import selected_page_size

    def refused(*args, **kwargs):
        raise RuntimeError("kernel refused")

    ops = types.SimpleNamespace()
    if fault == "raises":
        ops.cache_page_read = refused
    monkeypatch.setattr(tsl_api, "_lib", types.SimpleNamespace(ops=ops))
    with pytest.raises(error):
        selected_page_size()
