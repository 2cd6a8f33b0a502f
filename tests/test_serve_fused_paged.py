"""Fused paged attention tests (ISSUE 9).

The tentpole equivalence pin: with ``PagedConfig(fused=True)`` the engine
decodes and verifies KV-family slots DIRECTLY against the page pool through
the block table (``attention_decode_paged`` / ``attention_verify_paged``) —
and must emit exactly the tokens of the PR 8 lane-activated fallback
(``fused=False``), greedy AND sampled, through mid-stream slot reuse,
copy-on-write shared prefix pages, and speculative verify/commit. Plus the
host-spill tier: cold unshared pages evicted to host arrays under a tight
page budget must rehydrate bit-exactly (token-for-token vs the contiguous
engine, with at least one spill/rehydrate cycle observed), int8 fused pools
honour the absmax/254 bound, and the recurrent family with no paged leaves
(rwkv) falls back to lanes automatically.
"""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist.compression import dequantize_absmax_int8
from repro.serve import (PagedConfig, PagedKVStore, Request, SamplingConfig,
                         ServeEngine)
from repro.serve.spec import SpeculationConfig


def _requests(cfg, gen_lens, prompt_len=8, seed=0, stagger=0.05, prefix=None,
              enc_len=None):
    rng = np.random.default_rng(seed)
    out = []
    for i, g in enumerate(gen_lens):
        toks = rng.integers(0, cfg.vocab, prompt_len).astype(np.int32)
        if prefix is not None:
            toks[:len(prefix)] = prefix
        r = Request(rid=f"r{i}", tokens=toks, gen_len=g, arrival_s=i * stagger,
                    shared_prefix_len=len(prefix) if prefix is not None
                    else None)
        if cfg.family == "vlm":
            r.embeds = np.ones((cfg.vision_prefix, cfg.d_model), np.float32)
        if cfg.family == "audio":
            r.embeds = np.ones((enc_len, cfg.d_model), np.float32)
        out.append(r)
    return out


def _run(cfg, reqs, *, fused, enc_len=None, max_len=24, seed=0,
         sampling=None, speculation=None, **pkw):
    jax.clear_caches()
    eng = ServeEngine(cfg, batch=2, max_len=max_len, seed=seed,
                      enc_len=enc_len, sampling=sampling,
                      speculation=speculation,
                      paged=PagedConfig(fused=fused, **pkw))
    return eng.run([Request(**vars(r)) for r in reqs])


@pytest.mark.parametrize("arch,enc_len", [("qwen1.5-0.5b", None),
                                          ("zamba2-7b", None),
                                          ("whisper-tiny", 8),
                                          ("internvl2-2b", None)])
def test_fused_matches_lane_all_kv_families(arch, enc_len):
    """4 staggered requests on 2 lanes, greedy: the fused engine (tails-only
    activation, decode through the block table, mid-stream slot reuse) emits
    exactly the lane-activated engine's tokens — and never gathers pages
    into a lane (lane_activations == 0, tail restores observed)."""
    cfg = get_config(arch).reduced()
    max_len = 24 + (cfg.vision_prefix if cfg.family == "vlm" else 0)
    reqs = _requests(cfg, [5, 4, 4, 3], enc_len=enc_len)
    want = _run(cfg, reqs, fused=False, enc_len=enc_len, max_len=max_len)
    got = _run(cfg, reqs, fused=True, enc_len=enc_len, max_len=max_len)
    assert got["outputs"] == want["outputs"]
    assert got["paged"]["fused"] and not want["paged"]["fused"]
    # every fused decode step reads the stacked pools by layer index
    assert got["paged"]["layer_indexed_steps"] == got["decode_steps"] > 0
    assert want["paged"]["layer_indexed_steps"] == 0
    assert got["paged"]["lane_activations"] == 0
    assert got["paged"]["tail_restores"] > 0       # park -> reactivate ran
    assert got["paged"]["gather_bytes_eliminated"] > 0
    assert got["paged"]["resident_requests_peak"] > 2


def test_fused_decode_page_counters():
    """The report's page counters over the fused decode steps: the pages
    that hold each active slot's keys (a slot at position p reads p + 1
    rows, ceil((p + 1) / page) pages), and the whole block table's."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    page, prompt = 8, 8
    reqs = _requests(cfg, [9, 5, 12, 3], prompt_len=prompt)
    got = _run(cfg, reqs, fused=True, page_size=page, prefix_sharing=False)
    assert got["paged"]["preemptions"] == 0
    # the prefill serves the first token, one decode step each after it
    positions = [p for out in got["outputs"].values()
                 for p in range(prompt, prompt + len(out) - 1)]
    assert len(positions) == got["decode_slot_steps"]
    assert got["decode_live_pages"] == sum(-(-(p + 1) // page)
                                           for p in positions)
    assert got["decode_table_pages"] == got["decode_steps"] * 2 * (24 // page)
    assert 0 < got["decode_live_pages"] < got["decode_table_pages"]


def test_fused_sampled_matches_lane():
    """Sampled decoding (temperature + top-k) draws the SAME per-step keys:
    the fused pool read must not change a single draw vs lane activation.
    Both requests arrive at once, so neither engine's batching (and so its
    key draws) depends on how fast the host runs."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    samp = SamplingConfig(temperature=0.8, top_k=16)
    reqs = _requests(cfg, [6, 5], seed=2, stagger=0.0)
    want = _run(cfg, reqs, fused=False, sampling=samp, prefix_sharing=False)
    got = _run(cfg, reqs, fused=True, sampling=samp, prefix_sharing=False)
    assert got["outputs"] == want["outputs"]


def test_fused_cow_shared_prefix_pages():
    """Prefix sharing under fused decode: sharers read the published pages
    through their block tables (CoW keeps them immutable) and still emit the
    lane engine's tokens, prefill-once preserved."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    rng = np.random.default_rng(7)
    system = rng.integers(0, cfg.vocab, 16).astype(np.int32)
    reqs = _requests(cfg, [3, 3, 3, 3], prompt_len=24, seed=8, prefix=system)
    want = _run(cfg, reqs, fused=False, max_len=48, page_size=16)
    got = _run(cfg, reqs, fused=True, max_len=48, page_size=16)
    assert got["outputs"] == want["outputs"]
    assert got["paged"]["prefix_hits"] == 3
    assert got["paged"]["prefix_misses"] == 1


def test_fused_speculative_matches_lane():
    """Draft/verify through attention_verify_paged (and the zamba commit
    replay through the fused span) emits exactly the lane engine's tokens."""
    for arch in ("qwen1.5-0.5b", "zamba2-7b"):
        cfg = get_config(arch).reduced()
        reqs = _requests(cfg, [8, 8, 6], seed=3)
        spec = SpeculationConfig(drafter="ngram", k_max=3, fixed_k=2)
        want = _run(cfg, reqs, fused=False, max_len=32, speculation=spec)
        got = _run(cfg, reqs, fused=True, max_len=32, speculation=spec)
        assert got["outputs"] == want["outputs"], arch
        assert got["spec"]["verify_steps"] > 0
        assert got["paged"]["layer_indexed_steps"] == got["decode_steps"]


def test_spill_rehydrate_exact_tokens():
    """A page budget too small for five resident requests forces the spill
    tier: cold parked pages move to host arrays and rehydrate on
    reactivation — tokens must still match the contiguous engine exactly,
    with at least one full spill/rehydrate cycle observed."""
    cfg = get_config("qwen1.5-0.5b").reduced()
    reqs = _requests(cfg, [4, 4, 4, 4, 4], stagger=0.02, seed=9)
    jax.clear_caches()
    want = ServeEngine(cfg, batch=2, max_len=24, seed=0).run(
        [Request(**vars(r)) for r in reqs])
    probe = PagedConfig(page_size=8)
    jax.clear_caches()
    pb = ServeEngine(cfg, batch=2, max_len=24, seed=0,
                     paged=probe)._store.page_bytes
    got = _run(cfg, reqs, fused=True, page_size=8, hbm_budget_bytes=5 * pb)
    assert got["outputs"] == want["outputs"]
    assert got["paged"]["spills"] >= 1
    assert got["paged"]["rehydrates"] >= 1
    assert got["paged"]["host_spill_bytes"] == 0   # everything came back


def test_rwkv_falls_back_to_lanes():
    """No paged leaves -> no fused contract: the engine must flag
    fused=False and keep emitting the contiguous engine's tokens through
    the lane path."""
    cfg = get_config("rwkv6-7b").reduced()
    reqs = _requests(cfg, [5, 4, 4, 3])
    jax.clear_caches()
    want = ServeEngine(cfg, batch=2, max_len=24, seed=0).run(
        [Request(**vars(r)) for r in reqs])
    got = _run(cfg, reqs, fused=True)
    assert got["outputs"] == want["outputs"]
    assert not got["paged"]["fused"]
    assert got["paged"]["lane_activations"] > 0


def test_int8_fused_pool_absmax_bound():
    """int8 fused pools: rows written through store_donor guarantee
    |dequantized - original| <= absmax(row)/254 per last-axis row — the
    same bound the lane-path store pins, now on the (KH, NP, page, D)
    fused pool layout with its per-row scale pools."""
    rng = np.random.default_rng(3)
    shapes = {"k": jax.ShapeDtypeStruct((1, 2, 32, 8), np.float32)}
    st = PagedKVStore(shapes, {"k": 2}, page_size=8, n_pages=8, int8=True,
                      fused=True)
    donor = {"k": np.asarray(rng.normal(size=(1, 2, 32, 8)), np.float32)}
    st.attach("a", prompt_rows=32)
    st.store_donor("a", {n: jax.numpy.asarray(v) for n, v in donor.items()},
                   fill=32)
    pools = st.device_pools()
    tab = st.table_row("a", 4)
    # pool (1, KH, NP, page, D): token axis split in place at ax=2 — walk
    # the request's table to get its rows back in donor order
    kq = np.asarray(pools["k"])[0][:, tab[:4]].reshape(2, 32, 8)
    ks = np.asarray(pools["k__scale"])[0][:, tab[:4]].reshape(2, 32, 1)
    deq = np.asarray(dequantize_absmax_int8(kq, ks, dtype=np.float32))
    want = donor["k"][0]                           # (KH, 32, 8)
    err = np.abs(deq - want)
    bound = np.abs(want).max(-1, keepdims=True) / 254.0 + 1e-7
    assert (err <= bound).all()


def _scan_operands(jaxpr):
    """(carry invars, xs invars, ys outvars) of every scan, nested ones
    included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            n_c, n_k = eqn.params["num_consts"], eqn.params["num_carry"]
            out.append((eqn.invars[n_c:n_c + n_k], eqn.invars[n_c + n_k:],
                        eqn.outvars[n_k:]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out += _scan_operands(sub)
    return out


@pytest.mark.parametrize("arch,enc_len", [("qwen1.5-0.5b", None),
                                          ("zamba2-7b", None),
                                          ("whisper-tiny", 8)])
def test_fused_decode_carries_pools_through_layer_scan(arch, enc_len):
    """In each family's fused paged decode program the stacked pool leaves
    enter the layer scan as carries and never as scan xs or ys: no layer's
    slice of a pool is taken out or stacked back."""
    cfg = get_config(arch).reduced()
    eng = ServeEngine(cfg, batch=2, max_len=24, seed=0, enc_len=enc_len,
                      paged=PagedConfig(fused=True))
    pools = eng._store.device_pools()
    state = eng._init_state()
    tables = np.zeros((2, eng._table_width), np.int32)
    closed = jax.make_jaxpr(eng.model.decode_step_paged)(
        eng.params, state, pools, tables, np.zeros((2, 1), np.int32),
        np.zeros((2,), np.int32))
    pool_vars = set(closed.jaxpr.invars[
        len(jax.tree.leaves(eng.params)) + len(jax.tree.leaves(state)):][
            :len(pools)])
    tails = {a.shape[-4:] for a in pools.values()}
    scans = _scan_operands(closed.jaxpr)
    assert pool_vars and pool_vars <= {v for carry, _, _ in scans
                                       for v in carry
                                       if type(v).__name__ != "Literal"}
    for _, xs, ys in scans:
        for v in (*xs, *ys):
            assert tuple(v.aval.shape[-4:]) not in tails, (arch, v.aval)
