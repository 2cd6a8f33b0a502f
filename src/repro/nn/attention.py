"""Attention layer on TSL primitives: GQA + RoPE + optional qk_norm/bias.

Full-sequence path uses tsl.flash_attention (Pallas on TPU targets);
decode path uses tsl.attention_decode + tsl.cache_update (KV cache layout
(B, KH, S_max, hd) — heads-major so the TP shard dim is contiguous).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.paged import rows as paged_rows
from repro.tsl_api import ops as tsl

from .common import dense_init, split_keys
from .rope import rope_tables


def init_attention(key, cfg, dtype):
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = split_keys(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * hd), dtype),
        "wk": dense_init(ks[1], (d, kh * hd), dtype),
        "wv": dense_init(ks[2], (d, kh * hd), dtype),
        "wo": dense_init(ks[3], (h * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kh * hd,), dtype)
        p["bv"] = jnp.zeros((kh * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(p, x, cfg, positions):
    """x: (B,S,D) -> q (B,H,S,hd), k/v (B,KH,S,hd) with RoPE applied."""
    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = tsl.matmul(x, p["wq"])
    k = tsl.matmul(x, p["wk"])
    v = tsl.matmul(x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kh, hd)
    v = v.reshape(b, s, kh, hd)
    if cfg.qk_norm:
        q = tsl.rmsnorm(q, p["q_norm"], eps=cfg.norm_eps)
        k = tsl.rmsnorm(k, p["k_norm"], eps=cfg.norm_eps)
    cos, sin = rope_tables(positions, hd, cfg.rope_theta)   # (S, hd/2) or (B,S,hd/2)
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q = tsl.rope_apply(q, cos, sin)
    k = tsl.rope_apply(k, cos, sin)
    # heads-major, heads TP-sharded (megatron-style attention parallelism)
    from repro.dist.sharding import logical_constraint
    q = logical_constraint(q.transpose(0, 2, 1, 3), "batch", "heads", None, None)
    k = logical_constraint(k.transpose(0, 2, 1, 3), "batch", "heads", None, None)
    v = logical_constraint(v.transpose(0, 2, 1, 3), "batch", "heads", None, None)
    return q, k, v


def attention_forward(p, x, cfg, *, causal: bool = True, positions=None):
    """Full-sequence attention. x: (B,S,D) -> (B,S,D); returns (y, (k, v))."""
    b, s, d = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(p, x, cfg, positions)
    o = tsl.flash_attention(q, k, v, causal=causal)          # (B,H,S,hd)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.hd)
    return tsl.matmul(o, p["wo"]), (k, v)


def cross_attention_forward(p, x, k, v, cfg):
    """Decoder cross-attention against precomputed encoder K/V."""
    b, s, d = x.shape
    h, hd = cfg.n_heads, cfg.hd
    q = tsl.matmul(x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    o = tsl.flash_attention(q, k, v, causal=False)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return tsl.matmul(o, p["wo"])


def project_kv(p, x, cfg):
    """Encoder-side K/V projection for cross attention. x: (B,S,D)."""
    b, s, _ = x.shape
    kh, hd = cfg.n_kv_heads, cfg.hd
    k = tsl.matmul(x, p["wk"])
    v = tsl.matmul(x, p["wv"])
    if cfg.qkv_bias:
        k, v = k + p["bk"], v + p["bv"]
    return (k.reshape(b, s, kh, hd).transpose(0, 2, 1, 3),
            v.reshape(b, s, kh, hd).transpose(0, 2, 1, 3))


def attention_prefill_chunk(p, x, k_cache, v_cache, pos, cfg):
    """Continuation prefill of one chunk into an existing cache.

    x: (B, C, D) chunk activations; caches (B, KH, S_max, hd) filled to
    ``pos`` real rows. Writes the chunk's K/V at rows [pos, pos+C) and
    attends the chunk queries against the whole cache through
    ``tsl.attention_prefill_chunk`` (causal, ends-aligned at pos+C).

    Rows the caller marks as padding (its ``n_real < C``) need no masking
    here: a padded row i >= n_real sits at position pos+i, strictly AFTER
    every real row, so the causal mask already hides its key from every real
    query; its own output row is garbage the caller discards, and its cache
    row lies beyond the real fill (pos+n_real) where the decode-path kv_len
    mask hides it until the next chunk/decode step overwrites it.

    ``pos`` may be traced (jit-stable over cache fill) and may be a (B,)
    vector of PER-SLOT base positions (ragged chunks over the slot table:
    RoPE, the slab scatter, and kv_len all become per-slot). Returns
    (y (B,C,D), k_cache', v_cache')."""
    return _span_attend(p, x, k_cache, v_cache, pos, cfg,
                        tsl.attention_prefill_chunk)


def attention_verify(p, x, k_cache, v_cache, pos, cfg):
    """Speculative-decoding verify span: x (B,SV,D) holds each slot's pending
    token + drafted continuation; ``pos`` is the span's base write position
    (scalar or (B,) per-slot). Writes the span's K/V at rows [pos, pos+SV)
    and scores every row in ONE ragged batched step through
    ``tsl.attention_verify`` (causal, ends-aligned at pos+SV), so row j's
    output is independent of rows > j — the accepted-prefix contract.
    Rollback of rejected rows is free: they lie beyond the committed kv_len,
    where the decode-path mask hides them until overwritten.

    Returns (y (B,SV,D), k_cache', v_cache')."""
    return _span_attend(p, x, k_cache, v_cache, pos, cfg, tsl.attention_verify)


def _span_attend(p, x, k_cache, v_cache, pos, cfg, span_op):
    """Shared prefill-chunk / verify-span body: project, slab-write, attend."""
    b, c, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    positions = (pos[:, None] + jnp.arange(c)[None, :] if per_slot
                 else pos + jnp.arange(c))
    # same projection pipeline (bias/qk_norm/RoPE/TP sharding) as the
    # full-sequence path — q/k/v come back heads-major (B,{H|KH},C,hd)
    q, k, v = _project_qkv(p, x, cfg, positions)
    if per_slot:
        # per-slot slab scatter: vmap the TSL update over the batch axis so
        # each slot writes its C rows at its own base (leaf (KH,S,hd): the
        # update (KH,C,hd) lands along axis 1 = S)
        upd = jax.vmap(tsl.cache_update)
        k_cache = upd(k_cache, k, pos)
        v_cache = upd(v_cache, v, pos)
    else:
        # contiguous C-row slab write at the chunk's base position (cache
        # layout (B,KH,S,hd): tsl.cache_update writes along axis 1 -> swap
        # S forward)
        k_cache = jnp.swapaxes(
            tsl.cache_update(jnp.swapaxes(k_cache, 1, 2),
                             k.transpose(0, 2, 1, 3), pos), 1, 2)
        v_cache = jnp.swapaxes(
            tsl.cache_update(jnp.swapaxes(v_cache, 1, 2),
                             v.transpose(0, 2, 1, 3), pos), 1, 2)
    o = span_op(q, k_cache, v_cache, kv_len=pos + c)
    o = o.transpose(0, 2, 1, 3).reshape(b, c, h * hd)
    return tsl.matmul(o, p["wo"]), k_cache, v_cache


def attention_span_paged(p, x, pools, layer, tables, pos, cfg, span_op, *,
                         names=("k", "v")):
    """Fused paged decode/verify span: project a span of C tokens per slot,
    write each row STRAIGHT into its block-table page, and attend directly
    against the page pool — no page->lane gather anywhere.

    x: (B, C, D) span activations (C == 1 is the decode step); ``pools`` the
    serve-layer pool dict, whose ``names`` leaves are this attention's K and
    V pools stacked over layers, (L, ..., KH, n_pages, page // f, w) in the
    packed row format of ``kernels/paged/rows.py``; ``layer`` (traced
    int32) the flat index of this layer in the stack's leading axes; tables
    (B, P) int32 page ids; ``pos`` scalar or (B,) per-slot base write
    positions. The rows land at [layer, :, page, row] in place
    (``cache_token_write_paged``) and the attention primitive reads the
    stack at ``layer``: no layer slice is taken, so a caller that
    carries the pools through its layer loop updates them in place.
    ``span_op`` is ``tsl.attention_decode_paged`` (C == 1) or
    ``tsl.attention_verify_paged``; both mask ends-aligned at kv_len = pos +
    C, so rows beyond a slot's committed fill are dead — rollback stays free
    exactly as in the lane path. ``{name}__scale`` pools (..., page, 1)
    switch a leaf to the absmax-int8 wire format: rows quantize per write
    and dequantize per touched page inside the primitive. Inactive slots
    must point at a scratch page (valid id): their row writes and reads land
    there harmlessly.

    Returns (y (B,C,D), pools')."""
    from repro.dist.compression import quantize_absmax_int8

    b, c, _ = x.shape
    h, hd = cfg.n_heads, cfg.hd
    kn, vn = names
    pool_k = pools[kn]
    page = paged_rows.page_tokens(pool_k, hd)
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (b,))
    positions = pos[:, None] + jnp.arange(c)[None, :]          # (B, C)
    q, k, v = _project_qkv(p, x, cfg, positions)  # q (B,H,C,hd) k/v (B,KH,C,hd)
    tab = jnp.asarray(tables, jnp.int32)
    pid = jnp.take_along_axis(tab, positions // page, axis=1)  # (B, C)
    off = positions % page
    pools = dict(pools)
    for name, rows in ((kn, k), (vn, v)):
        if f"{name}__scale" in pools:
            rows, sc = quantize_absmax_int8(rows)
            pools[f"{name}__scale"] = tsl.cache_token_write_paged(
                pools[f"{name}__scale"], sc, pid, off, layer=layer)
        pools[name] = tsl.cache_token_write_paged(pools[name], rows, pid,
                                                  off, layer=layer)
    o = span_op(q, pools[kn], pools[vn], tab, kv_len=pos + c, layer=layer,
                k_scale=pools.get(f"{kn}__scale"),
                v_scale=pools.get(f"{vn}__scale"))
    o = o.transpose(0, 2, 1, 3).reshape(b, c, h * hd)
    return tsl.matmul(o, p["wo"]), pools


def attention_decode(p, x_t, k_cache, v_cache, pos, cfg, *, rope: bool = True):
    """One-token decode. x_t: (B,1,D); caches (B,KH,S_max,hd); pos: scalar
    write index, or a (B,) vector of PER-SLOT write indices (continuous
    batching: each slot of the live batch sits at its own position — RoPE,
    the cache scatter, and the kv_len mask all become per-slot).

    Returns (y (B,1,D), k_cache', v_cache')."""
    b = x_t.shape[0]
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    q = tsl.matmul(x_t, p["wq"])
    k = tsl.matmul(x_t, p["wk"])
    v = tsl.matmul(x_t, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, 1, h, hd)
    k = k.reshape(b, 1, kh, hd)
    v = v.reshape(b, 1, kh, hd)
    if cfg.qk_norm:
        q = tsl.rmsnorm(q, p["q_norm"], eps=cfg.norm_eps)
        k = tsl.rmsnorm(k, p["k_norm"], eps=cfg.norm_eps)
    if rope:
        if per_slot:
            cos, sin = rope_tables(pos[:, None], hd, cfg.rope_theta)  # (B,1,hd/2)
            cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        else:
            cos, sin = rope_tables(pos[None], hd, cfg.rope_theta)
            cos, sin = cos[None, :, None, :], sin[None, :, None, :]
        q = tsl.rope_apply(q, cos, sin)
        k = tsl.rope_apply(k, cos, sin)
    q = q.transpose(0, 2, 1, 3)
    if per_slot:
        # per-slot scatter: vmap the TSL update over the batch axis, so each
        # slot writes its own row (cache leaf (KH,S,hd): axis 1 is still S)
        upd = jax.vmap(tsl.cache_update)
        k_cache = upd(k_cache, k.transpose(0, 2, 1, 3), pos)
        v_cache = upd(v_cache, v.transpose(0, 2, 1, 3), pos)
    else:
        # cache layout (B,KH,S,hd): update along axis 2 -> move axis for
        # tsl.cache_update (axis 1)
        k_cache = jnp.swapaxes(
            tsl.cache_update(jnp.swapaxes(k_cache, 1, 2), k, pos), 1, 2)
        v_cache = jnp.swapaxes(
            tsl.cache_update(jnp.swapaxes(v_cache, 1, 2), v, pos), 1, 2)
    o = tsl.attention_decode(q, k_cache, v_cache, kv_len=pos + 1)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * hd)
    return tsl.matmul(o, p["wo"]), k_cache, v_cache
