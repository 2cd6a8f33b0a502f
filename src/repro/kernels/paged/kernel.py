"""Pallas TPU kernel: fused paged attention over a block table.

The lane-gather elimination behind the ``attention_decode_paged`` /
``attention_verify_paged`` UPD primitives: instead of activating a slot's
pages into a contiguous lane and running ``attention_decode`` there, the
kernel walks the PAGE POOL directly. The block table and per-slot kv_len
arrive as scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so
each K/V BlockSpec index map translates a *logical* page index into a
*physical* pool page before the DMA is issued — the page indirection is
folded into the Pallas pipeline itself.

Schedule: grid (B, KH // hg, P), P the table width. One grid step takes
one slot, one table page and a group of ``hg`` KV heads: all of them
whenever their double-buffered pages fit the VMEM budget, so the step count
follows slots and pages, not heads x key blocks. Inside the page the body
walks ``block_k``-row key sub-blocks (the UPD candidates) in a loop that
stops at kv_len, each one a head-batched score and readout product feeding
an f32 online softmax whose (m, l, acc) scratch carries across the
sequential page axis as in the flash-attention forward. GQA is folded by
shaping q as (B, KH, group * SQ, D): all of a KV head's query heads ride in
the q block's row axis, so each pool page is fetched once per KV head.

Nothing is fetched past a slot's kv_len: the index maps clamp a page past
the last live one to that last live page, and a block index that repeats
from one grid step to the next issues no copy. Table entries must still
hold VALID page ids (the serving layer points them at a scratch page).
kv_len == 0 rows finalize to exactly 0 (l stays 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ..common import LANES, ROW_BLOCK_VMEM_BYTES, round_up

NEG_INF = -1e30


def head_group(kh: int, page: int, d: int, itemsize: int) -> int:
    """KV heads per grid step for a pool of ``kh`` heads of ``page`` x ``d``
    rows: the largest divisor of ``kh`` whose double-buffered K and V page
    blocks fit :data:`ROW_BLOCK_VMEM_BYTES` (a row takes whole 128-lane
    tiles in VMEM)."""
    head_page = 2 * 2 * page * round_up(d, LANES) * itemsize   # K, V; 2 buffers
    return max(g for g in range(1, kh + 1)
               if kh % g == 0 and (g == 1 or g * head_page <= ROW_BLOCK_VMEM_BYTES))


def _update(q, k, v, k0, kvl, m_scr, l_scr, acc_scr, *, scale: float,
            sq: int):
    """One online-softmax step of every head in the group over the key rows
    at logical positions [k0, k0 + bk). q (hg, rq, D); k/v (hg, bk, D)."""
    hg, rq, _ = q.shape
    bk = k.shape[1]
    ct = jnp.promote_types(q.dtype, k.dtype)
    s = jax.lax.dot_general(
        q.astype(ct), k.astype(ct), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale     # (hg, rq, bk)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, (hg, rq, bk), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (hg, rq, bk), 1)
    # span rows are ends-aligned at kv_len: row r sits at kvl - sq + r%sq
    valid = k_pos <= kvl - sq + jax.lax.rem(row, sq)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[:, :, :1]                             # (hg, rq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[:, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _paged_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, sq: int, page: int,
                  bk: int, n_p: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    kvl = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # this step's page holds logical keys [j*page, (j+1)*page); its live
    # sub-blocks are those below kv_len, none once the page lies past it
    n_sub = jnp.clip(pl.cdiv(kvl - j * page, bk), 0, page // bk)

    def sub(t, carry):
        off = pl.multiple_of(t * bk, bk)
        _update(q_ref[0], k_ref[:, pl.ds(off, bk), :],
                v_ref[:, pl.ds(off, bk), :], j * page + off, kvl,
                m_scr, l_scr, acc_scr, scale=scale, sq=sq)
        return carry

    jax.lax.fori_loop(0, n_sub, sub, 0)

    @pl.when(j == n_p - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        o = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = jnp.where(l > 0.0, o, 0.0).astype(o_ref.dtype)


def paged_attention_4d(q, k_flat, v_flat, tables, kv_len, *, sq: int,
                       page: int, block_k: int, scale: float | None = None,
                       interpret: bool = False):
    """q: (B, KH, RQ, D) — RQ = group*sq padded to a sublane multiple;
    k_flat/v_flat: (KH, n_pages*page, D) row-flattened pools; tables: (B, P)
    int32 page ids; kv_len: (B,) int32. Returns (B, KH, RQ, D)."""
    b, kh, rq, d = q.shape
    assert page % block_k == 0, (page, block_k)
    n_p = tables.shape[1]
    hg = head_group(kh, page, d, jnp.dtype(k_flat.dtype).itemsize)
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    def kv_idx(b_, g, j, tab, ln):
        # the step's page, clamped to the slot's last live page: past it the
        # block index repeats, and a repeated block index issues no DMA
        last = jnp.maximum(pl.cdiv(ln[b_], page) - 1, 0)
        return (g, tab[b_, jnp.minimum(j, last)], 0)

    q_spec = pl.BlockSpec((1, hg, rq, d), lambda b_, g, j, tab, ln: (b_, g, 0, 0))
    kv_spec = pl.BlockSpec((hg, page, d), kv_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh // hg, n_p),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hg, rq, LANES), jnp.float32),   # running max
            pltpu.VMEM((hg, rq, LANES), jnp.float32),   # running denominator
            pltpu.VMEM((hg, rq, d), jnp.float32),       # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=sc, sq=sq, page=page,
                               bk=block_k, n_p=n_p)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tsl_paged_attention",
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(kv_len, jnp.int32),
      q, k_flat, v_flat)
