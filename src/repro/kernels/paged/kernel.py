"""Pallas TPU kernel: fused paged attention over a block table.

The lane-gather elimination behind the ``attention_decode_paged`` /
``attention_verify_paged`` UPD primitives: instead of activating a slot's
pages into a contiguous lane and running ``attention_decode`` there, the
kernel walks the PAGE POOL directly. The block table and per-slot kv_len
arrive as scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so
each K/V BlockSpec index map translates a *logical* page index into a
*physical* pool page before the DMA is issued — the page indirection is
folded into the Pallas pipeline itself.

The pool may be a whole stack of layers: the row-flattened operand is
``(L * KH, rows, W)`` and the layer to read arrives as a third
scalar-prefetch operand, which the K/V index maps turn into the head-group
block ``layer * (KH // hg) + g``. Only the step's pages of that layer are
fetched; the stack is never sliced or copied.

A pool row may pack ``f = W // D`` consecutive tokens, its lanes past
``f * D`` padding (a page is then ``page // f`` rows; the serving layer's
format, ``rows.py``). The query arrives expanded to match (``f`` row
blocks, block ``s`` holding q in lanes ``[s*D, (s+1)*D)`` and zeros
elsewhere), so block ``s`` scores exactly the keys of token slot ``s`` of
each row; every slot updates the one online softmax of its query rows, and
with ``f`` > 1 its readout keeps only its own lanes and the output is f32,
its ``f`` lane blocks summing to the result. With ``f`` = 1 the output is
q's dtype and shape.

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
            sq: int, fold: int, slot: int, d: int):
    """One online-softmax step of every head in the group over key rows
    r = 0 .. bk-1 of a sub-block, token ``slot`` of each row at logical
    position k0 + fold * r + slot. q (hg, rq, W); k/v (hg, bk, W); with
    ``fold`` > 1 only lanes [slot*d, (slot+1)*d) of the readout are kept."""
    hg, rq, _ = q.shape
    bk = k.shape[1]
    ct = jnp.promote_types(q.dtype, k.dtype)
    s = jax.lax.dot_general(
        q.astype(ct), k.astype(ct), (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale     # (hg, rq, bk)
    k_pos = k0 + slot + fold * jax.lax.broadcasted_iota(jnp.int32,
                                                        (hg, rq, bk), 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (hg, rq, bk), 1)
    # span rows are ends-aligned at kv_len: row r sits at kvl - sq + r%sq
    valid = k_pos <= kvl - sq + jax.lax.rem(row, sq)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[:, :, :1]                             # (hg, rq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_scr[:, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)              # (hg, rq, W)
    if fold > 1:
        lane = jax.lax.broadcasted_iota(jnp.int32, pv.shape, 2)
        pv = jnp.where(lane // d == slot, pv, 0.0)
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _paged_kernel(tab_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, sq: int, page: int,
                  bk: int, n_p: int, fold: int, d: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    kvl = len_ref[b]
    rq = o_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # this step's page holds logical keys [j*page, (j+1)*page), ``bk`` pool
    # rows of ``fold`` keys each per sub-block; its live sub-blocks are
    # those below kv_len, none once the page lies past it
    rows = page // fold
    n_sub = jnp.clip(pl.cdiv(kvl - j * page, bk * fold), 0, rows // bk)

    def sub(t, carry):
        off = pl.multiple_of(t * bk, bk)
        k = k_ref[:, pl.ds(off, bk), :]
        v = v_ref[:, pl.ds(off, bk), :]
        for slot in range(fold):
            _update(q_ref[0, :, slot * rq:(slot + 1) * rq], k, v,
                    j * page + off * fold, kvl, m_scr, l_scr, acc_scr,
                    scale=scale, sq=sq, fold=fold, slot=slot, d=d)
        return carry

    jax.lax.fori_loop(0, n_sub, sub, 0)

    @pl.when(j == n_p - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        o = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0] = jnp.where(l > 0.0, o, 0.0).astype(o_ref.dtype)


def paged_attention_4d(q, k_flat, v_flat, tables, kv_len, layer, *, sq: int,
                       page: int, d: int, block_k: int,
                       scale: float | None = None, interpret: bool = False):
    """q: (B, KH, fold*RQ, W) — ``fold = W // d`` row blocks of RQ =
    group*sq rows padded to a sublane multiple, block s holding the d query
    lanes at [s*d, (s+1)*d) and zeros elsewhere; k_flat/v_flat:
    (L*KH, n_pages*page // fold, W) row-flattened pools of L layers, a page
    of ``page`` tokens in ``page // fold`` rows; tables: (B, P) int32 page
    ids; kv_len: (B,) int32; layer: int32 scalar, the layer of the stack to
    read; ``block_k``: pool rows per key sub-block. Returns (B, KH, RQ, W):
    q's dtype at fold 1, else float32 whose ``fold`` lane blocks sum to the
    output."""
    b, kh, frq, w = q.shape
    fold = w // d
    rq, rows = frq // fold, page // fold
    assert rows % block_k == 0, (rows, block_k)
    n_p = tables.shape[1]
    hg = head_group(kh, rows, w, jnp.dtype(k_flat.dtype).itemsize)
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    def kv_idx(b_, g, j, tab, ln, lyr):
        # the layer's head group, and the step's page clamped to the slot's
        # last live page: past it the block index repeats, and a repeated
        # block index issues no DMA
        last = jnp.maximum(pl.cdiv(ln[b_], page) - 1, 0)
        return (lyr[0] * (kh // hg) + g, tab[b_, jnp.minimum(j, last)], 0)

    def q_idx(b_, g, j, tab, ln, lyr):
        return (b_, g, 0, 0)

    kv_spec = pl.BlockSpec((hg, rows, w), kv_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kh // hg, n_p),
        in_specs=[pl.BlockSpec((1, hg, frq, w), q_idx), kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, hg, rq, w), q_idx),
        scratch_shapes=[
            pltpu.VMEM((hg, rq, LANES), jnp.float32),   # running max
            pltpu.VMEM((hg, rq, LANES), jnp.float32),   # running denominator
            pltpu.VMEM((hg, rq, w), jnp.float32),       # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=sc, sq=sq, page=page,
                               bk=block_k, n_p=n_p, fold=fold, d=d)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, kh, rq, w), q.dtype if fold == 1 else jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tsl_paged_attention",
    )(jnp.asarray(tables, jnp.int32), jnp.asarray(kv_len, jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q, k_flat, v_flat)
