"""Pallas TPU kernel: fused paged attention over a block table.

The lane-gather elimination behind the ``attention_decode_paged`` /
``attention_verify_paged`` UPD primitives: instead of activating a slot's
pages into a contiguous lane and running ``attention_decode`` there, the
kernel walks the PAGE POOL directly. The block table and per-slot kv_len
arrive as scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``), so
each K/V BlockSpec index map can translate a *logical* key-block index into
a *physical* pool row before the DMA is issued — the page indirection is
folded into the Pallas pipeline itself and the only HBM traffic is the
touched pages.

Grid: (B, KH, n_j) where n_j = max_pages * (page // block_k); the j axis is
"arbitrary" (sequential) so the online-softmax (m, l, acc) scratch carries
across key blocks exactly as in the flash-attention forward. GQA is folded
by shaping q as (B, KH, group * SQ, D): all of a KV head's query heads ride
in the q block's row axis, so each pool page is fetched once per KV head.

Blocks past a slot's kv_len are skipped by a block-level early exit on the
prefetched length; their table entries must still hold a VALID page id (the
serving layer points them at a scratch page) because the index map runs
unconditionally. kv_len == 0 rows finalize to exactly 0 (l stays 0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _paged_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, sq: int,
                  bk: int, n_j: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    kvl = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # key blocks are visited in logical order (page-major, sub-block minor),
    # so this block covers logical key positions [j*bk, (j+1)*bk)
    @pl.when(j * bk < kvl)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)              # (rq, D)
        k = k_ref[0].astype(jnp.float32)                 # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        rq = q.shape[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rq, bk)
        k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (rq, bk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rq, bk), 0)
        # span rows are ends-aligned at kv_len: row r sits at kvl - sq + r%sq
        q_pos = kvl - sq + jax.lax.rem(row, sq)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_scr[:, :1]                            # (rq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(k_pos <= q_pos, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_j - 1)
    def _finalize():
        l = l_scr[:, :1]
        o = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[0, 0] = jnp.where(l > 0.0, o, 0.0).astype(o_ref.dtype)


def paged_attention_4d(q, k_flat, v_flat, tables, kv_len, *, sq: int,
                       page: int, block_k: int, scale: float | None = None,
                       interpret: bool = False):
    """q: (B, KH, RQ, D) — RQ = group*sq padded to a sublane multiple;
    k_flat/v_flat: (KH, n_pages*page, D) row-flattened pools; tables: (B, P)
    int32 page ids; kv_len: (B,) int32. Returns (B, KH, RQ, D)."""
    b, kh, rq, d = q.shape
    assert page % block_k == 0, (page, block_k)
    spp = page // block_k                       # key sub-blocks per page
    n_p = tables.shape[1]
    n_j = n_p * spp
    sc = scale if scale is not None else 1.0 / (d ** 0.5)

    def kv_idx(b_, h_, j, tab, _len):
        # physical block index into the row-flattened pool, in bk units:
        # page id * sub-blocks-per-page + sub-block within the page
        return (h_, tab[b_, j // spp] * spp + jax.lax.rem(j, spp), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, n_j),
        in_specs=[
            pl.BlockSpec((1, 1, rq, d), lambda b_, h_, j, tab, ln: (b_, h_, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_idx),
            pl.BlockSpec((1, block_k, d), kv_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, rq, d),
                               lambda b_, h_, j, tab, ln: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rq, _LANES), jnp.float32),   # running max
            pltpu.VMEM((rq, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((rq, d), jnp.float32),        # output accumulator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=sc, sq=sq, bk=block_k,
                               n_j=n_j)
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
