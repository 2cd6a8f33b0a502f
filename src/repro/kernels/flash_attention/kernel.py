"""Pallas TPU kernels: blockwise flash attention (fwd + bwd) with GQA folding.

TPU adaptation of the (GPU-origin) FlashAttention online-softmax algorithm
(DESIGN.md §2): instead of warp-level shared-memory staging, blocks of
Q (bq × D) and K/V (bk × D) are staged HBM→VMEM by the Pallas pipeline; the
two matmuls per step are MXU-shaped (bq,D)x(D,bk) and (bq,bk)x(bk,D) with
f32 VREG accumulators held in VMEM scratch across the sequential k-grid.

Forward grid: (B, H, Sq/bq, Sk/bk) — the last dimension is "arbitrary"
(sequential) so the running (m, l, acc) scratch carries across k blocks; the
first three are "parallel". GQA is folded via the K/V index maps
(h -> h // group), so KV blocks are fetched once per KV head group without
materializing the H-times-replicated cache in HBM — that replication is
exactly the waste the GPU implementations avoid with shared memory, adapted
here to VMEM reuse.

Backward (FlashAttention-2 style recomputation): the forward additionally
emits per-row logsumexp residuals ``lse = m + log(l)`` of shape
(B, H, Sq, 1) — a trailing unit lane axis, so the (1, 1, bq, 1) residual
block meets the TPU tiling rule (second-minor a multiple of 8, minor equal
to the array's),
so the backward never re-materializes the (Sq, Sk) score matrix — each tile
is recomputed as ``p = exp(s - lse)`` and immediately contracted away:

* ``dq`` kernel, q-tiled: grid (B, H, Sq/bq, Sk/bk), sequential over k
  blocks, accumulating ``dq += (p * (dO·vᵀ - delta)) @ k`` in VMEM scratch;
* ``dk/dv`` kernel, k-tiled: grid (B, H, Sk/bk, Sq/bq), sequential over q
  blocks, accumulating ``dv += pᵀ @ dO`` and ``dk += dsᵀ @ q`` per *query*
  head (f32 outputs); the GQA head-group reduction to KV heads is a cheap
  O(Sk·D) reshape-sum done by the caller.

``delta = rowsum(dO ⊙ O)`` is O(Sq) per head and precomputed outside.

VMEM per step (bq=bk=512, D=128, bf16): q 128K, k/v 256K, acc f32 256K,
p f32 1M — ≈ 2 MiB, far under the v5e budget; larger bq trades grid steps
for VMEM (hillclimb lever recorded in EXPERIMENTS.md §Perf).

Causal masking uses global row/col iota comparison; fully-masked (qi, ki)
tiles still execute (static grid) — skipping them is the classic 2x win,
implemented as an early-exit `when` on the block predicate shared by the
forward and both backward kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1e30
_LANES = 128


def _block_needed(qi, ki, *, causal: bool, q_offset: int, bq: int, bk: int):
    """Static-grid early-exit predicate: is causal tile (qi, ki) reachable?"""
    return jnp.logical_or(
        jnp.logical_not(causal),
        (ki * bk) <= (qi * bq + bq - 1 + q_offset),
    )


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs, scale: float,
                  causal: bool, kv_len: int, q_offset: int,
                  bq: int, bk: int):
    # refs = (m, l, acc) scratch, optionally preceded by an lse output ref
    if len(refs) == 4:
        lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # global positions of this tile (ends-aligned causal: logical q row r
    # attends to keys <= r + q_offset, supporting prefill continuation;
    # q_offset = kv_len - logical_sq, computed on the UNPADDED q length)
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    # block-level early exit: skip fully-masked causal tiles
    block_needed = _block_needed(qi, ki, causal=causal, q_offset=q_offset,
                                 bq=bq, bk=bk)

    @pl.when(block_needed)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                         # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_new = corr * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        o = acc_scr[...] / jnp.maximum(l, 1e-30)
        o = jnp.where(l > 0.0, o, 0.0)
        o_ref[0, 0] = o.astype(o_ref.dtype)
        if lse_ref is not None:
            m = m_scr[:, :1]
            lv = l_scr[:, :1]
            # fully-masked rows: lse := 0 keeps the backward's
            # exp(NEG_INF - lse) at exactly 0 instead of NaN
            lse_ref[0, 0] = jnp.where(lv > 0.0, m + jnp.log(jnp.maximum(lv, 1e-30)),
                                      0.0)


def _prep(q, k, block_q, block_k, scale, kv_len, q_offset):
    b, h, sq, d = q.shape
    _, kh, sk, _ = k.shape
    assert h % kh == 0, (h, kh)
    group = h // kh
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_len = kv_len if kv_len is not None else sk
    q_offset = q_offset if q_offset is not None else kv_len - sq
    return b, h, kh, sq, sk, d, group, bq, bk, scale, kv_len, q_offset


def _fa_call(q, k, v, *, causal, scale, kv_len, q_offset, block_q, block_k,
             interpret, emit_lse: bool):
    b, h, kh, sq, sk, d, group, bq, bk, scale, kv_len, q_offset = _prep(
        q, k, block_q, block_k, scale, kv_len, q_offset)
    grid = (b, h, sq // bq, sk // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, kv_len=kv_len,
        q_offset=q_offset, bq=bq, bk=bk)
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    out_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    if emit_lse:
        out_shape = [out_shape, jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)]
        out_spec = [out_spec,
                    pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0))]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max (lane-replicated)
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((bq, d), jnp.float32),        # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tsl_flash_attention_fwd" if emit_lse else "tsl_flash_attention",
    )(q, k, v)


def flash_attention_4d(q, k, v, *, causal: bool = True, scale: float | None = None,
                       kv_len: int | None = None, q_offset: int | None = None,
                       block_q: int = 512, block_k: int = 512,
                       interpret: bool = False):
    """q: (B,H,Sq,D); k,v: (B,KH,Sk,D). Shapes pre-padded to block multiples.

    ``q_offset``: causal alignment of logical q row 0 (defaults kv_len - sq)."""
    return _fa_call(q, k, v, causal=causal, scale=scale, kv_len=kv_len,
                    q_offset=q_offset, block_q=block_q, block_k=block_k,
                    interpret=interpret, emit_lse=False)


def flash_attention_fwd_4d(q, k, v, *, causal: bool = True,
                           scale: float | None = None, kv_len: int | None = None,
                           q_offset: int | None = None, block_q: int = 512,
                           block_k: int = 512, interpret: bool = False):
    """Forward that also returns the (B, H, Sq, 1) f32 logsumexp residual — the
    only extra state the recomputation backward needs (O(Sq), not O(Sq·Sk))."""
    return _fa_call(q, k, v, causal=causal, scale=scale, kv_len=kv_len,
                    q_offset=q_offset, block_q=block_q, block_k=block_k,
                    interpret=interpret, emit_lse=True)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, scale: float, causal: bool,
                         kv_len: int, q_offset: int, bq: int, bk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    @pl.when(_block_needed(qi, ki, causal=causal, q_offset=q_offset, bq=bq, bk=bk))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, D)
        do = do_ref[0, 0].astype(jnp.float32)         # (bq, D)
        lse = lse_ref[0, 0]                           # (bq, 1)
        delta = delta_ref[0, 0]                       # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                          # (bq, bk), masked -> 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bq, bk)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                          causal: bool, kv_len: int, q_offset: int,
                          bq: int, bk: int):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    @pl.when(_block_needed(qi, ki, causal=causal, q_offset=q_offset, bq=bq, bk=bk))
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, D)
        do = do_ref[0, 0].astype(jnp.float32)         # (bq, D)
        lse = lse_ref[0, 0]                           # (bq, 1)
        delta = delta_ref[0, 0]                       # (bq, 1)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)                          # (bq, bk), masked -> 0
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bk, D)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bq, bk)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # (bk, D)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd_dq_4d(q, k, v, do, lse, delta, *, causal: bool = True,
                              scale: float | None = None,
                              kv_len: int | None = None,
                              q_offset: int | None = None, block_q: int = 512,
                              block_k: int = 512, interpret: bool = False):
    """dq, q-tiled: grid (B, H, Sq/bq, Sk/bk), sequential k accumulation.

    ``lse``/``delta``: (B, H, Sq, 1) f32 residuals. Shapes pre-padded."""
    b, h, kh, sq, sk, d, group, bq, bk, scale, kv_len, q_offset = _prep(
        q, k, block_q, block_k, scale, kv_len, q_offset)
    grid = (b, h, sq // bq, sk // bk)
    kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal, kv_len=kv_len,
        q_offset=q_offset, bq=bq, bk=bk)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, qi, ki: (b_, h_, qi, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tsl_flash_attention_bwd_dq",
    )(q, k, v, do, lse, delta)


def flash_attention_bwd_dkv_4d(q, k, v, do, lse, delta, *, causal: bool = True,
                               scale: float | None = None,
                               kv_len: int | None = None,
                               q_offset: int | None = None, block_q: int = 512,
                               block_k: int = 512, interpret: bool = False):
    """dk/dv, k-tiled: grid (B, H, Sk/bk, Sq/bq), sequential q accumulation.

    Returns f32 (B, H, Sk, D) gradients per *query* head; the caller reduces
    head groups to KV heads (GQA) and casts — keeping the in-kernel
    accumulation and the cross-head sum in f32."""
    b, h, kh, sq, sk, d, group, bq, bk, scale, kv_len, q_offset = _prep(
        q, k, block_q, block_k, scale, kv_len, q_offset)
    grid = (b, h, sk // bk, sq // bq)
    kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal, kv_len=kv_len,
        q_offset=q_offset, bq=bq, bk=bk)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b_, h_, ki, qi: (b_, h_, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda b_, h_, ki, qi, g=group: (b_, h_ // g, ki, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b_, h_, ki, qi: (b_, h_, qi, 0))
    dkv_spec = pl.BlockSpec((1, 1, bk, d), lambda b_, h_, ki, qi: (b_, h_, ki, 0))
    dkv_shape = jax.ShapeDtypeStruct((b, h, sk, d), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[dkv_spec, dkv_spec],
        out_shape=[dkv_shape, dkv_shape],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tsl_flash_attention_bwd_dkv",
    )(q, k, v, do, lse, delta)
