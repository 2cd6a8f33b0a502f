"""Pallas TPU kernel: movemask workaround (paper Fig 3/6 MSB-extract).

TPU has no movemask instruction (DESIGN.md §2, changed assumption 3), so —
exactly like the paper's non-BMI2 SSE fallback — this is an `is_native: false`
workaround: a lane-weighted integer reduction. Each VMEM tile is
(bm, 32-lane-packed-into-128) bool; the weighted sum runs on the VPU with
int32 lanes. Input is staged as int8 (Pallas interpret-mode friendly) and
widened in-register.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _to_integral_kernel(m_ref, o_ref, *, n: int):
    m = m_ref[...].astype(jnp.uint32)                      # (bm, n_pad)
    w = jnp.left_shift(
        jnp.uint32(1),
        jax.lax.broadcasted_iota(jnp.uint32, m.shape, 1))
    valid = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1) < n
    o_ref[...] = jnp.sum(jnp.where(valid, m * w, 0), axis=-1,
                         keepdims=True).astype(jnp.uint32)


def to_integral_2d(mask8, *, n: int, block_rows: int = 512,
                   interpret: bool = False):
    """mask8: (rows, n_pad) int8 0/1; returns (rows, 1) uint32."""
    rows, n_pad = mask8.shape
    bm = min(block_rows, rows)
    assert rows % bm == 0
    return pl.pallas_call(
        functools.partial(_to_integral_kernel, n=n),
        grid=(rows // bm,),
        in_specs=[pl.BlockSpec((bm, n_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="tsl_to_integral",
    )(mask8)
