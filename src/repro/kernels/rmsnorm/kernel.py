"""Pallas TPU kernel: fused RMSNorm over row blocks.

Tiling: x is viewed as (rows, d); the grid walks row blocks of
``block_rows`` × d. One VMEM tile holds the row block plus the (1, d) weight
(broadcast to every grid step via a constant index map). Statistics are
computed in f32 on-tile, so bf16 inputs never round-trip through HBM in f32.

VMEM budget (v5e SRU, 128 MiB): block_rows=256, d=8192, bf16 in+out tiles +
f32 intermediates ≈ 256·8192·(2+2+4+4) B ≈ 25 MiB — comfortably inside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from ..common import cdiv


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_2d(x, weight, *, eps: float = 1e-6, block_rows: int = 256,
               interpret: bool = False):
    """x: (rows, d), weight: (d,) -> (rows, d). rows % block_rows == 0 assumed
    (ops.py pads)."""
    rows, d = x.shape
    grid = (cdiv(rows, block_rows),)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="tsl_rmsnorm",
    )(x, weight.reshape(1, d))
