"""Pallas TPU kernel: row-block softmax, single VMEM pass.

Same tiling family as rmsnorm: (block_rows, d) tiles, f32 max/exp/sum on the
VPU, one HBM read + one write per element (the fused alternative to XLA's
max-read / sub-exp-read / sum-read / div-read chain).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _softmax_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    o_ref[...] = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)


def softmax_2d(x, *, block_rows: int = 256, interpret: bool = False):
    rows, d = x.shape
    bm = min(block_rows, rows)
    assert rows % bm == 0
    return pl.pallas_call(
        _softmax_kernel,
        grid=(rows // bm,),
        in_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="tsl_softmax",
    )(x)
