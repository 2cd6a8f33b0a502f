"""Pallas TPU kernel: horizontal add with explicit log2 adder tree.

This is the TPU-native rendering of the paper's Fig 11 FPGA adder tree: the
outer `stage` loop of Fig 11 becomes a Python-unrolled halving loop over VREG
lane groups inside one VMEM tile; cross-tile partial sums accumulate in
scratch across the sequential column grid — f32 for float inputs, int32 for
integer inputs, so integer sums are exact (the paper's `#pragma unroll` has
no TPU analogue — unrolling happens at trace time, DESIGN.md §2).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _tree_sum_last(x):
    """Explicit pairwise halving tree over a power-of-two last axis."""
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x


def _hadd_kernel(x_ref, o_ref, acc_scr, *, n_valid: int, bn: int):
    j = pl.program_id(1)
    ncols = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...].astype(acc_scr.dtype)                # (bm, bn)
    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(col < n_valid, x, jnp.zeros_like(x))
    acc_scr[...] += _tree_sum_last(x)                    # (bm, 1)

    @pl.when(j == ncols - 1)
    def _finalize():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def hadd_2d(x2, *, n_valid: int, block_rows: int = 256, block_cols: int = 1024,
            interpret: bool = False):
    """x2: (rows, cols) with cols a power-of-two multiple of block_cols;
    returns (rows, 1) row sums."""
    rows, cols = x2.shape
    bm = min(block_rows, rows)
    bn = min(block_cols, cols)
    assert rows % bm == 0 and cols % bn == 0 and (bn & (bn - 1)) == 0
    acc = jnp.int32 if jnp.issubdtype(x2.dtype, jnp.integer) else jnp.float32
    return pl.pallas_call(
        functools.partial(_hadd_kernel, n_valid=n_valid, bn=bn),
        grid=(rows // bm, cols // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 1), x2.dtype),
        scratch_shapes=[pltpu.VMEM((bm, 1), acc)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="tsl_hadd",
    )(x2)
