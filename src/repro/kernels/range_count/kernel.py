"""Pallas TPU kernel: fused range-count (the paper's Fig 8 algorithm as ONE
kernel instead of a primitive chain — the beyond-paper fusion the generator's
data model can carry as a specialized variant).

The paper's SIMD loop (load -> between_inclusive -> mask->int -> add, then a
final hadd) becomes: grid over (rows/bm) VMEM tiles of a (rows, 128) view;
each step counts in-range lanes of its tile on the VPU and accumulates
per-lane counts in a (1, 128) VMEM scratch row; the final step reduces the
row and writes the total, lane-replicated, into a (1, 128) output block (a
vector store: the TPU cannot store a scalar to VMEM).

The finalization `hadd` of Fig 8/9 is the in-tile jnp.sum reduction — on TPU
the adder tree of Fig 11 is what the VPU cross-lane reduction emits anyway
(DESIGN.md §2: VREG 8x128 tiles replace 128-512 bit registers).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu



def _range_count_kernel(x_ref, lo_ref, hi_ref, o_ref, acc_scr, *, n_valid: int,
                        bm: int, lanes: int):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]
    lo = lo_ref[...]                                    # (1, 1), broadcast
    hi = hi_ref[...]
    # global element index of each lane, to mask the tail padding
    row0 = i * bm
    pos = (row0 + jax.lax.broadcasted_iota(jnp.int32, (bm, lanes), 0)) * lanes \
        + jax.lax.broadcasted_iota(jnp.int32, (bm, lanes), 1)
    in_range = jnp.logical_and(x >= lo, x <= hi)
    in_range = jnp.logical_and(in_range, pos < n_valid)
    acc_scr[...] += jnp.sum(in_range.astype(jnp.int32), axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _finalize():
        total = jnp.sum(acc_scr[...], axis=-1, keepdims=True)   # (1, 1)
        o_ref[...] = jnp.broadcast_to(total, o_ref.shape)


def range_count_2d(x2, low, high, *, n_valid: int, block_rows: int = 512,
                   interpret: bool = False):
    """x2: (rows, lanes) padded view; returns int32 scalar count."""
    rows, lanes = x2.shape
    bm = min(block_rows, rows)
    assert rows % bm == 0
    grid = (rows // bm,)
    lo = jnp.asarray(low, x2.dtype).reshape(1, 1)
    hi = jnp.asarray(high, x2.dtype).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_range_count_kernel, n_valid=n_valid, bm=bm,
                          lanes=lanes),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, lanes), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, lanes), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, lanes), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="tsl_range_count",
    )(x2, lo, hi)
    return out[0, 0]
