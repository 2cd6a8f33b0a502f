"""Pallas TPU kernel: write a span's new K/V tokens into a fused page pool
in place.

The decode/verify layer loop carries the whole stacked pool and writes each
layer's B x C new tokens into it. Any XLA write there (a scatter, or a loop
of dynamic-update-slices) lets the TPU lay the carried pool out for that
write, and the paged attention kernel, which needs the pool row-major, then
gets a copy of the whole stack around every call. This kernel takes the pool
aliased to its output in the row-major layout the attention kernel reads,
so the pool stays where it is and only the touched tiles move.

Grid: one step per token, slot-major, so a slot's tokens are consecutive
steps and their row tiles ascend. A step's pool block is the tile of
``rb`` pool rows of the layer's KV heads that holds the token. The block
arrives from the pool when it differs from the previous step's, and is
copied to the output block; a step whose tile repeats keeps the output block
it already holds (the pipeline keeps it while the index repeats), so the
slot's earlier writes stay. The token's row and lanes are then selected into
the block, which is written back once its run of steps ends. Tiles of
different slots never coincide, since each slot writes its own pages; slots
that write nothing point at one scratch page, whose contents are never read
as data.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu
from jax.sharding import PartitionSpec as P

from ...dist import sharding as dist_sharding
from ..common import LANES, per_shard, sublane_multiple
from . import ref, rows as _rows


def _write_kernel(blk_ref, row_ref, lane_ref, layer_ref, tok_ref, pool_ref,
                  out_ref, *, d: int):
    t = pl.program_id(0)

    @pl.when((t == 0) | (blk_ref[t] != blk_ref[jnp.maximum(t - 1, 0)]))
    def _load():
        out_ref[...] = pool_ref[...]

    shape = out_ref.shape                                    # (KH, rb, w)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    lo = lane_ref[t]
    sel = (row == row_ref[t]) & (lane >= lo) & (lane < lo + d)
    tok = jnp.broadcast_to(tok_ref[0][:, None, :], shape)
    out_ref[...] = jnp.where(sel, tok, out_ref[...])


def token_write_3d(pool, tok, blk, row, lane, layer, *, rb: int, d: int,
                   interpret: bool = False):
    """pool: (L*KH, n_pages*rows, w), aliased to the result; tok: (T, KH, w)
    the T tokens, each repeated across the lane blocks of a row; blk, row,
    lane: (T,) int32 the tile of ``rb`` pool rows, the row in it and the
    first lane of each token; layer: int32 scalar."""
    n, kh, w = tok.shape

    def pool_idx(t, blk, row, lane, lyr):
        return (lyr[0], blk[t], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, kh, w), lambda t, *_: (t, 0, 0)),
                  pl.BlockSpec((kh, rb, w), pool_idx)],
        out_specs=pl.BlockSpec((kh, rb, w), pool_idx),
    )
    return pl.pallas_call(
        partial(_write_kernel, d=d),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="tsl_paged_token_write",
    )(blk, row, lane, jnp.asarray(layer, jnp.int32).reshape(1), tok, pool)


@partial(jax.jit, static_argnames=("interpret",))
def token_write(pool, tokens, pages, offs, *, layer=None,
                interpret: bool = False):
    """``pool`` with ``tokens`` (B, KH, C, d) written as token ``offs[b, c]``
    of page ``pages[b, c]``, in the row format of ``rows.py``; ``pool`` is
    one layer's (KH, n_pages, rows, w) or, with ``layer``, a stack
    (L, ..., KH, n_pages, rows, w) written at that layer. A pool whose
    rows do not fill whole lanes (an int8 pool's one-lane scale pool) takes
    the jnp reference: the TPU lays it out otherwise, and the kernel would
    need it copied."""
    if pool.shape[-1] % LANES:
        return ref.token_write(pool, tokens, pages, offs, layer=layer)
    b, kh, c, d = tokens.shape
    shape = pool.shape
    rows, w = shape[-2:]
    f = _rows.fold(pool, d)
    sub = sublane_multiple(pool.dtype)
    rb = sub if rows % sub == 0 else rows
    flat_row = (jnp.asarray(pages, jnp.int32) * rows
                + jnp.asarray(offs, jnp.int32) // f).reshape(-1)
    lane = ((jnp.asarray(offs, jnp.int32) % f) * d).reshape(-1)
    # each token repeated across the row's lane blocks, the padding zero
    tok = jnp.swapaxes(tokens, 1, 2).reshape(b * c, kh, d).astype(pool.dtype)
    tok = jnp.pad(jnp.tile(tok, (1, 1, f)), [(0, 0), (0, 0), (0, w - f * d)])
    stack, layer = ref.stack_layers(pool, layer=layer)

    def local(stack, tok, blk, row, lane, layer):
        out = token_write_3d(
            stack.reshape(-1, stack.shape[2] * rows, w), tok, blk, row,
            lane, layer, rb=rb, d=d, interpret=interpret)
        return out.reshape(stack.shape)

    def specs(mesh):
        # the KV heads where the pool keeps them (``model`` when they
        # divide it); every slot's tokens on every shard, since each data
        # replica of the pool takes all of them
        heads = dist_sharding.dim_entry(mesh, kh, model=True)
        scalar = P(None)
        return ((P(None, heads, None, None, None), P(None, heads, None),
                 scalar, scalar, scalar, P()),
                P(None, heads, None, None, None))

    out = per_shard(local, specs)(stack, tok, flat_row // rb, flat_row % rb,
                                  lane, layer)
    return out.reshape(shape)


__all__ = ["token_write", "token_write_3d"]
