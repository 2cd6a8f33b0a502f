"""Shared helpers for Pallas kernels: padding/tiling arithmetic.

TPU tiling invariants (DESIGN.md §2, changed assumption 2): last dim in
multiples of 128 lanes, second-to-last in multiples of 8 sublanes (f32) /
16 (bf16); MXU likes 128x128 operands. Kernels pad to these and slice back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as dist_sharding

LANES = 128
SUBLANES = 8
# VMEM that the double-buffered blocks of a row-tiled kernel may take: half
# of the TPU compiler's default 16 MiB scoped-VMEM limit, leaving the other
# half for the kernel's f32 temporaries.
ROW_BLOCK_VMEM_BYTES = 8 << 20


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(x: int, m: int) -> int:
    return cdiv(x, m) * m


def pad_to(x, axis: int, multiple: int, value=0.0):
    """Pad ``axis`` of x up to a multiple; returns (padded, original_size)."""
    n = x.shape[axis]
    target = round_up(n, multiple)
    if target == n:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, target - n)
    return jnp.pad(x, widths, constant_values=value), n


def sublane_multiple(dtype) -> int:
    """Min second-to-last-dim tile for a dtype (8 for 32-bit, 16 for 16-bit, 32 for 8-bit)."""
    bits = jnp.dtype(dtype).itemsize * 8
    return {32: 8, 16: 16, 8: 32}.get(bits, 8)


def row_block(rows: int, d: int, dtype, n_streams: int, block_rows: int) -> int:
    """Rows per block of a (rows, d) row-tiled kernel that streams
    ``n_streams`` arrays of ``dtype``: at most ``block_rows``, a multiple of
    the dtype's sublane tile, and small enough that the double-buffered
    blocks fit :data:`ROW_BLOCK_VMEM_BYTES` (wide f32 rows would otherwise
    exhaust VMEM)."""
    sub = sublane_multiple(dtype)
    fit = ROW_BLOCK_VMEM_BYTES // (2 * n_streams * d * jnp.dtype(dtype).itemsize)
    br = min(block_rows, round_up(rows, sub), fit)
    return max(sub, br // sub * sub)


def per_shard(fn, specs):
    """``fn``, a Pallas call, run once per shard of the ambient mesh.

    The TPU compiler cannot partition a Mosaic kernel, so under a
    multi-device mesh each call runs on the shard that ``specs(mesh) ->
    (in_specs, out_specs)`` gives it, and XLA moves the operands into that
    layout. Without such a mesh ``fn`` runs as it is."""
    mesh = dist_sharding.ambient_mesh()
    if mesh is None or mesh.size == 1:
        return fn
    in_specs, out_specs = specs(mesh)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def rows_spec(mesh, shape, *, model_last: bool = False):
    """PartitionSpec of a row-tiled operand: the leading dimension on the
    data axes, and (``model_last``) the last on ``model``."""
    entries = [None] * len(shape)
    entries[0] = dist_sharding.dim_entry(mesh, shape[0])
    if model_last and len(shape) > 1:
        entries[-1] = dist_sharding.dim_entry(mesh, shape[-1], model=True)
    return P(*entries)


def heads_spec(mesh, b: int, *heads: int):
    """PartitionSpec of a (B, heads, ...) attention operand: batch on the
    data axes, heads on ``model`` when every head count given (query and KV
    heads) divides, which keeps each query head with its KV group."""
    model = [dist_sharding.dim_entry(mesh, n, model=True) for n in heads]
    m = model[0] if all(e is not None for e in model) else None
    return P(dist_sharding.dim_entry(mesh, b), m, None, None)
