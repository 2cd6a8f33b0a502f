"""Public wrapper for the RMSNorm kernel: shape-polymorphic, padded tiling.

Differentiable: the forward is the Pallas kernel, the backward is the VJP of
the jnp reference (``ref.rmsnorm``) — an O(N·D) elementwise pass that XLA
fuses well, so no Pallas backward is needed."""

from __future__ import annotations

from functools import partial

import jax
from jax.sharding import PartitionSpec as P

from ..common import pad_to, per_shard, row_block, rows_spec
from . import kernel, ref


def _rmsnorm_local(x, weight, *, eps, block_rows, interpret):
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    # tile alignment: rows to block multiple, block to sublane multiple
    br = row_block(x2.shape[0], d, x.dtype, 2, block_rows)
    x2, n = pad_to(x2, 0, br)
    out = kernel.rmsnorm_2d(x2, weight, eps=eps, block_rows=br,
                            interpret=interpret)
    return out[:n].reshape(orig_shape)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x, weight, eps, block_rows, interpret):
    body = partial(_rmsnorm_local, eps=eps, block_rows=block_rows,
                   interpret=interpret)
    spec = lambda mesh: ((rows_spec(mesh, x.shape), P()),  # noqa: E731
                         rows_spec(mesh, x.shape))
    return per_shard(body, spec)(x, weight)


def _rmsnorm_fwd(x, weight, eps, block_rows, interpret):
    return _rmsnorm(x, weight, eps, block_rows, interpret), (x, weight)


def _rmsnorm_bwd(eps, block_rows, interpret, res, g):
    x, weight = res
    _, vjp = jax.vjp(partial(ref.rmsnorm, eps=eps), x, weight)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, weight, *, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool = False):
    """RMSNorm over the last axis of an arbitrary-rank input."""
    return _rmsnorm(x, weight, eps, block_rows, interpret)


__all__ = ["rmsnorm", "ref"]
