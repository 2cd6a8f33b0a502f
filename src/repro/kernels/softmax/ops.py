"""Public wrapper for the softmax kernel.

Differentiable: the backward is the VJP of the jnp reference
(``ref.softmax``), recomputed from the saved input."""

from __future__ import annotations

from functools import partial

import jax

from ..common import pad_to, per_shard, row_block, rows_spec
from . import kernel, ref


def _softmax_local(x, *, block_rows, interpret):
    orig = x.shape
    d = orig[-1]
    x2 = x.reshape(-1, d)
    bm = row_block(x2.shape[0], d, x.dtype, 2, block_rows)
    x2, n = pad_to(x2, 0, bm)
    out = kernel.softmax_2d(x2, block_rows=bm, interpret=interpret)
    return out[:n].reshape(orig)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _softmax(x, block_rows, interpret):
    body = partial(_softmax_local, block_rows=block_rows, interpret=interpret)
    spec = lambda mesh: ((rows_spec(mesh, x.shape),),  # noqa: E731
                         rows_spec(mesh, x.shape))
    return per_shard(body, spec)(x)


def _softmax_fwd(x, block_rows, interpret):
    return _softmax(x, block_rows, interpret), x


def _softmax_bwd(block_rows, interpret, x, g):
    _, vjp = jax.vjp(ref.softmax, x)
    return vjp(g)


_softmax.defvjp(_softmax_fwd, _softmax_bwd)


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def softmax(x, *, block_rows: int = 256, interpret: bool = False):
    """Stable softmax over the last axis, arbitrary rank. Padded rows are
    zeros, which normalize harmlessly and are sliced off."""
    return _softmax(x, block_rows, interpret)


__all__ = ["softmax", "ref"]
