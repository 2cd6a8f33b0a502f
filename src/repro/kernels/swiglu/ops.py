"""Public wrapper for the fused SwiGLU kernel.

Differentiable: the backward is the VJP of the jnp reference
(``ref.swiglu``), recomputed from the saved gate and up inputs."""

from __future__ import annotations

from functools import partial

import jax

from ..common import pad_to, per_shard, row_block, rows_spec
from . import kernel, ref


def _swiglu_local(gate, up, *, block_rows, interpret):
    orig = gate.shape
    d = orig[-1]
    g2 = gate.reshape(-1, d)
    u2 = up.reshape(-1, d)
    bm = row_block(g2.shape[0], d, gate.dtype, 3, block_rows)
    g2, n = pad_to(g2, 0, bm)
    u2, _ = pad_to(u2, 0, bm)
    out = kernel.swiglu_2d(g2, u2, block_rows=bm, interpret=interpret)
    return out[:n].reshape(orig)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _swiglu(gate, up, block_rows, interpret):
    # elementwise: the hidden (last) axis stays split on ``model`` as the
    # tensor-parallel up-projections left it
    body = partial(_swiglu_local, block_rows=block_rows, interpret=interpret)

    def spec(mesh):
        s = rows_spec(mesh, gate.shape, model_last=True)
        return (s, s), s

    return per_shard(body, spec)(gate, up)


def _swiglu_fwd(gate, up, block_rows, interpret):
    return _swiglu(gate, up, block_rows, interpret), (gate, up)


def _swiglu_bwd(block_rows, interpret, res, g):
    _, vjp = jax.vjp(ref.swiglu, *res)
    return vjp(g)


_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def swiglu(gate, up, *, block_rows: int = 256, interpret: bool = False):
    return _swiglu(gate, up, block_rows, interpret)


__all__ = ["swiglu", "ref"]
