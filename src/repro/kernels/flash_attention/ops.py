"""Public wrapper: pads sequence dims to block multiples, restores shape.

Differentiable end-to-end in Pallas: the forward kernel emits per-row
logsumexp residuals and the backward runs dedicated recomputation kernels —
a q-tiled pass for dq and a k-tiled pass for dk/dv (GQA head groups reduced
outside the kernel in f32). The ``custom_vjp`` therefore saves only
O(Sq)-per-head state (inputs + out + lse); the (Sq, Sk) attention matrix is
never materialized on the training path. ``flash_attention_vjp`` exposes the
same backward directly for the UPD ``flash_attention_bwd`` primitive, where
block sizes are owned by the §4.2 bench-selection machinery.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..common import heads_spec, pad_to, per_shard
from . import kernel, ref


def _specs(q, k, n_in: int, n_out: int):
    """Per-shard specs: batch on the data axes, query and KV heads on
    ``model`` together (each shard keeps whole GQA groups)."""
    def specs(mesh):
        s = heads_spec(mesh, q.shape[0], q.shape[1], k.shape[1])
        return (s,) * n_in, (s,) * n_out if n_out > 1 else s
    return specs


def _fwd_local(q, k, v, *, causal, scale, kv_len, block_q, block_k,
               interpret, emit_lse):
    qp, _ = pad_to(q, 2, block_q)
    kp, _ = pad_to(k, 2, block_k)
    vp, _ = pad_to(v, 2, block_k)
    call = kernel.flash_attention_fwd_4d if emit_lse else kernel.flash_attention_4d
    res = call(qp, kp, vp, causal=causal, scale=scale, kv_len=kv_len,
               q_offset=kv_len - q.shape[2], block_q=block_q,
               block_k=block_k, interpret=interpret)
    sq = q.shape[2]
    if emit_lse:
        return res[0][:, :, :sq], res[1][:, :, :sq]
    return res[:, :, :sq]


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _fa(causal, scale, kv_len, block_q, block_k, interpret, q, k, v):
    body = partial(_fwd_local, causal=causal, scale=scale, kv_len=kv_len,
                   block_q=block_q, block_k=block_k, interpret=interpret,
                   emit_lse=False)
    return per_shard(body, _specs(q, k, 3, 1))(q, k, v)


def _fa_fwd(causal, scale, kv_len, block_q, block_k, interpret, q, k, v):
    body = partial(_fwd_local, causal=causal, scale=scale, kv_len=kv_len,
                   block_q=block_q, block_k=block_k, interpret=interpret,
                   emit_lse=True)
    out, lse = per_shard(body, _specs(q, k, 3, 2))(q, k, v)
    # residuals are O(Sq) per head: inputs + out + logsumexp — no S×S tensor
    return out, (q, k, v, out, lse)


def _fa_bwd_kernels(q, k, v, g, out, lse, **kw):
    """(dq, dk, dv), one shard at a time under a multi-device mesh."""
    body = partial(_bwd_local, **kw)
    return per_shard(body, _specs(q, k, 6, 3))(q, k, v, g, out, lse)


def _bwd_local(q, k, v, g, out, lse, *, causal, scale, kv_len,
               block_q, block_k, interpret):
    """Shared backward body: pad to block multiples, run dq + dk/dv kernels,
    reduce GQA head groups, slice back to logical shapes."""
    b, h, sq, d = q.shape
    _, kh, sk, _ = k.shape
    group = h // kh
    q_offset = kv_len - sq
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
                    keepdims=True)
    qp, _ = pad_to(q, 2, block_q)
    gp, _ = pad_to(g.astype(q.dtype), 2, block_q)
    lsep, _ = pad_to(lse, 2, block_q)
    deltap, _ = pad_to(delta, 2, block_q)
    kp, _ = pad_to(k, 2, block_k)
    vp, _ = pad_to(v, 2, block_k)
    common = dict(causal=causal, scale=scale, kv_len=kv_len, q_offset=q_offset,
                  block_q=block_q, block_k=block_k, interpret=interpret)
    dq = kernel.flash_attention_bwd_dq_4d(qp, kp, vp, gp, lsep, deltap, **common)
    dkf, dvf = kernel.flash_attention_bwd_dkv_4d(qp, kp, vp, gp, lsep, deltap,
                                                 **common)
    skp = dkf.shape[2]
    dk = dkf.reshape(b, kh, group, skp, d).sum(2)[:, :, :sk].astype(k.dtype)
    dv = dvf.reshape(b, kh, group, skp, d).sum(2)[:, :, :sk].astype(v.dtype)
    return dq[:, :, :sq], dk, dv


def _fa_bwd(causal, scale, kv_len, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _fa_bwd_kernels(q, k, v, g, out, lse, causal=causal, scale=scale,
                           kv_len=kv_len, block_q=block_q, block_k=block_k,
                           interpret=interpret)


_fa.defvjp(_fa_fwd, _fa_bwd)


@partial(jax.jit, static_argnames=("causal", "scale", "kv_len", "block_q",
                                   "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    kv_len: int | None = None, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False):
    """Flash attention with GQA. q (B,H,Sq,D), k/v (B,KH,Sk,D) -> (B,H,Sq,D).

    Padded q rows are garbage and sliced off; padded k columns are masked by
    kv_len inside the kernel; causal alignment uses the logical sq."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(128, sk))
    kv_len = kv_len if kv_len is not None else sk
    return _fa(causal, scale, kv_len, bq, bk, interpret, q, k, v)


@partial(jax.jit, static_argnames=("causal", "scale", "kv_len", "block_q",
                                   "block_k", "interpret"))
def flash_attention_vjp(q, k, v, g, *, causal: bool = True,
                        scale: float | None = None, kv_len: int | None = None,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = False):
    """Standalone (dq, dk, dv) for cotangent ``g`` — the UPD
    ``flash_attention_bwd`` entry point. Re-runs the residual-emitting
    forward, then the recomputation backward kernels; peak memory stays
    O(Sq + Sk) per head for any sequence length."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(128, sk))
    kv_len = kv_len if kv_len is not None else sk
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    _, (q, k, v, out, lse) = _fa_fwd(causal, sc, kv_len, bq, bk, interpret,
                                     q, k, v)
    return _fa_bwd_kernels(q, k, v, g, out, lse, causal=causal, scale=sc,
                           kv_len=kv_len, block_q=bq, block_k=bk,
                           interpret=interpret)


__all__ = ["flash_attention", "flash_attention_vjp", "ref"]
