"""Public wrapper for fused paged attention: shapes the pool and the GQA
query block for the Pallas kernel, restores the logical (B, H, SQ, D) view.

The page size is NOT a parameter — it is read off the pool's page axis, so
one definition serves whatever page size the bench selected for
``cache_page_read`` (the SVE length-agnostic discipline applied twice over:
page size owned by the memory primitive, block_k owned by this one). The
effective key block is clamped to divide the page: candidates smaller than
the page tile it; anything else degrades to one block per page.

int8 pools (``k_scale``/``v_scale`` present) route to the jnp reference,
whose scan dequantizes per touched page — still inside the primitive, never
at a park/activate boundary.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..common import heads_spec, per_shard
from . import kernel, ref


def _sublane_pad(x, mult=8):
    r = x.shape[2]
    rp = max(mult, -(-r // mult) * mult)
    if rp == r:
        return x
    pad = [(0, 0)] * x.ndim
    pad[2] = (0, rp - r)
    return jnp.pad(x, pad)


@partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def paged_attention(q, k_pool, v_pool, tables, kv_len, *, k_scale=None,
                    v_scale=None, scale=None, block_k: int = 64,
                    interpret: bool = False):
    """q (B,H,SQ,D); k_pool/v_pool (KH, n_pages, page, D); tables (B,P) int32
    page ids; kv_len (B,) int32 (scalars broadcast). Returns (B,H,SQ,D)."""
    b, h = q.shape[:2]
    kh, _, page, _ = k_pool.shape
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    if k_scale is not None:
        return ref.paged_attention_ref(q, k_pool, v_pool, tables, kvl,
                                       k_scale=k_scale, v_scale=v_scale,
                                       scale=scale)
    bk = block_k if (block_k <= page and page % block_k == 0) else page

    def local(q, k_pool, v_pool, tables, kvl):
        b, h, sq, d = q.shape
        kh = k_pool.shape[0]
        rq = (h // kh) * sq
        q4 = _sublane_pad(q.reshape(b, kh, rq, d))
        out = kernel.paged_attention_4d(
            q4, k_pool.reshape(kh, -1, d), v_pool.reshape(kh, -1, d),
            tables, kvl, sq=sq, page=page, block_k=bk, scale=scale,
            interpret=interpret)
        return out[:, :, :rq].reshape(b, h, sq, d)

    def specs(mesh):
        # slots on the data axes, KV heads (with their query heads) on
        # ``model``: each shard reads whole pages of its own heads
        qs = heads_spec(mesh, b, h, kh)
        pool = P(qs[1], None, None, None)
        rows = P(qs[0])
        return (qs, pool, pool, P(qs[0], None), rows), qs

    return per_shard(local, specs)(q, k_pool, v_pool, tables, kvl)


__all__ = ["paged_attention", "ref"]
