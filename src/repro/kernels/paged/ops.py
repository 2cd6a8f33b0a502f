"""Public wrapper for fused paged attention: shapes the pool and the GQA
query block for the Pallas kernel, restores the logical (B, H, SQ, D) view.

The page size is NOT a parameter — it is read off the pool's page axis
(times the tokens a pool row packs, ``rows.page_tokens``), so
one definition serves whatever page size the bench selected for
``cache_page_read`` (the SVE length-agnostic discipline applied twice over:
page size owned by the memory primitive, block_k owned by this one). The
effective key block is clamped to divide the page: candidates smaller than
the page tile it; anything else degrades to one block per page.

int8 pools (``k_scale``/``v_scale`` present) route to the jnp reference,
whose scan dequantizes per touched page — still inside the primitive, never
at a park/activate boundary.

``layer`` reads one layer of a stacked pool ``(L, ..., KH, n_pages, ·, ·)``
(the leading axes flatten to the layer index) without slicing it out: the
kernel resolves the layer in its index maps. ``None`` takes a single
layer's pool. A pool row ``w`` lanes wide packs ``f = w // D`` tokens
(``rows.py``): the query is expanded to the kernel's ``f`` lane blocks
here, and the output's blocks summed back.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..common import heads_spec, per_shard, round_up, sublane_multiple
from . import kernel, ref, rows as _rows


def _sublane_pad(x, mult=8):
    r = x.shape[2]
    rp = max(mult, -(-r // mult) * mult)
    if rp == r:
        return x
    pad = [(0, 0)] * x.ndim
    pad[2] = (0, rp - r)
    return jnp.pad(x, pad)


@partial(jax.jit, static_argnames=("scale", "block_k", "interpret"))
def paged_attention(q, k_pool, v_pool, tables, kv_len, *, layer=None,
                    k_scale=None, v_scale=None, scale=None, block_k: int = 64,
                    interpret: bool = False):
    """q (B,H,SQ,D); k_pool/v_pool (KH, n_pages, page // f, w) — f = w // D
    tokens per pool row (``rows.py``), the plain (KH, n_pages, page, D) at
    f = 1 — or with ``layer`` a stack (L, ..., KH, n_pages, page // f, w);
    tables (B,P) int32 page ids; kv_len (B,) int32 (scalars broadcast).
    Returns (B,H,SQ,D)."""
    b, h, _, d = q.shape
    kh, _, rows, w = k_pool.shape[-4:]
    fold = _rows.fold(k_pool, d)
    page = _rows.page_tokens(k_pool, d)
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    if k_scale is not None:
        return ref.paged_attention_ref(q, k_pool, v_pool, tables, kvl,
                                       layer=layer, k_scale=k_scale,
                                       v_scale=v_scale, scale=scale)
    bk = block_k if (block_k <= page and page % block_k == 0) else page
    # pool rows per key sub-block: whole sublane tiles of the pool's dtype
    bk = min(round_up(max(bk // fold, 1), sublane_multiple(k_pool.dtype)),
             rows)
    bk = bk if rows % bk == 0 else rows
    k_pool, v_pool, layer = ref.stack_layers(k_pool, v_pool, layer=layer)

    def local(q, k_pool, v_pool, tables, kvl, layer):
        b, h, sq, d = q.shape
        kh = k_pool.shape[1]
        rq = (h // kh) * sq
        q4 = _sublane_pad(q.reshape(b, kh, rq, d))
        if fold > 1:
            # row block s: q in lanes [s*d, (s+1)*d), zeros elsewhere
            eye = jnp.eye(fold, dtype=q.dtype)
            q4 = (q4[:, :, None, :, None, :]
                  * eye[None, None, :, None, :, None]).reshape(
                      b, kh, fold * q4.shape[2], fold * d)
        if q4.shape[-1] < w:
            q4 = jnp.pad(q4, [(0, 0)] * 3 + [(0, w - q4.shape[-1])])
        out = kernel.paged_attention_4d(
            q4, k_pool.reshape(-1, k_pool.shape[2] * rows, w),
            v_pool.reshape(-1, v_pool.shape[2] * rows, w),
            tables, kvl, layer, sq=sq, page=page, d=d, block_k=bk,
            scale=scale, interpret=interpret)[:, :, :rq]
        if fold > 1:
            out = out[..., :fold * d].reshape(b, kh, rq, fold, d).sum(3)
        return out[..., :d].astype(q.dtype).reshape(b, h, sq, d)

    def specs(mesh):
        # slots on the data axes, KV heads (with their query heads) on
        # ``model``: each shard reads whole pages of its own heads, where
        # the serving layer's pools already lie
        qs = heads_spec(mesh, b, h, kh)
        pool = P(None, qs[1], None, None, None)
        return (qs, pool, pool, P(qs[0], None), P(qs[0]), P()), qs

    return per_shard(local, specs)(q, k_pool, v_pool, tables, kvl, layer)


__all__ = ["paged_attention", "ref"]
