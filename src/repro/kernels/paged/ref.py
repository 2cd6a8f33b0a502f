"""Paged cache gather/scatter: the reference bodies behind the
``cache_page_read`` / ``cache_page_write`` UPD primitives.

The pool is a FLAT token-row store ``(capacity_rows, *row_shape)``: one row
per cache token, trailing dims free (a KV row, an (L, KH, hd) stack, an int8
row + its scale row — the primitives are layout-agnostic). A page is
``page_size`` CONSECUTIVE rows, and the page table passed to the primitives
holds each page's STARTING ROW offset, so the same pool array serves any
page-size candidate — the vector-length-agnostic discipline (ARM SVE)
applied to cache geometry: page size is a property of the *definition*, not
of the call site.

Two schedules, mirroring the flash-attention block_k candidates:

* ``page_read``/``page_write`` with small pages — one flat index gather /
  scatter (``jnp.take`` / ``.at[].set``): many small slices, fine-grained
  residency, more index traffic.
* the ``*_blocked`` variants — one ``dynamic_slice`` per page: contiguous
  page-sized block copies, the Mosaic/Triton-friendly schedule for large
  pages (a 256-row page of 128-wide rows is a whole (sublane, lane)-aligned
  tile stream).

Bench selection (``python -m repro.core bench``) times the candidates per
hardware key; the winning definition's page size is what the serving layer
builds its pools with (``repro.serve.paging.selected_page_size`` probes it).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import rows as _rows

_NEG_INF = -1e30


def page_read(pool, table, *, page: int):
    """Gather ``page`` consecutive rows per table entry.

    pool: (cap_rows, *row); table: (N,) int32 page start-row offsets.
    Returns (N * page, *row), pages concatenated in table order."""
    rows = (table[:, None] + jnp.arange(page, dtype=table.dtype)).reshape(-1)
    return jnp.take(pool, rows, axis=0)


def page_read_blocked(pool, table, *, page: int):
    """Same semantics as :func:`page_read`, one contiguous dynamic_slice per
    page — the large-page schedule."""

    def one(start):
        return jax.lax.dynamic_slice_in_dim(pool, start, page, axis=0)

    out = jax.vmap(one)(table)                      # (N, page, *row)
    return out.reshape((-1,) + pool.shape[1:])


def page_write(pool, rows, table, *, page: int):
    """Scatter ``page`` consecutive rows per table entry into the pool.

    rows: (N * page, *row) content in table order; returns the updated pool."""
    idx = (table[:, None] + jnp.arange(page, dtype=table.dtype)).reshape(-1)
    return pool.at[idx].set(rows.astype(pool.dtype))


def token_write(pool, tokens, pages, offs, *, layer=None):
    """``pool`` with ``tokens`` (B, KH, C, d) written as token ``offs[b, c]``
    of page ``pages[b, c]``, in the row format of ``rows.py``; ``pool`` is
    one layer's (KH, n_pages, rows, w) or, with ``layer``, a stack
    (L, ..., KH, n_pages, rows, w) written at that layer. One
    dynamic-update-slice per token, in a loop whose body is one write at
    any batch and span."""
    b, kh, c, d = tokens.shape
    lead = () if layer is None else jnp.unravel_index(
        jnp.asarray(layer, jnp.int32), pool.shape[:-4])
    flat = jnp.swapaxes(tokens, 1, 2).reshape(b * c, kh, d)
    pid = jnp.asarray(pages, jnp.int32).reshape(-1)
    off = jnp.asarray(offs, jnp.int32).reshape(-1)

    def one(t, pool):
        return _rows.write_token(pool, flat[t], lead, pid[t], off[t])

    return jax.lax.fori_loop(0, b * c, one, pool)


def stack_layers(*pools, layer=None):
    """``pools`` as ``(L, KH, n_pages, rows, W)`` stacks and the int32 layer
    to read: a single layer's ``(KH, n_pages, rows, W)`` pool is a stack of
    one (``layer`` None reads it); a ``(L, ..., KH, n_pages, rows, W)``
    stack flattens its leading axes into the layer index. Reshapes only."""
    pools = [jnp.asarray(p) for p in pools]
    if layer is None:
        return (*(p[None] for p in pools), jnp.int32(0))
    return (*(p.reshape((-1,) + p.shape[-4:]) for p in pools),
            jnp.asarray(layer, jnp.int32))


def paged_attention_ref(q, k_pool, v_pool, tables, kv_len, *, layer=None,
                        k_scale=None, v_scale=None, scale=None,
                        pages_per_step: int = 1):
    """Gather-free paged attention: decode/verify straight off the page pool.

    q: (B, H, SQ, D) — SQ = 1 for decode, the verify span width otherwise.
    k_pool/v_pool: (KH, n_pages, page, D) — the WHOLE pool, every resident
    request's pages interleaved; a pool may pack f consecutive tokens per
    row, (KH, n_pages, page // f, w) (the format of ``rows.py``) — or, with
    ``layer``, a stack of such pools (L, ..., KH, n_pages, ·, ·) read at
    that layer page by page (the layer is never sliced out first).
    tables: (B, P) int32 PAGE IDS (indices into the pool's page axis —
    not the start-row offsets ``page_read`` takes: the block table never
    leaves page-id space here, which is the point).
    kv_len: (B,) int32 rows written per sequence; rows of page p beyond it
    are masked, and table entries past the covered range must still be
    *valid* page ids (a scratch page) — they are fetched, then masked.

    The span is ends-aligned at kv_len (row r of SQ sits at absolute
    position kv_len - SQ + r), matching ``attention_verify``. int8 pools
    pass per-row ``k_scale``/``v_scale`` pools of shape (KH, n_pages, page,
    1); dequantization happens per touched page inside the scan — never at
    a park/activate boundary. kv_len == 0 rows return exactly 0.

    ``pages_per_step`` is the scan's key-block knob (the ref-side analogue
    of the Pallas ``block_k`` candidates): each step fetches that many table
    entries and runs one page-group-wide online-softmax update. The table
    is padded to a multiple with its own first entry — padded positions sit
    past every query row and mask out.
    """
    b, h, sq, d = q.shape
    kh = k_pool.shape[-4]
    page = _rows.page_tokens(k_pool, d)
    if k_scale is not None:
        k_scale, v_scale, _ = stack_layers(k_scale, v_scale, layer=layer)
    k_pool, v_pool, layer = stack_layers(k_pool, v_pool, layer=layer)
    group = h // kh
    n_p = tables.shape[1]
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    r = group * sq
    # heads are KV-head-major (h = kh * group + g), as in the flash kernels
    qf = q.astype(jnp.float32).reshape(b, kh, r, d)
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    tables = jnp.asarray(tables, jnp.int32)
    # absolute query position of span row (row % sq), per sequence
    qi = kvl[:, None] - sq + (jnp.arange(r, dtype=jnp.int32) % sq)[None, :]

    g = max(int(pages_per_step), 1)
    n_steps = -(-n_p // g)
    if n_steps * g != n_p:
        # pad with each sequence's own first entry: padded logical positions
        # are >= n_p * page > every qi, so they mask out below
        pad = jnp.broadcast_to(tables[:, :1], (b, n_steps * g - n_p))
        tables = jnp.concatenate([tables, pad], axis=1)
    grouped = tables.reshape(b, n_steps, g)                  # scan xs, axis 1
    width = g * page

    def step(carry, xs):
        m, l, acc = carry
        p, pid = xs                                          # (), (B, G)

        def pages(pool, width):
            # (B, G, KH, page, width): the table's pages of this layer, rows
            # that pack several tokens unpacked
            return _rows.unpack(pool[layer, :, pid], width)

        k, v = pages(k_pool, d), pages(v_pool, d)
        if k_scale is not None:
            k = k.astype(jnp.float32) * pages(k_scale, 1)
            v = v.astype(jnp.float32) * pages(v_scale, 1)
        k = jnp.moveaxis(k, 2, 1).astype(jnp.float32)
        v = jnp.moveaxis(v, 2, 1).astype(jnp.float32)
        k = k.reshape(b, kh, width, d)                   # (B, KH, G*page, D)
        v = v.reshape(b, kh, width, d)
        s = jnp.einsum("bkrd,bkcd->bkrc", qf, k) * sc
        kpos = p * width + jnp.arange(width, dtype=jnp.int32)  # logical pos
        valid = kpos[None, None, :] <= qi[:, :, None]        # (B, R, width)
        s = jnp.where(valid[:, None], s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        pr = jnp.where(valid[:, None], jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pr.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bkrc,bkcd->bkrd", pr, v)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kh, r), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kh, r), jnp.float32)
    a0 = jnp.zeros((b, kh, r, d), jnp.float32)
    (_, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(n_steps, dtype=jnp.int32), jnp.moveaxis(grouped, 1, 0)))
    out = jnp.where(l[..., None] > 0, acc / jnp.maximum(l, 1e-30)[..., None],
                    0.0)
    return out.reshape(b, h, sq, d).astype(q.dtype)


def page_write_blocked(pool, rows, table, *, page: int):
    """Same semantics as :func:`page_write`, one contiguous
    dynamic_update_slice per page — the large-page schedule."""
    blocks = rows.astype(pool.dtype).reshape((-1, page) + pool.shape[1:])

    def one(p, sb):
        start, blk = sb
        return jax.lax.dynamic_update_slice_in_dim(p, blk, start, axis=0), 0

    pool, _ = jax.lax.scan(one, pool, (table, blocks))
    return pool
