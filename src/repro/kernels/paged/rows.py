"""The row format of a fused page pool, in one place.

A fused pool keeps its leaf's layout with the token axis split into
``(n_pages, page)``. Where that axis sits just before the last one (a KV
leaf's ``(..., tokens, d)``), a pool row holds ``f`` consecutive tokens of
``d`` lanes each, padded to whole 128-lane tiles:
``(..., n_pages, page // f, w)``, ``f * d <= w``. The TPU keeps an array
whose last dimension is a multiple of the lanes in plain row-major order,
where a page is one contiguous block and a stack of layers flattens into the
paged kernel's ``(L * KH, rows, w)`` operand by a bitcast; it lays out other
last dimensions for itself, and the kernel could then read a layer of a
carried stack only after copying the whole stack (found compiling for a v5e
at d = 64 and at d = 112). Heads of 128 lanes or a multiple keep
``f = 1, w = d``: the plain pool.

Every reader and writer of a pool goes through these functions; the format
is read back off a pool as ``f = w // d``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import LANES, round_up


def row_format(page: int, d: int) -> tuple[int, int]:
    """(tokens per row, lanes per row) for a page of ``page`` tokens of
    width ``d``: as many tokens as fit the 128 lanes, padded to a tile.
    A page the fold does not divide keeps one unpadded token a row."""
    f = max(LANES // d, 1)
    if page % f:
        return 1, d
    return f, round_up(f * d, LANES)


def fold(pool, d: int) -> int:
    """Tokens per row of ``pool`` (last dimension ``w``), tokens ``d`` wide."""
    return pool.shape[-1] // d


def page_tokens(pool, d: int) -> int:
    """Tokens per page of a pool ``(..., n_pages, rows, w)``."""
    return pool.shape[-2] * fold(pool, d)


def pack(x, w: int):
    """``(..., T, d)`` tokens as ``(..., T // f, w)`` rows, ``f = w // d``,
    the lanes past ``f * d`` zero."""
    d = x.shape[-1]
    f = w // d
    x = x.reshape(x.shape[:-2] + (x.shape[-2] // f, f * d))
    if f * d < w:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, w - f * d)])
    return x


def unpack(x, d: int):
    """``(..., R, w)`` rows as ``(..., R * f, d)`` tokens, ``f = w // d``."""
    f = x.shape[-1] // d
    return x[..., :f * d].reshape(x.shape[:-2] + (x.shape[-2] * f, d))


def write_token(pool, token, lead, page_id, off):
    """``pool`` with ``token`` (``(KH, d)``) written in place as token
    ``off`` of page ``page_id`` of the pool at leading index ``lead``
    (``pool`` ``(*lead, KH, n_pages, rows, w)``); all indices traced."""
    kh, d = token.shape
    f = fold(pool, d)
    upd = token.astype(pool.dtype).reshape((1,) * len(lead) + (kh, 1, 1, d))
    zero = jnp.int32(0)
    return jax.lax.dynamic_update_slice(
        pool, upd, (*lead, zero, page_id, off // f, (off % f) * d))
