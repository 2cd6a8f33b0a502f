"""Plain float32 reference of a dense decoder LM: RMSNorm, GQA attention with
half-split rotary embeddings (and optional q/k/v biases and per-head q/k
RMSNorm), a SwiGLU MLP, and a tied or untied head.

It follows the published Qwen1.5 / Qwen3 description in plain ``jax.numpy``
and imports nothing of the program. Matmuls run at
``jax.lax.Precision.HIGHEST``, since a float32 matmul on a TPU otherwise
rounds its inputs to bfloat16. A sequence runs one layer at a time (only one
layer's weights are in float32 at once), attention in blocks of query rows,
and the head in blocks of the vocabulary, so a 14B-wide layer and a
152k-wide head fit beside the bfloat16 weights on one chip. Sequences are
padded at the end to a multiple of ``PAD`` so that few shapes compile;
causal attention keeps the padding from touching real rows.

``quant`` computes every matmul in a lower precision than float32 (inputs
rounded, float32 accumulation): ``"bfloat16"``, ``"int8"`` (symmetric,
weights per output column, activations per row) or ``"fp8"`` (float8 e4m3,
scaled the same way). That is the control of the
correctness check: the reference one precision step below the one the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..spec import Dims

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PAD = 512                 # sequences pad to a multiple of this
Q_BLOCK = 512             # query rows per attention block
VOCAB_BLOCKS = 16         # the padded vocabulary splits into this many


def _round(x, quant, axis):
    """x rounded to ``quant``."""
    if quant is None:
        return x
    if quant == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(F32)
    elif quant == "int8":
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        q = jnp.clip(jnp.round(x / s), -127, 127) * s
    elif quant == "fp8":
        # float8 e4m3, scaled so the largest magnitude maps to its max 448
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
        s = jnp.where(s > 0, s, 1.0)
        q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    else:
        raise ValueError(f"unknown quant {quant!r}")
    return q


def mm(x, w, quant=None):
    """x (..., K) @ w (K, N) in float32, inputs optionally rounded."""
    return jnp.matmul(_round(x, quant, -1), _round(w.astype(F32), quant, 0),
                      precision=HI)


def rmsnorm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * w.astype(F32)


def rope(x, positions, theta):
    """x (S, heads, hd): rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, quant=None):
    """Causal GQA: q (S, H, hd), k/v (S, KH, hd) -> (S, H * hd), in blocks
    of query rows."""
    s, h, hd = q.shape
    kh = k.shape[1]
    q = q.reshape(s, kh, h // kh, hd)
    kr, vr = _round(k, quant, -1), _round(v, quant, 0)
    keys = jnp.arange(s)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        rows = q0 + jnp.arange(qb.shape[0])
        sc = jnp.einsum("qkgd,tkd->kgqt", _round(qb, quant, -1), kr,
                        precision=HI) * hd ** -0.5
        sc = jnp.where(rows[:, None] >= keys[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", _round(p, quant, -1), vr,
                       precision=HI)
        out.append(o.reshape(qb.shape[0], h * hd))
    return jnp.concatenate(out, axis=0)


def layer(x, p, dims: Dims, quant=None):
    """One decoder layer on a whole sequence x (S, d), causal."""
    s = x.shape[0]
    h, kh, hd = dims.heads, dims.kv_heads, dims.head_dim
    a = rmsnorm(x, p["attn_norm"]["w"], dims.eps)
    at = p["attn"]
    q, k, v = (mm(a, at["wq"], quant), mm(a, at["wk"], quant),
               mm(a, at["wv"], quant))
    if dims.qkv_bias:
        q = q + at["bq"].astype(F32)
        k = k + at["bk"].astype(F32)
        v = v + at["bv"].astype(F32)
    q, k, v = q.reshape(s, h, hd), k.reshape(s, kh, hd), v.reshape(s, kh, hd)
    if dims.qk_norm:
        q = rmsnorm(q, at["q_norm"], dims.eps)
        k = rmsnorm(k, at["k_norm"], dims.eps)
    pos = jnp.arange(s)
    q, k = rope(q, pos, dims.rope_theta), rope(k, pos, dims.rope_theta)
    x = x + mm(attention(q, k, v, quant), at["wo"], quant)
    m = rmsnorm(x, p["mlp_norm"]["w"], dims.eps)
    mp = p["mlp"]
    gate, up = mm(m, mp["w_gate"], quant), mm(m, mp["w_up"], quant)
    return x + mm(jax.nn.silu(gate) * up, mp["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer_jit(x, blocks, i, dims, quant):
    p = jax.tree.map(lambda a: a[i], blocks)
    return layer(x, p, dims, quant)


def hidden(params, tokens, dims: Dims, quant=None):
    """Final-normed hidden states (S, d) of one token sequence (padded at
    the end to a multiple of PAD; the caller reads the first len(tokens)
    rows)."""
    toks = np.asarray(tokens, np.int32)
    n = len(toks)
    toks = np.pad(toks, (0, -n % PAD))
    x = jnp.take(params["embed"], jnp.asarray(toks), axis=0).astype(F32)
    for i in range(dims.layers):
        x = _layer_jit(x, params["blocks"], i, dims, quant)
    return rmsnorm(x, params["final_norm"]["w"], dims.eps)


def _table(params, dims: Dims):
    """The head as (padded vocab, d) rows: the embedding when tied."""
    return params["embed"] if dims.tied else params["head"].T


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def head_stats(h, params, dims: Dims, quant=None):
    """(max, argmax) over the real vocabulary of the logits h @ head, one
    vocabulary block at a time; padding rows of the table never win."""
    table = _table(params, dims)
    blk = table.shape[0] // VOCAB_BLOCKS

    def body(i, carry):
        best, arg = carry
        w = jax.lax.dynamic_slice_in_dim(table, i * blk, blk, 0)
        lg = mm(h, w.T, quant)
        ids = i * blk + jnp.arange(blk)
        lg = jnp.where(ids[None, :] < dims.vocab, lg, -jnp.inf)
        m, a = lg.max(-1), i * blk + lg.argmax(-1)
        return jnp.maximum(best, m), jnp.where(m > best, a, arg)

    s = h.shape[0]
    init = (jnp.full((s,), -jnp.inf, F32), jnp.zeros((s,), jnp.int32))
    return jax.lax.fori_loop(0, VOCAB_BLOCKS, body, init)


@functools.partial(jax.jit, static_argnames=("dims",))
def token_logits(h, params, ids, dims: Dims):
    """The float32 logit of token ids[s] at each row s of h."""
    w = jnp.take(_table(params, dims), ids, axis=0).astype(F32)   # (S, d)
    return jnp.einsum("sd,sd->s", h, w, precision=HI)


def logits(params, tokens, dims: Dims, quant=None):
    """(S, vocab) float32 logits of one token sequence (small models)."""
    h = hidden(params, tokens, dims, quant)[:len(tokens)]
    w = _table(params, dims)[:dims.vocab]
    return mm(h, w.T, quant)
