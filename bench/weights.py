"""Weights drawn from the seed by the benchmark itself, in one jitted call on
the device, in the dtype they are served in.

The program and the reference get the same values: each leaf is drawn from
the seed and its path alone. The layout is the benchmark's own statement of
the program's parameter tree; the harness checks it against the program's
before a run, so a changed layout fails loudly instead of comparing wrong
weights. Norm scales and biases are drawn too (the program's own init sets
them to 1 and 0), so the check sees a norm or a bias that is dropped."""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from .spec import Dims


def param_shapes(dims: Dims) -> dict:
    """{path: (shape, dtype)} of the dense LM's parameter tree, layers
    stacked on a leading axis."""
    L, d, hd = dims.layers, dims.d, dims.head_dim
    h, kh, ff, pv = dims.heads, dims.kv_heads, dims.d_ff, dims.padded_vocab
    dt = dims.dtype
    out = {
        "embed": ((pv, d), dt),
        "final_norm/w": ((d,), dt),
        "blocks/attn_norm/w": ((L, d), dt),
        "blocks/mlp_norm/w": ((L, d), dt),
        "blocks/attn/wq": ((L, d, h * hd), dt),
        "blocks/attn/wk": ((L, d, kh * hd), dt),
        "blocks/attn/wv": ((L, d, kh * hd), dt),
        "blocks/attn/wo": ((L, h * hd, d), dt),
        "blocks/mlp/w_gate": ((L, d, ff), dt),
        "blocks/mlp/w_up": ((L, d, ff), dt),
        "blocks/mlp/w_down": ((L, ff, d), dt),
    }
    if dims.qkv_bias:
        out["blocks/attn/bq"] = ((L, h * hd), dt)
        out["blocks/attn/bk"] = ((L, kh * hd), dt)
        out["blocks/attn/bv"] = ((L, kh * hd), dt)
    if dims.qk_norm:
        out["blocks/attn/q_norm"] = ((L, hd), dt)
        out["blocks/attn/k_norm"] = ((L, hd), dt)
    if not dims.tied:
        out["head"] = ((d, pv), dt)
    return out


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        *parents, leaf = path.split("/")
        node = tree
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, path + "/"))
        else:
            out[path] = v
    return out


def check_layout(dims: Dims, program_shapes: dict) -> None:
    """Raise unless the program's parameter tree (``jax.eval_shape`` of its
    init) has exactly this module's paths, shapes and dtypes."""
    want = {p: (tuple(s), jnp.dtype(t)) for p, (s, t) in
            param_shapes(dims).items()}
    got = {p: (tuple(a.shape), jnp.dtype(a.dtype)) for p, a in
           flatten(program_shapes).items()}
    if want != got:
        diff = sorted(set(want.items()) ^ set(got.items()))
        raise ValueError(f"the program's parameter layout differs from the "
                         f"benchmark's: {diff}")


def seed_key(seed: int):
    """A PRNG key for any whole seed up to 2**64 (both halves folded in)."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _leaf(key, path: str, shape, dtype, vocab: int):
    name = path.rsplit("/", 1)[-1]
    k = jax.random.fold_in(key, np.uint32(zlib.crc32(path.encode())))
    z = jax.random.normal(k, shape, jnp.float32)
    if name in ("w", "q_norm", "k_norm"):
        x = 1.0 + 0.1 * z
    elif name in ("bq", "bk", "bv"):
        x = 0.1 * z
    elif name == "embed":
        x = 0.02 * z
        x = jnp.where(jnp.arange(shape[0])[:, None] < vocab, x, 0.0)
    else:
        x = z * shape[-2] ** -0.5           # fan-in scaled
        if name == "head":
            x = jnp.where(jnp.arange(shape[1])[None, :] < vocab, x, 0.0)
    return x.astype(dtype)


def make_params(dims: Dims, seed: int, dtype=None) -> dict:
    """The nested parameter tree for ``seed``, made on the default device in
    one jitted call (``dtype`` overrides the configuration's)."""
    shapes = param_shapes(dims)

    def gen(key):
        return nest({p: _leaf(key, p, s, dtype or t, dims.vocab)
                     for p, (s, t) in shapes.items()})

    return jax.jit(gen)(seed_key(seed))
