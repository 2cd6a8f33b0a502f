"""zamba2 hybrid LM: Mamba2 backbone + ONE shared attention block applied
every ``attn_every`` layers (weight sharing — the arch's defining trick).

Structure: n_groups = n_layers // attn_every groups of [attn_every mamba
layers + shared-attn application], plus a remainder stack. Group params are
stacked (G, k, ...) for a two-level scan; the shared attention block's weights
are closed over (NOT scanned), so XLA sees a single copy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.tsl_api import ops as tsl

from repro.nn import flags as _nn_flags


def _scan(f, init, xs, **kw):
    return jax.lax.scan(f, init, xs, unroll=_nn_flags.scan_unroll(), **kw)


from .attention import (attention_decode, attention_forward, attention_prefill_chunk,
                        attention_span_paged, attention_verify, init_attention)
from .common import apply_norm_params, dense_init, embed_init, init_norm, split_keys
from .lm import lm_head
from .mamba2 import dims as m2_dims, init_mamba2, mamba2_decode, mamba2_forward


def layout(cfg) -> tuple[int, int, int]:
    """(n_groups, group_size, remainder)."""
    k = cfg.attn_every
    g = cfg.n_layers // k
    return g, k, cfg.n_layers - g * k


def _init_mamba_block(key, cfg, dtype):
    return {
        "norm": init_norm(cfg, dtype),
        "mamba": init_mamba2(key, cfg, dtype),
    }


def init_zamba(key, cfg):
    dtype = jnp.dtype(cfg.dtype)
    g, k, rem = layout(cfg)
    ks = split_keys(key, 6)
    gkeys = jnp.stack(split_keys(ks[0], g * k)).reshape(g, k, -1)
    params = {
        "embed": embed_init(ks[1], (cfg.padded_vocab, cfg.d_model), dtype),
        "groups": jax.vmap(jax.vmap(lambda kk: _init_mamba_block(kk, cfg, dtype)))(gkeys),
        "shared_attn_norm": init_norm(cfg, dtype),
        "shared_attn": init_attention(ks[2], cfg, dtype),
        "final_norm": init_norm(cfg, dtype),
        "head": dense_init(ks[3], (cfg.d_model, cfg.padded_vocab), dtype),
    }
    if rem:
        rkeys = jnp.stack(split_keys(ks[4], rem))
        params["rest"] = jax.vmap(lambda kk: _init_mamba_block(kk, cfg, dtype))(rkeys)
    return params


def _mamba_block_fwd(bp, x, cfg):
    from repro.dist.sharding import logical_constraint
    y, (h, conv) = mamba2_forward(bp["mamba"], apply_norm_params(cfg, bp["norm"], x), cfg)
    return logical_constraint(x + y, "batch", None, None)


def zamba_forward(params, tokens, cfg, *, remat: bool = True):
    """tokens (B,S) -> (logits, aux=0, None)."""
    x = tsl.embed_lookup(params["embed"], tokens)
    positions = jnp.arange(x.shape[1])

    def mamba_body(x, bp):
        return _mamba_block_fwd(bp, x, cfg), None

    def group_body(x, gp):
        x, _ = _scan(mamba_body, x, gp)
        h, _ = attention_forward(
            params["shared_attn"],
            apply_norm_params(cfg, params["shared_attn_norm"], x),
            cfg, causal=True, positions=positions)
        return x + h, None

    gb = jax.checkpoint(group_body, prevent_cse=False) if remat else group_body
    x, _ = _scan(gb, x, params["groups"])
    if "rest" in params:
        mb = jax.checkpoint(mamba_body, prevent_cse=False) if remat else mamba_body
        x, _ = _scan(mb, x, params["rest"])
    x = apply_norm_params(cfg, params["final_norm"], x)
    return lm_head(params, x, cfg), jnp.float32(0), None


def zamba_prefill(params, tokens, cfg, *, max_len: int):
    """Full-sequence prefill collecting SSM states, conv tails and shared-attn
    KV caches for decode continuation."""
    x = tsl.embed_lookup(params["embed"], tokens)
    positions = jnp.arange(x.shape[1])

    def mamba_body(x, bp):
        y, (h, conv) = mamba2_forward(
            bp["mamba"], apply_norm_params(cfg, bp["norm"], x), cfg)
        return x + y, (h, conv)

    def group_body(x, gp):
        x, (h_g, conv_g) = _scan(mamba_body, x, gp)
        h, (k, v) = attention_forward(
            params["shared_attn"],
            apply_norm_params(cfg, params["shared_attn_norm"], x),
            cfg, causal=True, positions=positions)
        return x + h, (h_g, conv_g, k, v)

    x, (h, conv, k, v) = _scan(group_body, x, params["groups"])
    pad = max_len - k.shape[3]
    if pad > 0:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        k, v = jnp.pad(k, widths), jnp.pad(v, widths)
    state = {"h": h, "conv": conv, "attn_k": k, "attn_v": v}
    if "rest" in params:
        x, (h_r, conv_r) = _scan(mamba_body, x, params["rest"])
        state["h_rest"] = h_r
        state["conv_rest"] = conv_r
    x = apply_norm_params(cfg, params["final_norm"], x[:, -1:])
    return lm_head(params, x, cfg)[:, 0], state


def zamba_prefill_chunk(params, state, tokens, pos, cfg, *, n_real=None,
                        attend=attention_prefill_chunk):
    """Continuation prefill of one chunk into a live hybrid decode state:
    the mamba layers carry (h, conv) forward exactly (padding rows are
    identity updates — see mamba2_forward), the shared attention block
    writes the chunk's K/V at rows [pos, pos+C) of each group's cache.
    ``pos``/``n_real`` may be (B,) per-slot vectors (ragged commit replay
    over the slot table); ``attend`` swaps the shared-attn span op (the
    verify path routes through the attention_verify primitive). Returns
    (logits (B,C,V), new state)."""
    x = tsl.embed_lookup(params["embed"], tokens)

    def mamba_body(x_c, inp):
        bp, h0, conv_prev = inp
        y, (h_f, conv_tail) = mamba2_forward(
            bp["mamba"], apply_norm_params(cfg, bp["norm"], x_c), cfg,
            h0=h0, conv_prev=conv_prev, n_real=n_real)
        return x_c + y, (h_f, conv_tail)

    def group_body(x_c, inp):
        gp, h_g, conv_g, kc, vc = inp
        x_c, (h_new, conv_new) = _scan(mamba_body, x_c, (gp, h_g, conv_g))
        a, kc, vc = attend(
            params["shared_attn"],
            apply_norm_params(cfg, params["shared_attn_norm"], x_c),
            kc, vc, pos, cfg)
        return x_c + a, (h_new, conv_new, kc, vc)

    x, (h, conv, kc, vc) = _scan(
        group_body, x,
        (params["groups"], state["h"], state["conv"],
         state["attn_k"], state["attn_v"]))
    new_state = {"h": h, "conv": conv, "attn_k": kc, "attn_v": vc}
    if "rest" in params:
        x, (h_r, conv_r) = _scan(
            mamba_body, x, (params["rest"], state["h_rest"],
                            state["conv_rest"]))
        new_state["h_rest"] = h_r
        new_state["conv_rest"] = conv_r
    x = apply_norm_params(cfg, params["final_norm"], x)
    return lm_head(params, x, cfg), new_state


def zamba_verify_step(params, state, tokens, pos, cfg):
    """Speculative-decoding verify span, PURE scoring: the SSM states cannot
    be truncated, so the incoming state is returned UNCHANGED (checkpoint)
    and the engine replays the accepted prefix through
    :func:`zamba_prefill_chunk` with per-slot ``n_real`` (verify_commit) —
    the shared-attn K/V slab writes of that replay are idempotent over what
    this scoring pass computed and then discarded. The shared attention
    routes through the attention_verify primitive. Returns
    (logits (B,SV,V), state)."""
    logits, _ = zamba_prefill_chunk(params, state, tokens, pos, cfg,
                                    attend=attention_verify)
    return logits, state


def init_zamba_state(cfg, batch: int, max_len: int, dtype):
    g, k, rem = layout(cfg)
    d_in, nh, n, p_dim = m2_dims(cfg)
    kw = cfg.conv_width
    state = {
        "h": jnp.zeros((g, k, batch, nh, p_dim, n), jnp.float32),
        "conv": jnp.zeros((g, k, batch, kw - 1, d_in), dtype),
        "attn_k": jnp.zeros((g, batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
        "attn_v": jnp.zeros((g, batch, cfg.n_kv_heads, max_len, cfg.hd), dtype),
    }
    if rem:
        state["h_rest"] = jnp.zeros((rem, batch, nh, p_dim, n), jnp.float32)
        state["conv_rest"] = jnp.zeros((rem, batch, kw - 1, d_in), dtype)
    return state


def state_batch_axes(state):
    """Slot-axis position per state leaf (serve-layer state surgery): the
    grouped SSM/conv leaves are (G, k, B, ...) — request axis at 2; the
    shared-attn caches (G, B, KH, S, hd) and the remainder stack
    (rem, B, ...) carry it at 1."""
    return {k: 2 if k in ("h", "conv") else 1 for k in state}


# the shared attention block's K and V cache (and page-pool) leaves
_ATTN = ("attn_k", "attn_v")


def state_page_axes(state):
    """Token-axis per leaf for PAGED serving: only the shared-attention KV
    caches (G, B, KH, S, hd) grow per token (axis 3). The SSM/conv leaves
    are fixed-size recurrent state — ``None`` marks them as the per-request
    TAIL the paged store snapshots whole (and shares at prefix boundaries)
    instead of paging."""
    return {k: 3 if k in _ATTN else None for k in state}


def _zamba_paged_chunk(params, state, pools, tables, tokens, pos, cfg, *,
                       span_op, n_real=None):
    """Fused-paged analogue of :func:`zamba_prefill_chunk`: the mamba layers
    carry the TAIL state (h, conv) exactly as before, while the shared
    attention block writes and reads its span straight against the page
    pools (attention_span_paged) — the stacked pools ride the group scan's
    carry, read and written at the group's index. Returns (logits, new tail
    state, new pools)."""
    x = tsl.embed_lookup(params["embed"], tokens)

    def mamba_body(x_c, inp):
        bp, h0, conv_prev = inp
        y, (h_f, conv_tail) = mamba2_forward(
            bp["mamba"], apply_norm_params(cfg, bp["norm"], x_c), cfg,
            h0=h0, conv_prev=conv_prev, n_real=n_real)
        return x_c + y, (h_f, conv_tail)

    def group_body(carry, inp):
        x_c, pl = carry
        gp, h_g, conv_g, group = inp
        x_c, (h_new, conv_new) = _scan(mamba_body, x_c, (gp, h_g, conv_g))
        a, pl = attention_span_paged(
            params["shared_attn"],
            apply_norm_params(cfg, params["shared_attn_norm"], x_c),
            pl, group, tables, pos, cfg, span_op, names=_ATTN)
        return (x_c + a, pl), (h_new, conv_new)

    (x, pools), (h, conv) = _scan(
        group_body, (x, pools),
        (params["groups"], state["h"], state["conv"],
         jnp.arange(state["h"].shape[0])))
    new_state = {**state, "h": h, "conv": conv}
    if "rest" in params:
        x, (h_r, conv_r) = _scan(
            mamba_body, x, (params["rest"], state["h_rest"],
                            state["conv_rest"]))
        new_state["h_rest"] = h_r
        new_state["conv_rest"] = conv_r
    x = apply_norm_params(cfg, params["final_norm"], x)
    return lm_head(params, x, cfg), new_state, pools


def zamba_decode_step_paged(params, state, pools, tables, tokens_t, pos, cfg):
    """Fused paged decode: the mamba recurrence updates its TAIL state
    bit-identically to :func:`zamba_decode_step` (same mamba2_decode), and
    the shared attention block decodes straight off the page pools, carried
    whole through the group scan. Returns (logits (B,V), new tail state, new
    pools)."""
    x = tsl.embed_lookup(params["embed"], tokens_t)

    def mamba_step(x_t, inp):
        bp, h, conv = inp
        y, h, conv = mamba2_decode(bp["mamba"],
                                   apply_norm_params(cfg, bp["norm"], x_t),
                                   cfg, h, conv)
        return x_t + y, (h, conv)

    def group_step(carry, inp):
        x_t, pl = carry
        gp, h_g, conv_g, group = inp
        x_t, (h_g, conv_g) = _scan(mamba_step, x_t, (gp, h_g, conv_g))
        a, pl = attention_span_paged(
            params["shared_attn"],
            apply_norm_params(cfg, params["shared_attn_norm"], x_t),
            pl, group, tables, pos, cfg, tsl.attention_decode_paged,
            names=_ATTN)
        return (x_t + a, pl), (h_g, conv_g)

    (x, pools), (h, conv) = _scan(
        group_step, (x, pools),
        (params["groups"], state["h"], state["conv"],
         jnp.arange(state["h"].shape[0])))
    new_state = {**state, "h": h, "conv": conv}
    if "rest" in params:
        x, (h_r, conv_r) = _scan(
            mamba_step, x, (params["rest"], state["h_rest"],
                            state["conv_rest"]))
        new_state["h_rest"] = h_r
        new_state["conv_rest"] = conv_r
    x = apply_norm_params(cfg, params["final_norm"], x)
    return lm_head(params, x, cfg)[:, 0], new_state, pools


def zamba_verify_step_paged(params, state, pools, tables, tokens, pos, cfg):
    """Fused paged verify span, PURE scoring for the tails: the incoming
    tail state comes back UNCHANGED (the engine replays the accepted prefix
    through :func:`zamba_verify_commit_paged`), while the span's K/V rows
    land in the pools — the replay's writes are idempotent over them and
    rejected rows sit beyond the committed kv_len. Returns
    (logits (B,SV,V), state, pools)."""
    logits, _, pools = _zamba_paged_chunk(
        params, state, pools, tables, tokens, pos, cfg,
        span_op=tsl.attention_verify_paged)
    return logits, state, pools


def zamba_verify_commit_paged(params, state, pools, tables, tokens, pos, cfg,
                              n_commit):
    """Commit replay on the pools: re-run the accepted prefix with per-slot
    ``n_commit`` (B,) real rows — n_commit == 0 is an exact identity for
    that slot's tails. Returns (new tail state, new pools)."""
    _, state, pools = _zamba_paged_chunk(
        params, state, pools, tables, tokens, pos, cfg,
        span_op=tsl.attention_verify_paged, n_real=n_commit)
    return state, pools


def zamba_decode_step(params, state, tokens_t, pos, cfg):
    x = tsl.embed_lookup(params["embed"], tokens_t)

    def mamba_step(x_t, inp):
        bp, h, conv = inp
        y, h, conv = mamba2_decode(bp["mamba"],
                                   apply_norm_params(cfg, bp["norm"], x_t),
                                   cfg, h, conv)
        return x_t + y, (h, conv)

    def group_step(x_t, inp):
        gp, h_g, conv_g, kc, vc = inp
        x_t, (h_g, conv_g) = _scan(mamba_step, x_t, (gp, h_g, conv_g))
        a, kc, vc = attention_decode(
            params["shared_attn"],
            apply_norm_params(cfg, params["shared_attn_norm"], x_t),
            kc, vc, pos, cfg)
        return x_t + a, (h_g, conv_g, kc, vc)

    x, (h, conv, kc, vc) = _scan(
        group_step, x,
        (params["groups"], state["h"], state["conv"],
         state["attn_k"], state["attn_v"]))
    new_state = {"h": h, "conv": conv, "attn_k": kc, "attn_v": vc}
    if "rest" in params:
        x, (h_r, conv_r) = _scan(
            mamba_step, x, (params["rest"], state["h_rest"], state["conv_rest"]))
        new_state["h_rest"] = h_r
        new_state["conv_rest"] = conv_r
    x = apply_norm_params(cfg, params["final_norm"], x)
    return lm_head(params, x, cfg)[:, 0], new_state
