"""Whisper-style encoder-decoder backbone (conv frontend is a STUB: the
encoder consumes precomputed frame embeddings from input_specs).

Encoder: bidirectional self-attn + GELU MLP (LayerNorm).
Decoder: causal self-attn (KV cache) + cross-attn against encoder output
(cross K/V computed once) + GELU MLP.
Positions: sinusoidal added to encoder input; RoPE in decoder self-attention
(documented deviation from Whisper's learned positions — DESIGN.md §4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.tsl_api import ops as tsl

from repro.nn import flags as _nn_flags


def _scan(f, init, xs, **kw):
    return jax.lax.scan(f, init, xs, unroll=_nn_flags.scan_unroll(), **kw)


from .attention import (attention_decode, attention_forward,
                        attention_prefill_chunk, attention_span_paged,
                        attention_verify, cross_attention_forward,
                        init_attention, project_kv)
from .common import apply_norm_params, dense_init, embed_init, init_norm, split_keys
from .mlp import init_mlp, mlp_forward


def _sinusoid(s: int, d: int):
    # Computed on the HOST (numpy) so the table enters the graph as a literal
    # constant. A traced formulation (iota -> sin/cos -> concatenate) is
    # miscompiled by XLA CPU's SPMD partitioner when the result feeds any
    # sharded computation — the partitioned concat-of-iotas reassembles with
    # the halves misplaced, silently corrupting every encoder activation
    # (observed under --xla_force_host_platform_device_count; the constant
    # costs nothing and is immune).
    pos = np.arange(s, dtype=np.float32)[:, None]
    dim = np.arange(d // 2, dtype=np.float32)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    return jnp.asarray(np.concatenate([np.sin(ang), np.cos(ang)], axis=-1))


def _init_enc_block(key, cfg, dtype):
    ks = split_keys(key, 2)
    return {
        "attn_norm": init_norm(cfg, dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "mlp_norm": init_norm(cfg, dtype),
        "mlp": init_mlp(ks[1], cfg, dtype),
    }


def _init_dec_block(key, cfg, dtype):
    ks = split_keys(key, 3)
    return {
        "self_norm": init_norm(cfg, dtype),
        "self_attn": init_attention(ks[0], cfg, dtype),
        "cross_norm": init_norm(cfg, dtype),
        "cross_attn": init_attention(ks[1], cfg, dtype),
        "mlp_norm": init_norm(cfg, dtype),
        "mlp": init_mlp(ks[2], cfg, dtype),
    }


def init_encdec(key, cfg):
    dtype = jnp.dtype(cfg.dtype)
    ks = split_keys(key, 5)
    ekeys = jnp.stack(split_keys(ks[0], cfg.n_enc_layers))
    dkeys = jnp.stack(split_keys(ks[1], cfg.n_layers))
    return {
        "embed": embed_init(ks[2], (cfg.padded_vocab, cfg.d_model), dtype),
        "enc_blocks": jax.vmap(lambda k: _init_enc_block(k, cfg, dtype))(ekeys),
        "enc_norm": init_norm(cfg, dtype),
        "dec_blocks": jax.vmap(lambda k: _init_dec_block(k, cfg, dtype))(dkeys),
        "final_norm": init_norm(cfg, dtype),
        "head": dense_init(ks[3], (cfg.d_model, cfg.padded_vocab), dtype),
    }


def encode(params, audio_embeds, cfg, *, remat: bool = True):
    """audio_embeds (B,S,D) -> encoder output (B,S,D)."""
    s, d = audio_embeds.shape[1], audio_embeds.shape[2]
    x = audio_embeds + _sinusoid(s, d).astype(audio_embeds.dtype)
    positions = jnp.arange(s)

    from repro.dist.sharding import logical_constraint

    def body(x, bp):
        h, _ = attention_forward(bp["attn"], apply_norm_params(cfg, bp["attn_norm"], x),
                                 cfg, causal=False, positions=positions)
        x = x + h
        x = x + mlp_forward(bp["mlp"], apply_norm_params(cfg, bp["mlp_norm"], x), cfg)
        return logical_constraint(x, "batch", None, None), None

    b = jax.checkpoint(body, prevent_cse=False) if remat else body
    x, _ = _scan(b, x, params["enc_blocks"])
    return apply_norm_params(cfg, params["enc_norm"], x)


def encdec_forward(params, tokens, cfg, *, audio_embeds, remat: bool = True,
                   collect_cache: bool = False, last_only: bool = False):
    """Teacher-forced decode over full token sequence."""
    enc = encode(params, audio_embeds, cfg, remat=remat)
    x = tsl.embed_lookup(params["embed"], tokens)
    positions = jnp.arange(tokens.shape[1])

    from repro.dist.sharding import logical_constraint

    def body(x, bp):
        h, kv = attention_forward(bp["self_attn"],
                                  apply_norm_params(cfg, bp["self_norm"], x),
                                  cfg, causal=True, positions=positions)
        x = x + h
        ck, cv = project_kv(bp["cross_attn"], enc, cfg)
        x = x + cross_attention_forward(
            bp["cross_attn"], apply_norm_params(cfg, bp["cross_norm"], x), ck, cv, cfg)
        x = x + mlp_forward(bp["mlp"], apply_norm_params(cfg, bp["mlp_norm"], x), cfg)
        return logical_constraint(x, "batch", None, None), (kv if collect_cache else None)

    b = jax.checkpoint(body, prevent_cse=False) if remat else body
    x, kvs = _scan(b, x, params["dec_blocks"])
    if last_only:
        x = x[:, -1:]
    x = apply_norm_params(cfg, params["final_norm"], x)
    logits = tsl.matmul(x, params["head"])
    return logits, jnp.float32(0), (kvs, enc) if collect_cache else None


def init_encdec_state(cfg, batch: int, max_len: int, enc_len: int, dtype):
    kh, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((cfg.n_layers, batch, kh, max_len, hd), dtype),
        "v": jnp.zeros((cfg.n_layers, batch, kh, max_len, hd), dtype),
        # cross K/V precomputed from the encoder at prefill time
        "cross_k": jnp.zeros((cfg.n_layers, batch, kh, enc_len, hd), dtype),
        "cross_v": jnp.zeros((cfg.n_layers, batch, kh, enc_len, hd), dtype),
    }


def state_batch_axes(state):
    """Slot-axis position per state leaf (serve-layer state surgery): self-
    attn caches AND the per-request cross K/V are (L, B, KH, S, hd) — the
    request axis sits at 1. NOTE: cross K/V leaves are sized by the encoder
    length, so a donor only fits a batched state built with the SAME
    enc_len (the engine validates this before inserting)."""
    return {k: 1 for k in state}


def state_page_axes(state):
    """Token-axis per leaf for PAGED serving: decoder self-attention caches
    grow one row per emitted token (axis 3) and page; the cross K/V leaves
    are computed ONCE from the encoder at prefill and never grow — they are
    per-request TAIL state (``None``), sized by enc_len, snapshotted whole
    (and shared with the prefix store when prompts coincide)."""
    return {k: 3 if k in ("k", "v") else None for k in state}


def encdec_prefill(params, tokens, cfg, *, audio_embeds, max_len: int):
    enc = encode(params, audio_embeds, cfg, remat=False)

    def cross_kv(bp):
        return project_kv(bp["cross_attn"], enc, cfg)

    ck, cv = jax.lax.map(cross_kv, params["dec_blocks"])
    logits, _, cache = encdec_forward(params, tokens, cfg,
                                      audio_embeds=audio_embeds, remat=False,
                                      collect_cache=True, last_only=True)
    (k, v), _ = cache
    pad = max_len - k.shape[3]
    if pad > 0:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        k, v = jnp.pad(k, widths), jnp.pad(v, widths)
    return logits[:, -1], {"k": k, "v": v, "cross_k": ck, "cross_v": cv}


def encdec_prefill_chunk(params, state, tokens, pos, cfg, *, audio_embeds=None):
    """Continuation prefill of one decoder chunk. On the FIRST chunk
    (``audio_embeds`` given) the encoder runs once and the per-request cross
    K/V are seeded into the state; later chunks reuse them. Self-attention
    writes the chunk's K/V at rows [pos, pos+C). Returns (logits (B,C,V),
    new state)."""
    if audio_embeds is not None:
        enc = encode(params, audio_embeds, cfg, remat=False)

        def cross_kv(bp):
            return project_kv(bp["cross_attn"], enc, cfg)

        ck, cv = jax.lax.map(cross_kv, params["dec_blocks"])
        state = {**state, "cross_k": ck.astype(state["cross_k"].dtype),
                 "cross_v": cv.astype(state["cross_v"].dtype)}
    x = tsl.embed_lookup(params["embed"], tokens)

    def body(x_c, inp):
        bp, kc, vc, ck, cv = inp
        h, kc, vc = attention_prefill_chunk(
            bp["self_attn"], apply_norm_params(cfg, bp["self_norm"], x_c),
            kc, vc, pos, cfg)
        x_c = x_c + h
        q_in = apply_norm_params(cfg, bp["cross_norm"], x_c)
        x_c = x_c + cross_attention_forward(bp["cross_attn"], q_in, ck, cv, cfg)
        x_c = x_c + mlp_forward(bp["mlp"], apply_norm_params(cfg, bp["mlp_norm"], x_c), cfg)
        return x_c, (kc, vc)

    x, (k, v) = _scan(
        body, x, (params["dec_blocks"], state["k"], state["v"],
                  state["cross_k"], state["cross_v"]))
    x = apply_norm_params(cfg, params["final_norm"], x)
    logits = tsl.matmul(x, params["head"])
    return logits, {**state, "k": k, "v": v}


def encdec_verify_step(params, state, tokens, pos, cfg):
    """Speculative-decoding verify span: causal self-attention over the span
    through the attention_verify primitive (K/V slab at [pos, pos+SV));
    cross-attention is non-causal row-by-row against the precomputed cross
    K/V, so any span width scores exactly. KV rollback is free (kv_len
    truncation) — the updated state is returned, rejected rows sit beyond
    the committed fill. Returns (logits (B,SV,V), new state)."""
    x = tsl.embed_lookup(params["embed"], tokens)

    def body(x_c, inp):
        bp, kc, vc, ck, cv = inp
        h, kc, vc = attention_verify(
            bp["self_attn"], apply_norm_params(cfg, bp["self_norm"], x_c),
            kc, vc, pos, cfg)
        x_c = x_c + h
        q_in = apply_norm_params(cfg, bp["cross_norm"], x_c)
        x_c = x_c + cross_attention_forward(bp["cross_attn"], q_in, ck, cv, cfg)
        x_c = x_c + mlp_forward(bp["mlp"], apply_norm_params(cfg, bp["mlp_norm"], x_c), cfg)
        return x_c, (kc, vc)

    x, (k, v) = _scan(
        body, x, (params["dec_blocks"], state["k"], state["v"],
                  state["cross_k"], state["cross_v"]))
    x = apply_norm_params(cfg, params["final_norm"], x)
    logits = tsl.matmul(x, params["head"])
    return logits, {**state, "k": k, "v": v}


def _encdec_paged_span(params, state, pools, tables, tokens, pos, cfg,
                       span_op):
    """Fused-paged decode/verify body: decoder self-attention writes and
    reads its span straight against the page pools (attention_span_paged),
    carried whole through the layer scan and indexed by layer;
    cross-attention still runs against the per-request cross K/V TAILS in
    ``state`` (fixed-size, never paged). Returns (logits, pools)."""
    x = tsl.embed_lookup(params["embed"], tokens)

    def body(carry, inp):
        x_c, pl = carry
        bp, ck, cv, layer = inp
        h, pl = attention_span_paged(
            bp["self_attn"], apply_norm_params(cfg, bp["self_norm"], x_c),
            pl, layer, tables, pos, cfg, span_op)
        x_c = x_c + h
        q_in = apply_norm_params(cfg, bp["cross_norm"], x_c)
        x_c = x_c + cross_attention_forward(bp["cross_attn"], q_in, ck, cv, cfg)
        x_c = x_c + mlp_forward(bp["mlp"], apply_norm_params(cfg, bp["mlp_norm"], x_c), cfg)
        return (x_c, pl), None

    (x, pools), _ = _scan(
        body, (x, pools),
        (params["dec_blocks"], state["cross_k"], state["cross_v"],
         jnp.arange(state["cross_k"].shape[0])))
    x = apply_norm_params(cfg, params["final_norm"], x)
    return tsl.matmul(x, params["head"]), pools


def encdec_decode_step_paged(params, state, pools, tables, tokens_t, pos, cfg):
    """Fused paged decode for the decoder: self-attention straight off the
    page pools, cross-attention against the cross K/V tails. Returns
    (logits (B,V), state, pools)."""
    logits, pools = _encdec_paged_span(params, state, pools, tables,
                                       tokens_t, pos, cfg,
                                       tsl.attention_decode_paged)
    return logits[:, 0], state, pools


def encdec_verify_step_paged(params, state, pools, tables, tokens, pos, cfg):
    """Fused paged verify span (rollback free — rejected rows sit beyond
    the committed kv_len). Returns (logits (B,SV,V), state, pools)."""
    logits, pools = _encdec_paged_span(params, state, pools, tables,
                                       tokens, pos, cfg,
                                       tsl.attention_verify_paged)
    return logits, state, pools


def encdec_decode_step(params, state, tokens_t, pos, cfg):
    x = tsl.embed_lookup(params["embed"], tokens_t)

    def body(x_t, inp):
        bp, kc, vc, ck, cv = inp
        h, kc, vc = attention_decode(
            bp["self_attn"], apply_norm_params(cfg, bp["self_norm"], x_t),
            kc, vc, pos, cfg)
        x_t = x_t + h
        q_in = apply_norm_params(cfg, bp["cross_norm"], x_t)
        x_t = x_t + cross_attention_forward(bp["cross_attn"], q_in, ck, cv, cfg)
        x_t = x_t + mlp_forward(bp["mlp"], apply_norm_params(cfg, bp["mlp_norm"], x_t), cfg)
        return x_t, (kc, vc)

    x, (k, v) = _scan(
        body, x, (params["dec_blocks"], state["k"], state["v"],
                  state["cross_k"], state["cross_v"]))
    x = apply_norm_params(cfg, params["final_norm"], x)
    logits = tsl.matmul(x, params["head"])[:, 0]
    return logits, {**state, "k": k, "v": v}
