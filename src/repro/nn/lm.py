"""Decoder-only transformer LM (dense / MoE / VLM backbones).

Layer params are stacked along a leading L axis and iterated with lax.scan
(critical for 60-88-layer configs: HLO stays O(1) in depth); the block body is
wrapped in jax.checkpoint for training (remat policy from train/).
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
from .mlp import init_mlp, mlp_forward
from .moe import init_moe, moe_forward


def _init_block(key, cfg, dtype):
    ks = split_keys(key, 4)
    p = {
        "attn_norm": init_norm(cfg, dtype),
        "attn": init_attention(ks[0], cfg, dtype),
        "mlp_norm": init_norm(cfg, dtype),
    }
    if cfg.n_experts:
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[2], cfg, dtype)
    return p


def init_lm(key, cfg):
    dtype = jnp.dtype(cfg.dtype)
    ks = split_keys(key, 4)
    block_keys = jnp.stack(split_keys(ks[0], cfg.n_layers))
    params = {
        "embed": embed_init(ks[1], (cfg.padded_vocab, cfg.d_model), dtype),
        "blocks": jax.vmap(lambda k: _init_block(k, cfg, dtype))(block_keys),
        "final_norm": init_norm(cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = dense_init(ks[2], (cfg.d_model, cfg.padded_vocab), dtype)
    if cfg.vision_prefix:
        # stub frontend's projection stands in for the ViT adapter
        params["vision_proj"] = dense_init(ks[3], (cfg.d_model, cfg.d_model), dtype)
    return params


def _block_forward(bp, x, cfg, positions):
    from repro.dist.sharding import logical_constraint
    h, kv = attention_forward(bp["attn"], apply_norm_params(cfg, bp["attn_norm"], x),
                              cfg, causal=True, positions=positions)
    x = x + h
    y = apply_norm_params(cfg, bp["mlp_norm"], x)
    if cfg.n_experts:
        y, aux = moe_forward(bp["moe"], y, cfg)
    else:
        y, aux = mlp_forward(bp["mlp"], y, cfg), jnp.float32(0)
    # pin the residual stream layout at block boundaries: stops GSPMD from
    # ping-ponging shardings between (unrolled) layers; under --sp the stream
    # is sequence-sharded on the model axis (SP-TP)
    x = logical_constraint(x + y, *_nn_flags.residual_axes())
    return x, aux, kv


def embed_inputs(params, tokens, cfg, vision_embeds=None):
    x = tsl.embed_lookup(params["embed"], tokens)
    if cfg.vision_prefix and vision_embeds is not None:
        v = tsl.matmul(vision_embeds.astype(x.dtype), params["vision_proj"])
        x = jnp.concatenate([v, x], axis=1)
    return x


def lm_forward(params, tokens, cfg, *, vision_embeds=None, remat: bool = True,
               collect_cache: bool = False, remat_policy=None,
               last_only: bool = False):
    """tokens (B,S) -> (logits (B,S_total,V), aux_loss, caches|None).

    last_only: compute logits for the final position only (prefill path —
    avoids materializing the (B,S,V) tensor)."""
    x = embed_inputs(params, tokens, cfg, vision_embeds)
    s_total = x.shape[1]
    positions = jnp.arange(s_total)

    def body(x, bp):
        xo, aux, kv = _block_forward(bp, x, cfg, positions)
        out = (aux, kv) if collect_cache else (aux, None)
        return xo, out

    if remat:
        body = jax.checkpoint(body, policy=remat_policy,
                              prevent_cse=False)
    x, (auxs, kvs) = _scan(body, x, params["blocks"])
    x = apply_norm_params(cfg, params["final_norm"], x)
    if last_only:
        x = x[:, -1:]
    logits = lm_head(params, x, cfg)
    return logits, jnp.sum(auxs), kvs


def lm_head(params, x, cfg):
    from repro.dist.sharding import logical_constraint
    if cfg.tie_embeddings:
        logits = tsl.matmul(x, params["embed"].T)
    else:
        logits = tsl.matmul(x, params["head"])
    # vocab-sharded logits: the single biggest activation — keep it TP-sharded
    # so xent's logsumexp runs shard-local + one small psum (GSPMD)
    if logits.ndim == 3:
        logits = logical_constraint(logits, "batch", None, "vocab")
    else:
        logits = logical_constraint(logits, "batch", "vocab")
    return logits


def init_decode_state(cfg, batch: int, max_len: int, dtype):
    kh, hd = cfg.n_kv_heads, cfg.hd
    shape = (cfg.n_layers, batch, kh, max_len, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def state_batch_axes(state):
    """Slot-axis position per state leaf (serve-layer state surgery): KV
    cache leaves are (L, B, KH, S_max, hd) — the request axis sits at 1."""
    return {k: 1 for k in state}


def state_page_axes(state):
    """Token-axis position per state leaf for PAGED serving (None = not
    paged): every KV leaf grows along axis 3, one row per cache token, so
    both leaves page. KV rows depend only on their absolute position (rotary
    at write time), which is what makes prefix pages exactly shareable."""
    return {k: 3 for k in state}


def lm_prefill(params, tokens, cfg, *, max_len: int, vision_embeds=None):
    """Full-sequence prefill; returns (last_logits, decode state)."""
    logits, _, kvs = lm_forward(params, tokens, cfg, vision_embeds=vision_embeds,
                                remat=False, collect_cache=True, last_only=True)
    k, v = kvs                                   # (L,B,KH,S,hd)
    pad = max_len - k.shape[3]
    if pad > 0:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
        k, v = jnp.pad(k, widths), jnp.pad(v, widths)
    return logits[:, -1], {"k": k, "v": v}


def lm_prefill_chunk(params, state, tokens, pos, cfg, *, vision_embeds=None):
    """Continuation prefill of one chunk into a live decode state.

    tokens (B,C): the next chunk of the prompt; ``pos`` (scalar, may be
    traced) is the cache fill before this chunk — the chunk's K/V land at
    rows [pos, pos+C) and its queries attend causally to everything up to
    themselves. ``vision_embeds`` (vlm, first chunk only, (B,prefix,D))
    prepends the projected vision prefix rows to the chunk.

    Trailing padding rows in the chunk need no masking (see
    attention_prefill_chunk); the caller reads logits at its last real row.
    Returns (logits (B, C', V) with C' = prefix+C on the vision chunk, new
    state)."""
    x = embed_inputs(params, tokens, cfg, vision_embeds)

    def body(x_c, inp):
        bp, kc, vc = inp
        h, kc, vc = attention_prefill_chunk(
            bp["attn"], apply_norm_params(cfg, bp["attn_norm"], x_c),
            kc, vc, pos, cfg)
        x_c = x_c + h
        y = apply_norm_params(cfg, bp["mlp_norm"], x_c)
        if cfg.n_experts:
            y, _ = moe_forward(bp["moe"], y, cfg)
        else:
            y = mlp_forward(bp["mlp"], y, cfg)
        return x_c + y, (kc, vc)

    x, (k_new, v_new) = _scan(body, x, (params["blocks"], state["k"],
                                        state["v"]))
    x = apply_norm_params(cfg, params["final_norm"], x)
    logits = lm_head(params, x, cfg)
    return logits, {"k": k_new, "v": v_new}


def lm_verify_step(params, state, tokens, pos, cfg):
    """Speculative-decoding verify span. tokens (B,SV): each slot's pending
    token + drafted continuation; ``pos`` scalar or (B,) per-slot base write
    index. The span's K/V land at rows [pos, pos+SV); one ragged batched
    attention_verify scores every row (logits row j validates draft j+1).

    Rollback is free for this family: the accepted fill pos+m+1 simply stops
    short of the rejected rows, whose cache entries sit beyond kv_len where
    the decode mask hides them until overwritten. Returns
    (logits (B,SV,V), new state)."""
    x = tsl.embed_lookup(params["embed"], tokens)

    def body(x_c, inp):
        bp, kc, vc = inp
        h, kc, vc = attention_verify(
            bp["attn"], apply_norm_params(cfg, bp["attn_norm"], x_c),
            kc, vc, pos, cfg)
        x_c = x_c + h
        y = apply_norm_params(cfg, bp["mlp_norm"], x_c)
        if cfg.n_experts:
            y, _ = moe_forward(bp["moe"], y, cfg)
        else:
            y = mlp_forward(bp["mlp"], y, cfg)
        return x_c + y, (kc, vc)

    x, (k_new, v_new) = _scan(body, x, (params["blocks"], state["k"],
                                        state["v"]))
    x = apply_norm_params(cfg, params["final_norm"], x)
    logits = lm_head(params, x, cfg)
    return logits, {"k": k_new, "v": v_new}


def _lm_paged_span(params, pools, tables, tokens, pos, cfg, span_op):
    """Shared fused-paged decode/verify body: every layer's span attends
    DIRECTLY against the block-table page pools (see attention_span_paged).
    The stacked pools ride the layer scan's CARRY, whole, and each layer
    reads and writes them at its own index (the scan's counter): no layer
    slice is taken or stacked back, so the loop updates the donated pools in
    place and HLO stays O(1) in depth. Returns (logits (B,C,V), new pools
    dict)."""
    x = tsl.embed_lookup(params["embed"], tokens)

    def body(carry, inp):
        x_c, pl = carry
        bp, layer = inp
        h, pl = attention_span_paged(
            bp["attn"], apply_norm_params(cfg, bp["attn_norm"], x_c),
            pl, layer, tables, pos, cfg, span_op)
        x_c = x_c + h
        y = apply_norm_params(cfg, bp["mlp_norm"], x_c)
        if cfg.n_experts:
            y, _ = moe_forward(bp["moe"], y, cfg)
        else:
            y = mlp_forward(bp["mlp"], y, cfg)
        return (x_c + y, pl), None

    (x, pools), _ = _scan(body, (x, pools),
                          (params["blocks"], jnp.arange(cfg.n_layers)))
    x = apply_norm_params(cfg, params["final_norm"], x)
    return lm_head(params, x, cfg), pools


def lm_decode_step_paged(params, state, pools, tables, tokens_t, pos, cfg):
    """Fused paged decode: tokens_t (B,1) scored straight off the page
    pools via attention_decode_paged (tables (B,P) int32 page ids, ``pos``
    (B,) per-slot positions). ``state`` is the family's TAIL-only dict —
    empty for this family (every leaf pages) — passed through untouched.
    Returns (logits (B,V), state, pools)."""
    logits, pools = _lm_paged_span(params, pools, tables, tokens_t, pos, cfg,
                                   tsl.attention_decode_paged)
    return logits[:, 0], state, pools


def lm_verify_step_paged(params, state, pools, tables, tokens, pos, cfg):
    """Fused paged verify span: tokens (B,SV) score in one ragged batched
    attention_verify_paged pass; the span's K/V rows land in their pages,
    rejected rows sit beyond the committed kv_len — rollback free, exactly
    the lane-path contract. Returns (logits (B,SV,V), state, pools)."""
    logits, pools = _lm_paged_span(params, pools, tables, tokens, pos, cfg,
                                   tsl.attention_verify_paged)
    return logits, state, pools


def lm_decode_step(params, state, tokens_t, pos, cfg):
    """tokens_t (B,1); pos: scalar int32 write index, or a (B,) vector of
    per-slot indices (continuous batching — see attention_decode). Returns
    (logits (B,V), new state)."""
    x = tsl.embed_lookup(params["embed"], tokens_t)

    def body(x_t, inp):
        bp, kc, vc = inp
        h, kc, vc = attention_decode(
            bp["attn"], apply_norm_params(cfg, bp["attn_norm"], x_t),
            kc, vc, pos, cfg)
        x_t = x_t + h
        y = apply_norm_params(cfg, bp["mlp_norm"], x_t)
        if cfg.n_experts:
            y, _ = moe_forward(bp["moe"], y, cfg)
        else:
            y = mlp_forward(bp["mlp"], y, cfg)
        return x_t + y, (kc, vc)

    x, (k_new, v_new) = _scan(body, x, (params["blocks"], state["k"],
                                               state["v"]))
    x = apply_norm_params(cfg, params["final_norm"], x)
    logits = lm_head(params, x, cfg)[:, 0]
    return logits, {"k": k_new, "v": v_new}
