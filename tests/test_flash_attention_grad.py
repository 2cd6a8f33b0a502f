"""Flash-attention backward: dedicated recomputation dq/dk/dv Pallas kernels
(interpret mode on CPU) vs the jnp oracle VJP, plus the memory contract —
the custom_vjp saves only O(Sq)-per-head residuals, never the (Sq, Sk)
attention matrix (ISSUE 3 acceptance criteria)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(7)


def _arr(shape, dt="float32", lo=-1, hi=1):
    return jnp.asarray(RNG.uniform(lo, hi, shape), dtype=dt)


def _tol(dt):
    return dict(rtol=5e-2, atol=5e-2) if dt == "bfloat16" \
        else dict(rtol=2e-3, atol=2e-3)


def _oracle_grads(q, k, v, g, **kw):
    from repro.kernels.flash_attention import ref

    _, vjp = jax.vjp(lambda q_, k_, v_: ref.attention(q_, k_, v_, **kw), q, k, v)
    return vjp(g)


@pytest.mark.parametrize("b,h,kh,sq,sk,d,causal,kv_len", [
    (1, 2, 2, 64, 64, 32, True, None),     # block-multiple causal
    (2, 4, 2, 96, 160, 32, True, None),    # GQA + non-multiple Sq/Sk + padding
    (1, 2, 1, 64, 64, 16, False, None),    # MQA non-causal
    (1, 8, 4, 200, 72, 16, True, None),    # sq > sk (fully-masked early rows)
    (1, 2, 2, 40, 64, 16, False, 48),      # kv_len-masked cache tail
    (1, 4, 2, 1, 64, 32, True, 40),        # decode shape: sq=1, kv_len < Sk
    (1, 2, 2, 16, 64, 16, True, 40),       # prefill continuation: causal AND
                                           #   kv_len < Sk with sq > 1
])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_flash_attention_grads_match_oracle(b, h, kh, sq, sk, d, causal,
                                            kv_len, dt):
    from repro.kernels.flash_attention import ops

    q = _arr((b, h, sq, d), dt)
    k = _arr((b, kh, sk, d), dt)
    v = _arr((b, kh, sk, d), dt)
    g = _arr((b, h, sq, d), dt)

    def f(q_, k_, v_):
        return ops.flash_attention(q_, k_, v_, causal=causal, kv_len=kv_len,
                                   block_q=32, block_k=64, interpret=True)

    _, vjp = jax.vjp(f, q, k, v)
    got = vjp(g)
    want = _oracle_grads(q, k, v, g, causal=causal, kv_len=kv_len)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b_, np.float32),
                                   err_msg=name, **_tol(dt))


def test_causal_kv_len_alignment_agrees_across_dialects():
    """Prefill continuation (causal=True, kv_len < Sk, sq > 1): the jnp
    oracle, the chunked jnp variant, and the Pallas kernel must share the
    ends-at-kv_len causal alignment — otherwise the flash_attention_bwd
    primitive returns different gradients on cpu_xla vs pallas targets."""
    from repro.kernels.flash_attention import ops, ref

    q = _arr((1, 2, 16, 16))
    k, v = _arr((1, 2, 64, 16)), _arr((1, 2, 64, 16))
    kw = dict(causal=True, kv_len=40)
    a = ref.attention(q, k, v, **kw)
    b = ref.attention_chunked(q, k, v, block_k=32, **kw)
    c = ops.flash_attention(q, k, v, block_q=8, block_k=32, interpret=True,
                            **kw)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_vjp_standalone_entry():
    """The UPD flash_attention_bwd primitive calls flash_attention_vjp
    directly — same contract as differentiating through flash_attention."""
    from repro.kernels.flash_attention import ops

    q, g = _arr((1, 4, 40, 16)), _arr((1, 4, 40, 16))
    k, v = _arr((1, 2, 56, 16)), _arr((1, 2, 56, 16))
    got = ops.flash_attention_vjp(q, k, v, g, causal=True, block_q=32,
                                  block_k=32, interpret=True)
    want = _oracle_grads(q, k, v, g, causal=True)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   err_msg=name, **_tol("float32"))


def test_fwd_residuals_are_linear_in_sequence():
    """The residuals saved by _fa_fwd are O(Sq) per head: the three inputs,
    the output, and a (B, H, Sq, 1) logsumexp — no S×S tensor (the oracle-VJP
    fallback this kernel replaced materialized exp-scores of (Sq, Sk))."""
    from repro.kernels.flash_attention import ops

    b, h, sq, sk, d = 1, 2, 64, 192, 16   # sq != sk disambiguates axes
    q = _arr((b, h, sq, d))
    k, v = _arr((b, h, sk, d)), _arr((b, h, sk, d))
    out, res = ops._fa_fwd(True, None, sk, 32, 64, True, q, k, v)
    assert out.shape == q.shape
    expected = {q.shape, k.shape, (b, h, sq, 1)}
    for leaf in res:
        assert tuple(leaf.shape[-2:]) != (sq, sk), \
            f"S×S residual materialized: {leaf.shape}"
        assert leaf.shape in expected, leaf.shape
    # total residual bytes are linear in sequence length: well under one
    # f32 (Sq, Sk) score matrix per head
    res_bytes = sum(x.size * x.dtype.itemsize for x in res)
    assert res_bytes < 4 * b * h * sq * sk


def test_fwd_logsumexp_residual_values():
    """lse must equal log-sum-exp of the masked scaled scores row-wise —
    the backward recomputes p = exp(s - lse) from it."""
    from repro.kernels.flash_attention import kernel

    b, h, s, d = 1, 2, 64, 16
    q, k, v = _arr((b, h, s, d)), _arr((b, h, s, d)), _arr((b, h, s, d))
    out, lse = kernel.flash_attention_fwd_4d(q, k, v, causal=True,
                                             block_q=32, block_k=32,
                                             interpret=True)
    sc = 1.0 / (d ** 0.5)
    sm = np.einsum("bhqd,bhkd->bhqk", np.asarray(q, np.float32),
                   np.asarray(k, np.float32)) * sc
    mask = np.tril(np.ones((s, s), bool))
    sm = np.where(mask, sm, -np.inf)
    want = np.log(np.exp(sm - sm.max(-1, keepdims=True)).sum(-1)) \
        + sm.max(-1, keepdims=True)[..., 0]
    assert lse.shape == (b, h, s, 1)
    np.testing.assert_allclose(np.asarray(lse)[..., 0], want, rtol=1e-5,
                               atol=1e-5)


def test_generated_tsl_trains_through_pallas_backward():
    """End-to-end through the generated pallas_interpret TSL: grad of a loss
    over ops.flash_attention runs the Pallas backward kernels and matches the
    oracle — the training path no longer relies on the jnp-oracle VJP."""
    from repro.core import load_library
    from repro.kernels.flash_attention import ref

    lib = load_library("pallas_interpret")
    q = _arr((1, 4, 32, 16))
    k, v = _arr((1, 2, 32, 16)), _arr((1, 2, 32, 16))

    def loss_tsl(q_):
        return jnp.sum(lib.ops.flash_attention(q_, k, v, causal=True) ** 2)

    def loss_ref(q_):
        return jnp.sum(ref.attention(q_, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_tsl)(q)
    g2 = jax.grad(loss_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=2e-3, atol=2e-3)
