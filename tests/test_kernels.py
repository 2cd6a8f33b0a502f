"""Per-kernel validation: shape/dtype sweeps in Pallas interpret mode against
the pure-jnp ref.py oracles (task brief deliverable (c))."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = np.random.default_rng(42)


def _arr(shape, dt, lo=-2, hi=2):
    return jnp.asarray(RNG.uniform(lo, hi, shape), dtype=dt)


def _tol(dt):
    return dict(rtol=3e-2, atol=3e-2) if dt == "bfloat16" else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", [(4, 128), (7, 250), (33, 512), (2, 3, 257)])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_rmsnorm_sweep(shape, dt):
    from repro.kernels.rmsnorm import ops, ref

    x = _arr(shape, dt)
    w = _arr(shape[-1:], dt)
    got = ops.rmsnorm(x, w, interpret=True)
    want = ref.rmsnorm(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("b,h,kh,sq,sk,d,causal", [
    (1, 2, 2, 128, 128, 64, True),
    (2, 4, 2, 96, 160, 32, True),      # GQA + padding + ends alignment
    (1, 2, 1, 64, 64, 64, False),      # MQA non-causal
    (1, 8, 4, 200, 72, 16, True),      # sq > sk
])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_flash_attention_sweep(b, h, kh, sq, sk, d, causal, dt):
    from repro.kernels.flash_attention import ops, ref

    q = _arr((b, h, sq, d), dt, -1, 1)
    k = _arr((b, kh, sk, d), dt, -1, 1)
    v = _arr((b, kh, sk, d), dt, -1, 1)
    got = ops.flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
    want = ref.attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("n", [64, 1000, 4096, 100_000])
@pytest.mark.parametrize("dt", ["float32", "int32"])
def test_range_count_sweep(n, dt):
    from repro.kernels.range_count import ops, ref

    d = _arr((n,), dt, 0, 100)
    got = int(ops.range_count(d, 5.0 if dt == "float32" else 5,
                              15.0 if dt == "float32" else 15, interpret=True))
    want = int(ref.range_count(d, 5 if dt == "int32" else 5.0,
                               15 if dt == "int32" else 15.0))
    assert got == want


@pytest.mark.parametrize("shape,n", [((13,), 8), ((100,), 32), ((4, 5), 16)])
def test_to_integral_sweep(shape, n):
    from repro.kernels.to_integral import ops, ref

    m = jnp.asarray(RNG.uniform(size=shape + (n,)) > 0.4)
    got = ops.to_integral(m, interpret=True)
    want = ref.to_integral(m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("shape", [(3, 64), (10, 1000), (2, 2, 4096)])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_hadd_sweep(shape, dt):
    from repro.kernels.hadd import ops, ref

    v = _arr(shape, dt, -1, 1)
    got = ops.hadd(v, interpret=True)
    want = ref.hadd(v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2 if dt == "bfloat16" else 1e-4,
                               atol=5e-2 if dt == "bfloat16" else 1e-4)


@pytest.mark.parametrize("shape", [(5, 64), (19, 300), (2, 3, 129)])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_softmax_sweep(shape, dt):
    from repro.kernels.softmax import ops, ref

    x = _arr(shape, dt, -6, 6)
    got = ops.softmax(x, interpret=True)
    want = ref.softmax(x)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))
    np.testing.assert_allclose(np.asarray(got, np.float32).sum(-1), 1.0,
                               rtol=3e-2)


@pytest.mark.parametrize("shape", [(9, 64), (3, 7, 128)])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_swiglu_sweep(shape, dt):
    from repro.kernels.swiglu import ops, ref

    g, u = _arr(shape, dt), _arr(shape, dt)
    got = ops.swiglu(g, u, interpret=True)
    want = ref.swiglu(g, u)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dt))


@pytest.mark.parametrize("t,chunk", [(17, 32), (64, 32), (130, 64)])
def test_ssd_chunked_vs_scan(t, chunk):
    from repro.kernels.ssd import ops, ref

    B, H, P, N = 2, 3, 8, 4
    x = _arr((B, t, H, P), "float32", -1, 1)
    a = jnp.asarray(RNG.uniform(0.8, 0.999, (B, t, H)), jnp.float32)
    b = _arr((B, t, N), "float32", -1, 1)
    c = _arr((B, t, N), "float32", -1, 1)
    y1, h1 = ref.ssd_scan(x, a, b, c)
    y2, h2 = ops.ssd_chunked(x, a, b, c, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t,chunk", [(16, 16), (50, 32), (96, 32)])
def test_wkv6_chunked_vs_scan(t, chunk):
    from repro.kernels.wkv6 import ops, ref

    B, H, K, V = 2, 2, 8, 8
    r = _arr((B, t, H, K), "float32", -1, 1)
    k = _arr((B, t, H, K), "float32", -1, 1)
    v = _arr((B, t, H, V), "float32", -1, 1)
    w = jnp.asarray(RNG.uniform(0.7, 0.999, (B, t, H, K)), jnp.float32)
    u = _arr((H, K), "float32", -1, 1)
    y1, s1 = ref.wkv6_scan(r, k, v, w, u)
    y2, s2 = ops.wkv6_chunked(r, k, v, w, u, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


def test_flash_attention_grad_matches_ref():
    """Backward pass through the kernel (interpret) vs oracle — training uses
    the kernel, so d/dq must agree."""
    from repro.kernels.flash_attention import ops, ref

    q = _arr((1, 2, 64, 32), "float32", -1, 1)
    k = _arr((1, 2, 64, 32), "float32", -1, 1)
    v = _arr((1, 2, 64, 32), "float32", -1, 1)

    def f_kernel(q):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True, block_q=32,
                                           block_k=32, interpret=True) ** 2)

    def f_ref(q):
        return jnp.sum(ref.attention(q, k, v, causal=True) ** 2)

    g1 = jax.grad(f_kernel)(q)
    g2 = jax.grad(f_ref)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=2e-3, atol=2e-3)


# -- gradients of the Pallas row kernels (custom VJPs over the jnp refs) -------
# The loss squares the forward output, so each cotangent depends on the
# kernel's own forward values. In f32 the kernel and the reference agree to
# rounding (1e-5). In bf16 a forward element may round one ulp (2^-8) the
# other way, and that enters the cotangent: 3e-2, as for the forward sweeps.


def _grad_pair(kernel_fn, ref_fn, args):
    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2)

    argnums = tuple(range(len(args)))
    got = jax.grad(loss(kernel_fn), argnums=argnums)(*args)
    want = jax.grad(loss(ref_fn), argnums=argnums)(*args)
    return got, want


@pytest.mark.parametrize("prim", ["rmsnorm", "swiglu", "softmax"])
@pytest.mark.parametrize("dt", ["float32", "bfloat16"])
def test_row_kernel_grads_match_ref(prim, dt):
    import importlib

    ops = importlib.import_module(f"repro.kernels.{prim}.ops")
    ref = importlib.import_module(f"repro.kernels.{prim}.ref")
    shape = (2, 7, 96)
    args = {"rmsnorm": (_arr(shape, dt), _arr(shape[-1:], dt)),
            "swiglu": (_arr(shape, dt), _arr(shape, dt)),
            "softmax": (_arr(shape, dt, -4, 4),)}[prim]
    kernel_fn = getattr(ops, prim)
    got, want = _grad_pair(lambda *a: kernel_fn(*a, interpret=True),
                           getattr(ref, prim), args)
    tol = _tol(dt) if dt == "bfloat16" else dict(rtol=1e-5, atol=1e-5)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), **tol)


def test_hadd_int32_is_exact():
    """Integer sums accumulate in int32: 65536 values below 2^14 sum past
    2^24, where an f32 accumulator would start to round."""
    from repro.kernels.hadd import ops

    v = RNG.integers(0, 1 << 14, (2, 1 << 16)).astype(np.int32)
    got = ops.hadd(jnp.asarray(v), interpret=True)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), v.astype(np.int64).sum(-1))


def test_train_step_through_pallas_interpret_library(bind_library):
    """One train step of reduced qwen1.5 on the pallas_interpret library:
    rmsnorm, swiglu and flash attention differentiate through their Pallas
    kernels, and the loss matches the portable library's."""
    from repro.configs import get_config
    from repro.data.pipeline import SyntheticTokens
    from repro.nn.model import build_model
    from repro.train.optimizer import OptConfig
    from repro.train.step import init_train_state, make_train_step

    cfg = get_config("qwen1.5-0.5b").reduced()
    opt = OptConfig(lr=1e-2, total_steps=10, warmup_steps=1)
    batch = SyntheticTokens(cfg.vocab, 2, 32, seed=0).batch_at(0)
    losses = {}
    for target in ("pallas_interpret", "cpu_xla"):
        assert bind_library(target).TARGET_NAME == target
        model = build_model(cfg)
        state = init_train_state(model, opt, jax.random.PRNGKey(0))
        before = np.asarray(state["params"]["final_norm"]["w"])
        new, metrics = jax.jit(make_train_step(model, opt))(state, batch)
        losses[target] = float(metrics["loss"])
        assert np.isfinite(losses[target]) and np.isfinite(
            float(metrics["grad_norm"]))
        assert not np.array_equal(before,
                                  np.asarray(new["params"]["final_norm"]["w"]))
    assert losses["pallas_interpret"] == pytest.approx(losses["cpu_xla"],
                                                       rel=1e-5)
