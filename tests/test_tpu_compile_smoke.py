"""Compile checks for the TPU v5e: the generated TPU library and the Pallas
kernels of the main path, compiled for a described (not attached) v5e chip.

``jax.jit(...).lower(...).compile()`` against the first device of a described
``v5e:2x2`` topology runs the TPU compiler, which refuses what interpret mode
accepts: blocks that break the (8, 128) tiling rule, scalar stores to VMEM,
blocks that overflow VMEM. Shapes are qwen1.5-0.5b's (d_model 1024, d_ff
2816, 16 heads of 64, batch 4 x sequence 512 for attention). Nothing runs,
so these say nothing about results or times.

The topology is described inside a module fixture, never while a module is
imported: only one process may hold the TPU library, and the test workers
must all collect the same tests.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

DTYPES = [jnp.bfloat16, jnp.float32]
ROWS, D_MODEL, D_FF = 4096, 1024, 2816           # batch 4 x seq 1024 tokens
B, H, S, HD = 4, 16, 512, 64


@pytest.fixture(scope="module")
def lib_tpu():
    from repro.core import load_library

    return load_library("pallas_tpu")


def test_tpu_library_generates_and_imports(lib_tpu):
    assert lib_tpu.TARGET_NAME == "pallas_tpu"
    assert not lib_tpu.TARGET.runs_on_host
    assert lib_tpu.TARGET.has("tpu", "mxu")


def test_tpu_selection_uses_pallas_kernels(lib_tpu):
    """The compiled-TPU SRU must route the hot primitives through the Pallas
    definitions (interpret=False), not the portable jnp fallbacks."""
    import json
    from pathlib import Path

    man = json.loads(
        (Path(lib_tpu.__file__).parent / "_manifest.json").read_text())
    for prim in ("rmsnorm", "softmax", "hadd", "swiglu", "flash_attention"):
        flags = man["primitives"][prim]["float32"]["required_flags"]
        assert "pallas" in flags, (prim, flags)


@pytest.mark.parametrize("prim,shapes", [
    ("rmsnorm", [(8, 256), (256,)]),
    ("softmax", [(8, 256)]),
    ("swiglu", [(8, 256), (8, 256)]),
    ("hadd", [(8, 256)]),
    ("flash_attention", [(1, 2, 128, 64)] * 3),
])
def test_tpu_pallas_bodies_trace_without_execution(lib_tpu, prim, shapes):
    """Abstract-evaluate each Pallas-routed primitive: traces the kernel body
    with interpret=False, no TPU needed, no execution performed."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    fn = getattr(lib_tpu.ops, prim)
    out = jax.eval_shape(fn, *args)
    leaves = jax.tree_util.tree_leaves(out)
    assert leaves and all(leaf.dtype == jnp.float32 for leaf in leaves)


def test_tpu_pallas_bodies_trace_bf16(lib_tpu):
    x = jax.ShapeDtypeStruct((16, 512), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((512,), jnp.bfloat16)
    assert jax.eval_shape(lib_tpu.ops.rmsnorm, x, w).dtype == jnp.bfloat16
    assert jax.eval_shape(lib_tpu.ops.softmax, x).shape == (16, 512)


# -- compiled for a described v5e ---------------------------------------------


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for an absent chip is written to the persistent cache but
    # cannot be read back: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_described_device_is_the_v5e_target(topo):
    from repro.core.hwprobe import TPU_TARGETS

    assert TPU_TARGETS[topo.devices[0].device_kind] == "tpu_v5e"


@pytest.mark.parametrize("dt", DTYPES)
def test_rmsnorm_compiles_for_v5e(one_chip, dt):
    from repro.kernels.rmsnorm.ops import rmsnorm

    _compile(one_chip, rmsnorm, ((ROWS, D_MODEL), dt), ((D_MODEL,), dt))


@pytest.mark.parametrize("dt", DTYPES)
def test_softmax_compiles_for_v5e(one_chip, dt):
    from repro.kernels.softmax.ops import softmax

    _compile(one_chip, softmax, ((ROWS, D_MODEL), dt))


@pytest.mark.parametrize("dt", DTYPES)
def test_swiglu_compiles_for_v5e(one_chip, dt):
    """f32 at d_ff 2816 overflowed VMEM at 256-row blocks."""
    from repro.kernels.swiglu.ops import swiglu

    _compile(one_chip, swiglu, ((ROWS, D_FF), dt), ((ROWS, D_FF), dt))


@pytest.mark.parametrize("dt", DTYPES)
def test_row_kernel_grads_compile_for_v5e(one_chip, dt):
    """The training path differentiates rmsnorm and swiglu (custom VJPs)."""
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.swiglu.ops import swiglu

    def loss(x, w, u):
        y = swiglu(rmsnorm(x, w), u)
        return jnp.sum(y.astype(jnp.float32))

    _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
             ((ROWS, D_MODEL), dt), ((D_MODEL,), dt), ((ROWS, D_MODEL), dt))


@pytest.mark.parametrize("dt", DTYPES)
def test_flash_forward_with_lse_compiles_for_v5e(one_chip, dt):
    """The residual-emitting forward of the training path: the lse block
    once broke the (8, 128) tiling rule."""
    from repro.kernels.flash_attention import kernel

    def fwd(q, k, v):
        return kernel.flash_attention_fwd_4d(q, k, v, block_q=64, block_k=128)

    _compile(one_chip, fwd, *[((B, H, S, HD), dt)] * 3)


@pytest.mark.parametrize("dt", DTYPES)
def test_flash_backward_compiles_for_v5e(one_chip, dt):
    from repro.kernels.flash_attention.ops import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, block_q=64, block_k=128)
        return jnp.sum(o.astype(jnp.float32))

    compiled = _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                        *[((B, H, S, HD), dt)] * 3)
    text = compiled.as_text()
    for name in ("tsl_flash_attention_fwd", "tsl_flash_attention_bwd_dq",
                 "tsl_flash_attention_bwd_dkv"):
        assert name in text, name


@pytest.mark.parametrize("page,block_k", [(64, 16), (64, 64)])
@pytest.mark.parametrize("dt", DTYPES)
def test_paged_decode_compiles_for_v5e(one_chip, dt, page, block_k):
    from repro.kernels.paged.ops import paged_attention

    n_pages, slots, per_slot = 64, 8, 4
    _compile(one_chip,
             lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                                   block_k=block_k),
             ((slots, H, 1, HD), dt), ((H, n_pages, page, HD), dt),
             ((H, n_pages, page, HD), dt), ((slots, per_slot), jnp.int32),
             ((slots,), jnp.int32))


def test_range_count_compiles_for_v5e(one_chip):
    """The count once went out as a scalar store to VMEM, which the chip
    refuses."""
    from repro.kernels.range_count.ops import range_count

    _compile(one_chip, range_count, ((1 << 24,), jnp.int32),
             ((), jnp.int32), ((), jnp.int32))


def test_hadd_int32_compiles_for_v5e(one_chip):
    from repro.kernels.hadd.ops import hadd

    _compile(one_chip, hadd, ((1 << 24,), jnp.int32))


# -- the same kernels on a described v5e:2x2 mesh -------------------------------
# The TPU compiler cannot partition a Mosaic kernel; under a multi-device mesh
# (jax.set_mesh) each kernel call runs per shard. These compiles fail with
# "Mosaic kernels cannot be automatically partitioned" if a call escapes that.


@pytest.fixture(scope="module", params=[(1, 4), (2, 2)], ids=["1x4", "2x2"])
def mesh(request, topo):
    import numpy as np
    from jax.sharding import AxisType, Mesh

    dp, tp = request.param
    return Mesh(np.array(topo.devices).reshape(dp, tp), ("data", "model"),
                axis_types=(AxisType.Auto, AxisType.Auto))


def _compile_on_mesh(mesh, fn, *shapes):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    args = [jax.ShapeDtypeStruct(s, dt, sharding=NamedSharding(mesh, P(*spec)))
            for s, dt, spec in shapes]
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_row_kernels_and_grads_compile_on_v5e_mesh(mesh):
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.swiglu.ops import swiglu

    def loss(x, w, u):
        y = swiglu(rmsnorm(x, w), u)
        return jnp.sum(y.astype(jnp.float32))

    bf = jnp.bfloat16
    _compile_on_mesh(mesh, jax.grad(loss, argnums=(0, 1, 2)),
                     ((B, S, D_MODEL), bf, ("data",)), ((D_MODEL,), bf, ()),
                     ((B, S, D_MODEL), bf, ("data", None, "model")))


def test_flash_grad_compiles_on_v5e_mesh(mesh):
    from repro.kernels.flash_attention.ops import flash_attention

    def loss(q, k, v):
        o = flash_attention(q, k, v, block_q=64, block_k=128)
        return jnp.sum(o.astype(jnp.float32))

    spec = ("data", "model")
    _compile_on_mesh(mesh, jax.grad(loss, argnums=(0, 1, 2)),
                     *[((B, H, S, HD), jnp.bfloat16, spec)] * 3)


def test_paged_decode_compiles_on_v5e_mesh(mesh):
    """The serving engine shards its page pools on the page axis."""
    from repro.kernels.paged.ops import paged_attention

    pool = ((H, 48, 64, HD), jnp.bfloat16, (None, "model"))
    _compile_on_mesh(mesh,
                     lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                                           block_k=16),
                     ((8, H, 1, HD), jnp.bfloat16, ()), pool, pool,
                     ((8, 3), jnp.int32, ()), ((8,), jnp.int32, ()))
