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
import numpy as np
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


# (slots, heads, KV heads, pool pages, table width, head dim)
_PAGED_SHAPES = [
    pytest.param(64, 16, (8, H, H, 64, 4, HD), id="64-16"),
    pytest.param(64, 64, (8, H, H, 64, 4, HD), id="64-64"),
    # the chat cell: qwen1.5-0.5b, 8 slots of max_len 1536, the default pool
    pytest.param(64, 16, (8, 16, 16, 384, 24, 64), id="chat"),
    # GQA: qwen3-14b's 40 query heads over 8 KV heads of 128
    pytest.param(64, 16, (8, 40, 8, 384, 24, 128), id="gqa"),
]


@pytest.mark.parametrize("page,block_k,shape", _PAGED_SHAPES)
@pytest.mark.parametrize("dt", DTYPES)
def test_paged_decode_compiles_for_v5e(one_chip, dt, page, block_k, shape):
    from repro.kernels.paged.ops import paged_attention

    slots, h, kh, n_pages, per_slot, hd = shape
    compiled = _compile(
        one_chip,
        lambda q, k, v, t, n: paged_attention(q, k, v, t, n, block_k=block_k),
        ((slots, h, 1, hd), dt), ((kh, n_pages, page, hd), dt),
        ((kh, n_pages, page, hd), dt), ((slots, per_slot), jnp.int32),
        ((slots,), jnp.int32))
    assert "tsl_paged_attention" in compiled.as_text()


@pytest.mark.parametrize("pool_shape,d,dt", [
    ((24, 1, 16, 384, 32, 128), 64, jnp.bfloat16),    # two tokens a row
    ((2, 1, 32, 384, 64, 128), 112, jnp.bfloat16),    # 112 lanes of 128
    ((24, 1, 16, 384, 32, 128), 64, jnp.float32),
    ((24, 1, 16, 384, 32, 128), 64, jnp.int8),        # an int8 payload
], ids=["hd64", "hd112", "f32", "int8"])
def test_paged_token_write_compiles_for_v5e(one_chip, pool_shape, d, dt):
    """The row-write kernel on a donated stacked pool, 8 slots x a span of
    5, its output aliased to the pool: no copy of the pool around it."""
    from repro.kernels.paged.write import token_write

    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in (
        (pool_shape, dt), ((8, pool_shape[2], 5, d), dt),
        ((8, 5), jnp.int32), ((8, 5), jnp.int32), ((), jnp.int32))]
    text = jax.jit(lambda p, t, g, o, l: token_write(p, t, g, o, layer=l),
                   donate_argnums=0).lower(*args).compile().as_text()
    assert "tsl_paged_token_write" in text
    ops = _ops_holding(text, {int(np.prod(pool_shape))})
    assert ops <= _IN_PLACE_POOL_OPS, ops - _IN_PLACE_POOL_OPS


# ops that may take or make a page-pool-shaped array in the compiled decode
# program: the pools enter and leave aliased, ride the layer loop's carry,
# and reach the row-write and attention kernels through bitcasts, the
# row-write kernel's output aliased to its pool operand
_IN_PLACE_POOL_OPS = {"parameter", "bitcast", "custom-call",
                      "get-tuple-element", "tuple", "while"}


def _ops_holding(text, sizes):
    """The HLO ops whose result holds an array of one of ``sizes`` elements,
    in whatever shape or view."""
    import re

    return {op for dims, op in re.findall(
        r"\w+\[([\d,]+)\]\{[^}]*\} ([\w-]+)\(", text)
        if int(np.prod([int(x) for x in dims.split(",")])) in sizes}


@pytest.mark.parametrize("arch,n_layers,slots,span,temp_mb", [
    ("qwen1.5-0.5b", None, 8, 1, 64),    # the chat cell's decode program
    ("qwen1.5-0.5b", None, 8, 5, 64),    # a verify span of k_max + 1 = 5
    ("qwen1.5-0.5b", None, 24, 1, 64),   # 24 slots
    # heads of 112 lanes, padded to a 128-lane row; two shared-attention
    # groups. Its temporaries are the mamba weights', not checked here
    ("zamba2-7b", 12, 8, 1, None),
    ("whisper-tiny", None, 8, 1, 64),    # decoder self-attention, hd 64
], ids=["qwen-decode", "qwen-verify5", "qwen-24slots", "zamba-hd112",
        "whisper"])
def test_fused_decode_program_moves_no_pool_for_v5e(one_chip, monkeypatch,
                                                    arch, n_layers, slots,
                                                    span, temp_mb):
    """A family's whole fused decode (or verify) program at its published
    widths, 384 pages of 64 tokens and tables of 24, on the v5e library: no
    copy, slice or relayout of the page pools, only the in-place row-write
    kernel and the paged attention kernel, both reading the stack by layer
    index; the program's size does not grow with slots or span."""
    import dataclasses
    import re

    from repro import tsl_api
    from repro.configs import get_config
    from repro.core import load_library
    from repro.kernels.paged.rows import row_format
    from repro.nn.model import build_model

    monkeypatch.setattr(tsl_api, "_lib", load_library("tpu_v5e"))
    jax.clear_caches()
    cfg = get_config(arch)
    if n_layers:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    model = build_model(cfg)
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        model.init, jax.random.PRNGKey(0)))
    page, n_pages, width = 64, 384, 24
    # the serving store's fused layout: the slot axis of size 1, the token
    # axis split into (n_pages, page // f) rows of w lanes
    state = jax.eval_shape(lambda: model.init_decode_state(slots,
                                                           page * width))
    axes, slot_axes = model.state_page_axes(state), \
        model.state_batch_axes(state)
    tails, pools = {}, {}
    for name, leaf in state.items():
        if axes[name] is None:
            tails[name] = sds(leaf.shape, leaf.dtype)
            continue
        row = list(leaf.shape)
        row[slot_axes[name]] = 1
        del row[axes[name]]
        f, w = row_format(page, row[-1])
        pools[name] = sds(tuple(row[:-1]) + (n_pages, page // f, w),
                          jnp.bfloat16)
    step_fn = model.decode_step_paged if span == 1 else \
        model.verify_step_paged
    try:
        compiled = jax.jit(step_fn, donate_argnums=(1, 2)).lower(
            params, tails, pools, sds((slots, width), jnp.int32),
            sds((slots, span), jnp.int32),
            sds((slots,), jnp.int32)).compile()
    finally:
        jax.clear_caches()
    text = compiled.as_text()
    assert "tsl_paged_attention" in text and "tsl_paged_token_write" in text
    ops = _ops_holding(text, {int(np.prod(p.shape)) for p in pools.values()})
    assert "custom-call" in ops
    assert ops <= _IN_PLACE_POOL_OPS, ops - _IN_PLACE_POOL_OPS
    # one write per pool leaf and layer body, whatever the slots and span
    assert len(re.findall(r"%tsl_paged_token_write[.\d]* = \S+ custom-call\(",
                          text)) == len(pools)
    if temp_mb is not None:
        # the loop's temporaries are small: no second pool anywhere
        assert compiled.memory_analysis().temp_size_in_bytes < temp_mb << 20


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
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


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


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one-layer", "stacked"])
def test_paged_decode_compiles_on_v5e_mesh(mesh, stacked):
    """One layer's pool, its page axis on ``model`` (``layer=None``, the
    UPD tests' and bench set-ups' form: moved to the kernel's heads), and
    the serving engine's pools: a stack of layers, two tokens a row, the KV
    heads on ``model`` where the kernel runs them, read at a layer index
    with nothing moved between the chips."""
    from repro.kernels.paged.ops import paged_attention

    q = ((8, H, 1, HD), jnp.bfloat16, ())
    tables = ((8, 3), jnp.int32, ())
    kv_len = ((8,), jnp.int32, ())
    if not stacked:
        pool = ((H, 48, 64, HD), jnp.bfloat16, (None, "model"))
        _compile_on_mesh(mesh,
                         lambda q, k, v, t, n: paged_attention(q, k, v, t, n,
                                                               block_k=16),
                         q, pool, pool, tables, kv_len)
        return
    pool = ((4, 1, H, 48, 32, 2 * HD), jnp.bfloat16, (None, None, "model"))
    text = _compile_on_mesh(
        mesh,
        lambda q, k, v, t, n, l: paged_attention(q, k, v, t, n, layer=l,
                                                 block_k=16),
        q, pool, pool, tables, kv_len, ((), jnp.int32, ()))
    assert "all-to-all" not in text


def test_paged_token_write_compiles_on_v5e_mesh(mesh):
    """The row-write kernel on the engine's stacked pool, KV heads on
    ``model``: each shard writes its own heads of every slot's tokens in
    place, with nothing moved between the chips."""
    from repro.kernels.paged.write import token_write

    pool = ((4, 1, H, 48, 32, 2 * HD), jnp.bfloat16, (None, None, "model"))
    text = _compile_on_mesh(
        mesh,
        lambda p, t, g, o, l: token_write(p, t, g, o, layer=l),
        pool, ((8, H, 2, HD), jnp.bfloat16, ()), ((8, 2), jnp.int32, ()),
        ((8, 2), jnp.int32, ()), ((), jnp.int32, ()))
    assert "tsl_paged_token_write" in text and "all-to-all" not in text
