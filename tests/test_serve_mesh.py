"""Mesh-sharded serving tests (ISSUE 10 tentpole).

Two tiers:

* pure admission/pricing tests against a shape-only fake mesh — always run;
* token-for-token equivalence of the (data=2, model=4) engine vs the
  1-device engine, across all four decode families, greedy AND sampled,
  with per-step sharding asserted stable — these need 8 simulated devices
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the CI
  multi-device lane) and skip elsewhere.
"""

import numpy as np
import pytest

from repro.configs import get_config
from repro.serve import (CostModelAdmission, PagedConfig, Request,
                         SamplingConfig, ServeEngine)
from repro.serve.scheduler import PagedAdmission


class _FakeMesh:
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, dtype=object)


def _mesh24():
    return _FakeMesh((2, 4), ("data", "model"))


@pytest.mark.parametrize("n_heads,n_kv_heads,fused", [
    (8, 4, True),      # heads divide the model axis: KV heads on ``model``
    (8, 2, True),      # KV heads do not: replicated, as the kernel takes them
    (8, 4, False),     # lane-mode flat pools keep their row axis on ``model``
])
def test_pool_sharding_follows_kernel_heads_spec(n_heads, n_kv_heads, fused):
    """A fused pool's KV-head axis takes exactly the ``model`` entry the
    paged kernel's ``heads_spec`` gives its heads, so each layer's call
    finds its pages on its own shard; the page axis is never sharded."""
    from types import SimpleNamespace

    import jax
    from jax.sharding import AbstractMesh

    from repro.kernels.common import heads_spec
    from repro.serve import PagedKVStore

    mesh = AbstractMesh((2, 4), ("data", "model"))
    leaf = jax.ShapeDtypeStruct((3, 1, n_kv_heads, 64, 16), np.float32)
    store = PagedKVStore({"k": leaf, "v": leaf}, {"k": 3, "v": 3},
                         page_size=8, n_pages=8, int8=True, fused=fused)
    eng = SimpleNamespace(mesh=mesh, _store=store, cfg=SimpleNamespace(
        n_heads=n_heads, n_kv_heads=n_kv_heads))
    rules = ServeEngine._pool_sharding_rules(eng)
    assert set(rules) == {"k", "v", "k__scale", "v__scale"}
    want = heads_spec(mesh, 2, n_heads, n_kv_heads)[1]
    for name, sharding in rules.items():
        spec = tuple(sharding.spec)
        pool = (store.pools | {f"{n}__scale": a for n, a in
                               store.scale_pools.items()})[name]
        assert len(spec) == pool.ndim
        if fused:
            assert spec[-4] == want and spec.count("model") == (
                want is not None)
        else:
            assert spec == ("model",) + (None,) * (pool.ndim - 1)


# -- mesh-aware admission (no devices needed) ----------------------------------


def test_cost_admission_divides_roofline_by_shards():
    cfg = get_config("qwen1.5-0.5b").reduced()
    solo = CostModelAdmission(cfg, batch=4, max_len=64)
    mesh = CostModelAdmission(cfg, batch=4, max_len=64, mesh=_mesh24())
    assert (mesh.dp, mesh.tp, mesh.shards) == (2, 4, 8)
    # same logical bytes, divided over 8 shards — but the TP collectives add
    # interconnect time, so the step is faster yet NOT a clean 8x
    assert mesh.decode_bytes_per_step() == solo.decode_bytes_per_step()
    assert mesh.step_seconds() < solo.step_seconds()
    assert mesh.comms_bytes_per_step() > 0.0
    assert solo.comms_bytes_per_step() == 0.0        # tp=1: ring term vanishes


@pytest.mark.parametrize("arch", ["rwkv6-7b", "zamba2-7b", "whisper-tiny"])
def test_comms_priced_for_every_family(arch):
    cfg = get_config(arch).reduced()
    adm = CostModelAdmission(cfg, batch=4, max_len=64,
                             enc_len=8 if cfg.family == "audio" else None,
                             mesh=_mesh24())
    assert adm.comms_bytes_per_step() > 0.0


def test_mesh_info_report_fields():
    cfg = get_config("qwen1.5-0.5b").reduced()
    adm = CostModelAdmission(cfg, batch=4, max_len=64, mesh=_mesh24())
    info = adm.mesh_info()
    assert info["axes"] == {"data": 2, "model": 4}
    assert info["shards"] == 8
    assert info["param_bytes_per_shard"] == adm.param_bytes / 8
    assert info["comms_bytes_per_step"] == adm.comms_bytes_per_step()
    off = CostModelAdmission(cfg, batch=4, max_len=64)
    assert off.mesh_info() is None


def test_paged_admission_divides_page_budget():
    class _Budget:
        n_pages = 16
        page_bytes = 4096

        def pages_for_rows(self, rows):
            return 1

    cfg = get_config("qwen1.5-0.5b").reduced()
    adm = PagedAdmission(cfg, batch=4, max_len=64, budget=_Budget(),
                         mesh=_mesh24())
    info = adm.mesh_info()
    assert info["page_budget_bytes_per_shard"] == 16 * 4096 / 8


# -- (2,4) mesh vs 1 device: token-for-token equivalence -----------------------

import jax  # noqa: E402  (device count must be read after jax init)

needs_8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "(CI multi-device lane)")


def _mesh():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("data", "model"))


def _run(arch, *, mesh=None, sampling=None, paged=None, batch=4, gen=5,
         shared_prefix=False):
    cfg = get_config(arch).reduced()
    enc_len = 8 if cfg.family == "audio" else None
    rng = np.random.default_rng(0)
    eng = ServeEngine(cfg, batch=batch, max_len=64, seed=0, mesh=mesh,
                      sampling=sampling, paged=paged, enc_len=enc_len)
    reqs = []
    for i in range(batch + 1):           # one more than lanes: slot reuse
        if shared_prefix:
            toks = np.array(list(range(1, 17)) + [30 + i], np.int32)
        else:
            toks = rng.integers(0, cfg.vocab, 8 + i).astype(np.int32)
        r = Request(rid=f"r{i}", tokens=toks, gen_len=gen)
        if enc_len is not None:
            r.embeds = (0.1 * (i + 1) *
                        np.ones((enc_len, cfg.d_model), np.float32))
        if sampling is not None and i == 0:
            r.temperature = 0.9          # per-request override rides along
        reqs.append(r)
    rep = eng.run(reqs)
    return {k: tuple(v) for k, v in rep["outputs"].items()}, rep


def _assert_equivalent(arch, **kw):
    base, _ = _run(arch, **kw)
    toks, rep = _run(arch, mesh=_mesh(), **kw)
    assert toks == base                     # token-for-token, every request
    # compiled once against rule-sharded donors: zero steady-state resharding
    assert rep["mesh"]["reshard_events"] == 0
    assert rep["mesh"]["axes"] == {"data": 2, "model": 4}
    assert rep["mesh"]["hbm_resident_bytes_per_shard"] > 0
    assert rep["mesh"]["comms_bytes_per_step"] > 0
    return rep


@needs_8
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "rwkv6-7b", "zamba2-7b",
                                  "whisper-tiny"])
def test_mesh_greedy_equivalence_all_families(arch):
    _assert_equivalent(arch)


@needs_8
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "rwkv6-7b"])
def test_mesh_sampled_equivalence(arch):
    """Sampled too: the partitionable threefry stream draws the same tokens
    whatever the logits' layout (mixed greedy/sampled slots included)."""
    _assert_equivalent(arch,
                       sampling=SamplingConfig(temperature=0.8, top_k=20))


@needs_8
def test_mesh_paged_fused_prefix_sharing_equivalence():
    rep = _assert_equivalent(
        "qwen1.5-0.5b", batch=2, shared_prefix=True,
        paged=PagedConfig(prefix_sharing=True, fused=True, page_size=8))
    assert rep["paged"]["prefix_hits"] >= 1
    assert "pricing" in rep["mesh"]
    assert rep["mesh"]["pricing"]["page_budget_bytes_per_shard"] > 0


@needs_8
def test_mesh_train_step_runs_sharded():
    """make_train_step(mesh=...) pins params AND float moments to the rules:
    one step on the (2,4) mesh matches the unmeshed step's loss and keeps
    every parameter leaf on its rule sharding."""
    import jax.numpy as jnp

    from repro.dist import sharding as dist_sharding
    from repro.nn.model import build_model
    from repro.train.optimizer import OptConfig
    from repro.train.step import init_train_state, make_train_step

    cfg = get_config("qwen1.5-0.5b").reduced()
    model = build_model(cfg)
    opt_cfg = OptConfig(lr=1e-3, total_steps=10, warmup_steps=2)
    key = jax.random.PRNGKey(0)
    batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
             "labels": jnp.zeros((4, 16), jnp.int32)}

    solo = jax.jit(make_train_step(model, opt_cfg))(
        init_train_state(model, opt_cfg, key), batch)
    mesh = _mesh()
    state = init_train_state(model, opt_cfg, key, mesh=mesh)
    step = jax.jit(make_train_step(model, opt_cfg, mesh=mesh),
                   donate_argnums=(0,))
    with jax.set_mesh(mesh):
        new_state, metrics = step(state, batch)
    assert np.allclose(float(metrics["loss"]), float(solo[1]["loss"]),
                       rtol=1e-5)
    expected = dist_sharding.param_shardings(mesh, new_state["params"])
    for got, want in zip(jax.tree.leaves(new_state["params"]),
                         jax.tree.leaves(expected)):
        assert got.sharding.is_equivalent_to(want, got.ndim)
