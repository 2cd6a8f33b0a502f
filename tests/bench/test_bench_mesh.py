"""A cell served over a mesh, rehearsed on four simulated CPU devices: the
weights drawn into the program's shardings are the whole draw's, the
reference reads the same gaps on them, and a tiny chat run over a
``(data=1, model=4)`` mesh comes out correct with nothing compiled in the
window and nothing resharded.

The four devices exist only in a process started with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, so one child
process (this file run as a script) does the work on them and prints its
readings as one JSON line; the tests read that line.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
MESH = (1, 4)
CHILD_TIMEOUT_S = 600


def _child() -> dict:
    """Runs in the child, on four CPU devices."""
    import jax
    import numpy as np
    from conftest import tiny_mix, tiny_qwen3_config

    from bench import check, harness, spec, weights
    from repro import tsl_api
    from repro.dist import sharding as dist_sharding
    from repro.launch.mesh import make_host_mesh
    from repro.nn.model import build_model

    assert len(jax.devices()) == 4, jax.devices()
    tsl_api.use("cpu_xla")
    out = {}
    cfg = tiny_qwen3_config(mesh=MESH)
    dims = spec.Dims.from_config(cfg)
    mesh = make_host_mesh(*MESH)
    shapes = jax.eval_shape(build_model(spec.arch_config(cfg)).init,
                            jax.random.PRNGKey(0))
    shardings = dist_sharding.param_shardings(mesh, shapes)
    seed = 2**33 + 11
    whole = weights.make_params(dims, seed)
    sharded = weights.make_params(dims, seed, shardings=shardings)
    flat_w, flat_s = weights.flatten(whole), weights.flatten(sharded)
    out["draw_equal"] = sorted(
        p for p in flat_w
        if not np.array_equal(np.asarray(flat_w[p]), np.asarray(flat_s[p])))
    out["draw_split"] = sorted(
        p for p, a in flat_s.items()
        if a.addressable_shards[0].data.shape != a.shape)
    out["draw_placed"] = all(
        a.sharding.is_equivalent_to(s, a.ndim) for a, s in
        zip(jax.tree.leaves(sharded), jax.tree.leaves(shardings)))

    # served tokens drawn at random, so most positions have a wide gap
    rng = np.random.default_rng(4)
    sample = [{"rid": f"r{i}",
               "prompt": rng.integers(0, dims.vocab, 30 + 9 * i),
               "served": rng.integers(0, dims.vocab, 12 + 5 * i)}
              for i in range(3)]
    out["gaps_whole"] = check.check_served(whole, dims, sample)
    out["gaps_sharded"] = check.check_served(sharded, dims, sample)
    del whole, sharded

    bench = spec.benchmark()
    cell = dict(spec.cell(bench, "qwen1.5-0.5b.chat"), chips=4)
    ctx = harness.Ctx(bench=bench, cell=cell, config=cfg, mix=tiny_mix(),
                      seed=2**33 + 7, seconds=1.5, trace=False,
                      t_start=time.perf_counter())
    res = harness.execute(ctx)
    out["run"] = {"correct": res["correct"], "failed": res["failed"],
                  "check": res["check"], "counters": res["counters"]}
    return out


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT),
                                           str(HERE)]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                          capture_output=True, text=True,
                          timeout=CHILD_TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_sharded_draw_is_the_whole_draw(child):
    assert child["draw_equal"] == []
    assert child["draw_placed"]
    # the large matrices are split over the model axis, not replicated
    assert {"embed", "head", "blocks/attn/wq", "blocks/mlp/w_down"} <= \
        set(child["draw_split"])


def test_the_reference_reads_the_same_gaps_on_sharded_weights(child):
    whole, sharded = child["gaps_whole"], child["gaps_sharded"]
    assert whole["tokens"] == sharded["tokens"] > 0
    assert whole["served_logit_gap"] > 0.1      # random tokens: wide gaps
    for k in ("served_logit_gap", "served_logit_gap_mean"):
        assert sharded[k] == pytest.approx(whole[k], rel=1e-6, abs=1e-6), k


def test_a_tiny_chat_run_over_a_mesh_is_correct(child):
    run = child["run"]
    assert run["correct"] and run["failed"] == 0, run["check"]
    assert run["counters"]["window_compiles"] == 0
    mesh = run["counters"]["mesh"]
    assert mesh["axes"] == {"data": MESH[0], "model": MESH[1]}
    assert mesh["reshard_events"] == 0
    assert mesh["param_bytes_per_shard"] > 0


@pytest.mark.parametrize("mesh,chips", [((1, 4), 1), ((1, 2), 4),
                                        ((2, 1), 1)])
def test_a_mesh_that_misses_the_cells_chips_is_refused(mesh, chips,
                                                       tiny_ctx):
    """The run is refused where the Ctx is made, before any set-up."""
    from conftest import tiny_qwen3_config

    from bench import harness

    ctx = tiny_ctx()
    with pytest.raises(ValueError, match="chip"):
        harness.Ctx(bench=ctx.bench, cell=dict(ctx.cell, chips=chips),
                    config=tiny_qwen3_config(mesh=mesh), mix=ctx.mix,
                    seed=1, seconds=1.0, trace=False, t_start=0.0)


if __name__ == "__main__":
    print(json.dumps(_child()))
