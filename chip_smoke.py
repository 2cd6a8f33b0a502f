#!/usr/bin/env python3
"""Chip smoke test: the generated TPU library, serving and training, run once
on a TPU through the program's own entry points.

    python3 chip_smoke.py                # one chip: library, paper app, serve, train
    python3 chip_smoke.py --four-chips   # four chips: mesh serving and a dp2 x tp2
                                         # train step against one chip, nothing else

Every phase prints lines that name the device (platform, device_kind, count);
any failure raises and exits non-zero. The last line of standard output is
one JSON object, ``{"ok": true, "device": {...}}``. Without a TPU the script
exits non-zero before it prints any result.

Everything runs in this one process (a chip belongs to one process at a
time). The TPU library is generated afresh from the tree's UPD data and
templates; a package left under ``build/tsl`` and the ``REPRO_TSL_*`` /
``TSL_STORE_ROOT`` variables are ignored. Compiled programs are kept where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``build/jax_cache``.

Weights are random, drawn from ``--seed``; the model is qwen1.5-0.5b at its
published widths. TTFT and decode tokens/s are printed as smoke observations,
not benchmark numbers.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen1.5-0.5b"
# primitives the chip's library must route through compiled Pallas kernels
PALLAS_PRIMITIVES = {"rmsnorm": ("float32", "bfloat16"),
                     "softmax": ("float32", "bfloat16"),
                     "swiglu": ("float32", "bfloat16"),
                     "flash_attention": ("float32", "bfloat16"),
                     "range_count": ("int32",),
                     "hadd": ("int32",)}
# Relative L2 error allowed between the prefill logits of the Pallas library
# and of the portable jnp library, both in bf16 on the same chip. bf16 keeps 8
# significant bits (unit roundoff 2^-9 = 0.2%). The Pallas kernels accumulate
# in f32 and round once per kernel, while the jnp path rounds between XLA
# fusions, so each of the 24 residual layers adds a few roundoffs of
# difference. 5% bounds that; a wrong mask, scale or tiling errs by O(1).
LOGITS_RTOL = 5e-2
# Two arithmetic paths over one model (two libraries, or one chip and a mesh)
# may pick different greedy tokens where the top logits are a near tie. Where
# their streams first differ, the two picks must lie within TIE_FACTOR times
# the largest logit difference between the paths at that position: 2 because
# each pick's logit moves by up to that difference, and 2 again because the
# engine decodes through the paged kernel, which rounds differently from the
# full forward the check recomputes. Past that point the streams have
# different prefixes and are not compared.
TIE_FACTOR = 4.0
# Relative tolerance between the first-step loss of a dp2 x tp2 mesh and of
# one chip: the tensor-parallel matmuls sum bf16 partial products across
# chips in a different order, which moves the f32 mean loss (about 12 at
# random init) by far less than 1%.
MESH_LOSS_RTOL = 1e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    reduced: bool = False          # ArchConfig.reduced(): the CPU rehearsal
    paper_n: int = 1 << 24         # int32 elements for range_count / hadd
    batch: int = 8                 # serving slots
    requests: int = 8
    prompt_len: int = 128
    gen_len: int = 32
    train_steps: int = 5
    train_batch: int = 4
    train_seq: int = 512
    # At full width and seed 0, five steps at the launcher's default 3e-4
    # leave the loss flat within batch-to-batch noise (about 0.03), 3e-3
    # and up oscillate or diverge; 1e-3 descends from 12.117 to 11.924
    train_lr: float = 1e-3


FULL = Sizes()


def check(ok, detail) -> None:
    """A failed check raises (``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise AssertionError(detail)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def say(phase: str, msg: str) -> None:
    d = device_info()
    print(f"[{phase}] platform={d['platform']} kind={d['kind']!r} "
          f"count={d['count']} {msg}", flush=True)


def model_config(sizes: Sizes):
    from repro.configs import get_config

    cfg = get_config(ARCH)
    return cfg.reduced() if sizes.reduced else cfg


def phase_library(target: str = "auto", build_root=None):
    """Generate and bind the library for ``target`` (``auto`` probes the
    device) and check that its hot primitives route to Pallas kernels."""
    from repro import tsl_api
    from repro.core import hwprobe, loader

    lib = tsl_api.use(target, force=True, build_root=build_root)
    want = hwprobe.live_target() if target == "auto" else target
    check(lib.TARGET_NAME == want, (lib.TARGET_NAME, want))
    pkg = Path(lib.__file__).parent
    man = json.loads((pkg / "_manifest.json").read_text())
    for prim, ctypes in PALLAS_PRIMITIVES.items():
        for ctype in ctypes:
            flags = man["primitives"][prim][ctype]["required_flags"]
            check("pallas" in flags, (prim, ctype, flags))
    if not lib.TARGET.interpret:
        for f in pkg.rglob("*.py"):
            check("interpret=True" not in f.read_text(), f)
    say("library", f"target={lib.TARGET_NAME} package={lib.__name__} "
        f"upd_fingerprint={loader.upd_fingerprint()} "
        f"interpret={lib.TARGET.interpret} pallas={sorted(PALLAS_PRIMITIVES)} ok")
    return lib


def phase_paper_app(lib, sizes: Sizes, seed: int) -> None:
    """The paper's range-count application (Fig 8), fused and composed from
    primitives, plus a plain hadd, all exact against numpy."""
    ops = lib.ops
    data = jax.random.randint(jax.random.PRNGKey(seed), (sizes.paper_n,), 0,
                              100, dtype=jnp.int32)
    lo, hi = 10, 40
    host = np.asarray(data)
    want_count = int(((host >= lo) & (host <= hi)).sum())
    want_sum = int(host.astype(np.int64).sum())

    fused = int(ops.range_count(data, lo, hi))
    shape = data.shape
    in_range = ops.between_inclusive(data, ops.set1(lo, shape, dtype="int32"),
                                     ops.set1(hi, shape, dtype="int32"))
    ones = ops.select(in_range, ops.set1(1, shape, dtype="int32"),
                      ops.set1(0, shape, dtype="int32"))
    composed = int(ops.hadd(ones))
    total = int(ops.hadd(data))
    check(fused == composed == want_count, (fused, composed, want_count))
    check(total == want_sum, (total, want_sum))
    say("paper_app", f"n={sizes.paper_n} range_count={fused} "
        f"composed={composed} hadd={total} numpy_count={want_count} "
        f"numpy_sum={want_sum} exact ok")


def run_serve(cfg, sizes: Sizes, seed: int, mesh=None):
    """One launcher engine (paged, fused, greedy) over ``sizes.requests``
    requests, all of which must complete; returns (report, engine,
    requests)."""
    from repro.launch.serve import build_engine, make_requests
    from repro.serve import SamplingConfig

    engine = build_engine(cfg, batch=sizes.batch, prompt_len=sizes.prompt_len,
                          gen_len=sizes.gen_len, sampling=SamplingConfig(),
                          seed=seed, mesh=mesh)
    requests = make_requests(cfg, sizes.requests, prompt_len=sizes.prompt_len,
                             gen_len=sizes.gen_len, seed=seed)
    report = engine.run(requests)
    check(report["requests"] == sizes.requests, report["requests"])
    check(not report["refused"], report["refused"])
    for r in requests:
        check(len(report["outputs"][r.rid]) == sizes.gen_len, r.rid)
    return report, engine, requests


def forward(engine, seq) -> np.ndarray:
    """f32 logits (len(seq), vocab) of the engine's model and parameters on
    one token sequence, through the library bound now."""
    tokens = jnp.asarray(np.asarray(seq, np.int32))[None]
    with (jax.set_mesh(engine.mesh) if engine.mesh is not None
          else contextlib.nullcontext()):
        logits = jax.jit(engine.model.forward_logits)(engine.params,
                                                      {"tokens": tokens})
    return np.asarray(logits[0, :, :engine.cfg.vocab], np.float32)


def bytes_in_use(mesh) -> list[int]:
    """Device memory in use per mesh device ([] on the CPU backend, which
    keeps no memory statistics)."""
    stats = [d.memory_stats() for d in mesh.devices.flat]
    return [st["bytes_in_use"] for st in stats if st]


def bytes_held(tree, mesh) -> list[int]:
    """Bytes of the arrays in ``tree`` that each mesh device holds, read
    from their shardings."""
    held = collections.Counter()
    for a in jax.tree.leaves(tree):
        n = math.prod(a.sharding.shard_shape(a.shape)) * a.dtype.itemsize
        for d in a.sharding.device_set:
            held[d.id] += n
    return [held[d.id] for d in mesh.devices.flat]


def logits_rel_err(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def first_divergence(a, b) -> int | None:
    return next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)


def check_greedy(got, want, prompt, fwd_got, fwd_want) -> str:
    """Greedy streams of two arithmetic paths over one model must agree
    until their first difference, and there the two picks must be a near
    tie. ``fwd_*(seq)`` give each path's logits on a token sequence; the
    context of the first difference is the streams' common prefix."""
    t0 = first_divergence(got, want)
    if t0 is None:
        return f"{len(got)}/{len(got)} tokens equal"
    seq = np.concatenate([prompt, got[:t0]])
    lg, lw = fwd_got(seq)[-1], fwd_want(seq)[-1]
    noise = float(np.max(np.abs(lg - lw)))
    a, b = got[t0], want[t0]
    gaps = (abs(float(lg[a] - lg[b])), abs(float(lw[a] - lw[b])))
    check(max(gaps) <= TIE_FACTOR * noise, (t0, a, b, gaps, noise))
    return (f"{t0}/{len(got)} tokens equal, then a near tie (tokens {a} vs "
            f"{b}: logit gaps {gaps[0]:.4g}/{gaps[1]:.4g} <= {TIE_FACTOR} x "
            f"path difference {noise:.4g})")


def phase_serve(sizes: Sizes, seed: int, portable: str = "cpu_xla") -> None:
    """Serve on the bound (Pallas) library, then on the portable library on
    the same device: prefill logits and request 0's greedy tokens agree."""
    from repro import tsl_api

    cfg = model_config(sizes)
    pallas = tsl_api.lib().TARGET_NAME
    rep, engine, requests = run_serve(cfg, sizes, seed)
    say("serve", f"arch={cfg.name} library={pallas} layers={cfg.n_layers} "
        f"d_model={cfg.d_model} vocab={cfg.vocab} dtype={cfg.dtype} "
        f"slots={sizes.batch} completed={rep['requests']}/{sizes.requests} "
        f"prompt={sizes.prompt_len} gen={sizes.gen_len}")
    say("serve", f"observation (not a benchmark; cold, compilation "
        f"included): ttft_s_mean={rep['ttft_s_mean']} "
        f"decode_tokens_per_s={rep['decode_tokens_per_s']}")
    prompt, got = requests[0].tokens, rep["outputs"]["req0"]
    # both libraries' logits, teacher-forced on the Pallas stream, from the
    # same parameters (the engines draw identical ones from the seed)
    seq = np.concatenate([prompt, got])
    logits = {pallas: forward(engine, seq)}
    tsl_api.use(portable)
    want = run_serve(cfg, sizes, seed)[0]["outputs"]["req0"]
    logits[portable] = forward(engine, seq)
    tsl_api.use(pallas)
    n = len(prompt)
    err = logits_rel_err(logits[pallas][:n], logits[portable][:n])
    check(err <= LOGITS_RTOL, (err, LOGITS_RTOL))
    verdict = check_greedy(got, want, prompt,
                           lambda s: logits[pallas][:len(s)],
                           lambda s: logits[portable][:len(s)])
    say("serve", f"req0 greedy {pallas} vs {portable}: {verdict}; "
        f"prefill_logits_rel_l2={err:.3e} (limit {LOGITS_RTOL}) ok")


def train_args(sizes: Sizes, steps: int, *extra: str) -> list[str]:
    args = ["--arch", ARCH, "--steps", str(steps),
            "--batch", str(sizes.train_batch), "--seq", str(sizes.train_seq),
            "--lr", str(sizes.train_lr), "--log-every", "1", *extra]
    return args + (["--reduced"] if sizes.reduced else [])


def phase_train(sizes: Sizes) -> None:
    """``launch/train.py``'s loop: finite losses that go down."""
    from repro import tsl_api
    from repro.launch.train import main as train_main

    r = train_main(train_args(sizes, sizes.train_steps))
    losses = r["losses"]
    check(len(losses) == sizes.train_steps, losses)
    check(all(np.isfinite(losses)), losses)
    check(losses[-1] < losses[0], losses)
    say("train", f"arch={r['arch']} library={tsl_api.lib().TARGET_NAME} "
        f"batch={sizes.train_batch} seq={sizes.train_seq} losses={losses} "
        f"finite and decreasing ok")


def phase_four_chips(sizes: Sizes, seed: int) -> None:
    """Mesh serving on (data=1, model=4) and (data=2, model=2) against one
    chip, and one dp2 x tp2 train step against one chip."""
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import main as train_main

    cfg = model_config(sizes)
    one, one_engine, requests = run_serve(cfg, sizes, seed)
    for dp, tp in ((1, 4), (2, 2)):
        mesh = make_host_mesh(dp, tp)
        before = bytes_in_use(mesh)
        rep, engine, _ = run_serve(cfg, sizes, seed, mesh=mesh)
        reshards = rep["mesh"]["reshard_events"]
        check(reshards == 0, reshards)
        # the parameters are spread: no device holds half of them
        params = bytes_held(engine.params, mesh)
        check(max(params) < 0.5 * sum(params), params)
        # device memory the mesh engine added (device 0 also holds the
        # one-chip engine's)
        added = [a - b for a, b in zip(bytes_in_use(mesh), before)]
        verdicts = [check_greedy(rep["outputs"][r.rid], one["outputs"][r.rid],
                                 r.tokens, lambda s: forward(engine, s),
                                 lambda s: forward(one_engine, s))
                    for r in requests]
        prompt = requests[0].tokens
        err = logits_rel_err(forward(engine, prompt),
                             forward(one_engine, prompt))
        say("four_chips", f"mesh=(data={dp}, model={tp}) vs one chip: "
            f"reshard_events={reshards} param_bytes_per_device={params} "
            f"bytes_in_use_per_device={bytes_in_use(mesh)} "
            f"bytes_in_use_added_per_device={added} "
            f"prefill_logits_rel_l2={err:.3e}")
        for r, v in zip(requests, verdicts):
            say("four_chips", f"mesh=(data={dp}, model={tp}) {r.rid}: {v}")
        del rep, engine
        gc.collect()        # the engine's closures hold its device buffers
    base = train_main(train_args(sizes, 1))["losses"][0]
    mesh_loss = train_main(train_args(sizes, 1, "--dp", "2", "--tp", "2")
                           )["losses"][0]
    rel = abs(mesh_loss - base) / abs(base)
    check(rel <= MESH_LOSS_RTOL, (mesh_loss, base))
    say("four_chips", f"train step dp=2 tp=2 loss={mesh_loss} one chip "
        f"loss={base} rel_diff={rel:.3e} (limit {MESH_LOSS_RTOL}) ok")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform={dev['platform']!r}); "
              "this check runs on the chip only", file=sys.stderr)
        return 2
    if args.four_chips and dev["count"] != 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{dev['count']}", file=sys.stderr)
        return 2
    for var in ("REPRO_TSL_TARGET", "REPRO_TSL_UPD_PATH", "TSL_STORE_ROOT"):
        os.environ.pop(var, None)

    from repro.compile_cache import enable_compile_cache

    say("setup", f"compile_cache={enable_compile_cache()}")
    lib = phase_library("auto")
    if args.four_chips:
        phase_four_chips(FULL, args.seed)
    else:
        phase_paper_app(lib, FULL, args.seed)
        phase_serve(FULL, args.seed)
        phase_train(FULL)
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
