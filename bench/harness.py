"""One run of one cell: set-up, the measured window, the check against the
reference, the metrics, and the result line.

The cell, its configuration, its traffic mix, its driver and each metric's
reader are all found by name: ``bench/drivers/<driver>.py`` runs the mix's
``driver`` kind and returns a record of what happened, and
``bench/metrics/<base>.py`` (``base`` = the metric's name before the first
dot) reads one metric from that record, or returns None where the record
holds nothing for it.
"""

from __future__ import annotations

import importlib
import json
import math
import os
import sys
from dataclasses import dataclass
from pathlib import Path

from . import spec

WORK_DIR = spec.ROOT / "build" / "bench"
# JAX's persistent compilation cache: a fixed directory inside the checkout
# (the path is part of the cache's key), given to the program through the
# variable it reads, so nothing is shared with a cache set from outside
CACHE_DIR = WORK_DIR / "jax_cache"


@dataclass
class Ctx:
    """Everything a driver needs for one run."""

    bench: dict
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                    # host clock at process start
    work_dir: Path = WORK_DIR
    keep_trace: Path | None = None    # where to keep the raw trace, gzipped

    def __post_init__(self):
        # (data, model) from the configuration's serve.mesh, or None; a
        # mesh that does not cover the cell's chips raises here, before
        # any set-up
        self.mesh_shape = spec.mesh_shape(self.config, self.cell["chips"])

    def log(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(n: int) -> dict:
    import jax

    devs = jax.devices()[:n]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def read_metrics(ctx: Ctx, rec: dict) -> dict:
    kind = "per_layer" if ctx.trace else "end_to_end"
    out = {}
    for m in spec.cell_metrics(ctx.bench, ctx.cell["name"], kind):
        base = m["name"].split(".")[0]
        reader = importlib.import_module(f"bench.metrics.{base}")
        value = reader.read(m["name"], rec)
        if value is None:
            continue
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(ctx: Ctx) -> dict:
    """Run the cell and return its result object (the last line)."""
    driver = importlib.import_module(f"bench.drivers.{ctx.mix['driver']}")
    rec = driver.run(ctx)
    metrics = read_metrics(ctx, rec)
    checks = dict(rec["check"])
    if ctx.trace:
        # a listed metric that reads nothing is a reader that lost sight of
        # what it reads (a renamed kernel, a missing device plane)
        missing = [m["name"] for m in
                   spec.cell_metrics(ctx.bench, ctx.cell["name"], "per_layer")
                   if m["name"] not in metrics]
        if missing:
            ctx.log(f"per-layer metrics that read nothing: {missing}")
        checks["metrics_missing"] = check_entry(len(missing), 0)
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": rec["device"]}
    summary = rec.get("trace")
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops()],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    result["counters"] = rec.get("counters", {})
    result["check"] = checks
    return result


def report(result: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard output."""
    for name, c in result["check"].items():
        op = ">=" if c["bound"] == "min" else "<="
        print(f"check {name} {c['value']!r} limit {op} {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def check_entry(value: float, limit: float, at_least: bool = False) -> dict:
    """One compared number, which must stay at or under its limit (at or
    over it with ``at_least``)."""
    ok = value >= limit if at_least else value <= limit
    return {"value": value, "limit": limit,
            "bound": "min" if at_least else "max", "ok": bool(ok)}


def enable_cache() -> str:
    """Turn on the compilation cache in CACHE_DIR, keeping every program
    however quick to compile, so a warm set-up compiles nothing."""
    import jax

    from repro.compile_cache import enable_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return enable_compile_cache()


def bind_chip_library():
    """The generated library for the device this process sees: ``auto``
    must resolve a TPU target, never the portable fallback."""
    from repro import tsl_api
    from repro.core import hwprobe

    for var in ("REPRO_TSL_TARGET", "REPRO_TSL_UPD_PATH", "TSL_STORE_ROOT"):
        os.environ.pop(var, None)
    lib = tsl_api.use("auto")
    want = hwprobe.live_target()
    if lib.TARGET_NAME != want or lib.TARGET.interpret:
        raise RuntimeError(f"library {lib.TARGET_NAME!r} bound, the chip "
                           f"needs {want!r} compiled")
    return lib


def main(args, t_start: float) -> int:
    import jax

    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: workload {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {platform} device(s)",
              file=sys.stderr)
        return 2
    ctx = Ctx(bench=bench, cell=cell,
              config=spec.config_file(bench, cell["config"]),
              mix=spec.traffic_file(cell["traffic"]), seed=args.seed,
              seconds=float(args.seconds), trace=bool(args.trace),
              t_start=t_start, keep_trace=args.keep_trace)
    cache = enable_cache()
    lib = bind_chip_library()
    ctx.log(f"platform={platform} device_kind={devs[0].device_kind!r} "
            f"count={len(devs)} library={lib.TARGET_NAME} cache={cache} "
            f"workload={cell['name']} mesh={ctx.mesh_shape} seed={args.seed} "
            f"seconds={args.seconds} trace={args.trace}")
    result = execute(ctx)
    report(result)
    return 0
