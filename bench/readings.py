#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from: the program's numbers
on many seeds (the lower reading) beside the control's, the float32
reference computed one precision step below the configuration's (the upper
reading), all in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 30

Each seed's traffic at the cell's own load runs through one engine (weights
from that seed), sampled as a run samples it; then the engine is freed and
the reference and its int8 and fp8 controls read each seed's sample. One
JSON line per seed. Runs on the chip only.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# one step below the bfloat16 the configurations state
CONTROLS = ("int8", "fp8")


def serve(ctx, seeds, dims):
    from bench import check, loadgen, weights
    from bench.drivers import serve_open_loop as drv
    from repro.serve import Request

    engine = drv.build_engine(drv.arch_config(ctx.config),
                              ctx.config["serve"], ctx.mix, seeds[0])
    engine.params = None
    plans = {s: loadgen.open_loop(s, ctx.seconds, ctx.mix, dims.vocab)
             for s in seeds}
    samples = {}
    for i, seed in enumerate(seeds):
        engine.params = None
        engine.params = weights.make_params(dims, seed)
        if i == 0:
            drv.warm_up(engine, plans[seed])
        rep = engine.run([Request(rid=p.rid, tokens=p.prompt,
                                  gen_len=p.gen_len, arrival_s=p.arrival_s)
                          for p in plans[seed]])
        samples[seed] = check.sample_served(
            drv.served_requests(plans[seed], rep["outputs"]), seed,
            ctx.mix["check"])
    del engine, rep
    gc.collect()
    for seed in seeds:
        params = weights.make_params(dims, seed)
        out = {"seed": seed}
        for quant in CONTROLS:
            got = check.check_served(params, dims, samples[seed], quant=quant)
            out.update({k: got[k] for k in ("served_logit_gap",
                                            "served_logit_gap_mean",
                                            "tokens", "requests")})
            out[f"control_{quant}"] = got["control_logit_gap"]
            out[f"control_{quant}_mean"] = got["control_logit_gap_mean"]
        del params
        print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, spec

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    harness.enable_cache()
    harness.bind_chip_library()
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    ctx = harness.Ctx(bench=bench, cell=cell,
                      config=spec.config_file(bench, cell["config"]),
                      mix=spec.traffic_file(cell["traffic"]), seed=0,
                      seconds=args.seconds, trace=False, t_start=T_START)
    dims = spec.Dims.from_config(ctx.config)
    seeds = [int(s) for s in args.seeds.split(",")]
    serve(ctx, seeds, dims)
    return 0


if __name__ == "__main__":
    sys.exit(main())
