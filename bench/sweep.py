#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest arrival rate the engine
sustains without a growing backlog. Run once on the chip when a cell is
defined; the rates it finds are written into the cell's traffic file.

    python3 bench/sweep.py --workload <cell> --rates 2,4,6 --seconds 30

One process, one engine: set-up once, then the cell's traffic at each rate
in ascending order (same seed, same shape of sizes; each rate's shapes
warmed up before its run), reporting per rate the tokens emitted per second
inside the window, the time to first token per prefill chunk (the prompt's
padded length over the chunk size) by half of the window, and how long the
engine took to drain after the last arrival. Dividing by the chunks takes
out the prompt's own size, so what is left grows only with the queue. A
rate is sustained when every request completes and the median of the
second half stays within GROWTH times that of the first; the sweep stops
at the first rate that is not, and its last line names the highest
sustained rate (``knee_rate_per_s``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
GROWTH = 1.5         # allowed growth of TTFT per chunk, second half / first


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import numpy as np

    from bench import harness, loadgen, spec, weights
    from bench.drivers import serve_open_loop as drv
    from repro.serve import BucketPolicy, Request

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    harness.enable_cache()
    harness.bind_chip_library()
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    config = spec.config_file(bench, cell["config"])
    mix = spec.traffic_file(cell["traffic"])
    dims = spec.Dims.from_config(config)
    engine = drv.build_engine(spec.arch_config(config), config["serve"], mix,
                              args.seed)
    engine.params = None
    engine.params = weights.make_params(dims, args.seed)
    T = args.seconds
    policy = engine.policy

    def chunks(n):           # prefill chunks of a prompt, padded to its bucket
        return (policy.assign(n) or BucketPolicy.round_up(n, policy.chunk)
                ) // policy.chunk

    knee = None
    for rate in sorted(float(r) for r in args.rates.split(",")):
        m = json.loads(json.dumps(mix))
        m["arrivals"]["rate_per_s"] = rate
        m["arrivals"]["backlog"] = 0        # the knee is of arrivals alone
        plan = loadgen.open_loop(args.seed, T, m, dims.vocab)
        drv.warm_up(engine, plan)
        t0 = time.perf_counter()
        rep = engine.run([Request(rid=p.rid, tokens=p.prompt,
                                  gen_len=p.gen_len, arrival_s=p.arrival_s)
                          for p in plan])
        wall = time.perf_counter() - t0
        per = {r["rid"]: r for r in rep["per_request"]}
        halves = []
        for k in range(2):
            xs = [per[p.rid]["ttft_s"] / chunks(len(p.prompt))
                  for p in plan if k * T / 2 <= p.arrival_s < (k + 1) * T / 2
                  and p.rid in per]
            halves.append(float(np.median(xs)) if xs else None)
        ends = [p.arrival_s + per[p.rid]["latency_s"] for p in plan
                if p.rid in per]
        done_in = sum(per[p.rid]["tokens_out"] for p in plan if p.rid in per
                      and p.arrival_s + per[p.rid]["latency_s"] <= T)
        sustained = (None not in halves
                     and halves[1] <= GROWTH * halves[0]
                     and len(per) == len(plan))
        print(json.dumps({
            "rate_per_s": rate, "requests": len(plan),
            "completed": len(per), "wall_s": wall,
            "drain_s": max(ends) - T if ends else None,
            "ttft_per_chunk_median_by_half_s": halves,
            "ttft_p90_s": float(np.percentile(
                [r["ttft_s"] for r in per.values()], 90)),
            "tokens_of_requests_done_in_window_per_s": done_in / T,
            "generated_tokens_per_wall_s": rep["generated_tokens"] / wall,
            "sustained": sustained,
        }), flush=True)
        if not sustained:
            break
        knee = rate
    print(json.dumps({"knee_rate_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
