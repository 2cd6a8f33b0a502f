#!/usr/bin/env python3
"""Chip benchmark of the serving engine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json in this process on the chips JAX finds:
set-up (compile cache, the generated TPU library, weights from the seed,
warm-up of every shape), the measured window, then the check against the
float32 reference. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and with
``--trace 1`` the per-layer metrics and a ``breakdown``); the numbers the
check compared, beside their limits, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", type=Path, default=None,
                    help="with --trace 1, keep the raw device trace here "
                         "(gzipped XSpace)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
