"""``bench/run.py`` prints no result and exits non-zero where it cannot
measure: without a TPU, and in a checkout that lacks the program."""

import json
import os
import shutil
import subprocess
import sys

from bench import spec


def _run(cwd, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen1.5-0.5b.chat",
         "--seed", str(2**33 + 1), "--seconds", "5", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def _no_result(proc):
    assert proc.returncode != 0
    last = proc.stdout.strip().splitlines()[-1:] or [""]
    assert not last[0].startswith("{")


def test_refuses_without_a_tpu():
    proc = _run(spec.ROOT)
    _no_result(proc)
    assert "needs 1 TPU chip(s)" in proc.stderr


def test_refuses_where_only_the_benchmark_is(tmp_path):
    bench = spec.benchmark()
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, BENCH_RUN=json.dumps({"ignored": True}))
    _no_result(proc)
    assert "src/repro" in proc.stderr
