"""BENCHMARK.json and the files it names: every cell's configuration, mix,
driver and metric readers are found by name, and the file keeps to the
benchmark contract's shape."""

import importlib
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (spec.ROOT / p).is_dir()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    w = spec.cell(BENCH, cell)
    config = spec.config_file(BENCH, w["config"])
    mix = spec.traffic_file(w["traffic"])
    importlib.import_module(f"bench.drivers.{mix['driver']}")
    assert spec.arch_config(config).n_layers == config["num_hidden_layers"]
    e2e = spec.cell_metrics(BENCH, cell, "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert spec.cell_metrics(BENCH, cell, "per_layer")
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_readers_are_found_by_name(metric):
    mod = importlib.import_module(f"bench.metrics.{metric.split('.')[0]}")
    assert callable(mod.read)


def test_names_units_and_links():
    names = [m["name"] for m in METRICS] + CELLS + \
        [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        moved = next(e for e in BENCH["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))
    for c in BENCH["configs"]:
        cfg = spec.load_json(spec.ROOT / c["file"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_keeps_the_registry_widths(config):
    """A configuration changes depth at most: every width is the program's
    registry value."""
    from repro.configs import get_config

    cfg = spec.config_file(BENCH, config)
    reg, run = get_config(cfg["arch"]), spec.arch_config(cfg)
    for field in ("d_model", "n_heads", "n_kv_heads", "hd", "d_ff", "vocab",
                  "qkv_bias", "qk_norm", "tie_embeddings"):
        assert getattr(run, field) == getattr(reg, field), field
    for key in cfg["reduced"]:
        assert key == "num_hidden_layers"
        assert cfg[key] < cfg["published"][key]
