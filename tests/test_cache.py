"""Incremental-engine tests: corpus memoisation (validate once per
fingerprint), artifact-cache warm paths (no GPO re-runs), and unified
invalidation keyed on (UPD fingerprint, hardware flags, generator version)
— ISSUE 2 acceptance criteria."""

import textwrap

import pytest

from repro.core import (GenConfig, corpus_cache_clear, generate_all,
                        generate_library, load_library)
from repro.core.generate import GenerateGPO
from repro.core.validate import ValidateGPO


@pytest.fixture()
def counted(monkeypatch):
    """Count ValidateGPO/GenerateGPO invocations via class-level patches."""
    counts = {"validate": 0, "generate": 0}
    real_validate = ValidateGPO.run
    real_generate = GenerateGPO.run

    def count_validate(self, ctx):
        counts["validate"] += 1
        return real_validate(self, ctx)

    def count_generate(self, ctx):
        counts["generate"] += 1
        return real_generate(self, ctx)

    monkeypatch.setattr(ValidateGPO, "run", count_validate)
    monkeypatch.setattr(GenerateGPO, "run", count_generate)
    return counts


def test_generate_all_validates_once(tmp_path, counted):
    """Regenerating a SECOND (and third) target from a warm corpus performs
    zero re-validation — the corpus phase ran exactly once."""
    corpus_cache_clear()
    out = generate_all(["cpu_xla", "pallas_interpret", "gpu_pallas"],
                       tmp_path, force=True)
    assert set(out) == {"cpu_xla", "pallas_interpret", "gpu_pallas"}
    for pkg_dir in out.values():
        assert (pkg_dir / "_manifest.json").exists()
    assert counted["validate"] == 1
    assert counted["generate"] == 3


def test_load_library_warm_path_runs_no_gpo(tmp_path, counted):
    """Repeated load_library() with unchanged fingerprint + hardware flags is
    served from the artifact cache: GenerateGPO does not re-run."""
    lib1 = load_library("cpu_xla", build_root=tmp_path)
    generated_after_cold = counted["generate"]
    assert generated_after_cold == 1
    lib2 = load_library("cpu_xla", build_root=tmp_path)
    assert counted["generate"] == generated_after_cold    # warm: zero re-runs
    assert lib2 is lib1


def _upd(root, flag="v1"):
    (root / "targets").mkdir(parents=True, exist_ok=True)
    (root / "primitives").mkdir(parents=True, exist_ok=True)
    (root / "targets" / "toy.yaml").write_text(textwrap.dedent(f"""\
    ---
    name: "toy"
    lscpu_flags: ["xla", "{flag}"]
    ctypes: ["float32"]
    ...
    """))
    (root / "primitives" / "toy.yaml").write_text(textwrap.dedent("""\
    ---
    primitive_name: "toy_add"
    group: "toy"
    parameters:
      - {name: "a", ctype: "register"}
      - {name: "b", ctype: "register"}
    returns: {ctype: "register"}
    definitions:
      - target_extension: "toy"
        ctype: ["float32"]
        lscpu_flags: ["xla"]
        implementation: |
          return a + b
    testing:
      - name: "adds"
        requires: []
        implementation: |
          a = ctx.array((2, 4), ctype)
          b = ctx.array((2, 4), ctype)
          ctx.allclose(ops.toy_add(a, b),
                       np.asarray(a, np.float64) + np.asarray(b, np.float64), ctype)
    ...
    """))


def test_fingerprint_change_forces_regeneration(tmp_path):
    upd = tmp_path / "upd"
    _upd(upd)
    cfg = GenConfig(target="toy", upd_paths=(str(upd),))
    dir1, res1 = generate_library(cfg, tmp_path / "cache")
    assert res1 is not None                              # cold: pipeline ran
    dir1b, res1b = generate_library(cfg, tmp_path / "cache")
    assert dir1b == dir1 and res1b is None               # warm: cache hit
    # editing any UPD document changes the fingerprint -> new artifact
    _upd(upd, flag="v2")
    dir2, res2 = generate_library(cfg, tmp_path / "cache")
    assert res2 is not None
    assert dir2 != dir1


def test_hardware_flag_change_forces_regeneration(tmp_path):
    upd = tmp_path / "upd"
    _upd(upd)
    base = dict(upd_paths=(str(upd),))
    d1, r1 = generate_library(
        GenConfig(target="toy", hardware_flags=("xla",), **base),
        tmp_path / "cache")
    d2, r2 = generate_library(
        GenConfig(target="toy", hardware_flags=("xla", "v1"), **base),
        tmp_path / "cache")
    assert r1 is not None and r2 is not None
    assert d1 != d2                                      # hardware keys the artifact
    # identical probe -> hit
    d3, r3 = generate_library(
        GenConfig(target="toy", hardware_flags=("xla",), **base),
        tmp_path / "cache")
    assert d3 == d1 and r3 is None


def test_generator_version_bump_forces_regeneration(tmp_path, monkeypatch):
    upd = tmp_path / "upd"
    _upd(upd)
    cfg = GenConfig(target="toy", upd_paths=(str(upd),))
    d1, _ = generate_library(cfg, tmp_path / "cache")
    from repro.core import cache as cache_mod

    monkeypatch.setattr(cache_mod, "GENERATOR_VERSION", "999.0.0-test")
    d2, r2 = generate_library(cfg, tmp_path / "cache")
    assert r2 is not None                                # bump retired the artifact
    assert d2 != d1


def test_cache_key_and_index_recorded(tmp_path):
    upd = tmp_path / "upd"
    _upd(upd)
    cfg = GenConfig(target="toy", upd_paths=(str(upd),))
    pkg_dir, _ = generate_library(cfg, tmp_path / "cache")
    import json

    key = json.loads((pkg_dir / "_cache_key.json").read_text())
    assert key["target"] == "toy"
    assert key["hardware_flags"] == ["v1", "xla"]        # sorted probe flags
    assert key["generator_version"]
    from repro.core import ArtifactCache

    stats = ArtifactCache(tmp_path / "cache").stats()
    assert pkg_dir.name in stats["index"]
    assert stats["index"][pkg_dir.name]["digest"] == key["digest"]


def test_bench_winner_store_is_hardware_keyed(tmp_path):
    """Bench winners share the package's content address minus the variant:
    same corpus + target on different hardware -> different bench entries."""
    from repro.core.cache import ArtifactCache, CacheKey

    store = ArtifactCache(tmp_path)
    k1 = CacheKey("fp", "cpu_xla", ("xla",), "2.0.0", "deadbeef")
    k2 = CacheKey("fp", "cpu_xla", ("avx512", "xla"), "2.0.0", "deadbeef")
    assert store.bench_path(k1) != store.bench_path(k2)
    # ...but variant-independent: all package flavours share one winner file
    k3 = CacheKey("fp", "cpu_xla", ("xla",), "2.0.0", "cafecafe")
    assert store.bench_path(k1) == store.bench_path(k3)
    store.bench_store(k1, {"p/float32": {"winner": 1}})
    assert store.bench_load(k3) == {"p/float32": {"winner": 1}}
    assert store.bench_load(k2) == {}


def test_bench_selection_persists_winners(tmp_path):
    """Regression: on targets where primitives have ≥2 valid candidates the
    measured winners must land in the unified bench store (a bad key once
    crashed bench_store after the first real benchmark)."""
    import json

    lib = load_library("pallas_interpret", only=("hadd",),
                       use_bench_selection=True, build_root=tmp_path)
    assert "hadd" in lib.PRIMITIVES
    benches = list((tmp_path / "bench").glob("pallas_interpret_*.json"))
    assert len(benches) == 1
    data = json.loads(benches[0].read_text())
    assert "hadd/float32" in data
    assert "winner" in data["hadd/float32"]
    assert len(data["hadd/float32"]["times_us"]) >= 2
    # second generation of a different variant reuses the same winner file
    _, res2 = generate_library(
        GenConfig(target="pallas_interpret", only=("hadd",),
                  use_bench_selection=True, emit_docs=True),
        tmp_path)
    assert res2 is not None
    assert list((tmp_path / "bench").glob("*.json")) == benches


@pytest.mark.parametrize("live", [True, False], ids=["live", "emulated"])
def test_failed_bench_candidate_is_an_error_on_the_live_target(
        tmp_path, monkeypatch, live):
    """On the live device's own target a candidate that fails is a
    generation error; on an emulated dialect it ranks last with a warning."""
    from repro.core import benchgen, hwprobe
    from repro.core.pipeline import GenerationError

    monkeypatch.setattr(hwprobe, "live_target",
                        lambda: "pallas_interpret" if live else "cpu_xla")

    def refused(fn, args, n_iter):
        raise RuntimeError("refused by the compiler")

    monkeypatch.setattr(benchgen, "_time_candidate", refused)
    cfg = GenConfig(target="pallas_interpret", only=("hadd",),
                    use_bench_selection=True)
    if live:
        with pytest.raises(GenerationError, match="refused by the compiler"):
            generate_library(cfg, tmp_path, force=True)
    else:
        _, res = generate_library(cfg, tmp_path, force=True)
        assert any("refused by the compiler" in w for w in res.warnings)


def test_live_target_is_runnable_for_bench(monkeypatch):
    """bench treats the probed device's target as runnable even though its
    UPD document says it does not run on the host."""
    from repro.core import hwprobe
    from repro.core.corpus import load_corpus

    targets = load_corpus().targets
    assert not hwprobe.runnable(targets["tpu_v5e"])
    monkeypatch.setattr(hwprobe, "live_target", lambda: "tpu_v5e")
    assert hwprobe.runnable(targets["tpu_v5e"])
    assert not hwprobe.runnable(targets["pallas_tpu"])


def test_cli_generate_and_cache_roundtrip(tmp_path, capsys):
    from repro.core.cli import main

    upd = tmp_path / "upd"
    _upd(upd)
    rc = main(["generate", "--targets", "toy", "--upd-path", str(upd),
               "--build-root", str(tmp_path / "cache")])
    assert rc == 0
    out = capsys.readouterr().out
    assert "toy:" in out
    rc = main(["cache", "stats", "--build-root", str(tmp_path / "cache")])
    assert rc == 0
    assert "toy" in capsys.readouterr().out
    rc = main(["cache", "clear", "--build-root", str(tmp_path / "cache")])
    assert rc == 0
    assert "removed" in capsys.readouterr().out


def test_cache_gc_age_eviction(tmp_path):
    """cache gc --max-age-days: evicts only stale packages/bench entries,
    prunes the index to match, and leaves stats/clear semantics intact."""
    import os
    import time

    from repro.core import ArtifactCache

    upd = tmp_path / "upd"
    _upd(upd)
    cfg = GenConfig(target="toy", upd_paths=(str(upd),))
    pkg_dir, _ = generate_library(cfg, tmp_path / "cache")
    store = ArtifactCache(tmp_path / "cache")
    from repro.core.cache import CacheKey

    fresh_key = CacheKey("fp", "toy", ("xla",), "2.0.0")
    store.bench_store(fresh_key, {"p/float32": {"winner": 0}})
    stale_bench = store.bench_root / "toy_deadbeefdeadbeef.json"
    stale_bench.write_text("{}")
    old = time.time() - 10 * 86400
    os.utime(pkg_dir / "_cache_key.json", (old, old))
    os.utime(stale_bench, (old, old))

    # nothing is young enough to die at 30 days
    assert store.gc(30) == 0
    # at 5 days the aged package and aged bench entry go, the fresh one stays
    assert store.gc(5) == 2
    assert not pkg_dir.exists()
    assert store.bench_path(fresh_key).exists()
    stats = store.stats()
    assert pkg_dir.name not in stats["index"]
    assert pkg_dir.name not in stats["packages"]

    # regeneration after gc is a clean cold start
    pkg_dir2, res2 = generate_library(cfg, tmp_path / "cache")
    assert res2 is not None and pkg_dir2.exists()


def test_cli_cache_gc(tmp_path, capsys):
    from repro.core.cli import main

    upd = tmp_path / "upd"
    _upd(upd)
    assert main(["generate", "--targets", "toy", "--upd-path", str(upd),
                 "--build-root", str(tmp_path / "cache")]) == 0
    capsys.readouterr()
    # gc without --max-age-days is a usage error
    assert main(["cache", "gc", "--build-root", str(tmp_path / "cache")]) == 2
    capsys.readouterr()
    rc = main(["cache", "gc", "--max-age-days", "30",
               "--build-root", str(tmp_path / "cache")])
    assert rc == 0
    assert "removed 0 expired" in capsys.readouterr().out


def test_cli_bench_sweep_persists_flash_attention_winners(tmp_path, capsys):
    """ISSUE 3 acceptance: `python -m repro.core bench` runs end-to-end on CPU
    and persists flash_attention fwd+bwd block-size winners into the
    content-addressed cache under the probed hardware key."""
    import json

    from repro.core.cli import main

    rc = main(["bench", "--smoke", "--targets", "pallas_interpret",
               "--build-root", str(tmp_path / "cache"),
               "--report", str(tmp_path / "report.json")])
    assert rc == 0
    report = json.loads((tmp_path / "report.json").read_text())
    assert report["smoke"] is True
    tgt = report["targets"]["pallas_interpret"]
    assert tgt["hardware_flags"]                  # probed hardware key recorded
    winners = tgt["winners"]
    for key in ("flash_attention/float32", "flash_attention_bwd/float32"):
        assert key in winners, sorted(winners)
        assert len(winners[key]["times_us"]) >= 2  # ≥2 block-size candidates
    # winners live in the unified hardware-keyed bench store
    bench_file = tmp_path / "cache" / "bench" / tgt["bench_entry"]
    assert bench_file.exists()
    persisted = json.loads(bench_file.read_text())
    assert "flash_attention_bwd/float32" in persisted
    # a second sweep reuses the persisted winners (no re-measure): same file
    capsys.readouterr()
    assert main(["bench", "--smoke", "--targets", "pallas_interpret",
                 "--build-root", str(tmp_path / "cache")]) == 0
    assert json.loads(bench_file.read_text()) == persisted


def test_bench_smoke_winners_do_not_pin_real_selection(tmp_path, capsys):
    """A smoke sweep (n_iter=1) must not permanently replace real adaptive
    selection: a later full-iteration sweep re-measures stale smoke entries."""
    import json

    from repro.core.cli import main

    root = str(tmp_path / "cache")
    assert main(["bench", "--smoke", "--targets", "cpu_xla",
                 "--build-root", root,
                 "--report", str(tmp_path / "smoke.json")]) == 0
    smoke = json.loads((tmp_path / "smoke.json").read_text())
    w = smoke["targets"]["cpu_xla"]["winners"]["attention_decode/float32"]
    assert w["n_iter"] == 1
    capsys.readouterr()
    assert main(["bench", "--targets", "cpu_xla", "--build-root", root,
                 "--report", str(tmp_path / "full.json")]) == 0
    full = json.loads((tmp_path / "full.json").read_text())
    w2 = full["targets"]["cpu_xla"]["winners"]["attention_decode/float32"]
    assert w2["n_iter"] > 1                  # re-measured, not reused
    # ...and the real measurement now sticks: smoke afterwards reuses it
    capsys.readouterr()
    assert main(["bench", "--smoke", "--targets", "cpu_xla",
                 "--build-root", root,
                 "--report", str(tmp_path / "smoke2.json")]) == 0
    smoke2 = json.loads((tmp_path / "smoke2.json").read_text())
    assert smoke2["targets"]["cpu_xla"]["winners"][
        "attention_decode/float32"]["n_iter"] == w2["n_iter"]


def test_cli_bench_rejects_bad_targets(tmp_path, capsys):
    from repro.core.cli import main

    assert main(["bench", "--targets", "nope",
                 "--build-root", str(tmp_path / "cache")]) == 2
    assert main(["bench", "--targets", "pallas_tpu",   # not host-runnable
                 "--build-root", str(tmp_path / "cache")]) == 2
    capsys.readouterr()


def test_bench_diff_winner_logic():
    """Trajectory diffing: surface changes always fail; a winner flip fails
    only when the FRESH measurement shows a clear (>=1.5x) margin, so
    near-tie candidates can't flake CI."""
    from repro.core.cli import _diff_bench_winners

    def entry(winner, cands, times):
        return {"winner": winner, "candidates": cands, "times_us": times,
                "n_iter": 3}

    old = {"winners": {"p/float32": entry(0, [0, 5], [100.0, 200.0])}}

    # identical winners: clean
    assert _diff_bench_winners(old, old) == []
    # flip with clear margin in the fresh run: regression
    fresh = {"winners": {"p/float32": entry(5, [0, 5], [400.0, 100.0])}}
    (p,) = _diff_bench_winners(old, fresh)
    assert "def[0] -> def[5]" in p and "1.5x" in p
    # flip within noise: reported but NOT a failure
    close = {"winners": {"p/float32": entry(5, [0, 5], [110.0, 100.0])}}
    assert _diff_bench_winners(old, close) == []
    # candidate-set change: always a failure (corpus moved under trajectory)
    cset = {"winners": {"p/float32": entry(0, [0, 5, 9], [1.0, 2.0, 3.0])}}
    assert any("candidate set changed" in p
               for p in _diff_bench_winners(old, cset))
    # benched-surface change in either direction: failure
    assert any("not benched now" in p
               for p in _diff_bench_winners(old, {"winners": {}}))
    assert any("newly benched" in p
               for p in _diff_bench_winners({"winners": {}}, old))


def test_cli_bench_trajectory_roundtrip(tmp_path, capsys, monkeypatch):
    """`bench --report` (bare) writes BENCH_<target>.json at the repo root;
    `bench --diff` against that trajectory passes on an unchanged corpus."""
    import json

    from repro.core import cli

    monkeypatch.setattr(cli, "_repo_root", lambda: tmp_path)
    root = str(tmp_path / "cache")
    assert cli.main(["bench", "--smoke", "--targets", "cpu_xla",
                     "--build-root", root, "--report"]) == 0
    traj = tmp_path / "BENCH_cpu_xla.json"
    assert traj.exists()
    data = json.loads(traj.read_text())
    assert data["target"] == "cpu_xla" and data["winners"]
    capsys.readouterr()
    assert cli.main(["bench", "--smoke", "--targets", "cpu_xla",
                     "--build-root", root, "--diff", str(traj)]) == 0
    # trajectory for a target that wasn't swept: usage error
    capsys.readouterr()
    assert cli.main(["bench", "--smoke", "--targets", "pallas_interpret",
                     "--build-root", root, "--diff", str(traj)]) == 2
    capsys.readouterr()


def test_checked_in_bench_trajectory_matches_corpus_surface():
    """The committed BENCH_cpu_xla.json must track the live corpus: every
    benched (primitive, ctype) pair with >1 valid cpu_xla candidate appears,
    with the candidate indices the corpus declares today."""
    import json
    import pathlib

    from repro.core.cli import _repo_root
    from repro.core.corpus import load_corpus
    from repro.core.select import valid_candidates

    traj_path = _repo_root() / "BENCH_cpu_xla.json"
    assert traj_path.exists(), "run: python -m repro.core bench " \
                               "--targets cpu_xla --report"
    traj = json.loads(traj_path.read_text())
    assert traj["smoke"] is False        # trajectory is a REAL measurement
    corpus = load_corpus(())
    hw = set(traj["hardware_flags"])
    for name, prim in corpus.primitives.items():
        if prim.bench is None:
            continue
        for ctype in corpus.targets["cpu_xla"].ctypes:
            cands = valid_candidates(prim, "cpu_xla", ctype, hw)
            if len(cands) < 2:
                continue
            key = f"{name}/{ctype}"
            assert key in traj["winners"], key
            assert traj["winners"][key]["candidates"] == \
                [prim.definitions.index(c) for c in cands], key


# -- shared store root (many processes, one directory) --------------------------


def test_shared_commit_publishes_by_rename(tmp_path):
    """Shared-mode commit stages privately and publishes atomically: a second
    writer racing the same name loses the rename and adopts the winner."""
    from dataclasses import dataclass

    from repro.core.cache import ArtifactCache, CacheKey

    @dataclass
    class F:
        relpath: str
        content: str

    key = CacheKey("fp", "cpu_xla", ("avx2",), "2.0.0", "v")
    ns = key.hw_namespace()
    a = ArtifactCache(tmp_path, shared=True, namespace=ns)
    b = ArtifactCache(tmp_path, shared=True, namespace=ns)
    d1 = a.commit("pkg_x", key, [F("m.py", "WINNER = 1\n")])
    d2 = b.commit("pkg_x", key, [F("m.py", "WINNER = 2\n")])
    assert d1 == d2
    assert (d1 / "m.py").read_text() == "WINNER = 1\n"   # first publish wins
    assert a.lookup("pkg_x") is not None
    # no staging litter survives
    leftovers = [p for p in a.package_root.iterdir()
                 if p.name.startswith(".")]
    assert leftovers == []
    # namespace isolation: a different hardware class sees nothing
    other = ArtifactCache(tmp_path, shared=True, namespace="hw_other")
    assert other.lookup("pkg_x") is None


def test_shared_writer_election_and_wait(tmp_path):
    from repro.core.cache import ArtifactCache

    store = ArtifactCache(tmp_path, shared=True, namespace="hw_t")
    assert store.acquire_writer("p") is True
    assert store.acquire_writer("p") is False      # held
    store.release_writer("p")
    assert store.acquire_writer("p") is True       # released -> retaken
    # a stale lock (crashed writer) is broken and retaken
    lock = store._lock_path("q")
    store._lock_root.mkdir(parents=True, exist_ok=True)
    lock.write_text("999999")
    import os

    old = 10_000.0
    os.utime(lock, (os.stat(lock).st_atime - old,
                    os.stat(lock).st_mtime - old))
    assert store.acquire_writer("q", stale_s=600.0) is True
    # wait_for with no lock and no package returns promptly (writer failed)
    assert store.wait_for("never", timeout_s=1.0) is None


def test_shared_store_race_one_writer_one_warm_hit(tmp_path):
    """Two PROCESSES generating the same artifact key against one shared
    store root: exactly one runs the generator, the other takes the warm hit
    (zero GPOs re-run) — the fleet warm-path acceptance criterion."""
    import os
    import subprocess
    import sys
    import textwrap as tw

    worker = tmp_path / "worker.py"
    worker.write_text(tw.dedent("""
        import sys
        from repro.core import GenConfig
        from repro.core.library import generate_library

        pkg_dir, result = generate_library(
            GenConfig(target="cpu_xla", emit_tests=False, emit_build=True))
        print("GENERATED" if result is not None else "WARM")
        print(pkg_dir)
    """))
    import pathlib

    import repro.core

    src = str(pathlib.Path(repro.core.__file__).resolve().parents[2])
    env = dict(os.environ)
    env["TSL_STORE_ROOT"] = str(tmp_path / "store")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    procs = [subprocess.Popen([sys.executable, str(worker)], env=env,
                              stdout=subprocess.PIPE, text=True)
             for _ in range(2)]
    outs = [p.communicate(timeout=600)[0].split() for p in procs]
    assert all(p.returncode == 0 for p in procs)
    marks = sorted(o[0] for o in outs)
    assert marks == ["GENERATED", "WARM"], outs
    assert outs[0][1] == outs[1][1]              # same published package dir
    # the published package lives under the hardware-key namespace
    store_root = tmp_path / "store" / "pkg"
    spaces = [d.name for d in store_root.iterdir()]
    assert len(spaces) == 1 and spaces[0].startswith("hw_")
