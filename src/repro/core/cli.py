"""CLI for the incremental multi-target generation engine.

    python -m repro.core generate --targets cpu_xla,pallas_interpret
    python -m repro.core generate --all --force
    python -m repro.core corpus
    python -m repro.core analyze --fail-on=error --format=json
    python -m repro.core bench --report bench-report.json
    python -m repro.core bench --smoke
    python -m repro.core cache stats
    python -m repro.core cache clear
    python -m repro.core cache gc --max-age-days 30

The paper drives its generator from a ``main.py`` invoked by cmake; this is
the JAX-analogue entry point, plus artifact-cache maintenance and the §4.2
"ongoing process" bench sweep that warms measured block-size/variant winners
for every host-runnable target under the probed hardware key.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def _add_common(ap: argparse.ArgumentParser) -> None:
    ap.add_argument("--upd-path", action="append", default=[],
                    help="extra UPD search path (repeatable)")
    ap.add_argument("--build-root", default=None,
                    help="artifact cache root (default: build/tsl)")


def _cmd_generate(args) -> int:
    from .corpus import load_corpus
    from .library import generate_all

    upd_paths = tuple(args.upd_path)
    corpus = load_corpus(upd_paths)
    if args.all:
        targets = None
    elif args.targets:
        targets = [t for chunk in args.targets for t in chunk.split(",") if t]
    else:
        print("error: pass --targets a,b,... or --all", file=sys.stderr)
        return 2
    out = generate_all(
        targets,
        Path(args.build_root) if args.build_root else None,
        force=args.force,
        corpus=corpus,
        upd_paths=upd_paths,
        only=tuple(args.only) if args.only else None,
        emit_docs=args.docs,
        use_bench_selection=args.bench,
    )
    for name, pkg_dir in out.items():
        print(f"{name}: {pkg_dir}")
    return 0


def _cmd_corpus(args) -> int:
    from .corpus import load_corpus

    corpus = load_corpus(tuple(args.upd_path))
    info = {
        "fingerprint": corpus.fingerprint,
        "targets": sorted(corpus.targets),
        "primitives": len(corpus.primitives),
        "warnings": len(corpus.warnings),
    }
    print(json.dumps(info, indent=1))
    if args.warnings:
        for w in corpus.warnings:
            print(f"  warning: {w}")
    return 0


def _repo_root() -> Path:
    # src/repro/core/cli.py -> src/repro/core -> src/repro -> src -> repo
    return Path(__file__).resolve().parents[3]


def _diff_bench_winners(trajectory: dict, fresh: dict) -> list[str]:
    """Selection regressions between a checked-in bench trajectory and a
    fresh sweep of the same target. The benched SURFACE (which
    primitive/ctype pairs are benched, with which candidate sets) must match
    exactly — a mismatch means the corpus changed without refreshing the
    trajectory. A WINNER change only fails when the fresh measurement shows
    the recorded winner clearly losing (>= 1.5x slower than the new winner):
    near-ties flip on timing noise and must not flake CI."""
    problems: list[str] = []
    old, new = trajectory.get("winners", {}), fresh.get("winners", {})
    for key in sorted(set(old) | set(new)):
        if key not in new:
            problems.append(f"{key}: benched in trajectory, not benched now")
            continue
        if key not in old:
            problems.append(f"{key}: newly benched; refresh the trajectory "
                            "(python -m repro.core bench --report)")
            continue
        o, n = old[key], new[key]
        if o["candidates"] != n["candidates"]:
            problems.append(f"{key}: candidate set changed "
                            f"{o['candidates']} -> {n['candidates']}; "
                            "refresh the trajectory")
            continue
        if o["winner"] == n["winner"]:
            continue
        times = dict(zip(n["candidates"], n["times_us"]))
        t_old, t_new = times.get(o["winner"]), times.get(n["winner"])
        if t_old is not None and t_new is not None and t_old >= 1.5 * t_new:
            problems.append(
                f"{key}: winner def[{o['winner']}] -> def[{n['winner']}] "
                f"({t_old:.0f}us vs {t_new:.0f}us, >=1.5x margin)")
        else:
            print(f"bench-diff: {key}: winner flipped "
                  f"def[{o['winner']}] -> def[{n['winner']}] within noise "
                  "margin; not failing", file=sys.stderr)
    return problems


def _cmd_bench(args) -> int:
    """Warm bench-selection winners for every target that runs here (the
    host-runnable dialects and the live device's own target) and emit a
    JSON report of winners per (target, primitive, hardware key)."""
    from . import hwprobe
    from .corpus import load_corpus
    from .library import (DEFAULT_BUILD_ROOT, artifact_key, generate_library)
    from .cache import ArtifactCache
    from .model import GenConfig

    upd_paths = tuple(args.upd_path)
    corpus = load_corpus(upd_paths)
    if args.targets:
        names = [t for chunk in args.targets for t in chunk.split(",") if t]
        unknown = sorted(set(names) - set(corpus.targets))
        if unknown:
            print(f"error: unknown target(s) {unknown}", file=sys.stderr)
            return 2
        not_host = [t for t in names
                    if not hwprobe.runnable(corpus.targets[t])]
        if not_host:
            print(f"error: target(s) {not_host} do not run on this host",
                  file=sys.stderr)
            return 2
    else:
        names = [t for t in sorted(corpus.targets)
                 if hwprobe.runnable(corpus.targets[t])]
    build_root = Path(args.build_root) if args.build_root else DEFAULT_BUILD_ROOT
    store = ArtifactCache(build_root)
    report: dict = {"smoke": args.smoke, "targets": {}}
    for name in names:
        cfg = GenConfig(target=name, upd_paths=upd_paths,
                        use_bench_selection=True, bench_smoke=args.smoke)
        # force: the sweep's job is to (re-)measure, not to hit the package
        # cache; already-measured winners are still reused from the bench store
        _, res = generate_library(cfg, build_root, force=True, corpus=corpus)
        key = artifact_key(cfg, corpus.fingerprint, corpus)
        winners = store.bench_load(key)
        report["targets"][name] = {
            "hardware_flags": list(key.hardware_flags),
            "bench_entry": store.bench_path(key).name,
            "winners": winners,
            "warnings": [w for w in (res.warnings if res else [])
                         if "bench" in w],
        }
    print(json.dumps(report, indent=1))
    if args.report == "__root__":
        # commit the bench trajectory: one BENCH_<target>.json per swept
        # target at the repo root, so selection changes show up in review
        for name, entry in report["targets"].items():
            out = _repo_root() / f"BENCH_{name}.json"
            out.write_text(json.dumps(
                {"target": name, "smoke": args.smoke, **entry}, indent=1)
                + "\n")
            print(f"trajectory: {out}", file=sys.stderr)
    elif args.report:
        Path(args.report).write_text(json.dumps(report, indent=1))
    if args.diff:
        trajectory = json.loads(Path(args.diff).read_text())
        tgt = trajectory.get("target")
        if tgt not in report["targets"]:
            print(f"error: trajectory target {tgt!r} was not swept",
                  file=sys.stderr)
            return 2
        problems = _diff_bench_winners(trajectory, report["targets"][tgt])
        for p in problems:
            print(f"bench-diff: REGRESSION {p}", file=sys.stderr)
        if problems:
            return 1
        print(f"bench-diff: winners match {args.diff}", file=sys.stderr)
    return 0


def _cmd_analyze(args) -> int:
    """TSL-Check: semantic static analysis over the validated corpus, the
    cost channel, and the Pallas kernels (stable TSL0xx finding codes)."""
    from .corpus import load_corpus
    from repro.analyze import run_analysis

    corpus = load_corpus(tuple(args.upd_path))
    roots = tuple(Path(p) for p in args.kernels_root) if args.kernels_root \
        else None
    rep = run_analysis(corpus, kernel_roots=roots)

    baseline = Path(args.baseline) if args.baseline else None
    if args.update_baseline:
        if baseline is None:
            print("error: --update-baseline requires --baseline PATH",
                  file=sys.stderr)
            return 2
        idents = sorted({f.identity() for f in rep.active_findings()})
        baseline.write_text("\n".join(idents) + ("\n" if idents else ""))
        print(f"baseline: {len(idents)} finding identit(ies) -> {baseline}")
        return 0
    if baseline is not None and baseline.exists():
        known = {ln.strip() for ln in baseline.read_text().splitlines()
                 if ln.strip()}
        rep.apply_baseline(known)

    if args.report:
        out = Path(args.report)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.with_suffix(".json").write_text(rep.to_json_str() + "\n")
        out.with_suffix(".md").write_text(rep.to_markdown() + "\n")
    print(rep.to_json_str() if args.format == "json" else rep.to_text())
    return rep.exit_code(args.fail_on)


def _cmd_cache(args) -> int:
    from .cache import ArtifactCache
    from .library import DEFAULT_BUILD_ROOT

    store = ArtifactCache(Path(args.build_root) if args.build_root
                          else DEFAULT_BUILD_ROOT)
    if args.action == "stats":
        print(json.dumps(store.stats(), indent=1))
    elif args.action == "gc":
        if args.max_age_days is None:
            print("error: cache gc requires --max-age-days N", file=sys.stderr)
            return 2
        print(f"removed {store.gc(args.max_age_days)} expired artifact(s)")
    else:  # clear
        print(f"removed {store.clear()} cached artifact(s)")
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(prog="python -m repro.core",
                                 description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="command", required=True)

    g = sub.add_parser("generate", help="generate libraries for target(s)")
    _add_common(g)
    g.add_argument("--targets", action="append", default=[],
                   help="comma-separated target names (repeatable)")
    g.add_argument("--all", action="store_true",
                   help="every target the corpus defines")
    g.add_argument("--only", action="append", default=[],
                   help="cherry-picked primitive (repeatable; paper 'slim')")
    g.add_argument("--force", action="store_true",
                   help="regenerate even on a cache hit")
    g.add_argument("--bench", action="store_true",
                   help="benchmark-driven adaptive selection (paper §4.2)")
    g.add_argument("--docs", action="store_true", help="emit docs/ markdown")
    g.set_defaults(fn=_cmd_generate)

    c = sub.add_parser("corpus", help="validate + summarize the UPD corpus")
    _add_common(c)
    c.add_argument("--warnings", action="store_true",
                   help="print every corpus warning")
    c.set_defaults(fn=_cmd_corpus)

    b = sub.add_parser(
        "bench", help="warm bench-selection winners for targets that run here")
    _add_common(b)
    b.add_argument("--targets", action="append", default=[],
                   help="comma-separated targets that run here "
                        "(default: every runs_on_host target plus the "
                        "live device's own)")
    b.add_argument("--report", nargs="?", const="__root__", default=None,
                   help="write the JSON winners report: with PATH, one "
                        "combined file there; bare, one BENCH_<target>.json "
                        "trajectory per target at the repo root (check in)")
    b.add_argument("--diff", default=None,
                   help="compare this sweep's winners against a checked-in "
                        "BENCH_<target>.json trajectory; exit 1 on a clear "
                        "selection regression")
    b.add_argument("--smoke", action="store_true",
                   help="single-iteration smoke sweep (CI: exercises the "
                        "benchgen path without the measurement cost)")
    b.set_defaults(fn=_cmd_bench)

    a = sub.add_parser(
        "analyze", help="TSL-Check: semantic static analysis (TSL0xx codes)")
    _add_common(a)
    a.add_argument("--fail-on", choices=("error", "warn", "info", "never"),
                   default="error",
                   help="lowest severity that makes the exit code nonzero")
    a.add_argument("--format", choices=("text", "json"), default="text")
    a.add_argument("--report", default=None,
                   help="write <path>.json and <path>.md report files")
    a.add_argument("--baseline", default=None,
                   help="accepted-findings file: listed identities do not "
                        "gate the exit code")
    a.add_argument("--update-baseline", action="store_true",
                   help="rewrite --baseline with the current findings")
    a.add_argument("--kernels-root", action="append", default=[],
                   help="extra kernel tree to lint (default: repro.kernels)")
    a.set_defaults(fn=_cmd_analyze)

    k = sub.add_parser("cache", help="artifact-cache maintenance")
    _add_common(k)
    k.add_argument("action", choices=("stats", "clear", "gc"))
    k.add_argument("--max-age-days", type=float, default=None,
                   help="gc: evict artifacts older than this many days")
    k.set_defaults(fn=_cmd_cache)
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
