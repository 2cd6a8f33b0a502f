"""The serving loop's spans on the device clock.

The engine names each phase of its loop (``repro/serve/phases.py``): in a
profiler trace each phase is a ``serve.<phase>`` event on the host plane,
on the same clock as the device's operations, and each of the engine's
programs is a ``jit_serve_*`` event on the device's ``XLA Modules`` line.
From one trace this reads:

- ``idle_by_span``: the seconds of device idle (the holes between the
  device's op intervals) that fall inside each innermost ``serve.*`` span,
  and ``outside`` any of them, from the start of the first whole
  ``serve.step`` span to the end of the last (a session starts and stops
  inside a step, whose spans it never records);
- ``program_s``: per program (the module name, its compile hash dropped),
  how many times it ran and its device seconds;
- ``host_gap_ms``: the mean, over the ``serve.step`` spans that ran device
  work and lie wholly inside the device's traced window, of the device idle
  time inside each span.

    python3 -m bench.spans <trace.xplane.pb[.gz]>

prints them as one JSON object.
"""

from __future__ import annotations

import bisect
import gzip
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

from .trace import DEVICE_PLANE, HOST_PLANE, OPS_LINE, gaps

MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "serve."
STEP = "serve.step"
OUTSIDE = "outside"
_HASH = re.compile(r"\(\d+\)$")


@dataclass
class Spans:
    idle_s: float                       # device idle between whole steps
    idle_by_span: dict                  # innermost span name -> idle seconds
    program_s: dict                     # program -> {"n": runs, "s": seconds}
    step_idle_s: list                   # idle inside each whole step span
    spans: list = field(default_factory=list)    # (name, start, end, args)
    modules: list = field(default_factory=list)  # (program, start, end)
    holes: list = field(default_factory=list)    # idle between whole steps

    @property
    def host_gap_ms(self) -> float | None:
        if not self.step_idle_s:
            return None
        return 1e3 * sum(self.step_idle_s) / len(self.step_idle_s)

    def idle_in(self, name: str) -> float:
        """Seconds of device idle inside the spans called ``name``."""
        return 1e-9 * sum(_overlap(self.holes, s, e)
                          for n, s, e, _ in self.spans if n == name)

    def summary(self) -> dict:
        return {"idle_s": self.idle_s, "idle_by_span": self.idle_by_span,
                "program_s": self.program_s, "host_gap_ms": self.host_gap_ms,
                "steps": len(self.step_idle_s)}


def load(path: Path):
    import jax

    path = Path(path)
    if path.suffix == ".gz":
        return jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return jax.profiler.ProfileData.from_file(str(path))


def program_name(module: str) -> str:
    """``jit_serve_decode_paged(1234)`` -> ``jit_serve_decode_paged``."""
    return _HASH.sub("", module)


def reduce_spans(path: Path) -> Spans | None:
    """Reduce one trace; None where it has no device plane or no step."""
    ops, modules, spans = None, [], []
    for plane in load(path).planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns),
                                      dict(ev.stats)))
        elif DEVICE_PLANE.match(plane.name) and ops is None:
            for line in plane.lines:
                evs = [(program_name(ev.name), int(ev.start_ns),
                        int(ev.start_ns) + int(ev.duration_ns))
                       for ev in line.events]
                if line.name == OPS_LINE:
                    ops = [(s, e) for _, s, e in evs]
                elif line.name == MODULES_LINE:
                    modules = evs
    steps = [(s, e) for name, s, e, _ in spans if name == STEP]
    if not ops or not steps:
        return None
    spans.sort(key=lambda sp: (sp[1], -sp[2]))
    win = (min(s for s, _ in ops), max(e for _, e in ops))
    lo, hi = max(win[0], min(steps)[0]), min(win[1], max(e for _, e in steps))
    holes = [(max(s, lo), min(e, hi)) for s, e in gaps(ops)
             if e > lo and s < hi]
    idle_by_span: dict = {}
    for name, s, e in _innermost(spans):
        idle = _overlap(holes, s, e)
        if idle:
            idle_by_span[name] = idle_by_span.get(name, 0.0) + idle * 1e-9
    idle_s = sum(e - s for s, e in holes) * 1e-9
    idle_by_span[OUTSIDE] = max(idle_s - sum(idle_by_span.values()), 0.0)
    program_s: dict = {}
    for name, s, e in modules:
        p = program_s.setdefault(name, {"n": 0, "s": 0.0})
        p["n"] += 1
        p["s"] += (e - s) * 1e-9
    step_idle = [_overlap(holes, s, e) * 1e-9 for name, s, e, args in spans
                 if name == STEP and win[0] <= s and e <= win[1]
                 and args.get("chunks", 1) + args.get("decoded", 1) > 0]
    return Spans(idle_s=idle_s, idle_by_span=idle_by_span,
                 program_s=program_s, step_idle_s=step_idle, spans=spans,
                 modules=modules, holes=holes)


def _innermost(spans: list) -> list:
    """The host timeline cut into pieces, each named by the innermost span
    open over it (the latest-started of the nested spans), as
    (name, start, end); time under no span is left out."""
    bounds = sorted({t for _, s, e, _ in spans for t in (s, e)})
    out, open_, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        open_ = [sp for sp in open_ if sp[2] > a]
        while i < len(spans) and spans[i][1] <= a:
            if spans[i][2] > a:
                open_.append(spans[i])
            i += 1
        if open_:
            out.append((open_[-1][0], a, b))
    return out


def _overlap(holes: list, s: int, e: int) -> int:
    """Length of the sorted, disjoint ``holes`` that falls inside [s, e)."""
    total = 0
    for hs, he in holes[max(bisect.bisect_right(holes, (s,)) - 1, 0):]:
        if hs >= e:
            break
        total += max(min(he, e) - max(hs, s), 0)
    return total


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    got = reduce_spans(Path(argv[0]))
    print(json.dumps(None if got is None else got.summary()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
