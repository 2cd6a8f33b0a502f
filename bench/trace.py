"""Device trace: take one with the JAX profiler, and reduce it to the numbers
the per-layer metrics read (busy and idle time, time by operation and by
kernel name, time in collectives, the longest idle gaps and what the host
was doing in them).

The reduction reads the ``.xplane.pb`` file that ``jax.profiler`` writes,
through ``jax.profiler.ProfileData`` and nothing else. On a TPU each chip is
a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
operation run; the host's threads are lines of the plane ``/host:CPU``.
An event's name is the operation's HLO text, ``%<name> = <shape>
<opcode>(<operands>), ...``, or the bare name in a hand-written trace; the
operation's own name is the part before `` = ``.
"""

from __future__ import annotations

import gzip
import re
import shutil
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10
# HLO collectives; their asynchronous halves are ``<opcode>-start`` and
# ``<opcode>-done``, which these prefixes cover
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z0-9-]*)\(")


def own_name(op: str) -> str:
    """``%tsl_rmsnorm.14 = bf16[16,1024]{...} custom-call(...)`` ->
    ``tsl_rmsnorm.14``."""
    return op.split(" = ", 1)[0].lstrip("%")


def is_kernel(op: str, name: str) -> bool:
    """Whether ``op`` is a call of the kernel ``name``: the kernel's name
    begins the op's own name (``tsl_paged_attention.9``). A fusion that
    merely reads the kernel's output, and so names it among its operands,
    is not a call."""
    return own_name(op).startswith(name)


def is_collective(op: str) -> bool:
    """Whether ``op`` is a collective: its own name or its HLO opcode
    begins with one of COLLECTIVES."""
    head, _, text = op.partition(" = ")
    words = [head.lstrip("%")]
    m = _OPCODE.search(text)
    if m:
        words.append(m.group(1))
    return any(w.startswith(COLLECTIVES) for w in words)


class Tracer:
    """One profiler session into ``log_dir`` (emptied first). Host Python
    calls are not traced (their cost would distort the host loop); JAX's
    own host annotations are."""

    def __init__(self, log_dir: Path):
        self.log_dir = Path(log_dir)
        self.window_s = 0.0
        self._t0 = 0.0

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> Path:
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        found = sorted(self.log_dir.rglob("*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.log_dir}")
        return found[-1]

    def collect(self, path: Path, keep: Path | None = None) -> "Summary":
        """Reduce the trace at ``path``, keep a gzipped copy at ``keep`` if
        given, and remove the session's files."""
        summary = reduce(path, self.window_s)
        if keep is not None:
            Path(keep).parent.mkdir(parents=True, exist_ok=True)
            Path(keep).write_bytes(gzip.compress(Path(path).read_bytes()))
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return summary


@dataclass
class Summary:
    window_s: float                     # traced window, host clock
    devices: int                        # device planes seen
    busy_s: float                       # union of op intervals, mean/device
    collective_s: float = 0.0           # collective ops' seconds, mean/device
    op_s: dict = field(default_factory=dict)       # op name -> seconds (all)
    op_count: dict = field(default_factory=dict)   # op name -> events (all)
    idle_gaps: list = field(default_factory=list)  # [(label, seconds)]

    def kernel_s(self, name: str) -> float:
        """Seconds of every call of the kernel ``name`` (``is_kernel``),
        summed over devices."""
        return sum(s for op, s in self.op_s.items() if is_kernel(op, name))

    def kernel_count(self, name: str) -> int:
        return sum(c for op, c in self.op_count.items()
                   if is_kernel(op, name))

    def top_ops(self, n: int = TOP) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]


def union_length(intervals: list[tuple[int, int]]) -> int:
    """Total length covered by half-open [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """The holes between the merged intervals, as (start, end)."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def reduce(path: Path, window_s: float) -> Summary:
    """Reduce one trace file. ``window_s`` is the traced window's length as
    the host clock read it around the session."""
    import jax

    path = Path(path)
    if path.suffix == ".gz":                # a trace kept compressed
        data = jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    else:
        data = jax.profiler.ProfileData.from_file(str(path))
    op_s: dict = defaultdict(float)
    op_count: dict = defaultdict(int)
    busy, devices, collective = 0, 0, 0
    first_device_intervals = None
    host_events = []
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    host_events.append((ev.start_ns, ev.duration_ns,
                                        line.name, ev.name))
            continue
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            devices += 1
            intervals = []
            for ev in line.events:
                s, d = int(ev.start_ns), int(ev.duration_ns)
                intervals.append((s, s + d))
                op_s[ev.name] += d * 1e-9
                op_count[ev.name] += 1
                if is_collective(ev.name):
                    collective += d
            busy += union_length(intervals)
            if first_device_intervals is None:
                first_device_intervals = intervals
    idle = []
    if first_device_intervals:
        holes = sorted(gaps(first_device_intervals),
                       key=lambda g: g[0] - g[1])[:TOP]
        idle = [(_host_label(host_events, s, e), (e - s) * 1e-9)
                for s, e in holes]
    return Summary(window_s=window_s, devices=devices,
                   busy_s=busy * 1e-9 / max(devices, 1),
                   collective_s=collective * 1e-9 / max(devices, 1),
                   op_s=dict(op_s),
                   op_count=dict(op_count), idle_gaps=idle)


def _host_label(host_events, s: int, e: int) -> str:
    """What the host was doing in a device gap [s, e): the shortest host
    event that covers the gap's middle (the most specific one), else the
    host event that ends last before it."""
    mid = (s + e) // 2
    best = None
    for start, dur, thread, name in host_events:
        if start <= mid < start + dur and (best is None or dur < best[0]):
            best = (dur, f"{name} [{thread}]")
    if best is not None:
        return best[1]
    before = [(start + dur, name, thread) for start, dur, thread, name
              in host_events if start + dur <= mid]
    if before:
        end, name, thread = max(before)
        return f"after {name} [{thread}]"
    return "no host event"
