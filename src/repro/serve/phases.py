"""Named phases of the serving loop.

Each phase is timed on the host clock into a per-run table (count and
seconds per phase name) and, while a profiler session runs, is also a
``serve.<name>`` span on the profiler's host plane, which shares its clock
with the device trace: a device gap can then be named by the phase the host
was in. With no session running a span costs one ``TraceMe`` check and two
``perf_counter`` reads.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class Phases:
    """Host seconds and counts per phase of one engine run."""

    def __init__(self):
        self._totals: dict[str, list] = {}      # name -> [count, seconds]

    def span(self, name: str, **args) -> "Span":
        """A context manager timing one ``name`` phase; ``args`` become the
        span's arguments in a trace."""
        return Span(self, name, args)

    def report(self) -> dict:
        return {n: {"n": c, "s": s} for n, (c, s) in self._totals.items()}


class Span:
    __slots__ = ("_phases", "_name", "_args", "_ann", "_t0", "counted")

    def __init__(self, phases: Phases, name: str, args: dict):
        self._phases, self._name, self._args = phases, name, args
        self._ann = None
        self.counted = True     # False: leave this span out of the table

    def set(self, **args) -> None:
        """Arguments known only at the span's end (in a trace only)."""
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self) -> "Span":
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(f"serve.{self._name}", **self._args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.counted:
            tot = self._phases._totals.setdefault(self._name, [0, 0.0])
            tot[0] += 1
            tot[1] += dt
