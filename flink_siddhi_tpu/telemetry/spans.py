"""Structured tracing spans + per-stage wall-clock accounting.

``StageTimes`` is the honest-wall-clock ledger the bench's
``stage_breakdown`` is computed from: every instrumented host code
section runs under ``with stages.span("name"):`` and its elapsed
monotonic time accrues to that stage's total. Two rules keep the ledger
summable against a wall clock:

* spans that open while another span is already active on the SAME
  thread accrue under ``nested.<name>`` — their time is already counted
  by the enclosing span, so only top-level names participate in
  "stages must sum to >= 95% of elapsed" arithmetic (the nested names
  remain visible for drill-down);
* spans on different threads (the drain fetch thread overlaps the run
  loop by design) accrue normally under their own names — wall-clock
  attribution sums only the run-loop lane's stage names
  (``TOP_LEVEL_STAGES`` in the package root).

Every span is also a ``jax.profiler.TraceAnnotation`` named
``fst.<name>``, so a profiler session shows the program's host spans on
the clock of the device planes (plane ``/host:CPU``, line ``python``),
with the keyword stats the caller gave (``seg=<ordinal>``). With no
session open an annotation costs under a microsecond.

A span's enter and exit on the run-loop thread are also the boundaries
of the job's starvation clock (telemetry/starve.py), where it has one:
a span entered while nothing is queued on the device carries the stat
``starved=1``. The clock's own work at a boundary falls inside the span.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from .starve import StarveClock

from jax.profiler import TraceAnnotation


class _NullSpan:
    """Shared no-op context for disabled telemetry (zero allocation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_st", "_name", "_stats", "_t0", "_nested", "_ann",
                 "_clock")

    def __init__(self, st: "StageTimes", name: str, stats: Dict) -> None:
        self._st = st
        self._name = name
        self._stats = stats

    def __enter__(self):
        st = self._st
        tls = st._tls
        depth = getattr(tls, "depth", 0)
        self._nested = depth > 0
        tls.depth = depth + 1
        self._t0 = time.perf_counter()
        stats = self._stats
        clock = st.starve
        if clock is not None and clock.owner != threading.get_ident():
            clock = None  # not the run loop's thread
        self._clock = clock
        if clock is not None and clock.enter(self._name, self._nested):
            stats = dict(stats, starved=1)
        self._ann = TraceAnnotation("fst." + self._name, **stats)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        if self._clock is not None:
            # before the annotation closes: the onset marker of a queue
            # that ran empty in this span lies inside it
            self._clock.exit(self._nested)
        self._ann.__exit__(*exc)
        dt = time.perf_counter() - self._t0
        self._st._tls.depth -= 1
        self._st.add(self._name, dt, nested=self._nested)
        return False


class StageTimes:
    """Thread-safe per-stage time accumulator."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._totals: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._tls = threading.local()
        # the job's starvation clock, while it has one (the executor
        # makes and drops it: Job._starve_clock)
        self.starve: Optional[StarveClock] = None

    def span(self, name: str, **stats) -> _Span:
        """``stats`` go to the profiler annotation only."""
        return _Span(self, name, stats)

    def add(
        self,
        name: str,
        seconds: float,
        count: int = 1,
        nested: bool = False,
    ) -> None:
        """Attribute ``seconds`` of wall-clock to stage ``name``.
        Callers measuring a section without a span (e.g. a duration
        computed before the registry existed) use this directly."""
        key = f"nested.{name}" if nested else name
        with self._lock:
            self._totals[key] = self._totals.get(key, 0.0) + seconds
            self._counts[key] = self._counts.get(key, 0) + count

    def total(self, name: str) -> float:
        with self._lock:
            return self._totals.get(name, 0.0)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {
                    "seconds": round(total, 6),
                    "count": self._counts.get(name, 0),
                }
                for name, total in sorted(self._totals.items())
            }
