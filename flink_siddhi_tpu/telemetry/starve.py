"""The starvation clock: which run-loop stage held the device idle.

The spans (telemetry/spans.py) say how long each stage of the run loop
took; the leg clock (telemetry/legs.py) how long a segment waited. This
clock says **whether the device had work meanwhile**. It is built from
the tickets the executor already makes, one per dispatched step or
segment: while the newest one is not ready, work is queued on the chip;
once it is ready nothing is, and until the next step is called every
microsecond of the run loop is a microsecond of device idleness with a
stage's name on it.

**The probe.** Every boundary on the run-loop thread is one: a span's
enter and exit (top-level and nested, ``spans._Span``), a run cycle's
begin and end, a step's call (``issue``). While work is believed queued
a boundary polls the in-flight tickets oldest first, one ``is_ready()``
on the oldest unretired one and one more for each it retires; the same
poll stamps ``SegmentRecord.complete`` (``stamp_complete``), so the leg
clock has the resolution of a span. When the last ticket is ready the
device is **starved**, and no further poll is made until ``issue``.

**The charge.** Starved time is booked into ``StageTimes`` as
``starved.<stage>``: the top-level span the thread is in (a nested span
charges its parent's stage), ``starved.between`` inside a run cycle and
outside every span, ``starved.outside_cycle`` between two run cycles
(the caller's time). In ``starved.dispatch`` the time runs up to the
step's call. The stretch between the last poll that found work queued
and the first that found none cannot be split: it goes whole to
``starved.onset``, so the true starved time lies between the sum
without ``onset`` and the sum with it. ``starved.backpressure_wait``
is 0 by construction (the executor hands a step's ticket over after
the wait, during which the queue cannot run dry, and no poll is made
between a step's call and its ticket). Every name of
``STARVED_STAGES`` is booked at 0 when the clock is made, so a window
without starvation reads 0, not nothing.

**On the device trace's clock.** A span entered while the device is
starved carries the profiler stat ``starved=1``; the poll that finds
the queue empty writes a zero-length ``fst.starved_onset`` annotation
(``since_us``: the width of its bracket), which lies inside the span in
which the queue ran empty.

**What it cannot see.** The host's belief, not the chip's state: an
upload still landing and a step's launch after its call has returned
read as queued though the chip idles (the residue between the
benchmark's ``device_idle_share`` and the ``starved.*`` sum); the small
programs of a drain (``jit_pack``, ``jit_init_acc``) do not end
starvation though the chip runs them; a clock made in mid-run
(telemetry switched back on) believes the queue empty until the next
step is called. With telemetry off the executor keeps no clock: no
``is_ready()`` call is made.
"""

from __future__ import annotations

import time
from collections import deque
from threading import get_ident
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

# every name booked when the clock is made (a top-level span of another
# name, ResidentReplay's, is charged under its own when it comes)
STARVED_STAGES = (
    "ingest", "reorder", "route", "tape_build", "stage.h2d_overlap",
    "dispatch", "backpressure_wait", "drain", "flush",
    "between", "outside_cycle", "onset",
)


class StarveClock:
    """One job's starvation clock. All of it runs on the run-loop
    thread (``owner``: the thread of the last ``cycle(True)``; spans of
    other threads pass it by)."""

    __slots__ = (
        "_stages", "_now", "owner", "inflight", "starved", "_issued",
        "_stage", "_in_cycle", "_t",
    )

    def __init__(
        self, stages, now: Callable[[], float] = time.monotonic
    ) -> None:
        self._stages = stages
        self._now = now
        self.owner: Optional[int] = None
        # (ticket, SegmentRecord or None) in dispatch order: the device
        # retires them in that order
        self.inflight: deque = deque()
        self.starved = False
        # a step was called and its ticket is not handed over yet
        self._issued = False
        self._stage: Optional[str] = None  # the open top-level span
        self._in_cycle = False
        self._t = now()  # the last boundary
        for name in STARVED_STAGES:
            stages.add("starved." + name, 0.0, count=0)

    # -- the tickets -------------------------------------------------------
    def stamp_complete(self, now: float) -> bool:
        """Retire every in-flight ticket the host now sees ready,
        oldest first, stamping ``complete`` on its segment's record
        (unless the drain's meta was seen ready first); whether nothing
        is left queued. No thread and no blocking call: one
        ``is_ready()`` on the oldest unretired ticket. Called at every
        boundary while work is believed queued, which is the resolution
        of the stamp: one span."""
        if self._issued:
            return False
        inflight = self.inflight
        while inflight:
            ticket, rec = inflight[0]
            if not ticket.is_ready():
                return False
            inflight.popleft()
            if rec is not None and rec.complete is None:
                rec.complete = now
        return True

    def issue(self) -> None:
        """A step or segment is about to be called: the boundary that
        ends starvation."""
        self._mark()
        self.starved = False
        self._issued = True

    def watch(self, ticket, rec=None) -> None:
        """The ticket of the step just called. A bare ticket (no
        record: ``ShardedJob``'s, a leaf of the step's output that the
        next step's donation deletes) stands for all before it."""
        if rec is None:
            self.inflight.clear()
        self.inflight.append((ticket, rec))
        self._issued = False

    # -- the boundaries ----------------------------------------------------
    def _mark(self) -> None:
        now = self._now()
        if self.starved:
            self._stages.add(
                "starved." + (self._stage or (
                    "between" if self._in_cycle else "outside_cycle"
                )),
                now - self._t,
            )
        elif self.stamp_complete(now):
            # the queue ran empty somewhere since the last boundary
            self.starved = True
            self._stages.add("starved.onset", now - self._t)
            with TraceAnnotation(
                "fst.starved_onset", since_us=int((now - self._t) * 1e6)
            ):
                pass
        self._t = now

    def cycle(self, inside: bool) -> None:
        """A run cycle begins (on the thread that owns the clock from
        here on) or ends."""
        if inside:
            self.owner = get_ident()
        self._mark()
        self._in_cycle = inside

    def enter(self, name: str, nested: bool) -> bool:
        """A span opens on the owner's thread; whether the device is
        starved as it does."""
        self._mark()
        if not nested:
            self._stage = name
        return self.starved

    def exit(self, nested: bool) -> None:
        self._mark()
        if not nested:
            self._stage = None
