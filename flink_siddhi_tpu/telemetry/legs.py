"""One clock from arrival to sink: the latency legs of a dispatched segment.

The executor keeps one ``SegmentRecord`` per dispatched segment (a
segment of one batch where nothing is fused) and stamps it, always with
``time.monotonic()``, where the work passes: each batch's ``arrival``
(source pull) and ``staged`` time with its event count, the segment's
``dispatch``, and ``complete``, the first time the host sees the
segment's ticket ready. The drain that delivers the segment's emissions
adds ``requested`` (the accumulator swap) and ``delivered`` (its last
emission has returned from the sinks) and closes the record into six
histograms, one sample per batch weighted by its events:

==============  =====================================  ======================
``leg.gate``    arrival -> staged                      watermark gate,
                                                       reorder, tape build
``leg.fill``    staged -> dispatch                     the segment filling
                                                       (or its age limit)
``leg.device``  dispatch -> complete                   H2D, the queue of
                                                       segments in flight,
                                                       the step
``leg.drain_wait``  complete -> requested              the drain interval,
                (0 if requested first)                 two drains in flight
``leg.drain``   max(requested, complete) -> delivered  readiness, fetch
                                                       queue, fetch, decode,
                                                       emit, sink
``leg.total``   arrival -> delivered                   the five above
==============  =====================================  ======================

The stamps are turned into whole microseconds before they are
differenced, so the five legs of a batch sum to its ``leg.total``
exactly. ``complete`` is stamped by the starvation clock's polls
(telemetry/starve.py ``stamp_complete``: every span boundary of the run
loop while work is queued), so it has the resolution of one span, not
of one run cycle: ``leg.device`` ends, and ``leg.drain_wait`` or
``leg.drain`` begins, at the first boundary after the segment's ticket
turned ready (docs/observability.md).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .histogram import LatencyHistogram

LEGS = ("gate", "fill", "device", "drain_wait", "drain", "total")


class SegmentRecord:
    """The host stamps of one dispatched segment."""

    __slots__ = (
        "seg", "arrival", "staged", "events", "dispatch", "complete",
    )

    def __init__(
        self,
        seg: int,
        arrival: List[float],
        staged: List[float],
        events: List[int],
        dispatch: float,
    ) -> None:
        self.seg = seg  # ordinal: every span of the segment carries it
        self.arrival = arrival  # per batch
        self.staged = staged  # per batch
        self.events = events  # per batch
        self.dispatch = dispatch
        # the first boundary at which the host saw the segment's ticket
        # ready (the starvation clock holds the ticket), or the drain's
        # meta if that was seen ready first
        self.complete: Optional[float] = None


def _us(seconds) -> np.ndarray:
    return np.rint(np.asarray(seconds, dtype=np.float64) * 1e6).astype(
        np.int64
    )


def record_legs(
    registry,
    records: Sequence[SegmentRecord],
    requested: float,
    delivered: float,
) -> None:
    """Close the records of one completed drain into the ``leg.*``
    histograms of ``registry``, in one vectorised pass."""
    if not records or not registry.enabled:
        return
    per_seg = [len(r.events) for r in records]
    arrival = _us([t for r in records for t in r.arrival])
    staged = _us([t for r in records for t in r.staged])
    events = np.asarray(
        [n for r in records for n in r.events], dtype=np.int64
    )
    dispatch = np.repeat(_us([r.dispatch for r in records]), per_seg)
    complete = np.repeat(_us([r.complete for r in records]), per_seg)
    requested, delivered = _us(requested), _us(delivered)
    drain_from = np.maximum(complete, requested)
    legs = (
        staged - arrival,
        dispatch - staged,
        complete - dispatch,
        drain_from - complete,
        delivered - drain_from,
        delivered - arrival,
    )
    LatencyHistogram.record_rows(
        [registry.histogram("leg." + name) for name in LEGS], legs, events
    )
