"""The host streaming runtime: micro-batcher + device step driver.

Plays the role of the reference's operator lifecycle + hot loop
(AbstractSiddhiOperator.open/processElement/processWatermark,
AbstractSiddhiOperator.java:274-278,209-247) re-shaped for an accelerator:

* events are pulled from sources in chunks, not pushed one at a time;
* event-time ordering happens once per micro-batch in a host reorder buffer
  gated by the min-watermark across sources (reference: per-element priority
  queue offer/poll);
* the compiled plan advances in ONE jitted device call per micro-batch;
* outputs decode from fixed-capacity device buffers to typed host records.
"""

from __future__ import annotations

import contextlib
import ctypes
import heapq
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler.plan import CompiledPlan
from ..schema.batch import EventBatch
from ..telemetry import MetricsRegistry
from ..telemetry import compile_events
from ..telemetry.attribution import limiting_leg as _attr_limiting_leg
from ..telemetry.flightrec import FlightRecorder
from ..telemetry.legs import SegmentRecord, record_legs
from ..telemetry.slo import SLOWatchdog
from ..telemetry.starve import StarveClock
from ..telemetry.tracing import TraceSampler
from .sources import Source
from .tape import bucket_size, build_wire_tape

# Hot-loop transfer contract (tests/conftest.py flips this for the
# jitted-step suites): with the flag on, run_cycle executes under
# jax.transfer_guard("disallow") so an IMPLICIT host<->device transfer
# in the steady-state loop — a numpy array silently riding a jit call
# where the design says "one explicit async device_put per segment" —
# fails loudly instead of costing a synchronous round trip per batch.
# The host's re-bucketing after group growth is re-allowed at its one
# call site via _staging_allow() (docs/static_analysis.md).
HOTLOOP_TRANSFER_GUARD = False


def _hotloop_guard():
    if HOTLOOP_TRANSFER_GUARD:
        return jax.transfer_guard("disallow")
    return contextlib.nullcontext()


def _keep_heap_warm() -> None:
    """glibc serves the process's large temporaries (2-30 MB: a column
    of a batch or a delivery) from a heap it keeps, not from a fresh
    mapping each, faulted in a page per 4 KB and handed back at free:
    3/4 of a delivery's emission tail, 1/4 of a tape's build (PERF.md
    §6, PR 48). Process-wide, idempotent; without glibc, nothing."""
    with contextlib.suppress(OSError, AttributeError):
        mallopt = ctypes.CDLL("libc.so.6").mallopt
        mallopt(-3, 32 << 20)  # M_MMAP_THRESHOLD: its maximum, and fixed
        mallopt(-1, (1 << 31) - 1)  # M_TRIM_THRESHOLD: hand nothing back
        mallopt(-2, 64 << 20)  # M_TOP_PAD: the heap grows 64 MB a time


def _staging_allow():
    """The legitimate staging transfer (host re-bucketing after group
    growth) — explicitly allowed inside the guarded hot loop, so the
    guard's findings are always contract violations, never the
    design's own uploads."""
    if HOTLOOP_TRANSFER_GUARD:
        return jax.transfer_guard("allow")
    return contextlib.nullcontext()


# Run-loop ownership contract (tests/conftest.py flips this for the
# control-plane/service/fault suites, like HOTLOOP_TRANSFER_GUARD):
# with the guard on, the first run()/run_cycle() stamps its thread id
# as the run-loop owner, and every state-mutating control entry point
# (add_plan / remove_plan / set_plan_enabled / _apply_control /
# reset_engine_state) asserts it executes on that thread. This is the
# DYNAMIC half of the fstrace FST201 invariant ("state mutates only
# via control events applied on the run-loop thread",
# docs/control_plane.md): the linter proves the call graph, the guard
# executes it under the service/control/fault tests.
RUNLOOP_OWNERSHIP_GUARD = False


class OwnershipViolation(RuntimeError):
    """A run-loop-owned mutation entry point ran on a thread other
    than the stamped run-loop owner — the FST201 hazard, caught live."""

MAX_WM = np.iinfo(np.int64).max
MIN_WM = -(2 ** 62)  # pre-first-event watermark sentinel
# side-output channel naming: a stream's late rows surface on
# '<stream_id>@late' (attach sinks there; ColumnarSink-capable)
LATE_STREAM_SUFFIX = "@late"


def late_stream(stream_id: str) -> str:
    """The side-output stream id carrying ``stream_id``'s late rows."""
    return stream_id + LATE_STREAM_SUFFIX
_LAZY_ORD_WRAP = 1 << 30  # reset lazy ordinal space before int32 wrap
_LOG = logging.getLogger(__name__)


def _clock_rows(schema, rows, epoch: int):
    """``rows`` with the fields that are times on the job's clock
    (``OutputField.on_clock``) as epoch ms, as the rows' stamps leave."""
    clock = [i for i, f in enumerate(schema.fields) if f.on_clock]
    if not clock:
        return rows
    return [
        (ts, tuple(v + epoch if i in clock else v
                   for i, v in enumerate(row)))
        for ts, row in rows
    ]


def _wire_sig(wire):
    """Structural signature of a wire tape: pytree aux + leaf layouts.
    Two tapes with equal signatures can stack into one scanned axis
    (shared by the fused streaming dispatch below and the bounded
    replay's pre-stager, runtime/replay.py)."""
    leaves, treedef = jax.tree.flatten(wire)
    return (
        str(treedef),
        tuple((np.shape(x), np.dtype(getattr(x, "dtype", type(x))))
              for x in leaves),
    )


def _stack_wires(wires):
    """Stack structurally-identical host wire tapes along a new leading
    (scan) axis — ONE definition for the fused streaming dispatch and
    the bounded replay's pre-stager."""
    return jax.tree.map(lambda *ls: np.stack(ls), *wires)


def _empty_wire_like(wire):
    """A padding tape for a partial trailing segment: structurally
    identical, zero valid events, time parked at the source tape's
    base (never advances the clock). Only ``n_valid`` is replaced —
    every other leaf aliases the source tape (read-only)."""
    import dataclasses

    return dataclasses.replace(
        wire, n_valid=np.zeros(1, dtype=np.int32)
    )


@dataclass
class _PlanRuntime:
    plan: CompiledPlan
    states: Dict
    # the mesh's step alone: ShardedJob fills it with make_sharded_step_acc
    # and calls it once a cycle (parallel/sharded.py); Job leaves it None
    jitted_acc: Callable = None
    # the hot loop's entry: a lax.scan of K stacked micro-batch tapes per
    # device call (the replay's segment shape, fed live). seg_pending holds
    # staged-but-undispatched tapes; states + acc are donated (the carry)
    jitted_seg: Callable = None
    seg_pending: List = field(default_factory=list)
    jitted_init_acc: Callable = None  # cached: zeroing program compiles once
    jitted_flush: Callable = None  # plan.flush under jit (device states)
    acc: Dict = None  # device-side output accumulator (None: fetch-per-cycle)
    wire_kinds: Dict = None  # sticky per-column wire widths (build_wire_tape)
    # the encoders' counters as last booked (Job._count_groups)
    group_stats: Dict = field(default_factory=dict)
    enabled: bool = True
    # NOTE: backpressure is ticket-based (see ``tickets`` below); there is
    # no per-cycle sawtooth sync anymore
    # sticky tape capacity: once a capacity is compiled, smaller batches
    # (e.g. the end-of-stream tail) pad up to it instead of bucketing down
    # — a mid-run capacity change costs a whole new XLA executable
    tape_capacity: int = 0
    flush_warm: object = None  # background flush-precompile future
    # sliding-window backpressure: state leaves of dispatched cycles;
    # the oldest is waited on once the window is full, so the device
    # stays <= max_inflight_cycles behind without sawtooth stalls
    tickets: deque = field(default_factory=deque)
    # how deep that window may be (Job._inflight_depth): tickets ever
    # made, the last one waited on as (ordinal, when it was ready), and
    # the device's time for one dispatch as two waits in a row measured
    # it (None until then)
    dispatched: int = 0
    waited: Optional[Tuple[int, float]] = None
    dispatch_s: Optional[float] = None
    # async drain pipeline: swapped-out accumulators whose meta/data
    # fetches are in flight (see Job._drain_request/_drain_poll)
    drain_q: deque = field(default_factory=deque)
    # False while the live accumulator is provably empty (freshly
    # swapped, no step since): a drain request then skips entirely —
    # each needless drain costs one host<->device round trip
    acc_dirty: bool = False
    # when the live accumulator FIRST became dirty after a swap: the
    # age of the oldest undrained match. The deadline drain scheduler
    # keys off it (next drain due = dirty_since + drain_interval_ms)
    # and drain.staleness records it per completed drain
    dirty_since: Optional[float] = None
    # latency legs (telemetry/legs.py): the records of the segments
    # dispatched since the last accumulator swap (the drain request
    # that takes the accumulator takes them with it). Those whose
    # ticket the host has not yet seen ready are the starvation
    # clock's (telemetry/starve.py ``inflight``)
    seg_open: List = field(default_factory=list)


class _LazyRing:
    """Host-retained projection-only columns (late materialization).

    Lazy-projected plans emit event ORDINALS; this ring maps them back
    to values at decode time. Entries are evicted oldest-first past a
    byte budget — an ordinal older than the horizon decodes as None
    (bounded-memory policy, counted in ``missed``), mirroring every
    other engine cap."""

    def __init__(self, budget_bytes: int = 256 << 20) -> None:
        import threading

        self.starts: List[int] = []
        self.lens: List[int] = []
        self.cols: List[Dict[str, np.ndarray]] = []
        self.bytes = 0
        self.budget = budget_bytes
        self.missed = 0
        # push happens on the run-loop thread, lookup on the drain fetch
        # thread (decode moved off the hot loop) — both are short
        self._lock = threading.Lock()

    def push(self, start: int, cols: Dict[str, np.ndarray]) -> None:
        with self._lock:
            n = len(next(iter(cols.values()))) if cols else 0
            self.starts.append(start)
            self.lens.append(n)
            self.cols.append(cols)
            self.bytes += sum(c.nbytes for c in cols.values())
            while self.bytes > self.budget and len(self.starts) > 1:
                old = self.cols.pop(0)
                self.starts.pop(0)
                self.lens.pop(0)
                self.bytes -= sum(c.nbytes for c in old.values())

    def lookup(self, key: str, ords) -> List:
        """Batch ordinal resolve: one searchsorted + one gather per ring
        entry touched (matches cluster in 1-2 entries), instead of a
        per-ordinal Python loop."""
        with self._lock:
            ords = np.asarray(ords, dtype=np.int64)
            n = len(ords)
            out: List = [None] * n
            if n == 0 or not self.starts:
                self.missed += n
                return out
            starts = np.asarray(self.starts, dtype=np.int64)
            lens = np.asarray(self.lens, dtype=np.int64)
            idx = np.searchsorted(starts, ords, side="right") - 1
            safe = np.clip(idx, 0, None)
            ok = (idx >= 0) & (ords - starts[safe] < lens[safe])
            self.missed += int(n - ok.sum())
            offs = ords - starts[safe]
            for i in np.unique(idx[ok]).tolist():
                sel = np.nonzero(ok & (idx == i))[0]
                entry = self.cols[i]
                if key not in entry:
                    self.missed += len(sel)
                    continue
                vals = entry[key][offs[sel]].tolist()
                for j, v in zip(sel.tolist(), vals):
                    out[j] = v
            return out

    def lookup_np(self, key: str, ords) -> np.ndarray:
        """Vectorized ordinal resolve for the columnar sink fast lane:
        same gather as :meth:`lookup`, but the product stays a numpy
        array — typed when every ordinal hits, object-dtype with None
        holes when any was evicted past the ring horizon."""
        with self._lock:
            ords = np.asarray(ords, dtype=np.int64)
            n = len(ords)
            if n == 0 or not self.starts:
                self.missed += n
                return np.full(n, None, dtype=object)
            starts = np.asarray(self.starts, dtype=np.int64)
            lens = np.asarray(self.lens, dtype=np.int64)
            idx = np.searchsorted(starts, ords, side="right") - 1
            safe = np.clip(idx, 0, None)
            ok = (idx >= 0) & (ords - starts[safe] < lens[safe])
            offs = ords - starts[safe]
            out = None
            found = np.zeros(n, dtype=bool)
            for i in np.unique(idx[ok]).tolist():
                sel = np.nonzero(ok & (idx == i))[0]
                entry = self.cols[i]
                if key not in entry:
                    continue
                col = entry[key]
                if out is None:
                    out = np.zeros(n, dtype=col.dtype)
                out[sel] = col[offs[sel]]
                found[sel] = True
            self.missed += int(n - found.sum())
            if out is None:
                return np.full(n, None, dtype=object)
            if not bool(found.all()):
                obj = out.astype(object)
                obj[~found] = None
                return obj
            return out


class ColumnarSink:
    """Protocol/base for sinks opting into the columnar fast lane.

    A sink exposing ``accept_columns(ts, cols)`` receives whole emission
    batches as ``(abs_ts int64 ndarray, {field_name: ndarray})`` with
    ``emission_order`` already applied — zero per-row tuples ever
    materialize on streams where EVERY attached sink is columnar (and
    host retention is off). On streams that still decode row-wise
    (mixed consumers, side-channel artifacts, retained results), the
    runtime converts once per emission batch and calls
    ``accept_columns`` with object-dtype columns, so a columnar sink
    observes identical data either way (the tier-1 equivalence test
    pins this). Duck-typed: any object with ``accept_columns`` counts;
    subclassing this base is optional."""

    def accept_columns(
        self, ts: np.ndarray, cols: Dict[str, np.ndarray]
    ) -> None:
        raise NotImplementedError


class _OutputRateLimiter:
    """Host emission-layer rate limiter (``output [all|last|first] every
    N events | <duration>``) — the role of siddhi-core's output rate
    limiters, applied where rows surface to collectors/sinks so thinned
    streams also skip the retention/callback cost."""

    def __init__(self, rate, snapshot_keys: tuple = ()) -> None:
        self.mode = rate.mode  # 'events' | 'time' | 'snapshot'
        self.which = rate.which  # all | last | first
        self.n = max(int(rate.n_events), 1)
        self.ms = float(rate.ms)
        self.count = 0  # events-mode position within the chunk
        self.buf: List = []
        self.deadline: Optional[float] = None
        # snapshot mode: latest row per group key (positions into the
        # output row); emitted in full every interval
        self.snapshot_keys = tuple(snapshot_keys or ())
        self.cur: Dict = {}

    def _normalize_buf_rows(self) -> None:
        """A stream can change lanes mid-flight (a row sink attached via
        add_sink drops it off the columnar lane): column fragments the
        other lane buffered are lifted to ``(ts, row)`` pairs so chunk
        accounting continues exactly where it left off."""
        from ..compiler.output import ColumnBatch

        if any(isinstance(b, ColumnBatch) for b in self.buf):
            self.buf = [
                r
                for b in self.buf
                for r in (
                    b.rows() if isinstance(b, ColumnBatch) else [b]
                )
            ]

    def _normalize_buf_columns(self, field_names) -> None:
        """Inverse lane switch: row-era ``(ts, row)`` entries become
        single-row ColumnBatches (order preserved) so concat/take on
        the columnar path stay uniform."""
        from ..compiler.output import ColumnBatch

        def lift(entry):
            if isinstance(entry, ColumnBatch):
                return entry
            ts, row = entry
            return ColumnBatch(
                np.asarray([ts], dtype=np.int64),
                {
                    name: np.asarray([val], dtype=object)
                    for name, val in zip(field_names, row)
                },
            )

        if not all(isinstance(b, ColumnBatch) for b in self.buf):
            self.buf = [lift(b) for b in self.buf]

    def feed(self, rows: List) -> List:
        # normalize only when rows are actually absorbed into the
        # buffer: a flush (idle interval poll or elapsed deadline)
        # releases buffered entries AS-IS — _emit_pending/flush route
        # ColumnBatch entries through the columnar emit path, so a
        # columnar-lane buffer never explodes into per-row tuples just
        # to be rebuilt into columns for its own sinks
        if self.buf and rows:
            self._normalize_buf_rows()
        if self.mode == "snapshot":
            # roll the interval BEFORE absorbing, as in time mode: rows
            # arriving after a deadline belong to the new interval
            now = time.monotonic()
            if self.deadline is None:
                self.deadline = now + self.ms / 1e3
            flushed: List = []
            if now >= self.deadline:
                flushed = list(self.cur.values())
                self.deadline = now + self.ms / 1e3
            for r in rows:  # (rel_ts, row)
                k = tuple(r[1][i] for i in self.snapshot_keys)
                self.cur[k] = r
            return flushed
        if self.mode == "events":
            out: List = []
            for r in rows:
                pos = self.count % self.n
                self.count += 1
                if self.which == "first":
                    if pos == 0:
                        out.append(r)
                elif self.which == "last":
                    self.buf = [r]
                    if pos == self.n - 1:
                        out.append(r)
                        self.buf = []
                else:  # all: release the chunk when it completes
                    self.buf.append(r)
                    if pos == self.n - 1:
                        out.extend(self.buf)
                        self.buf = []
            return out
        # time mode (processing time): roll the interval over BEFORE
        # processing — rows arriving after a deadline belong to the NEW
        # interval (processing them first would drop the new interval's
        # first event / misattribute late rows to the old interval)
        now = time.monotonic()
        if self.deadline is None:
            self.deadline = now + self.ms / 1e3
        flushed: List = []
        if now >= self.deadline:
            if self.which != "first":
                flushed = (
                    self.buf if self.which == "all" else self.buf[-1:]
                )
            self.buf = []
            self.deadline = now + self.ms / 1e3
        if self.which == "first":
            out = list(flushed)
            for r in rows:
                if not self.buf:
                    self.buf = [r]  # first of the interval
                    out.append(r)
            return out
        self.buf.extend(rows)
        return flushed

    def feed_columns(self, cb) -> List:
        """Columnar twin of :meth:`feed`: account a whole ColumnBatch
        with index arithmetic and array slices — no row tuples. Events
        and time modes only (snapshot needs per-group latest rows and
        is excluded from the columnar lane by Job._columnar_streams).
        Parity with the row path is pinned by tests."""
        n = len(cb)
        if self.buf:
            self._normalize_buf_columns(list(cb.cols))
        if self.mode == "events":
            pos0 = self.count
            self.count += n
            pos = (pos0 + np.arange(n, dtype=np.int64)) % self.n
            if self.which == "first":
                sel = np.nonzero(pos == 0)[0]
                return [cb.take(sel)] if sel.size else []
            if self.which == "last":
                sel = np.nonzero(pos == self.n - 1)[0]
                out = [cb.take(sel)] if sel.size else []
                if n and (pos0 + n) % self.n != 0:
                    # an incomplete chunk's latest row waits for flush()
                    self.buf = [cb.take(np.array([n - 1]))]
                elif sel.size:
                    self.buf = []
                return out
            # all: release through the end of the last COMPLETE chunk
            complete = np.nonzero(pos == self.n - 1)[0]
            if not complete.size:
                if n:
                    self.buf.append(cb)
                return []
            cut = int(complete[-1]) + 1
            parts = list(self.buf) + ([cb.take(np.arange(cut))]
                                      if cut else [])
            self.buf = (
                [cb.take(np.arange(cut, n))] if cut < n else []
            )
            from ..compiler.output import ColumnBatch

            return [ColumnBatch.concat(parts)] if parts else []
        # time mode: same deadline-roll-before-absorb contract as feed()
        now = time.monotonic()
        if self.deadline is None:
            self.deadline = now + self.ms / 1e3
        flushed: List = []
        if now >= self.deadline:
            if self.which != "first":
                flushed = (
                    self.buf if self.which == "all" else self.buf[-1:]
                )
            self.buf = []
            self.deadline = now + self.ms / 1e3
        if self.which == "first":
            out = list(flushed)
            if not self.buf and n:
                head = cb.take(np.array([0]))
                self.buf = [head]
                out.append(head)
            return out
        if n:
            if self.which == "last":
                # only the latest row can ever surface: keep just it
                self.buf = [cb.take(np.array([n - 1]))]
            else:
                self.buf.append(cb)
        return flushed

    def flush(self) -> List:
        """End of stream: pending buffered output surfaces."""
        if self.mode == "snapshot":
            out = list(self.cur.values())
            self.cur = {}
            return out
        if self.which == "first":
            self.buf = []
            return []
        out = (
            self.buf
            if self.which == "all"
            else self.buf[-1:]
        )
        self.buf = []
        return out


# fst:checkpointed by=flink_siddhi_tpu/runtime/checkpoint.py:snapshot_job,flink_siddhi_tpu/runtime/checkpoint.py:restore_job
class Job:
    """One running pipeline: sources -> compiled plan(s) -> collectors/sinks.

    Checkpoint coverage lives out-of-class in ``runtime/checkpoint.py``
    (``snapshot_job``/``restore_job``) — the ``fst:checkpointed``
    annotation above points FST106 at it: any NEW mutable ``self._*``
    state added to the run loop must either join the snapshot or carry
    an explicit ``# fst:ephemeral <reason>`` (the PR 10 event-time-gate
    class: state that silently dies on restore)."""

    def __init__(
        self,
        plans: Sequence[CompiledPlan],
        sources: Sequence[Source],
        batch_size: int = 4096,
        time_mode: str = "event",  # 'event' | 'processing'
        control_sources: Sequence = (),
        plan_compiler: Optional[Callable] = None,  # (cql, plan_id) -> plan
        retain_results: bool = True,  # keep emitted rows in collected[]
        # (the results() path); False = no host retention at all — rows
        # reach sinks only, so an unbounded run cannot grow host memory
        # (long-running pipeline / pure-benchmark mode)
    ) -> None:
        if time_mode not in ("event", "processing"):
            raise ValueError(time_mode)
        _keep_heap_warm()
        self.batch_size = batch_size
        self.time_mode = time_mode
        self.retain_results = retain_results
        self._sources = list(sources)
        self._source_wm: List[int] = [MIN_WM] * len(self._sources)
        self._source_done: List[bool] = [False] * len(self._sources)
        # the polls in flight on the poll thread, and the length of each
        # source's run of slow polls that brought a batch (``_poll``):
        # source index -> (source, future), -> polls
        # fst:ephemeral a poll in flight belongs to a source no checkpoint records (_poll)
        self._polled_ahead: Dict[int, Tuple[Source, object]] = {}
        # fst:ephemeral the run of polls a restored job counts anew
        self._poll_runs: Dict[int, int] = {}
        self._control = list(control_sources)
        self._control_wm: List[int] = [MIN_WM] * len(self._control)
        self._control_done: List[bool] = [False] * len(self._control)
        # fst:threadsafe single-writer (run loop); the finished property only bool-tests it off-thread
        self._control_pending: List[Tuple[int, object]] = []
        self._plan_compiler = plan_compiler
        # reorder buffer: stream_id -> pending EventBatches (event time)
        # fst:threadsafe single-writer (run loop); off-thread metrics() readers take list() snapshots only
        self._pending: Dict[str, List[EventBatch]] = {}
        self._epoch_ms: Optional[int] = None
        # fst:threadsafe single-writer (run loop); off-thread status/metrics readers use GIL-atomic get()/list() snapshots, never Python-level iteration
        self._plans: Dict[str, _PlanRuntime] = {}
        # dynamic chain groups: user plan_id -> (host runtime id, slot).
        # A structurally-identical chain query folds into a pre-padded
        # group slot as a DATA update — no XLA recompile (SURVEY.md §7
        # hard part 4)
        # fst:threadsafe single-writer (run loop); service reads are GIL-atomic get()/list() snapshots
        self._folded: Dict[str, Tuple[str, int]] = {}
        # fst:threadsafe single-writer (run loop); service reads are GIL-atomic get()/list() snapshots
        self._folded_enabled: Dict[str, bool] = {}  # host-side mirror
        self._dynamic_cql: Dict[str, str] = {}  # for checkpoint replay
        # cross-tenant shared subplans (analysis/share.py + the admit
        # ladder in add_plan, docs/control_plane.md): exact-predicate
        # share key -> {host_id, mid, prefix_cql, members}. The host
        # (@shr:<key>) runs the shared prefix ONCE and its mid-stream
        # rows loop back host-side into every member's suffix runtime;
        # retire reference-counts members and drops the host with the
        # last one. All three checkpointed via the "shared" block
        # (runtime/checkpoint.py) and re-formed by _replay_shared.
        # fst:threadsafe single-writer (run loop); off-thread readers take dict() snapshots
        self._shared: Dict[str, Dict] = {}
        # member plan id -> share key (the refcount's edge list)
        # fst:threadsafe single-writer (run loop); service reads are GIL-atomic get() only
        self._shared_member: Dict[str, str] = {}
        # loopback routing: mid stream id -> share key. _emit_rows
        # intercepts these streams BEFORE any counter/trace/sink so a
        # mid row is pure plumbing — per-tenant conservation (PR 14)
        # only ever counts member-suffix emissions.
        # fst:threadsafe single-writer (run loop); the emit path reads get() only
        self._loopback: Dict[str, str] = {}
        # mid stream id -> ([timestamps], [rows]) accumulated across a
        # drain: consumer suffixes are stepped ONCE per flush with one
        # coalesced batch, not once per drained host payload — the
        # per-dispatch fixed cost on fragmented mid batches would
        # otherwise dominate the shared side's drain wall clock
        # fst:ephemeral pending plumbing rows; flushed within the same drain pass
        self._loopback_buf: Dict[str, tuple] = {}
        # ladder gate: subplan sharing changes the runtime layout of a
        # dynamic admit (host + suffix instead of one runtime), so it
        # is opt-in — FST_SUBPLAN_SHARE=1 or job.share_subplans = True
        import os as _os

        self.share_subplans = _os.environ.get(
            "FST_SUBPLAN_SHARE", "0"
        ).lower() not in ("0", "", "false")
        # shape-keyed AOT executable cache (control/aotcache.py): a
        # dynamic add whose shape class was compiled before reuses the
        # whole jit wrapper set — the ~3.4s first-compile cost is paid
        # once per SHAPE, not once per query. Telemetry binds below
        # (the registry does not exist yet at this point in __init__).
        from ..control.aotcache import AOTExecutableCache

        self.aot_cache = AOTExecutableCache()
        # run-loop ownership stamp (RUNLOOP_OWNERSHIP_GUARD): thread id
        # of whoever drives run()/run_cycle(), stamped at the first
        # cycle; the control-path mutators assert against it when the
        # guard is on. A restored/rebuilt job re-stamps at its next
        # cycle, so supervisor restarts hand ownership over cleanly.
        # fst:ephemeral thread ids are process-local; the restored job's run loop re-stamps at its first cycle
        self._runloop_thread: Optional[int] = None
        # admission at APPLY time (docs/control_plane.md): the tenant
        # resource envelope every control-path add/update is judged
        # against (analysis/admit.AdmissionBudgets). None = structural
        # (PLC) + cost-hook (ADM001/002) tiers only, no budget verdicts.
        self.admission_budgets = None
        # recent control-path refusals, keyed by plan id: rule ids +
        # rendered findings + tenant — what GET /api/v1/health and
        # metrics() surface so a refused add is observable without
        # log-diving. Bounded ring (oldest evicted past the cap).
        # GENUINELY multi-writer: the run loop records apply-time
        # refusals AND the REST service thread records boundary
        # refusals (_admit, source="service") — so unlike the rest of
        # Job state the ring is lock-guarded, not run-loop-owned
        # (fstrace FST201/FST202, docs/static_analysis.md).
        import threading

        self.control_rejections: Dict[str, dict] = {}
        self._rejections_lock = threading.Lock()
        self.MAX_REJECTIONS_KEPT = 64
        # -- per-tenant observability (docs/observability.md) -----------
        # plan id -> tenant (from the control event that admitted it;
        # absent = the "default" tenant). Scoped metric attribution and
        # the metrics()["tenants"] rollup key off it.
        # fst:threadsafe single-writer (run loop apply path); off-thread status/metrics readers use GIL-atomic get()/dict() snapshots only
        self._plan_tenant: Dict[str, str] = {}
        # plan id -> admission-predicted worst-case device bytes
        # (state + accumulator, analysis/admit.py ADM101/102): the
        # denominator of the footprint meter's utilization gauge. Set
        # from carried admission summaries, from apply-time analysis,
        # or explicitly via set_admitted_footprint() for static jobs.
        # fst:threadsafe single-writer (run loop / pre-run setup); the footprint meter reads GIL-atomic get()
        self._plan_admitted_bytes: Dict[str, int] = {}
        # fst:ephemeral warning rate-limit clock (monotonic); the footprint.overruns counters stay exact
        self._footprint_warned_at = -1e9
        # output rate limiting: stream_id -> limiter (from plan
        # ``output ... every ...`` clauses, applied at emission)
        self._rate_limiters: Dict[str, _OutputRateLimiter] = {}
        # persistent warm-start compile store (fleet/warmstore.py):
        # the disk tier under the AOT cache. None (default) leaves the
        # single-process path untouched; bind_warm_store() wraps every
        # cacheable bundle's executables in store-backed dispatchers.
        # Initialized BEFORE the plan loop below: add_plan ->
        # _create_runtime reads it for warm-store provenance.
        self.warm_store = None
        # fleet identity for /health + metrics (fleet block); None
        # outside a replica process
        # fst:ephemeral process identity: the successor replica is handed its own id/role by its spec, never by the checkpoint
        self._replica_info = None
        # commit-log epoch as of the last prepared checkpoint + the
        # last rolling-restart handoff record — both ride the
        # checkpoint's optional "fleet" block (runtime/checkpoint.py)
        # so a successor replica resumes the fleet account
        self._fleet_epoch = 0
        self._last_handoff = None
        for p in plans:
            self.add_plan(p)
        # output_stream -> list[(ts, row_tuple)] and field names
        self.collected: Dict[str, List[Tuple[int, Tuple]]] = {}
        self.output_fields: Dict[str, List[str]] = {}
        # fst:threadsafe single-writer (run loop emit path); metrics() reads a dict() snapshot
        self.emitted_counts: Dict[str, int] = {}  # total rows ever emitted
        self._sinks: Dict[str, List[Callable]] = {}
        self.processed_events = 0  # observability (reference logs per runtime)
        # drain the device accumulators at least every N cycles so a
        # long-running job can't overflow them (2 fetches per plan per drain)
        self.drain_every_cycles = 256
        # bound match-visibility latency: the STALENESS BUDGET of the
        # deadline drain scheduler — a plan's accumulated matches are
        # drained when the oldest reaches this age (dirty_since +
        # interval; see run_cycle). Each drain costs host<->device round
        # trips, so this knob trades p99 match latency against traffic
        # on the device->host link.
        # None disables scheduled drains (capacity swaps still happen).
        self.drain_interval_ms = 500.0
        # fst:ephemeral drain-cadence phase is monotonic-clock-relative; restore re-arms the interval
        self._last_full_drain = time.monotonic()
        # fst:ephemeral drain-cadence phase restarts at resume (accumulators are drained pre-snapshot)
        self._cycles_since_drain = 0
        # backpressure: cap dispatched-but-unfinished device cycles per
        # plan. Without it the host races ahead of the device and match
        # latency grows with the whole backlog; with it, latency is
        # bounded by ~max_inflight_cycles * device_cycle_time + drain
        # interval, and the device stays fed as long as it is >= 2.
        self.max_inflight_cycles = 6
        # how many micro-batches make a dispatch (_fused_k is the one
        # reader; None, 0 and 1: one): every batch leaves as an entry of
        # a segment, one lax.scan-of-K-tapes device call (the bounded
        # replay's segment shape, fed live). Tapes stage host-side while
        # it fills; at dispatch the stacked segment crosses H2D in ONE
        # async jax.device_put, issued while the PREVIOUS segment still
        # computes (the ticket window keeps >= 2 outstanding; fusion.*
        # counters and span stage.h2d_overlap prove the overlap). Drains
        # fire between segments; checkpoints dispatch the pending partial
        # segment first, so state capture lands on a segment boundary.
        self.fused_segment_len: Optional[int] = None
        # per-plan capacity-check cadence (recomputed as plans come and go)
        self._drain_hints: Dict[str, int] = {}
        # telemetry: stage-attributed wall clock + latency histograms +
        # counters, snapshotted by metrics()/REST readers. Each drain's
        # request->completion decomposition (wait_ready: request ->
        # count prefix computed on device; queue: ready -> fetch thread
        # picks it up; fetch: d2h transfers, fetch_meta the count-prefix
        # half; decode: host decode; emit_lag; total; staleness: age of
        # the oldest undrained match) lands in the drain.* histograms.
        # All records
        # happen at batch/drain boundaries on the host — never inside
        # the jitted device path. Set .enabled = False to reduce every
        # span/record to a no-op (the bench overhead A/B switch).
        self.telemetry = MetricsRegistry()
        self.aot_cache.bind_telemetry(self.telemetry)
        # flight recorder (telemetry/flightrec.py): the job's bounded
        # black-box journal — control applies, checkpoint save/restore,
        # shed/late/stall bursts (rate-collapsed), AOT-cache traffic,
        # XLA compiles. Follows the registry's enabled switch; its
        # seq + entries are part of the checkpoint (runtime/
        # checkpoint.py), so the journal survives restore exactly once.
        self.flightrec = FlightRecorder(registry=self.telemetry)
        self.aot_cache.bind_flightrec(self.flightrec)
        # permanent compile telemetry (telemetry/compile_events.py):
        # the register-once jax.monitoring listener plus this job's
        # attribution sink — per-plan-signature lowering counts +
        # durations in metrics()["compiles"], mirrored into the
        # registry (compile.lowerings / compile.lowering) and journal.
        # fst:ephemeral per-process compile accounting; a restored job pays (and records) its own compiles
        self._compile_sink = compile_events.CompileSink(
            self.telemetry, self.flightrec
        )
        compile_events.install()
        # per-event trace sampling: a deterministic 1-in-N sample of
        # events (abs_ts % sample_every == 0) is stamped at source pull
        # and completed when a row carrying that timestamp surfaces to
        # a collector/sink — trace.e2e is a TRUE per-event end-to-end
        # latency histogram (queue time, device backlog, drain interval
        # and host decode all included), not per-leg p99 arithmetic.
        # Set sample_every=0 to disable sampling independently of the
        # rest of the registry.
        self.tracer = TraceSampler(self.telemetry)
        # SLO watchdog (telemetry/slo.py): per-tenant objectives
        # evaluated at micro-batch epoch boundaries from the scoped
        # registries, violations journaled into the flight recorder.
        # Always constructed; without policies (job.slo.set_policy)
        # every evaluate() call returns immediately.
        # fst:ephemeral burn/violation tallies; the durable account is the checkpointed journal
        self.slo = SLOWatchdog(self)
        # graceful degradation: bound the host reorder/pending backlog.
        # None = unbounded (historical behavior). With a bound, an
        # overload degrades by POLICY instead of OOMing the host:
        #   'block'       — stop pulling sources while over the bound
        #                   (backpressure: the backlog stays in the
        #                   broker / OS socket buffers / file, where it
        #                   belongs; pulls resume as the watermark
        #                   releases events to the device);
        #   'drop_oldest' — shed the oldest pending batches, loudly
        #                   (faults.shed_events counter + .shed_events
        #                   + a rate-limited warning). Oldest-first
        #                   because under watermark gating the oldest
        #                   rows are the ones a 'block' stall would
        #                   starve on anyway; shedding them lets the
        #                   stream keep moving at the cost of missed
        #                   (counted) matches.
        self.max_pending_events: Optional[int] = None
        self.shed_policy: str = "block"  # 'block' | 'drop_oldest'
        self.shed_events = 0  # total events ever shed (also a counter)
        # fst:ephemeral warning rate-limit clock (monotonic); counters stay exact
        self._shed_warned_at = -1e9  # monotonic ts of the last warning
        # -- event-time robustness (docs/event_time.md) -----------------
        # LATE-EVENT POLICY at the watermark gate: a row whose event
        # time is <= the horizon the gate has already released past
        # cannot merge in order anymore (the window/pattern state it
        # belongs to has advanced). Policies:
        #   'drop'        — discard, counted (faults.late_dropped);
        #   'side_output' — route the FULL input row to the dedicated
        #                   late channel '<stream>@late' (attach sinks
        #                   with add_sink(late_stream(sid), ...);
        #                   ColumnarSink-capable), counted;
        #   'allow'       — the gate holds its released horizon back by
        #                   allowed_lateness_ms, so rows late by at
        #                   most the allowance still release IN ORDER;
        #                   rows beyond the allowance are dropped with
        #                   a loud warning — admitting them would need
        #                   window re-fire (retract + re-emit panes per
        #                   the Dataflow model's accumulation modes,
        #                   PAPERS.md #5), which this engine documents
        #                   as a rejection, not a silent wrong answer.
        self.late_policy: str = "drop"
        self.allowed_lateness_ms: int = 0
        self.late_events = 0  # rows classified late (all policies)
        self.late_dropped = 0  # subset discarded ('drop'/'allow'-beyond)
        # fst:ephemeral warning rate-limit clock (monotonic); late counters ARE checkpointed
        self._late_warned_at = -1e9
        # the horizon (event-time ms) the gate has released through —
        # rows at or below it are late by definition
        self._released_wm: int = MIN_WM
        # monotone effective gate watermark: min-across-sources can
        # REGRESS when an idle source un-idles with an older claim; the
        # gate never moves backwards (the un-idled source's old rows
        # are late, handled by policy — Flink's idleness semantics)
        self._gate_wm: int = MIN_WM
        # IDLE-SOURCE HANDLING: a source that produces nothing for
        # idle_timeout_ms is marked temporarily idle and stops pinning
        # the min watermark (one silent topic must not stall every
        # stream); it un-idles on its next event. 0 marks a source idle
        # on its first empty poll (deterministic for tests); None
        # disables (historical behavior: an idle source pins forever).
        self.idle_timeout_ms: Optional[float] = None
        self._source_idle: List[bool] = [False] * len(self._sources)
        # monotonic time of each source's last produced event (None =
        # nothing yet; armed at the first poll so a never-producing
        # source can still go idle)
        # fst:ephemeral monotonic idle clocks re-arm at resume; the idle FLAGS are checkpointed
        self._source_last_t: List[Optional[float]] = (
            [None] * len(self._sources)
        )
        # max event time ever pulled: watermark.lag = max_ts - gate wm
        self._max_event_ts: Optional[int] = None
        # gate residency: per stream, (arrival monotonic, batch max
        # ts) per pending batch. Per-batch granularity is what keeps
        # the metric honest under partial releases — e.g. the 'allow'
        # policy holds every row back by the allowance, and a single
        # per-stream clock re-armed each cycle would report
        # milliseconds of residency while rows actually wait seconds
        self._pending_t: Dict[str, List[Tuple[float, int]]] = {}
        # latency legs: the earliest arrival among the source batches
        # behind each stream's newest released batch, and the ordinals
        # that the spans of one segment and of one drain share
        # fst:ephemeral per-cycle monotonic stamps of the gate's last release; a restored job releases anew
        self._ready_arrival: Dict[str, float] = {}
        # fst:ephemeral span ordinals of this process's profiler traces and leg records
        self._seg_ordinal = 0
        # fst:ephemeral span ordinals of this process's profiler traces and leg records
        self._drain_ordinal = 0
        # fault visibility: sources that can report state/transport
        # faults (KafkaSource retry counters, _DecodedLinesSource
        # degraded positions) mirror them into this job's registry
        for src in self._sources:
            bind = getattr(src, "bind_telemetry", None)
            if bind is not None:
                bind(self.telemetry)


    # -- run-loop ownership guard (the FST201 invariant, executed) ----------
    def _stamp_runloop_owner(self) -> None:
        import threading

        if self._runloop_thread is None:
            self._runloop_thread = threading.get_ident()

    def _assert_runloop_owner(self, what: str) -> None:
        """Debug-mode ownership assert at a state-mutating entry point:
        no-op unless RUNLOOP_OWNERSHIP_GUARD is on AND a run loop has
        stamped ownership (pre-run setup from the constructing thread
        is always legitimate)."""
        if not RUNLOOP_OWNERSHIP_GUARD or self._runloop_thread is None:
            return
        import threading

        me = threading.get_ident()
        if me != self._runloop_thread:
            raise OwnershipViolation(
                f"{what} executed on thread {me}, but the run loop "
                f"(thread {self._runloop_thread}) owns Job state — "
                "state mutates only via control events applied at "
                "micro-batch boundaries (push a control event instead "
                "of mutating directly; docs/control_plane.md)"
            )

    # -- plan management (dynamic control plane hooks) ----------------------
    # Parity: AbstractSiddhiOperator.onEventReceived (:399-467) — add/update/
    # remove QueryRuntimeHandlers, enable/disable gating — applied here at
    # micro-batch boundaries.
    def add_plan(
        self,
        plan: CompiledPlan,
        dynamic: bool = False,
        cql: Optional[str] = None,
    ) -> None:
        """``dynamic=True`` (the control-plane add path): template-able
        chain plans fold into / become padded dynamic groups so repeat
        adds are data updates. Static plans keep the single-query fast
        path (pallas chain core, no query axis). Pass ``cql`` so the add
        is checkpointable (snapshot replays dynamic queries from their
        CQL; the control-event path records it automatically)."""
        self._assert_runloop_owner("add_plan")
        admit0 = None
        # tenant attribution: the control path records the event's
        # tenant before calling add_plan, so admits/stack-joins/cache
        # traffic count into that tenant's scope too
        tenant = self.tenant_of(plan.plan_id) if dynamic else None
        if dynamic:
            if plan.plan_id in self._folded or plan.plan_id in self._plans:
                # re-add of a live id (e.g. an at-least-once control
                # channel redelivering): replace, never double-register
                self.remove_plan(plan.plan_id)
            if cql is not None:
                self._dynamic_cql[plan.plan_id] = cql
            if self._try_fold(plan):
                # data update into an existing group slot — the cheapest
                # admit: no runtime, no compile, no cache traffic
                self._inc_control("control.admitted")
                self._inc_control("control.stack_join")
                self._inc_tenant(tenant, "control.admitted")
                self._inc_tenant(tenant, "control.stack_join")
                self._frec(
                    "control.admit", plan=plan.plan_id, tenant=tenant,
                    stack_join=True,
                )
                return
            if self.share_subplans and self._try_share(plan, tenant):
                # shared-prefix admit: the prefix predicate already
                # runs as a live producer host (or was just compiled
                # once for this admit) and the tenant rode in as a
                # chained consumer suffix — counters + journal were
                # recorded by _try_share's inner dynamic add
                return
            self._frec(
                "control.admit", plan=plan.plan_id, tenant=tenant,
                stack_join=False,
            )
            plan, admit0 = self._wrap_dynamic(plan)
            self._inc_control("control.admitted")
            self._inc_tenant(tenant, "control.admitted")
        self._create_runtime(
            plan, admit0, cacheable=dynamic, tenant=tenant
        )

    def _frec(self, kind: str, **kw) -> None:
        """Flight-recorder append, safe during __init__ (the recorder
        is created after the static add_plan loop) — same shape as
        :meth:`_inc_control` below."""
        fr = getattr(self, "flightrec", None)
        if fr is not None:
            fr.record(kind, **kw)

    def _inc_control(self, name: str, n: int = 1) -> None:
        """Control-plane counters, safe during __init__ (the registry
        is created after the static add_plan loop)."""
        tel = getattr(self, "telemetry", None)
        if tel is not None:
            tel.inc(name, n)

    # -- per-tenant / per-plan scoped attribution ---------------------------
    def tenant_of(self, plan_id: str) -> str:
        """The tenant a plan is attributed to ('default' when it was
        admitted without one — static plans, untenanted control adds)."""
        return self._plan_tenant.get(plan_id) or "default"

    def _inc_tenant(self, tenant: Optional[str], name: str,
                    n: int = 1) -> None:
        """Tenant-scoped counter twin of _inc_control (safe pre-registry
        for the same __init__ reason)."""
        tel = getattr(self, "telemetry", None)
        if tel is not None and tel.enabled:
            tel.scope("tenant", tenant or "default").inc(name, n)

    def _stamp_attribution(self, plan: CompiledPlan) -> None:
        """Stamp every output schema of ``plan`` with the plan id its
        rows attribute to. Emission-path attribution reads the stamp
        (``_attr_scope``), so per-plan row counts are exact even when
        many plans insert into the SAME output stream: a dynamic chain
        group's per-slot decode carries each MEMBER's own schema
        object, stamped with the member's id below."""
        for schemas in plan.output_streams().values():
            for sch in schemas:
                sch.plan_attr = plan.plan_id
        from ..compiler.nfa import DynamicChainGroup

        for a in plan.artifacts:
            if isinstance(a, DynamicChainGroup):
                for m in a.members:
                    if m is not None:
                        m[1].plan_attr = m[0]

    def _attr_scope(self, schema):
        """The plan scope a schema's rows attribute to (None for
        unstamped schemas — e.g. hand-built test artifacts)."""
        pid = getattr(schema, "plan_attr", None)
        if pid is None:
            return None
        return self.telemetry.scope("plan", pid)

    def _scope_plans_of(self, rt: _PlanRuntime) -> List[str]:
        """USER plan ids a runtime serves: itself for a standalone
        plan, every live member for a dynamic-group host. Shared drain
        legs (total/staleness) record into EACH member's scope — every
        member's matches waited through that drain, so per-plan drain
        latency is each member's truth, while tenant rollups merging
        them see the shared drain once per member (documented)."""
        pid = rt.plan.plan_id
        if pid.startswith("@shr:"):
            # shared-prefix host: every member's matches waited through
            # its drain — same per-member truth as dyn-group hosts
            for e in self._shared.values():
                if e["host_id"] == pid:
                    return list(e["members"]) or [pid]
            return [pid]
        if not pid.startswith("@dyn:"):
            return [pid]
        from ..compiler.nfa import DynamicChainGroup

        arts = rt.plan.artifacts
        if arts and isinstance(arts[0], DynamicChainGroup):
            return [m[0] for m in arts[0].members if m is not None]
        return [pid]

    def _scoped_drain_record(
        self, rt: _PlanRuntime, total_s: float,
        staleness_s: Optional[float],
    ) -> None:
        tel = self.telemetry
        if not tel.enabled:
            return
        for pid in self._scope_plans_of(rt):
            sc = tel.scope("plan", pid)
            sc.record_seconds("drain.total", total_s)
            if staleness_s is not None:
                sc.record_seconds("drain.staleness", staleness_s)

    # -- admitted-vs-measured footprint meter -------------------------------
    def set_admitted_footprint(self, plan_id: str, nbytes: int) -> None:
        """Record the admission-predicted worst-case device bytes
        (state + accumulator) for a plan — the meter denominator. The
        control path records this automatically from admission
        summaries; static jobs (and tests) set it explicitly from
        ``analysis.admit.analyze_plan(plan, deep=True)``."""
        self._plan_admitted_bytes[plan_id] = int(nbytes)

    @staticmethod
    def _tree_live_nbytes(tree) -> int:
        """Sum of leaf nbytes — shape/dtype METADATA only, no host
        sync, no transfer (jax.Array.nbytes reads the aval), so the
        meter is legal inside the guarded hot loop (FST102 /
        HOTLOOP_TRANSFER_GUARD)."""
        total = 0
        for leaf in jax.tree.leaves(tree):
            nb = getattr(leaf, "nbytes", None)
            if nb is not None:
                total += int(nb)
        return total

    def _update_footprint(self, rt: _PlanRuntime) -> None:
        """Measure the runtime's LIVE device bytes (states + output
        accumulator) against the admission-time prediction. Polled at
        drain/checkpoint boundaries only — never per batch. Publishes
        ``footprint.measured_bytes`` (always), and for runtimes with a
        recorded admission prediction ``footprint.admitted_bytes``, a
        ``footprint.utilization`` gauge, and the loud
        ``footprint.overruns`` counter when measured exceeds admitted —
        a live soundness monitor on the admission analyzer. Dynamic
        group HOSTS publish measured bytes only: member predictions
        price a standalone query, while the padded group's device
        reality is capacity-sized shared state (docs/observability.md).
        """
        tel = self.telemetry
        if not tel.enabled:
            return
        measured = self._tree_live_nbytes(rt.states)
        if rt.acc is not None:
            measured += self._tree_live_nbytes(rt.acc)
        pid = rt.plan.plan_id
        sc = tel.scope("plan", pid)
        sc.gauge("footprint.measured_bytes", int(measured))
        admitted = self._plan_admitted_bytes.get(pid)
        if admitted is None or admitted <= 0:
            return
        sc.gauge("footprint.admitted_bytes", int(admitted))
        sc.gauge(
            "footprint.utilization", round(measured / admitted, 6)
        )
        if measured > admitted:
            tel.inc("footprint.overruns")
            sc.inc("footprint.overruns")
            now = time.monotonic()
            if now - self._footprint_warned_at >= 1.0:
                self._footprint_warned_at = now
                _LOG.warning(
                    "%s: measured device footprint %d B exceeds the "
                    "admitted worst-case %d B — the admission "
                    "prediction was unsound for this plan, or its "
                    "state grew past the admission-time shapes "
                    "(footprint.overruns counts every over-budget "
                    "poll; docs/observability.md has what this does "
                    "and does not mean)",
                    pid, measured, admitted,
                )

    def footprint_status(self) -> Dict[str, Dict[str, object]]:
        """Last-polled footprint per runtime ({plan_id: {measured,
        admitted?, utilization?}}); reads scope gauges only, safe
        off-thread."""
        out: Dict[str, Dict[str, object]] = {}
        for pid, reg in self.telemetry.scope_map("plan").items():
            measured = reg.gauge_value("footprint.measured_bytes")
            if measured is None:
                continue
            ent: Dict[str, object] = {"measured_bytes": int(measured)}
            admitted = reg.gauge_value("footprint.admitted_bytes")
            if admitted is not None:
                ent["admitted_bytes"] = int(admitted)
                ent["utilization"] = reg.gauge_value(
                    "footprint.utilization"
                )
            out[pid] = ent
        return out

    # -- serving fleet (fleet/warmstore.py, docs/fleet.md) ------------------
    def bind_warm_store(self, store) -> None:
        """Attach the persistent warm-start compile store. Must happen
        before plans are created/restored — _create_runtime consults it
        — so a replica factory binds it right after constructing the
        job. Telemetry/flight-recorder wiring rides the job's own."""
        self.warm_store = store
        if store is not None:
            store.bind_telemetry(self.telemetry)
            store.bind_flightrec(self.flightrec)

    def set_replica_info(
        self, replica_id: str, role: str = "replica", boot=None,
    ):
        """``boot`` is a live dict the replica process owns (bootstrap
        timings: restore_s, warm-start counters, first_row_s) — kept by
        reference so later updates surface in /health."""
        self._replica_info = {
            "id": str(replica_id), "role": str(role),
        }
        if boot is not None:
            self._replica_info["boot"] = boot

    def record_handoff(self, **data) -> None:
        """Journal a rolling-restart handoff (discrete flight-recorder
        kind) and pin it in the fleet status/checkpoint block."""
        info = self._replica_info or {}
        self._last_handoff = {"replica": info.get("id"), **data}
        self._frec("fleet.handoff", **self._last_handoff)

    # fst:runloop-only (walks live runtimes; checkpoint-boundary cadence)
    def persist_warm(self) -> Dict[str, object]:
        """Serialize every live cacheable plan's executables into the
        warm store (no-op without one). Called by the replica
        supervisor at checkpoint boundaries — off the hot path, outside
        any compile-attribution scope — so the store is caught up
        whenever a successor might boot from it."""
        store = self.warm_store
        if store is None:
            return {}
        for pid, rt in list(self._plans.items()):
            key = getattr(rt, "warm_key", None)
            entry = getattr(rt, "warm_entry", None)
            if key is None or entry is None:
                continue
            store.persist_entry(
                key, entry, acc_example=rt.acc,
                plan_id=pid, tenant=self.tenant_of(pid),
            )
        return store.stats()

    def fleet_status(self) -> Optional[Dict[str, object]]:
        """The /health + metrics ``fleet`` block: replica identity,
        warm-store counters, commit-log epoch, last handoff. None when
        the job is not part of a fleet (no store, no replica id) so
        single-process payloads stay unchanged."""
        if self.warm_store is None and self._replica_info is None:
            return None
        info = self._replica_info or {}
        out: Dict[str, object] = {
            "replica": info.get("id"),
            "role": info.get("role"),
            "warm_store": (
                self.warm_store.stats()
                if self.warm_store is not None else None
            ),
            "epoch": int(self._fleet_epoch),
            "last_handoff": self._last_handoff,
        }
        boot = info.get("boot")
        if boot:
            out["boot"] = dict(boot)
        return out

    def _create_runtime(
        self, plan: CompiledPlan, admit0=None, cacheable: bool = False,
        tenant: Optional[str] = None,
    ) -> None:
        from ..compiler import pallas_ops
        from ..control.aotcache import (
            CachedExecutables,
            cache_key,
            sig_label as _sig_label,
        )

        pallas_ops.warmup()  # probe TPU kernels outside any trace
        # the AOT executable cache (dynamic adds only — a static plan
        # is constructed once per job and pays signature hashing for
        # nothing): a hit reuses the whole jit wrapper set, so every
        # XLA executable already compiled for this shape class serves
        # the new plan with zero lowering (control/aotcache.py has the
        # soundness contract — dynamic-group hosts share by signature,
        # everything else only on exact source text)
        key = cache_key(plan, capacity=self.batch_size) if cacheable \
            else None
        entry = self.aot_cache.lookup(key) if cacheable else None
        # compile-attribution label (telemetry/compile_events.py): the
        # shape-class signature where the cache already computed it
        # (minted by aotcache.sig_label so it string-matches the
        # aotcache.* journal events); plan id for static plans, which
        # deliberately skip signature hashing (see the cache comment
        # above)
        sig_label = _sig_label(key) or f"plan:{plan.plan_id}"
        if cacheable:
            # tenant attribution on the AOT cache: a noisy tenant's
            # compile churn shows in ITS scope, not only job-wide
            self._inc_tenant(
                tenant,
                "control.cache_hit" if entry is not None
                else "control.cache_miss",
            )
        if entry is None:
            init_acc = jax.jit(plan.init_acc)
            traces = {"n": 0}

            # fst:hotpath
            def seg_scan(states, acc, seg):
                # ONE device call advances K stacked micro-batches — the
                # scan body the bounded replay proves row-identical
                # (runtime/replay.py), fed from live tapes
                traces["n"] += 1  # python body runs only while TRACING

                def body(carry, wire):
                    s, a = plan.step_acc(
                        carry[0], carry[1], wire.expand()
                    )
                    return (s, a), None

                (states, acc), _ = jax.lax.scan(
                    body, (states, acc), seg
                )
                return states, acc

            entry = CachedExecutables(
                # states + accumulator are donated: the scan's carry and
                # its only outputs, so XLA updates both (the output buffer
                # may be 100s of MB) in place across the whole segment
                jitted_seg=jax.jit(seg_scan, donate_argnums=(0, 1)),
                jitted_init_acc=init_acc,
                jitted_flush=jax.jit(plan.flush),
                traces=traces,
                first_plan_id=plan.plan_id,
            )
            if cacheable:
                self.aot_cache.insert(key, entry)
        if cacheable and key is not None and self.warm_store is not None:
            # the disk tier (fleet/warmstore.py): wrap the bundle's jit
            # wrappers in store-backed dispatchers and preload every
            # executable already serialized for this key — a replica
            # bootstrap reaches all-live with zero new lowerings.
            # Idempotent on the in-memory-hit path (already wrapped).
            entry = self.warm_store.wrap_entry(
                key, entry,
                plan_id=plan.plan_id,
                tenant=tenant or self.tenant_of(plan.plan_id),
            )
        rt = _PlanRuntime(
            plan=plan,
            states=plan.init_state(),
            jitted_seg=entry.jitted_seg,
            jitted_init_acc=entry.jitted_init_acc,
            jitted_flush=entry.jitted_flush,
            acc=entry.jitted_init_acc(),
            wire_kinds={},
        )
        rt.traces = entry.traces
        rt.sig_label = sig_label
        # drain pack programs ride the cache entry too: a cache-hit
        # admit's first drain must not pay a pack recompile
        rt.pack_jits = entry.pack_jits
        # warm-store provenance: persist_warm() walks these to
        # serialize this runtime's executables at checkpoint boundaries
        rt.warm_key = key if self.warm_store is not None else None
        rt.warm_entry = entry if self.warm_store is not None else None
        if admit0 is not None:
            rt.states = admit0(rt.states)
        lazy_keys = {
            key
            for a in plan.artifacts
            for key in getattr(a, "lazy_src_keys", ())
        }
        rt.lazy_keys = lazy_keys
        # compact lazy blocks drop the device ts row; the ring then also
        # retains rebased timestamps under the synthetic "@ts" key
        rt.lazy_ts = any(
            getattr(a, "ring_needs_ts", False) for a in plan.artifacts
        )
        rt.lazy = (
            _LazyRing(plan.config.lazy_ring_budget_bytes)
            if lazy_keys
            else None
        )
        # None = sync from the device 'seen' counter at the first step
        # (a restored checkpoint resumes mid-ordinal-space)
        rt.lazy_base = None
        rt.lazy_state_name = next(
            (
                a.name
                for a in plan.artifacts
                if getattr(a, "lazy_src_keys", ())
            ),
            None,
        )
        self._plans[plan.plan_id] = rt
        # after admit0: a dynamic host's first member is registered by
        # now, so its schema gets the MEMBER id stamp
        self._stamp_attribution(plan)
        for sid, rate in plan.output_rates.items():
            self._rate_limiters[sid] = _OutputRateLimiter(
                rate, plan.snapshot_keys.get(sid, ())
            )

    # -- dynamic chain groups (recompile-free runtime adds) -----------------
    def _group_string_tables(self, plan, tpl) -> Dict:
        out = {}
        for keys in tpl.filter_keys:
            for key in keys:  # per-element conjunct keys
                sid, fname = key.split(".", 1)
                out[key] = plan.schemas[sid].string_tables.get(fname)
        return out

    def _fold_into(
        self, host_id: str, plan: CompiledPlan, slot: int, t
    ) -> None:
        rt = self._plans[host_id]
        # tapes staged before this add must step WITHOUT the new
        # member (same boundary contract as set_plan_enabled)
        self._dispatch_segment(rt)
        group = rt.plan.artifacts[0]
        tpl, params, within = t
        states = dict(rt.states)
        states[group.name] = group.admit(
            states[group.name], slot, plan.plan_id,
            plan.artifacts[0].output_schema, params, within,
            self._group_string_tables(rt.plan, tpl),
        )
        rt.states = states
        self._folded[plan.plan_id] = (host_id, slot)
        self._folded_enabled[plan.plan_id] = True
        # the member's schema object is what the group's per-slot
        # decode will carry: stamp it with the MEMBER id so its rows
        # attribute exactly even though every member shares one stream
        plan.artifacts[0].output_schema.plan_attr = plan.plan_id

    def _try_fold(self, plan: CompiledPlan) -> bool:
        from ..compiler.nfa import DynamicChainGroup, chain_template_of

        if len(plan.artifacts) != 1:
            return False
        t = chain_template_of(plan.artifacts[0], plan.spec.column_types)
        if t is None:
            return False
        tpl = t[0]
        for host_id, rt in self._plans.items():
            arts = rt.plan.artifacts
            if not (
                len(arts) == 1
                and isinstance(arts[0], DynamicChainGroup)
                and arts[0].template == tpl
            ):
                continue
            slot = arts[0].free_slot()
            if slot is None:
                continue
            self._fold_into(host_id, plan, slot, t)
            return True
        return False

    def _wrap_dynamic(
        self, plan: CompiledPlan, host_id: Optional[str] = None,
        slot: int = 0,
    ):
        """Single template-able chain plans become a padded dynamic group
        (so the NEXT structurally-identical add is a data update)."""
        import dataclasses

        from ..compiler.nfa import DynamicChainGroup, chain_template_of

        if len(plan.artifacts) != 1:
            return plan, None
        t = chain_template_of(plan.artifacts[0], plan.spec.column_types)
        if t is None:
            return plan, None
        tpl, params, within = t
        art = plan.artifacts[0]
        host_id = host_id or f"@dyn:{plan.plan_id}"
        if host_id in self._plans:  # paranoid: id collision
            return plan, None
        group = DynamicChainGroup(
            name=art.name,
            template=tpl,
            stream_code_of=tuple(
                plan.spec.stream_codes[sid] for sid in tpl.stream_ids
            ),
            column_types=dict(plan.spec.column_types),
            members=[None] * plan.config.dyn_query_slots,
            pool=art.pool,
            capacity=plan.config.dyn_query_slots,
        )
        new_plan = dataclasses.replace(
            plan, plan_id=host_id, artifacts=[group]
        )
        tables = self._group_string_tables(plan, tpl)

        def admit0(states):
            states = dict(states)
            states[group.name] = group.admit(
                states[group.name], slot, plan.plan_id,
                art.output_schema, params, within, tables,
            )
            return states

        self._folded[plan.plan_id] = (host_id, slot)
        self._folded_enabled[plan.plan_id] = True
        return new_plan, admit0

    # -- cross-tenant shared subplans (analysis/share.py) -------------------
    def _try_share(self, plan: CompiledPlan, tenant) -> bool:
        """Subplan-share ladder rung (below stack-join, above the AOT
        cache): split a shareable filter prefix off the candidate,
        attach the tenant's residue as a consumer suffix, and run the
        prefix ONCE as a producer host shared by every tenant whose
        predicate is exactly equal (analysis/share.py has the two key
        spaces). Both halves are re-parsed + verified before any state
        mutates; any failure returns False and the admit falls through
        to the unshared rungs — never to a wrong program."""
        from ..analysis import share as shr
        from ..analysis.plancheck import verify_plan

        if self._plan_compiler is None:
            return False
        src = plan.source_ast
        if (
            len(src.queries) != 1
            or src.stream_defs
            or src.table_defs
            or plan.chained
        ):
            return False
        sp = shr.split_shared_prefix(src.queries[0])
        if sp is None:
            return False
        src_schema = plan.schemas.get(sp.stream_id)
        if src_schema is None:
            return False
        key = sp.key()
        mid = shr.mid_stream_of(key)
        host_id = shr.host_id_of(key)
        entry = self._shared.get(key)
        pid = plan.plan_id
        try:
            s_cql = shr.suffix_cql(
                src.queries[0], sp, mid, src_schema
            )
            suffix_plan = self._plan_compiler(s_cql, pid)
            if verify_plan(
                suffix_plan, trace=False, raise_on_error=False
            ):
                return False
            host_plan = None
            if entry is None:
                p_cql = shr.prefix_cql(sp, mid)
                host_plan = self._plan_compiler(p_cql, host_id)
                if verify_plan(
                    host_plan, trace=False, raise_on_error=False
                ):
                    return False
        except Exception:  # noqa: BLE001 — renderer/compiler fell over:
            # this predicate is outside the faithful subset; the admit
            # simply proceeds unshared (fail closed, never wrong)
            return False
        if entry is None:
            # the producer host is an ordinary cacheable runtime: its
            # executables land in the AOT cache and the warm store, so
            # a drop/re-form (or a replica bootstrap) pays no lowering
            self._create_runtime(host_plan, None, cacheable=True)
            entry = {
                "host_id": host_id,
                "mid": mid,
                "prefix_cql": p_cql,
                "src": sp.stream_id,
                # loopback encode schema: the prefix is `select *`, so
                # mid rows carry the SOURCE stream's fields in source
                # order — encode them with the source StreamSchema
                # (shared env string dictionary, codes comparable with
                # every suffix's DDL schema). Runtime-only; restore
                # re-derives it from the host plan.
                "mid_schema": src_schema,
                "members": [],
            }
            self._shared[key] = entry
            self._loopback[mid] = key
        if entry["members"]:
            host_rt = self._plans.get(entry["host_id"])
            if host_rt is not None:
                # flush the live host's pending loopback rows to the
                # EXISTING members before this one attaches: host
                # drains are deferred, and a late joiner must never
                # receive mid rows produced before its admit (the
                # unshared oracle's suffix would not have seen them)
                self._drain_plan(host_rt)
        entry["members"].append(pid)
        self._shared_member[pid] = key
        # checkpoint replay re-admits the SUFFIX verbatim (the host is
        # re-formed from the "shared" block first) — _apply_control's
        # setdefault leaves this in place
        self._dynamic_cql[pid] = s_cql
        self._inc_control("control.subplan_share")
        self._inc_tenant(tenant, "control.subplan_share")
        self._frec(
            "control.subplan_share", plan=pid, tenant=tenant,
            host=host_id, mid=mid, key=key,
            members=len(entry["members"]),
        )
        # the suffix rides the rest of the ladder itself: structurally-
        # equal suffixes stack-join into one dynamic group, so per-host
        # lowerings stay sub-linear in tenants; recursion is safe —
        # split_shared_prefix refuses _shr_ readers
        self.add_plan(suffix_plan, dynamic=True)
        rt = self._plans.get(pid)
        if rt is not None:
            # pre-size the suffix tape to the flush chunk bound
            # (_flush_loopback chunks at batch_size): the first trace
            # happens at the terminal bucket, so a large barrier flush
            # never regrows capacity and re-lowers mid-drain
            rt.tape_capacity = max(
                rt.tape_capacity, bucket_size(self.batch_size)
            )
        return True

    def _feed_loopback(self, schema, rows) -> None:
        """Host-side fan-out of a shared prefix's mid-stream rows into
        every consumer suffix: re-encode the decoded drain rows as an
        EventBatch (the mid DDL schema shares the environment string
        dictionary, so codes stay comparable) and step each enabled
        suffix runtime directly — no reorder buffer, no source path.
        Reached from _emit_rows BEFORE counters/traces/sinks: mid rows
        are plumbing, not output."""
        mid = schema.stream_id
        if mid not in self._loopback:
            return
        epoch = self._epoch_ms or 0
        pend = self._loopback_buf.get(mid)
        if pend is None:
            # third slot: wall age of the OLDEST buffered row — the
            # freshness bound for jobs that never take blocking drains
            pend = self._loopback_buf[mid] = ([], [], time.monotonic())
        pend[0].extend(epoch + rel_ts for rel_ts, _ in rows)
        pend[1].extend(row for _, row in rows)

    def _flush_loopback(self, force: bool = False) -> None:
        """Step consumer suffixes with their mid streams' coalesced
        pending rows. Two regimes:

        * **threshold** (``force=False``, the steady-state drain
          polls): a mid flushes only once it has buffered a full
          ``batch_size`` of rows — the suffix dispatch rate scales
          with the prefix's MATCH volume, not the host's tape volume,
          which is the entire economics of sharing (a per-drain flush
          was measured 7x SLOWER than unshared: per-dispatch fixed
          cost on fragmented mid batches swamped the saved scans)
        * **barrier** (``force=True``, every ``block=True`` drain:
          results/snapshot/retire/attach): flush everything — rows the
          host already produced must be visible to member suffixes
          before state is read, a member retires, or a late joiner
          attaches

        A supervised/serving job drains on interval deadlines and
        never blocks, so the threshold alone would let a trickle mid
        sit unboundedly; an AGE bound (one drain interval since the
        oldest buffered row) caps the added visibility latency at
        ~one extra interval without giving up coalescing under load.

        Flushes chunk to ``batch_size`` so the suffix tape capacity
        (and therefore its lowering bucket) stabilizes at the same
        bound the source path uses."""
        if not self._loopback_buf:
            return
        limit = max(
            1,
            int(self.batch_size) if self.batch_size is not None else 1,
        )
        age_s = (self.drain_interval_ms or 0.0) / 1e3
        now = time.monotonic()
        ready = [
            mid
            for mid, (_, rows, t0) in list(self._loopback_buf.items())
            if force
            or len(rows) >= limit
            or (age_s and now - t0 >= age_s)
        ]
        for mid in ready:
            pending = self._loopback_buf.pop(mid, None)
            if pending is None:
                continue  # a nested barrier flush beat us to it
            entry = self._shared.get(self._loopback.get(mid, ""))
            if entry is None or not pending[1]:
                continue
            # time-order once across the whole accumulation (stable:
            # equal timestamps keep emission order), then chunk
            pairs = sorted(
                zip(pending[0], pending[1]), key=lambda p: p[0]
            )
            consumers = [
                rt for rt in list(self._plans.values())
                if rt.enabled and mid in rt.plan.spec.stream_codes
            ]
            # mid rows arrived when the oldest of them was buffered
            self._ready_arrival[mid] = pending[2]
            for i in range(0, len(pairs), limit):
                part = pairs[i:i + limit]
                batch = EventBatch.from_records(
                    mid, entry["mid_schema"],
                    [row for _, row in part],
                    timestamps=[t for t, _ in part],
                )
                for rt in consumers:
                    self._step_plan(rt, [batch])

    def _replay_shared(self, shared: Dict[str, Dict]) -> None:
        """Checkpoint-restore replay of the share table: re-form every
        producer host from its recorded prefix CQL (cacheable — the
        warm store serves the lowerings) and rebuild the loopback
        routing BEFORE _replay_dynamic re-admits the member suffixes,
        so hosts precede their consumers in runtime insertion order
        (the drain-ordering invariant the loopback relies on)."""
        for key, info in sorted(shared.items()):
            members = [str(m) for m in info.get("members", ())]
            if not members:
                continue
            host_id = str(info["host_id"])
            try:
                host_plan = self._plan_compiler(
                    str(info["prefix_cql"]), host_id
                )
            except Exception:  # noqa: BLE001
                _LOG.warning(
                    "shared host %r could not be re-formed from its "
                    "prefix CQL; its members restore unshared-broken "
                    "(no producer) — retire and re-admit them", host_id,
                )
                continue
            self._create_runtime(host_plan, None, cacheable=True)
            mid = str(info["mid"])
            src = str(info["src"])
            self._shared[key] = {
                "host_id": host_id,
                "mid": mid,
                "prefix_cql": str(info["prefix_cql"]),
                "src": src,
                "mid_schema": host_plan.schemas[src],
                "members": members,
            }
            self._loopback[mid] = key
            for pid in members:
                self._shared_member[pid] = key

    def _replay_dynamic(
        self,
        dynamic_cql: Dict[str, str],
        folded: Dict[str, Tuple[str, int]],
        enabled: Dict[str, bool],
    ) -> None:
        """Checkpoint-restore replay: re-add dynamically-added queries so
        runtimes, groups, and SLOT assignments match the snapshot exactly
        (state restore then overlays params and partial-match pools)."""
        by_host: Dict[str, List[Tuple[int, str]]] = {}
        for pid, (host_id, slot) in folded.items():
            by_host.setdefault(host_id, []).append((slot, pid))
        for host_id, members in sorted(by_host.items()):
            members.sort()
            first = True
            for slot, pid in members:
                cql = dynamic_cql.get(pid)
                if cql is None:
                    _LOG.warning(
                        "dynamic plan %r has no recorded CQL; it cannot "
                        "be restored", pid,
                    )
                    continue
                plan = self._plan_compiler(cql, pid)
                if first:
                    wrapped, admit0 = self._wrap_dynamic(
                        plan, host_id=host_id, slot=slot
                    )
                    self._create_runtime(
                        wrapped, admit0,
                        cacheable=wrapped.plan_id == host_id,
                    )
                    if wrapped.plan_id != host_id:
                        # wrap fell through (template underivable / id
                        # collision): the host runtime does not exist, so
                        # the remaining members cannot fold into it —
                        # restore them as standalone runtimes instead of
                        # letting _fold_into abort the whole replay
                        _LOG.warning(
                            "dynamic group %r could not be re-formed; "
                            "restoring its members as standalone plans",
                            host_id,
                        )
                        self._folded.pop(pid, None)
                        self._folded_enabled.pop(pid, None)
                        for s2, p2 in members:
                            if s2 <= slot or p2 not in dynamic_cql:
                                continue
                            self.add_plan(
                                self._plan_compiler(dynamic_cql[p2], p2)
                            )
                        break
                    first = False
                else:
                    from ..compiler.nfa import chain_template_of

                    t = chain_template_of(
                        plan.artifacts[0], plan.spec.column_types
                    )
                    if t is None:
                        _LOG.warning(
                            "dynamic plan %r no longer folds into group "
                            "%r; restoring it standalone", pid, host_id,
                        )
                        self._folded.pop(pid, None)
                        self._folded_enabled.pop(pid, None)
                        self.add_plan(plan)
                        continue
                    self._fold_into(host_id, plan, slot, t)
        for pid, cql in dynamic_cql.items():
            if pid not in folded and pid not in self._plans:
                # standalone dynamic plans (non-chain: _wrap_dynamic fell
                # through at admit time) were created cacheable at line
                # ~888 (cacheable=dynamic) — replay them cacheable too,
                # NOT via the dynamic add path (whose _try_fold could
                # fold into a group re-formed above, diverging from the
                # snapshot's runtime layout). Cacheability here is what
                # lets a replica bootstrap warm these plans from the
                # persistent store (fleet/warmstore.py, docs/fleet.md).
                self._create_runtime(
                    self._plan_compiler(cql, pid), None,
                    cacheable=True, tenant=self.tenant_of(pid),
                )
        for pid, on in enabled.items():
            if not on:
                self.set_plan_enabled(pid, False)
        self._dynamic_cql.update(dynamic_cql)

    def remove_plan(self, plan_id: str) -> None:
        self._assert_runloop_owner("remove_plan")
        if plan_id in self._folded or plan_id in self._plans:
            self._frec(
                "control.retire", plan=plan_id,
                tenant=self._plan_tenant.get(plan_id),
            )
        skey = self._shared_member.pop(plan_id, None)
        if skey is not None:
            entry = self._shared.get(skey)
            if entry is not None:
                host_rt = self._plans.get(entry["host_id"])
                if host_rt is not None:
                    # surface the host's pending matches FIRST: its
                    # loopback rows step into this member's suffix,
                    # whose own drain below then carries them out —
                    # nothing produced before the retire is lost
                    self._drain_plan(host_rt)
                entry["members"] = [
                    m for m in entry["members"] if m != plan_id
                ]
                if not entry["members"]:
                    # last member retired: drop the producer host too
                    # (group.evict discipline — its executables stay
                    # warm in the AOT cache / warm store, so a later
                    # admit of this predicate re-forms it compile-free)
                    self._plans.pop(entry["host_id"], None)
                    self._drain_hints.pop(entry["host_id"], None)
                    self._loopback.pop(entry["mid"], None)
                    self._shared.pop(skey, None)
                    self._inc_control("control.subplan_unshare")
                    self._frec(
                        "control.subplan_unshare",
                        plan=plan_id, host=entry["host_id"], key=skey,
                    )
        folded = self._folded.pop(plan_id, None)
        self._folded_enabled.pop(plan_id, None)
        self._dynamic_cql.pop(plan_id, None)
        # the footprint denominator dies with the runtime (an update
        # re-records it); tenant attribution and the plan's SCOPE
        # persist — a retired plan's rows stay in the conservation sum
        # and its tenant's rollup
        self._plan_admitted_bytes.pop(plan_id, None)
        if folded is not None:
            host_id, slot = folded
            self._inc_control("control.retired")
            rt = self._plans.get(host_id)
            if rt is None:
                return
            self._drain_plan(rt)  # don't lose already-produced matches
            # retire leaves the slot as a ROW-INERT padded member
            # (enabled=False, active cleared — plancheck's padded-row
            # inertness class): a later admit reclaims it via
            # free_slot, so retire/admit churn never grows the group
            group = rt.plan.artifacts[0]
            states = dict(rt.states)
            states[group.name] = group.evict(states[group.name], slot)
            rt.states = states
            if all(m is None for m in group.members):
                # last member gone: the host runtime is dropped too —
                # its executables stay warm in the AOT cache, so a
                # later admit of this shape class re-forms the host
                # without recompiling
                self._plans.pop(host_id, None)
                self._drain_hints.pop(host_id, None)
            return
        rt = self._plans.get(plan_id)
        if rt is not None:
            self._drain_plan(rt)
            self._inc_control("control.retired")
        self._plans.pop(plan_id, None)
        self._drain_hints.pop(plan_id, None)

    def set_plan_enabled(self, plan_id: str, enabled: bool) -> None:
        self._assert_runloop_owner("set_plan_enabled")
        self._frec(
            "control.enable" if enabled else "control.disable",
            plan=plan_id,
        )
        folded = self._folded.get(plan_id)
        if folded is not None:
            self._folded_enabled[plan_id] = enabled
            host_id, slot = folded
            rt = self._plans.get(host_id)
            if rt is not None:
                # events staged before this control event must step under
                # the OLD member state (control takes effect at the next
                # boundary): dispatch the pending segment before mutating
                self._dispatch_segment(rt)
                group = rt.plan.artifacts[0]
                states = dict(rt.states)
                states[group.name] = group.set_enabled(
                    states[group.name], slot, enabled
                )
                rt.states = states
            return
        rt = self._plans.get(plan_id)
        if rt is not None:
            if not enabled:
                # events staged while the plan was enabled still step
                # (control takes effect at the NEXT boundary)
                self._dispatch_segment(rt)
            rt.enabled = enabled

    @property
    def plan_ids(self) -> List[str]:
        """Live plan ids. Safe off-thread (GET /api/v1/queries runs on
        the service thread): ``list(dict)`` snapshots atomically under
        the GIL, where the previous Python-level comprehension over the
        live dict could raise mid-iteration when the run loop admits or
        retires a plan concurrently."""
        return [
            pid
            for pid in list(self._plans)
            if not pid.startswith(("@dyn:", "@shr:"))
        ] + list(self._folded)

    def _apply_control(self, ev) -> None:
        self._assert_runloop_owner("_apply_control")
        from ..control.events import (
            MetadataControlEvent,
            OperationControlEvent,
        )

        if isinstance(ev, MetadataControlEvent):
            if (
                ev.added_plans or ev.updated_plans
            ) and self._plan_compiler is None:
                raise RuntimeError(
                    "control event adds a plan but the job has no plan "
                    "compiler (create it through the dynamic cql() path)"
                )
            # admission verdicts carried on the event (analysis/admit.py
            # summaries; getattr covers pre-admission checkpointed
            # events): a plan the gate already REJECTED must never
            # reach the compiler/runtime — counted + logged, the rest
            # of the event still applies
            verdicts = getattr(ev, "admission", None) or {}
            tenant = getattr(ev, "tenant", None)

            def _rejected(plan_id: str) -> bool:
                v = verdicts.get(plan_id)
                if v is None or v.get("admitted", True):
                    return False
                self._record_rejection(
                    plan_id,
                    [f.get("rule") for f in v.get("findings", ())],
                    [f.get("message", "") for f in v.get("findings", ())],
                    tenant,
                    source="carried-verdict",
                )
                _LOG.warning(
                    "control event %s plan %s refused: admission "
                    "verdict rejected it (%s)",
                    "adds" if plan_id in ev.added_plans else "updates",
                    plan_id,
                    [f.get("rule") for f in v.get("findings", ())],
                )
                return True

            def _precleared(plan_id: str) -> bool:
                """True when the carried service-gate verdict is a
                PASS that includes the deep tier's footprint numbers
                (state_bytes + acc_bytes): the gate already ran the
                full admission pipeline on this exact CQL, so the
                apply-time re-check can skip the redundant deep
                eval_shape pass. Events without a carried verdict (a
                raw control topic, a pre-gate checkpointed event) keep
                the full defense-in-depth path."""
                v = verdicts.get(plan_id)
                return bool(
                    v is not None
                    and v.get("admitted", False)
                    and v.get("state_bytes") is not None
                    and v.get("acc_bytes") is not None
                )

            def _note_admission(plan_id: str, plan) -> None:
                """Tenant + admitted-footprint bookkeeping for an
                accepted add/update: BEFORE add_plan, so the runtime's
                cache/stack counters land in the right tenant scope and
                the footprint meter has its denominator from the very
                first drain. The apply-time analyzer's own prediction
                (stamped on the compiled plan) wins over the carried
                service-gate summary — it judged exactly what runs."""
                if tenant is not None:
                    self._plan_tenant[plan_id] = tenant
                nb = getattr(plan, "_admitted_nbytes", None)
                if nb is None:
                    v = verdicts.get(plan_id) or {}
                    sb, ab = v.get("state_bytes"), v.get("acc_bytes")
                    if sb is not None and ab is not None:
                        nb = int(sb) + int(ab)
                if nb is not None:
                    self._plan_admitted_bytes[plan_id] = int(nb)

            for plan_id, cql in ev.added_plans.items():
                if _rejected(plan_id):
                    continue
                plan = self._compile_admitted(
                    plan_id, cql, tenant,
                    precleared=_precleared(plan_id),
                )
                if plan is None:
                    continue
                _note_admission(plan_id, plan)
                self.add_plan(plan, dynamic=True)
                # setdefault: a subplan-share admit already recorded
                # the tenant's SUFFIX CQL (what replay must re-admit —
                # the host is re-formed from the "shared" block)
                self._dynamic_cql.setdefault(plan_id, cql)
            for plan_id, cql in ev.updated_plans.items():
                if _rejected(plan_id):
                    continue  # the running plan stays as-is
                plan = self._compile_admitted(
                    plan_id, cql, tenant,
                    precleared=_precleared(plan_id),
                )
                if plan is None:
                    continue  # refused update: the running plan stays
                self.remove_plan(plan_id)
                _note_admission(plan_id, plan)
                self.add_plan(plan, dynamic=True)
                self._dynamic_cql.setdefault(plan_id, cql)
            for plan_id in ev.deleted_plan_ids:
                self.remove_plan(plan_id)
        elif isinstance(ev, OperationControlEvent):
            self.set_plan_enabled(ev.plan_id, ev.action == "enable")
        else:
            raise TypeError(f"unknown control event {type(ev)!r}")

    def _compile_admitted(
        self,
        plan_id: str,
        cql: str,
        tenant: Optional[str] = None,
        precleared: bool = False,
    ):
        """APPLY-time admission (docs/control_plane.md): compile the
        candidate, run plancheck's static tier and the admission
        analyzer against ``self.admission_budgets``, and return the
        plan — or None after counting + recording the refusal. Defense
        in depth behind the service-boundary gate: an event injected
        past the REST layer (a raw control topic, a checkpointed
        pre-gate event) is still judged before it touches the stack.

        ``precleared=True`` means the event carried a PASSING
        service-gate verdict with the deep tier's footprint numbers:
        the deep ``eval_shape`` + budget re-verdict is skipped on the
        run loop (the gate already ran both on this exact CQL
        off-loop; the carried state/acc bytes feed the footprint
        meter instead). The static verify + cost-hook tier —
        microseconds — still runs, so a forged verdict cannot smuggle
        an invalid plan past apply time. Observable as the
        ``control.preclear`` counter + journal kind.
        """
        from ..analysis.admit import AdmissionError, analyze_plan
        from ..analysis.plancheck import PlanCheckError, verify_plan
        from ..query.lexer import SiddhiQLError

        rules: List[str] = []
        rendered: List[str] = []
        try:
            plan = self._plan_compiler(cql, plan_id)
            issues = verify_plan(
                plan, trace=False, raise_on_error=False
            )
            rules += [i.rule for i in issues]
            rendered += [i.render() for i in issues]
            if not issues:
                # deep tier (eval_shape footprint + signature) only
                # under a configured budget — the static cost-hook
                # tier is microseconds and always runs. budgets=None
                # on a precleared add: analyze_plan's budget verdict
                # IMPLIES the deep tier (a budget can't be checked
                # against an uncomputed footprint), and the gate
                # already rendered both on this exact CQL off-loop —
                # its carried bytes feed the footprint meter instead.
                budgets = self.admission_budgets
                if precleared and budgets is not None:
                    budgets = None
                    self._inc_control("control.preclear")
                    self._frec(
                        "control.preclear", plan=plan_id,
                        tenant=tenant,
                    )
                report = analyze_plan(
                    plan,
                    budgets=budgets,
                    deep=budgets is not None,
                )
                rules += [i.rule for i in report.findings]
                rendered += [i.render() for i in report.findings]
                if (
                    report.state_bytes is not None
                    and report.acc_bytes is not None
                ):
                    # the footprint meter's denominator: what THIS
                    # compiled plan was predicted to cost (ADM101/102)
                    plan._admitted_nbytes = int(
                        report.state_bytes + report.acc_bytes
                    )
        except (PlanCheckError, AdmissionError) as e:
            # compile_plan itself verifies under FST_VERIFY_PLANS /
            # config budgets and raises — same refusal, same record
            rules += [i.rule for i in e.issues]
            rendered += [i.render() for i in e.issues]
        except SiddhiQLError as e:
            # a bad query pushed through a control channel refuses THIS
            # add, not the running queries
            rules += ["CQL000"]
            rendered += [f"{type(e).__name__}: {e}"]
        except Exception as e:  # noqa: BLE001 — recorded, never hidden
            # a compiler or device error is NOT a bad query: the other
            # tenants keep running, but the refusal carries its own rule
            # id and the traceback goes to the log at ERROR
            _LOG.exception(
                "control-path plan %s: engine error at apply time",
                plan_id,
            )
            rules += ["ENG000"]
            rendered += [f"{type(e).__name__}: {e}"]
        if rules:
            self._record_rejection(
                plan_id, rules, rendered, tenant, source="apply-time"
            )
            _LOG.warning(
                "control-path plan %s refused at apply time: %s",
                plan_id, rules,
            )
            return None
        return plan

    def _record_rejection(
        self,
        plan_id: str,
        rules,
        findings,
        tenant: Optional[str] = None,
        source: str = "apply-time",
    ) -> None:
        self._inc_control("control.admission_rejected")
        # journal the refusal too (the recorder has its own lock — the
        # service thread records boundary refusals concurrently)
        self._frec(
            "control.reject", plan=plan_id, tenant=tenant,
            rules=[r for r in rules if r], source=source,
        )
        # under the lock: the REST service thread records boundary
        # refusals concurrently with the run loop's apply-time ones,
        # and the eviction walk below iterates the dict
        with self._rejections_lock:
            # re-insert at the ring's tail: a repeated refusal of the
            # same plan id must refresh its eviction position, or the
            # freshest rejection could be the first one evicted
            self.control_rejections.pop(plan_id, None)
            self.control_rejections[plan_id] = {
                "rules": [r for r in rules if r],
                "findings": list(findings),
                "tenant": tenant,
                "source": source,
            }
            while (
                len(self.control_rejections) > self.MAX_REJECTIONS_KEPT
            ):
                self.control_rejections.pop(
                    next(iter(self.control_rejections))
                )

    # fst:runloop-only (completes in-flight drains synchronously)
    def add_sink(self, output_stream: str, fn: Callable) -> None:
        """Attach a sink. Drains already in flight are completed first:
        with no prior consumers they were swapped counts-only, so the
        boundary is deterministic — rows accumulated BEFORE the sink
        attached are counted but not delivered, rows after are."""
        for rt in self._plans.values():
            self._drain_poll(rt, block=True)
        # observability handles are ephemeral on the sink side
        # (fst:ephemeral there): binding at attach time is what keeps a
        # restored / re-attached sink journaling into THIS job's
        # recorder and counting into THIS job's registry
        bind_t = getattr(fn, "bind_telemetry", None)
        if bind_t is not None:
            bind_t(self.telemetry)
        bind_f = getattr(fn, "bind_flightrec", None)
        if bind_f is not None:
            bind_f(self.flightrec)
        self._sinks.setdefault(output_stream, []).append(fn)

    def reset_engine_state(self) -> None:
        """Benchmark/rerun aid: reset device state, staged fused
        segments, in-flight tickets, lazy rings, and host emission
        phase so the SAME job can replay an identical stream again
        with every compiled executable still warm — the second-run
        measurement contract of ``ResidentReplay.rerun``
        (tests/test_replay.py, tests/test_baseline_workloads.py)
        (ONE reset recipe, so a new runtime field cannot be forgotten
        in one of the copies). States re-grow to the interned encoder
        sizes: compiled programs were lowered against the GROWN
        shapes."""
        self._assert_runloop_owner("reset_engine_state")
        # a rerun is a fresh drive: the next run()/run_cycle() thread
        # (bench reruns sometimes move threads) re-stamps ownership
        self._runloop_thread = None
        self.telemetry.stages.starve = None  # its tickets go below
        for rt in self._plans.values():
            rt.states = jax.device_put(
                rt.plan.grow_state(rt.plan.init_state())
            )
            rt.acc = rt.jitted_init_acc()
            rt.acc_dirty = False
            rt.dirty_since = None
            rt.seg_pending = []
            rt.tickets.clear()
            rt.waited = None
            rt.seg_open = []
            if getattr(rt, "lazy", None) is not None:
                rt.lazy = _LazyRing(rt.lazy.budget)
                rt.lazy_base = None
        # host-side emission state too: a carried rate-limiter phase
        # (chunk position / buffered rows / deadlines) would make the
        # second run's flush emit at different boundaries
        for lim in self._rate_limiters.values():
            lim.count = 0
            lim.buf = []
            lim.cur = {}
            lim.deadline = None
        # drain-cadence phase: a carried _cycles_since_drain would put
        # the second run's first capacity swap at a different boundary
        # than the first run's (same contract as the limiter reset)
        self._cycles_since_drain = 0
        self._last_full_drain = time.monotonic()
        # event-time gate phase: a rerun replays the SAME stream, so a
        # carried released horizon would classify every row late
        self._released_wm = MIN_WM
        self._gate_wm = MIN_WM
        self._max_event_ts = None
        self._pending_t.clear()
        self._source_idle = [False] * len(self._sources)
        self._source_last_t = [None] * len(self._sources)
        # a poll in flight is of the run before
        self._polled_ahead.clear()
        self._poll_runs.clear()

    # -- run loop ------------------------------------------------------------
    # fst:thread-root name=run-loop
    def run(self, max_cycles: Optional[int] = None) -> None:
        self._stamp_runloop_owner()
        cycles = 0
        while not self.finished:
            self.run_cycle()
            cycles += 1
            if max_cycles is not None and cycles >= max_cycles:
                break
        if self.finished:
            self.flush()

    # fst:runloop-only (end-of-stream drain + timer emissions)
    def flush(self) -> None:
        """End-of-stream: drain accumulated matches, then fire final
        timer-driven emissions (timeBatch windows carry their last
        incomplete window out)."""
        for rt in self._plans.values():
            self._drain_plan(rt)
            if not rt.plan.has_flush:
                # statically nothing to flush: skip the program — even
                # an empty flush costs several fixed-latency fetches
                continue
            with self.telemetry.span("flush"):
                rt.states, outputs = self._flush_fn(rt)(rt.states)
                if outputs:
                    lazy = getattr(rt, "lazy", None)
                    self._decode_outputs(
                        rt.plan, outputs, only=set(outputs),
                        lookup=lazy.lookup if lazy is not None else None,
                        columnar_streams=self._columnar_streams(rt),
                        lookup_np=(
                            lazy.lookup_np if lazy is not None else None
                        ),
                    )
        # stream end: rate-limited output still buffered surfaces now
        with self.telemetry.span("flush"):
            for sid, limiter in self._rate_limiters.items():
                self._emit_pending(sid, limiter.flush())

    def _compile_scope(self, rt: _PlanRuntime):
        """Compile-attribution scope for one plan's jit calls
        (telemetry/compile_events.py): any XLA lowering fired inside
        it lands in ``metrics()["compiles"]`` under the plan's
        shape-class signature label. Thread-local and re-entrant; a
        plain attribute store on enter/exit, so the hot loop pays
        nothing measurable."""
        return compile_events.attribution(
            getattr(self, "_compile_sink", None),
            getattr(rt, "sig_label", None) or f"plan:{rt.plan.plan_id}",
        )

    _noop_jit = None

    @classmethod
    def _make_ticket(cls, states):
        """A tiny array whose completion implies the dispatched cycle
        finished: a fresh (non-donated) jit output derived from the
        smallest state leaf — safe to hold across cycles."""
        if cls._noop_jit is None:
            # a named function: the program is jit_ticket in a trace
            def ticket(x):
                return jnp.asarray(x).ravel()[:1] * 0

            cls._noop_jit = jax.jit(ticket)
        leaves = jax.tree.leaves(states)
        leaf = min(leaves, key=lambda x: getattr(x, "size", 1 << 30))
        return cls._noop_jit(leaf)

    @staticmethod
    def _state_sig(states) -> Tuple:
        return tuple(
            (np.shape(x), np.dtype(getattr(x, "dtype", type(x))))
            for x in jax.tree.leaves(states)
        )

    def _warm_flush(self, rt: _PlanRuntime) -> None:
        """Precompile the end-of-stream flush program in the background:
        skipped entirely for plans whose flush is statically a no-op.
        its (cached) compile/deserialize costs seconds and would otherwise
        land synchronously inside the final flush() call. Re-armed by
        _step_plan whenever the state shapes change (group-table growth),
        so the warm executable tracks the shapes flush() will see."""
        import concurrent.futures

        sig = self._state_sig(rt.states)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype), rt.states
        )

        # fst:thread-root name=warm-compile
        def compile_it():
            # attribution scope is thread-local: re-enter it on the
            # pool thread so the background lowering still lands in
            # this job's compile accounting
            with self._compile_scope(rt):
                return rt.jitted_flush.lower(abstract).compile()

        pool = getattr(self, "_compile_pool", None)
        if pool is None:
            pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="fst-warm"
            )
            # fst:ephemeral lazily-created background compile pool; a fresh process rebuilds it
            self._compile_pool = pool
        rt.flush_warm = (sig, pool.submit(compile_it))

    def _flush_fn(self, rt: _PlanRuntime) -> Callable:
        """The flush executable: the background-precompiled one when its
        input shapes still match, else the lazily-jitted fallback. The
        signature check happens BEFORE blocking on the future, so a stale
        warm compile is never waited for."""
        if rt.flush_warm is not None:
            sig, fut = rt.flush_warm
            if sig == self._state_sig(rt.states):
                try:
                    return fut.result()
                except Exception:
                    pass  # fall back to the jit path
        return rt.jitted_flush

    # max swapped-out accumulators whose fetches may be in flight per
    # plan; past this the oldest is force-completed (each holds the acc
    # buffer alive until its fetch runs, so the bound caps device HBM).
    # Deep enough to ride device->host bandwidth dips without stalling
    # the run loop.
    MAX_PENDING_DRAINS = 6

    # fst:runloop-only (run-loop-private: swaps device accumulators and emits to sinks)
    def drain_outputs(self, wait: bool = True) -> None:
        """Surface all on-device accumulated emissions to collectors and
        sinks. ``wait=True`` (default, and the contract of results() /
        snapshot()) completes synchronously; ``wait=False`` only STARTS
        the fetches — the accumulator is swapped for a fresh one and its
        meta/data transfers overlap with subsequent device cycles, to be
        decoded by a later poll (run_cycle) or a waiting drain."""
        for rt in self._plans.values():
            # staged-but-undispatched tapes must reach the device
            # before a drain whose caller will read state or rows
            # (results/snapshot/checkpoint) — this is what makes every
            # checkpoint land on a segment boundary
            self._dispatch_segment(rt)
        with self.telemetry.span("drain"):
            for rt in list(self._plans.values()):
                self._drain_request(rt)
                self._drain_poll(rt, block=wait)
        if self._loopback and wait:
            # shared-prefix fan-out: host drains above may have stepped
            # loopback rows into member suffixes AFTER those suffixes'
            # own drain passed (and staged without dispatch) —
            # a synchronous drain must settle them too, or snapshot()/
            # results() would miss rows the host already produced.
            # Hosts precede members in insertion order, so one extra
            # pass over the loopback consumers suffices.
            mids = set(self._loopback)
            with self.telemetry.span("drain"):
                for rt in list(self._plans.values()):
                    if not (mids & set(rt.plan.spec.stream_codes)):
                        continue
                    # hosts precede members in insertion order, so in
                    # streaming mode the first pass usually already
                    # drained the flushed rows — a consumer with no
                    # staged tape, no undrained dispatch, and no
                    # in-flight fetch has nothing left to surface, and
                    # skipping it spares a full drain round trip per
                    # suffix per drain_outputs
                    if (
                        not rt.seg_pending
                        and rt.dirty_since is None
                        and not rt.drain_q
                    ):
                        continue
                    self._dispatch_segment(rt)
                    self._drain_request(rt)
                    self._drain_poll(rt, block=True)

    def _drain_plan(self, rt: _PlanRuntime) -> None:
        """Synchronous per-plan drain (checkpoint / removal paths)."""
        self._dispatch_segment(rt)
        with self.telemetry.span("drain"):
            self._drain_request(rt)
            self._drain_poll(rt, block=True)

    def _interval_drain(self) -> None:
        """Latency-bounding drain pass over plans someone observes.

        Admission is STALENESS-ORDERED and backlog-aware: only plans
        whose oldest undrained match has reached the staleness budget
        are candidates, the stalest goes first, and a shared pending
        budget (MAX_PENDING_DRAINS across all plans) stops admission
        before the fetch backlog itself becomes match latency — under
        pressure the budget goes to the plans that need it most, not
        round-robin.

        Flow control: at most TWO drains in flight per plan. One is too
        few — a drain pays a readiness round trip (the count-prefix
        behind queued device work) and then the fetch phases, and
        serializing them makes the visibility cadence their SUM; with
        two, drain k+1's readiness wait overlaps drain k's fetch, so
        the cadence approaches one fetch duration. More than two only
        grows a backlog whose depth becomes match latency on a slow
        device->host link."""
        now = time.monotonic()
        interval_s = (self.drain_interval_ms or 0.0) / 1e3
        for rt in self._plans.values():
            self._drain_poll(rt)
        budget = self.MAX_PENDING_DRAINS - sum(
            len(rt.drain_q) for rt in self._plans.values()
        )
        cands = [
            rt
            for rt in self._plans.values()
            if rt.dirty_since is not None
            and now - rt.dirty_since >= interval_s
            and len(rt.drain_q) < 2
            and self._has_consumers(rt)
        ]
        cands.sort(key=lambda rt: rt.dirty_since)  # stalest first
        for rt in cands:
            if budget <= 0:
                break
            self._drain_request(rt)
            self._drain_poll(rt)
            budget -= 1

    # smallest data-fetch bucket: bounds the pack-program count to
    # log2(capacity/64) shapes while letting a sparse drain's transfer
    # shrink to ~64 columns instead of the old 1024 floor
    MIN_FETCH_WIDTH = 64

    def prewarm_drains(
        self, widths: Optional[Sequence[int]] = None
    ) -> None:
        """Compile the bucketed data-slice programs up front — EVERY
        power-of-two width the count-sized fetch can land on, by
        default. A first compile at a new width mid-run stalls the
        pipeline for as long as the compile takes; prewarming moves
        that out of the steady-state loop (benchmarks /
        latency-sensitive pipelines call this once at startup)."""
        for rt in self._plans.values():
            if rt.acc is None or not rt.plan.artifacts:
                continue
            cap = rt.plan.acc_capacity()
            ws = widths
            if ws is None:
                # every power of two up to the full accumulator width
                ws = []
                w = self.MIN_FETCH_WIDTH
                while w < cap:
                    ws.append(w)
                    w <<= 1
                ws.append(cap)
            for w in ws:
                if w <= cap:
                    self._pack_data(rt, rt.acc, w)  # compile; drop result

    @staticmethod
    def _pack_data(rt: _PlanRuntime, acc: Dict, width: int):
        """The data half of a two-phase drain: one device array holding
        ``buf[:, :width]``, dispatched only AFTER the count prefix came
        back, with ``width`` bucketed from the ACTUAL max match count —
        the transfer is sized to what was matched, never to a predicted
        width (the old fast path shipped a >=1024-wide slice on every
        drain and paid an extra round trip on misprediction).

        One of the two places that know the accumulator's rank (the
        other is _fetch_acc): ShardedJob overrides both for its stacked
        ``[shards, ...]`` accumulator and inherits the rest of the
        drain."""
        jits = getattr(rt, "pack_jits", None)
        if jits is None:
            # fst:threadsafe lazy idempotent init, GIL-atomic dict ops: prewarm (run loop) and the fetch thread may race the first width; the loser's entry is identical and a lost insert just recompiles once
            jits = rt.pack_jits = {}
        fn = jits.get(width)
        if fn is None:
            # fst:hotpath
            def pack(a, _w=width):
                rows = a["buf"].shape[0]
                return jax.lax.slice(a["buf"], (0, 0), (rows, _w))

            fn = jits[width] = jax.jit(pack)
        return fn(acc)

    def _drain_request(self, rt: _PlanRuntime) -> None:
        """Swap the device accumulator for a fresh one and queue the
        swapped-out copy for fetching. The entry stays in a cheap
        "waiting for the device" stage until its meta (count-prefix)
        array is_ready — polled for free from the run loop — and only
        then goes to the fetch thread. The fetch is TWO-PHASE: the tiny
        count prefix crosses first, then the data slice is dispatched
        at a width bucketed from the actual max count (zero matches =
        zero data transfer; see _fetch_acc)."""
        if rt.acc is None or not rt.plan.artifacts:
            return
        # footprint meter poll: drain boundaries only, metadata-only
        # (the FST102 hotpath rules — no host sync rides this)
        self._update_footprint(rt)
        if not rt.acc_dirty:
            return  # provably empty: nothing to swap or fetch
        with self.telemetry.span("drain.request"):
            old = rt.acc
            rt.acc = rt.jitted_init_acc()
            rt.acc_dirty = False
            t_dirty = rt.dirty_since
            rt.dirty_since = None
            want = self._has_consumers(rt)
            # the segments dispatched since the last swap left their
            # emissions in this accumulator: their records go with it
            # (plans nobody observes record no legs)
            segs, rt.seg_open = rt.seg_open, []
            self._drain_ordinal += 1
            # no-consumer entries (want=False) fetch counts only — the
            # data phase AND the host decode are skipped entirely; the
            # swap itself still happens (overflow accounting)
            rt.drain_q.append(
                {
                    "acc": old,
                    "want": want,
                    "segs": segs if want else (),
                    "ord": self._drain_ordinal,
                    # which output streams decode columnar (all
                    # consumers opted in): resolved at request time so a
                    # sink attached mid-flight (add_sink drains first)
                    # cannot race
                    "columnar": self._columnar_streams(rt) if want else
                    frozenset(),
                    "t_req": time.monotonic(),
                    # staleness is the deadline scheduler's report
                    # card: only consumer-visible drains contribute
                    # (unconsumed plans reach here via capacity swaps
                    # the scheduler deliberately never bounds)
                    "t_dirty": t_dirty if want else None,
                }
            )
            self._advance_ready(rt)
        if len(rt.drain_q) > self.MAX_PENDING_DRAINS:
            # the run loop blocked on the fetch thread's backlog (the
            # drain side's backpressure_wait; nested under "drain")
            with self.telemetry.span("drain.backlog_wait"):
                self._drain_poll(rt, block=True, limit=1)

    def _columnar_streams(self, rt: _PlanRuntime) -> frozenset:
        """Output streams of this plan whose rows never need to exist:
        host retention off, every attached sink speaks the columnar
        protocol, and any rate limiter can account batches (snapshot
        mode keys per-group rows, so it stays on the row path)."""
        if self.retain_results:
            return frozenset()
        out = set()
        for sid in rt.plan.output_streams():
            sinks = self._sinks.get(sid)
            if not sinks:
                continue
            if not all(
                hasattr(s, "accept_columns") for s in sinks
            ):
                continue
            lim = self._rate_limiters.get(sid)
            if lim is not None and lim.mode == "snapshot":
                continue
            out.add(sid)
        return frozenset(out)

    def _has_consumers(self, rt: _PlanRuntime) -> bool:
        """Whether any host-side consumer observes this plan's rows."""
        if self.retain_results:
            return True
        if self._loopback and any(
            sid in self._loopback for sid in rt.plan.output_streams()
        ):
            # a shared-prefix host's consumers are its member suffixes:
            # without this, the counts-only drain path would skip the
            # data fetch + decode and the loopback would starve
            return True
        return any(
            self._sinks.get(sid)
            for sid in rt.plan.output_streams()
        )

    def _advance_ready(self, rt: _PlanRuntime) -> None:
        """Promote waiting entries whose meta (count-prefix) array is
        ready to fetch jobs (FIFO: stop at the first not-ready entry).
        Meta readiness implies the whole accumulator's step work
        retired (same program execution), so the fetch thread's data
        phase pays pack+transfer only, never a block-on-unfinished-
        compute stall. Eager promotion (blocking from the fetch thread)
        does NOT help where a round trip is long: the readiness round
        trip just moves into fetch-thread queueing (wait_ready falls,
        queue grows by as much), while the gated form lets two
        in-flight drains pipeline readiness against fetch."""
        for entry in rt.drain_q:
            if "fut" in entry:
                continue
            if not entry["acc"]["meta"].is_ready():
                break
            t_ready = entry["t_ready"] = time.monotonic()
            for rec in entry["segs"]:
                # meta readiness implies the segment's work retired
                if rec.complete is None:
                    rec.complete = t_ready
            entry["stages"] = {}
            entry["fut"] = self._fetch_pool.submit(
                self._fetch_acc, rt, entry.pop("acc"),
                entry.pop("want"), entry.pop("columnar"),
                entry["stages"], self.telemetry, entry["ord"],
            )

    @property
    def _fetch_pool(self):
        """One fetch thread per job: FIFO completion order. Fetch AND
        decode run on this thread (host-side decode state like the lazy
        ring must be locked — see _LazyRing); sinks still only ever run
        on the run-loop thread (_drain_poll emits)."""
        import concurrent.futures

        pool = getattr(self, "_fetch_pool_", None)
        if pool is None:
            pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="fst-fetch"
            )
            # fst:ephemeral lazily-created drain fetch-thread pool; a fresh process rebuilds it
            self._fetch_pool_ = pool
        return pool

    @staticmethod
    def _book_prefix(tel: MetricsRegistry, plan, rows) -> None:
        """Rows 2 onward of the count prefix (plan.py ``init_acc``),
        summed over shards and booked at each drain. The step decides
        on the device, so the host learns it here: the accumulator's
        aligned appends since the last drain and, of them, those whose
        mask was a prefix already, so that the front-compaction moved
        nothing (compiler/compact.py); then what each artifact's steps
        counted, under the names its ``step_counters`` gives (a time
        window's ``window.time_expired`` and ``window.ring_evicted``)."""
        tel.inc("acc.compactions", int(rows[0].sum()))
        tel.inc("acc.compactions_identity", int(rows[1].sum()))
        for ai, a in enumerate(plan.artifacts):
            for row, name in zip(rows[2:], getattr(a, "step_counters", ())):
                tel.inc(name, int(row[ai]))

    @staticmethod
    # fst:thread-root name=drain-fetch
    def _fetch_acc(rt: _PlanRuntime, acc: Dict, want: bool,
                   columnar: frozenset,
                   stages: Dict, tel: MetricsRegistry, drain: int):
        """Fetch-thread body — the TWO-PHASE count-prefix fetch. Phase
        one transfers the tiny meta array (per-artifact counts +
        overflow, and the two compaction counts, booked at once). Phase
        two, only when matches exist and a consumer
        wants them, dispatches the data slice at a width bucketed from
        the ACTUAL max count and transfers exactly that — an empty
        drain never touches the data buffer, a sparse one ships a
        64-wide slice instead of the old predicted >=1024. Bucketed
        widths keep the pack-program count to a handful of shapes (a
        distinct shape per drain would compile a fresh program every
        time). Decode also happens here
        so the run loop only emits.

        Each phase is a profiler annotation (``fst.drain.fetch``,
        ``fst.drain.decode``, with the drain's ordinal) and is booked
        into ``drain.fetch`` / ``drain.decode`` HERE, as it ends: the
        busy share of this thread then counts work in the second it was
        done, not at the run loop's next poll. ``stages`` takes the
        stamps the run loop's own legs need.

        Reached as ``self._fetch_acc`` (_advance_ready), so a subclass
        whose accumulator has another rank supplies its own body and
        returns the same triple (ShardedJob: counts and overflow summed
        over shards, the shards' payloads merged)."""
        with tel.annotate("fst.drain.fetch", drain=drain):
            stages["t_fetch0"] = time.monotonic()
            meta = np.asarray(acc["meta"])  # phase one: the count prefix
            counts, overflow = meta[0], meta[1]
            Job._book_prefix(tel, rt.plan, meta[2:])
            max_n = int(counts.max()) if counts.size else 0
            stages["t_meta"] = time.monotonic()
            data = None
            if want and max_n:
                width = min(
                    bucket_size(max_n, minimum=Job.MIN_FETCH_WIDTH),
                    rt.plan.acc_capacity(),
                )
                # phase two: count-sized data slice (pack dispatch +
                # transfer)
                data = np.asarray(
                    Job._pack_data(rt, acc, width)
                )[:, :max_n]
            stages["t_dec0"] = time.monotonic()
        tel.record_seconds(
            "drain.fetch", stages["t_dec0"] - stages["t_fetch0"]
        )
        decoded = None
        with tel.annotate("fst.drain.decode", drain=drain):
            if data is not None:
                lazy = getattr(rt, "lazy", None)
                decoded = rt.plan.drain_decode(
                    counts, data,
                    lookup=lazy.lookup if lazy is not None else None,
                    columnar_streams=columnar,
                    lookup_np=lazy.lookup_np if lazy is not None else None,
                )
            # an empty or unwanted drain stamps its leg ends too
            # (drain.emit_lag counts from here)
            stages["t_fetch1"] = time.monotonic()
        tel.record_seconds(
            "drain.decode", stages["t_fetch1"] - stages["t_dec0"]
        )
        return counts, overflow, decoded

    def _drain_poll(
        self, rt: _PlanRuntime, block: bool = False, limit: int = 0
    ) -> None:
        """Complete finished fetches in FIFO order and emit the decoded
        rows (decode already happened on the fetch thread) to
        collectors/sinks. Without ``block`` this never stalls the host."""
        try:
            self._drain_poll_inner(rt, block, limit)
        finally:
            # coalesced suffix dispatch; a blocking poll is a barrier
            # (results/snapshot/retire/attach all route through here
            # via _drain_plan / drain_outputs with block=True), a
            # non-blocking one only flushes mids at the batch-size
            # threshold. The finally covers every early return above.
            self._flush_loopback(force=block)

    def _drain_poll_inner(
        self, rt: _PlanRuntime, block: bool = False, limit: int = 0
    ) -> None:
        self._advance_ready(rt)
        done = 0
        while rt.drain_q:
            entry = rt.drain_q[0]
            if "fut" not in entry:
                if not block:
                    return
                # block path (results/flush/checkpoint): force the wait
                jax.block_until_ready(entry["acc"]["meta"])
                self._advance_ready(rt)
                entry = rt.drain_q[0]
            fut = entry["fut"]
            if not block and not fut.done():
                return
            # from the fetch's result to the legs' close: the emission
            # tail of one completed drain, on the run loop
            with self.telemetry.span("drain.emit"):
                self._emit_drain(rt, fut)
            done += 1
            if limit and done >= limit:
                return

    def _emit_drain(self, rt: _PlanRuntime, fut) -> None:
        """The run loop's half of one completed drain: take the fetch
        thread's result, book the drain's legs, hand the decoded rows
        to the emission tails and close the latency legs of the
        segments it covered."""
        counts, overflow, decoded = fut.result()
        done_entry = rt.drain_q.popleft()
        tel = self.telemetry
        if tel.enabled:
            now = time.monotonic()
            st = done_entry.get("stages") or {}
            t_req = done_entry["t_req"]
            t_rdy = done_entry.get("t_ready", t_req)
            t_f0 = st.get("t_fetch0", t_rdy)
            t_f1 = st.get("t_fetch1", now)
            # drain.fetch (d2h only: meta + data phase) and
            # drain.decode (host decode only) are booked by the
            # fetch thread as each ends (_fetch_acc)
            legs = {
                "wait_ready": t_rdy - t_req,
                "queue": t_f0 - t_rdy,
                "emit_lag": now - t_f1,
                "total": now - t_req,
            }
            # two-phase split: the count-prefix transfer alone
            # (drain.fetch minus it is the count-sized data phase)
            t_meta = st.get("t_meta")
            if t_meta is not None:
                legs["fetch_meta"] = t_meta - t_f0
            # per-leg latency distributions: these histograms (not
            # ad-hoc lists) are what the bench's latency breakdown
            # and /api/v1/metrics report
            for leg, dt in legs.items():
                tel.record_seconds(f"drain.{leg}", dt)
            # staleness: age of the plan's OLDEST undrained match
            # when its drain completed — the number the deadline
            # scheduler exists to bound (~interval + drain time)
            t_dirty = done_entry.get("t_dirty")
            if t_dirty is not None:
                tel.record_seconds("drain.staleness", now - t_dirty)
            tel.inc("drains.completed")
            # plan-scoped twins of total/staleness: each plan this
            # runtime serves waited through this drain
            self._scoped_drain_record(
                rt, legs["total"],
                (now - t_dirty) if t_dirty is not None else None,
            )
        for ai, a in enumerate(rt.plan.artifacts):
            if overflow[ai] > 0:
                _LOG.warning(
                    "%s: %d emissions dropped (accumulator full; "
                    "raise EngineConfig.acc_budget_bytes or drain "
                    "more often)", a.name, int(overflow[ai]),
                )
                tel.inc("faults.emissions_dropped", int(overflow[ai]))
        # the only place the engine degrades instead of failing
        # loudly: a lazy-projected value older than the ring budget
        # decodes as None in user rows — surface it (round-5 verdict
        # item 9), rate-limited to newly-missed counts
        lazy = getattr(rt, "lazy", None)
        if lazy is not None:
            warned = getattr(rt, "_lazy_miss_warned", 0)
            if lazy.missed > warned:
                _LOG.warning(
                    "%s: %d lazy-projected values were evicted past "
                    "the ring horizon and decoded as None (raise "
                    "EngineConfig.lazy_ring_budget_bytes, or drain "
                    "results more often)",
                    rt.plan.plan_id, lazy.missed - warned,
                )
                tel.inc("faults.lazy_evicted", lazy.missed - warned)
                rt._lazy_miss_warned = lazy.missed
        if decoded is not None:
            from ..compiler.output import ColumnBatch

            for a in rt.plan.artifacts:
                for schema, payload in decoded.get(a.name) or []:
                    if self.telemetry.enabled:
                        # matches = drained match rows BEFORE rate
                        # limiting (rows_emitted is the post-limit
                        # twin); a stacked group's per-slot decode
                        # attributes each member exactly
                        sc = self._attr_scope(schema)
                        if sc is not None:
                            sc.inc("matches", len(payload))
                        counters = getattr(a, "drain_counters", None)
                        if counters is not None:
                            for name, n in counters(payload).items():
                                tel.inc(name, n)
                    if isinstance(payload, ColumnBatch):
                        self._emit_columns(schema, payload)
                    else:
                        self._emit_rows(schema, payload)
        else:
            # counts-only drain (no consumers / empty): keep the
            # emitted counters truthful. Stacked groups attribute to
            # their representative stream.
            for ai, a in enumerate(rt.plan.artifacts):
                c = int(counts[ai]) if ai < counts.size else 0
                sch = getattr(a, "output_schema", None)
                if c and sch is not None:
                    self.emitted_counts[sch.stream_id] = (
                        self.emitted_counts.get(sch.stream_id, 0) + c
                    )
                    if self.telemetry.enabled:
                        # counts-only drains never fetch the data
                        # block, so a stacked group cannot split by
                        # slot: rows attribute to the representative
                        # member, exactly as the stream count above
                        # does — the conservation sum stays exact
                        sc = self._attr_scope(sch)
                        if sc is not None:
                            sc.inc("rows_emitted", c)
                            sc.inc("matches", c)
        if done_entry["segs"]:
            # the drain's last emission has returned from the
            # sinks: close the latency legs of every segment whose
            # emissions this accumulator held
            record_legs(
                tel, done_entry["segs"], done_entry["t_req"],
                time.monotonic(),
            )

    def _emit_rows(self, schema, rows, rate_limit: bool = True) -> None:
        """Shared append-to-collectors/sinks tail for all decode paths."""
        if not rows:
            return
        sid = schema.stream_id
        epoch = self._epoch_ms or 0
        if self._loopback and sid in self._loopback:
            # shared-prefix mid stream: pure host-side plumbing into
            # the consumer suffixes — no counters, no traces, no sinks
            # (per-tenant conservation counts member emissions only)
            self._feed_loopback(schema, _clock_rows(schema, rows, epoch))
            return
        if rate_limit:
            limiter = self._rate_limiters.get(sid)
            if limiter is not None:
                rows = limiter.feed(rows)
                if not rows:
                    return
        rows = _clock_rows(schema, rows, epoch)
        self.output_fields.setdefault(sid, schema.field_names)
        # rows surfacing to a consumer complete their event's trace
        # (post-rate-limit: a thinned row is not visible, so it
        # must not stop the clock)
        with self.telemetry.span("trace_complete"):
            self.tracer.complete_rows(epoch, rows)
        sinks = self._sinks.get(sid)
        self.emitted_counts[sid] = self.emitted_counts.get(sid, 0) + len(rows)
        if self.telemetry.enabled:
            # per-plan attribution, at EXACTLY the site the job total
            # counts — conservation (sum of plan scopes == job total)
            # holds by construction (docs/observability.md)
            sc = self._attr_scope(schema)
            if sc is not None:
                sc.inc("rows_emitted", len(rows))
        if not sinks:
            # retention off means off everywhere: an unbounded run must
            # not grow collected[] whether or not a sink consumes the
            # stream (the reference's StreamOutputHandler never retains —
            # it collects downstream, StreamOutputHandler.java:62-92)
            if self.retain_results:  # bulk path: drains carry millions
                self.collected.setdefault(sid, []).extend(
                    (epoch + rel_ts, row) for rel_ts, row in rows
                )
            return
        bucket = (
            self.collected.setdefault(sid, [])
            if self.retain_results
            else None
        )
        # a columnar sink attached to a stream that still decodes
        # row-wise (mixed consumers, side-channel artifacts, retained
        # results) gets the batch converted ONCE per emission — it
        # observes identical data on either lane (tier-1 equivalence)
        col_sinks = [
            s for s in sinks if hasattr(s, "accept_columns")
        ]
        row_sinks = [s for s in sinks if not hasattr(s, "accept_columns")]
        # sink delivery time is its own (nested) stage: callbacks are
        # user code whose cost must be visible in the breakdown
        with self.telemetry.span("sink"):
            if col_sinks:
                abs_ts = np.fromiter(
                    (epoch + r[0] for r in rows), np.int64, len(rows)
                )
                cols: Dict[str, np.ndarray] = {}
                for i, name in enumerate(schema.field_names):
                    c = np.empty(len(rows), dtype=object)
                    for j, r in enumerate(rows):
                        c[j] = r[1][i]
                    cols[name] = c
                for sink in col_sinks:
                    sink.accept_columns(abs_ts, cols)
            if row_sinks or bucket is not None:
                for rel_ts, row in rows:
                    abs_ts = epoch + rel_ts
                    if bucket is not None:
                        bucket.append((abs_ts, row))
                    for sink in row_sinks:
                        sink(abs_ts, row)

    def _emit_columns(
        self, schema, cb, rate_limit: bool = True
    ) -> None:
        """The columnar sink fast lane's emission tail: the batch stays
        columnar end to end — counts, traces, rate limiting and sink
        delivery all account arrays, never row tuples. Reached only for
        streams where _columnar_streams approved every consumer (the
        per-row _emit_rows path above is the fallback and the oracle)."""
        if not len(cb):
            return
        sid = schema.stream_id
        if rate_limit:
            limiter = self._rate_limiters.get(sid)
            if limiter is not None:
                for part in limiter.feed_columns(cb):
                    self._emit_columns(schema, part, rate_limit=False)
                return
        self.output_fields.setdefault(sid, schema.field_names)
        epoch = self._epoch_ms or 0
        clock = [f.name for f in schema.fields if f.on_clock]
        if clock:  # times on the job's clock leave as epoch ms
            cb = type(cb)(cb.ts, {
                **cb.cols,
                **{k: cb.cols[k] + np.int64(epoch) for k in clock},
            })
        # rows surfacing to a consumer complete their event's trace
        # (post-rate-limit, same contract as the row path)
        with self.telemetry.span("trace_complete"):
            self.tracer.complete_ts(epoch, cb.ts)
        self.emitted_counts[sid] = (
            self.emitted_counts.get(sid, 0) + len(cb)
        )
        if self.telemetry.enabled:
            # same attribution contract as the row path
            sc = self._attr_scope(schema)
            if sc is not None:
                sc.inc("rows_emitted", len(cb))
        sinks = self._sinks.get(sid)
        if self.retain_results:
            # the columnar gate excludes retained jobs; this defensive
            # path (direct _emit_columns callers) must not lose rows
            self.collected.setdefault(sid, []).extend(
                (epoch + rel_ts, row) for rel_ts, row in cb.rows()
            )
        if not sinks:
            return
        abs_ts = cb.ts + np.int64(epoch)
        with self.telemetry.span("sink"):
            rows = None
            for sink in sinks:
                acc = getattr(sink, "accept_columns", None)
                if acc is not None:
                    acc(abs_ts, cb.cols)
                else:  # defensive: gate guarantees none, stay correct
                    if rows is None:
                        rows = cb.rows()
                    for t, (_rel, row) in zip(abs_ts.tolist(), rows):
                        sink(t, row)

    @property
    def finished(self) -> bool:
        return (
            all(self._source_done)
            and all(self._control_done)
            and not any(batches for batches in self._pending.values())
            and not self._control_pending
        )

    def idle_source_ids(self) -> List[str]:
        """Stream ids of sources currently marked idle (safe to call
        off-thread; the REST health route reports it)."""
        return [
            getattr(src, "stream_id", f"source[{i}]")
            for i, (src, idle) in enumerate(
                zip(list(self._sources), list(self._source_idle))
            )
            if idle
        ]

    # fst:thread-root name=run-loop
    def run_cycle(self) -> int:
        """Pull, apply control, reorder, step, decode. Returns events
        processed. Control events take effect at micro-batch boundaries
        (the reference applies them per event; §3.4)."""
        self._stamp_runloop_owner()
        clock = self._starve_clock(make=True)
        if clock is not None:
            clock.cycle(True)
        try:
            with _hotloop_guard():
                return self._run_cycle_guarded()
        finally:
            if clock is not None:
                clock.cycle(False)

    def _starve_clock(self, make: bool = False) -> Optional[StarveClock]:
        """The run loop's starvation clock (telemetry/starve.py), or
        None: made by the first run cycle with telemetry on, dropped
        with telemetry off, so that no ``is_ready()`` is called then."""
        tel = self.telemetry
        if not tel.enabled:
            tel.stages.starve = None
            return None
        if tel.stages.starve is None and make:
            tel.stages.starve = StarveClock(tel.stages)
        return tel.stages.starve

    def _run_cycle_guarded(self) -> int:
        tel = self.telemetry
        tel.inc("cycles")
        with tel.span("ingest"):
            self._pull_sources()
            self._pull_control()
            self._apply_ready_control()
        with tel.span("reorder"):
            ready = self._release_ready()
        total = 0
        if ready:
            total = sum(len(b) for b in ready)
            self.processed_events += total
            if self._epoch_ms is None:
                self._epoch_ms = min(
                    int(b.timestamps.min()) for b in ready
                )
            for rt in list(self._plans.values()):
                if rt.enabled:
                    self._step_plan(rt, ready)
            self._cycles_since_drain += 1
        # advance any in-flight drain fetches (never blocks the host)
        with tel.span("drain"):
            for rt in self._plans.values():
                self._drain_poll(rt)
        # a partial segment must not wait forever for a slow source to
        # fill it: once its oldest staged tape reaches the drain
        # staleness budget, dispatch short — visibility latency stays
        # bounded by ~interval + drain time at any segment length (a
        # segment of one leaves nothing staged). (`is None` check, not
        # `or`: drain_interval_ms=0 means "tightest visibility", which
        # must not round up to 500ms). Not while the ticket window is
        # full: the run loop would wait at that dispatch for the device,
        # staging nothing, and the segment would go out a batch or two
        # short for good (at 0.24 s a batch four tapes take 0.4-0.5 s to
        # stage: three tapes and a padding tape cost the device 0.85 s,
        # the fourth and three paddings 0.64 s more); left open it fills
        # while the device works
        age_s = (
            500.0
            if self.drain_interval_ms is None
            else self.drain_interval_ms
        ) / 1e3
        now0 = time.monotonic()
        for rt in self._plans.values():
            if rt.seg_pending and (
                now0 - rt.seg_pending[0]["t"] >= age_s
            ) and not self._window_full(rt):
                self._dispatch_segment(rt)
        now = time.monotonic()
        if self.drain_interval_ms is not None:
            interval_s = self.drain_interval_ms / 1e3
            # DEADLINE-driven drain scheduling: the next drain is due
            # when the OLDEST undrained accumulator's matches reach the
            # staleness budget (dirty_since + interval) — not on a fixed
            # metronome whose phase is unrelated to how stale visible
            # matches already are. Fires on idle cycles too: a stalled
            # source must not delay visibility of matches already
            # produced. Plans NOBODY observes (no sinks, retention off)
            # never set a deadline: each drain costs a host<->device
            # round trip, and with no consumer there is no visibility
            # to bound — their capacity swaps below suffice.
            due = None
            for rt in self._plans.values():
                t0 = rt.dirty_since
                if t0 is not None and self._has_consumers(rt):
                    t = t0 + interval_s
                    if due is None or t < due:
                        due = t
            if due is not None and now >= due:
                with tel.span("drain"):
                    self._interval_drain()
            # time-mode rate limiters emit on their own schedule; poll
            # them on the fixed cadence (they hold host-side rows only)
            if now - self._last_full_drain >= interval_s:
                with tel.span("drain"):
                    self._poll_rate_limiters()
                self._last_full_drain = time.monotonic()
        if ready and self._cycles_since_drain >= min(
            self.drain_every_cycles,
            min(self._drain_hints.values(), default=self.drain_every_cycles),
        ):
            # capacity-bounding swap: resets the accumulator before the
            # no-overflow horizon, without a host sync
            self.drain_outputs(wait=False)
            self._cycles_since_drain = 0
        # SLO evaluation at the epoch boundary, AFTER this cycle's
        # drains so the merged drain histograms the objectives read
        # include the freshest completed work (rate-limited inside;
        # immediate no-op without policies)
        self.slo.evaluate()
        return total

    def _poll_rate_limiters(self) -> None:
        """Time-mode ``output ... every <duration>`` limiters emit on a
        schedule, not only when new rows arrive for their stream
        (siddhi's time-based limiters run off a scheduler thread;
        ADVICE r4): buffered output whose interval elapsed surfaces
        from the same interval-drain cadence that bounds visibility."""
        for sid, limiter in self._rate_limiters.items():
            if limiter.mode == "time":
                if not limiter.buf:
                    continue
            elif limiter.mode == "snapshot":
                if not limiter.cur:
                    continue
            else:
                continue
            self._emit_pending(sid, limiter.feed([]))

    def _emit_pending(self, sid: str, pending: List) -> None:
        """Emit limiter-released output to ``sid``'s first output schema
        (bypassing the limiter — it already passed it). Entries are
        ``(ts, row)`` pairs or ColumnBatch fragments, depending on
        which lane fed the limiter."""
        if not pending:
            return
        from ..compiler.output import ColumnBatch

        for rt in self._plans.values():
            schemas = rt.plan.output_streams().get(sid)
            if schemas:
                rows = [p for p in pending
                        if not isinstance(p, ColumnBatch)]
                if rows:
                    self._emit_rows(schemas[0], rows, rate_limit=False)
                for p in pending:
                    if isinstance(p, ColumnBatch):
                        self._emit_columns(
                            schemas[0], p, rate_limit=False
                        )
                return

    def _pull_control(self) -> None:
        for i, src in enumerate(self._control):
            if self._control_done[i]:
                continue
            events, wm, done = src.poll(self.batch_size)
            self._control_pending.extend(events)
            if wm is not None:
                self._control_wm[i] = max(self._control_wm[i], wm)
            if done:
                self._control_done[i] = True
                self._control_wm[i] = MAX_WM

    def _pop_ready_control(self) -> List:
        """Ready control events — ts at or below the current watermark
        (processing mode: all of them) — removed from the pending list
        in timestamp order. ONE definition of the epoch-boundary
        selection: the streaming loop applies what this returns, and
        control-in-replay (runtime/replay.py) partitions the bounded
        stream at the same boundaries, so the two modes cannot
        diverge."""
        pending = self._control_pending
        if not pending:
            return []
        pending.sort(key=lambda p: p[0])
        # index walk + one tail-del, not pop(0) per event: a control
        # backlog held behind the watermark gate can grow long, and the
        # O(n^2) front-pop drain was quadratic in it
        n_apply = len(pending)
        if self.time_mode != "processing":
            wm = self._watermark()
            n_apply = 0
            while n_apply < len(pending) and pending[n_apply][0] <= wm:
                n_apply += 1
        out = [ev for _ts, ev in pending[:n_apply]]
        if n_apply:
            del pending[:n_apply]
        return out

    def _apply_ready_control(self) -> None:
        for ev in self._pop_ready_control():
            try:
                self._apply_control(ev)
            except Exception:
                # a bad dynamic query (e.g. unparsable CQL pushed through
                # a control channel with no up-front validation) must not
                # take down the running queries
                _LOG.exception("control event rejected: %r", ev)

    def _watermark(self) -> int:
        """min watermark across non-idle sources + control streams.

        Idle sources are EXCLUDED (they stopped producing; their stale
        claim must not pin every other stream). When every data source
        is idle and there is no control stream the watermark HOLDS at
        the last gate value instead of jumping to MAX — idle means "no
        information", not "stream complete" (Flink idleness semantics).
        """
        idle = self._source_idle
        wms = [
            wm
            for i, wm in enumerate(self._source_wm)
            if not (i < len(idle) and idle[i])
        ] + self._control_wm
        if not wms:
            return self._gate_wm if self._sources else MAX_WM
        return min(wms)

    def _pending_total(self) -> int:
        return sum(len(b) for bs in self._pending.values() for b in bs)

    def _pull_sources(self) -> None:
        # graceful degradation (see __init__): over the pending bound,
        # 'block' stops pulling every source EXCEPT the watermark
        # laggards — the sources pinning the min watermark must keep
        # polling or the backlog could never release (single-source
        # jobs therefore keep pulling: their own watermark IS the min).
        over = (
            self.max_pending_events is not None
            and self._pending_total() >= self.max_pending_events
        )
        block = over and self.shed_policy == "block"
        if block:
            # the MONOTONE gate watermark: an idle (or just-un-idled)
            # laggard compares below it and keeps polling — exactly the
            # sources that must not stop for the backlog to release
            wm = max(self._watermark(), self._gate_wm)
        if len(self._source_idle) != len(self._sources):
            # bench/profilers swap job._sources directly (re_source);
            # re-size the per-source idle tracking rather than desync
            self._source_idle = [False] * len(self._sources)
            self._source_last_t = [None] * len(self._sources)
        timeout = self.idle_timeout_ms
        now = time.monotonic() if timeout is not None else 0.0
        for i, src in enumerate(self._sources):
            if self._source_done[i]:
                continue
            if block and self._source_wm[i] > wm:
                self.telemetry.inc("faults.backpressure_blocks")
                self._frec(
                    "fault.backpressure", stream=src.stream_id,
                )
                continue
            # the call into the source is the user's code
            with self.telemetry.span("source_pull"):
                batch, swm, done = self._poll(i, src)
            if batch is not None and len(batch):
                sid = src.stream_id
                self._pending.setdefault(sid, []).append(batch)
                bmax = int(batch.timestamps.max())
                # gate residency: per-batch arrival stamp; an entry is
                # retired only once the horizon passes ITS max ts
                self._pending_t.setdefault(sid, []).append(
                    (time.monotonic(), bmax)
                )
                if self._max_event_ts is None or bmax > self._max_event_ts:
                    self._max_event_ts = bmax
                # trace sampling stamps INGEST time (pre-reorder), so a
                # completed trace includes watermark-gate queueing
                with self.telemetry.span("trace_stamp"):
                    self.tracer.stamp_ingest(batch.timestamps)
                if timeout is not None:
                    self._source_last_t[i] = now
                    if self._source_idle[i]:
                        # un-idle on the next event: its watermark claim
                        # rejoins the min from this cycle on
                        self._source_idle[i] = False
                        self.telemetry.inc("idle.unidled")
                        self._frec(
                            "watermark.unidle", stream=src.stream_id
                        )
            elif timeout is not None and not self._source_idle[i]:
                if self._source_last_t[i] is None:
                    self._source_last_t[i] = now  # arm at first poll
                if (now - self._source_last_t[i]) * 1e3 >= timeout:
                    # temporarily idle: stops pinning the min watermark
                    # (visible in metrics()["sources"] and /health)
                    self._source_idle[i] = True
                    self.telemetry.inc("idle.marked")
                    self._frec(
                        "watermark.idle", stream=src.stream_id,
                        idle_ms=round(
                            (now - self._source_last_t[i]) * 1e3, 1
                        ),
                    )
                    _LOG.debug(
                        "source %s idle for %.0fms; excluded from the "
                        "min watermark until its next event",
                        src.stream_id, (now - self._source_last_t[i]) * 1e3,
                    )
            if swm is not None:
                self._source_wm[i] = max(self._source_wm[i], swm)
            if done:
                self._source_done[i] = True
                self._source_wm[i] = MAX_WM
                self._source_idle[i] = False
        if (
            self.max_pending_events is not None
            and self.shed_policy == "drop_oldest"
        ):
            self._shed_pending()

    # a source whose polls cost the run loop time is polled one batch
    # ahead once this many polls in a row have each brought a batch ...
    POLL_AHEAD_AFTER = 16
    # ... and each cost at least this many seconds
    POLL_AHEAD_MIN_S = 0.004

    def _poll(self, i: int, src: Source):
        """One poll of source ``i``, what ``src.poll`` returns. A
        source that hands over a batch at every poll and takes its time
        over each (a replay's or a backfill's: a file's parse, a
        generator; ``POLL_AHEAD_*``: one quick poll starts the count
        anew) is polled ONE batch ahead on the
        poll thread from then on, so that its work overlaps the run
        loop's: the run loop takes the poll in flight, waiting for it
        as it would have waited in ``src.poll``, and starts the next.
        The first poll that brings no batch ends it (a live source
        between events is asked when the run loop asks, as ever).
        Never a source with a ``state_dict``: a checkpoint records its
        position, and a batch polled ahead would lie past it and in no
        checkpoint. The source sees one ``poll`` at a time, in order,
        from one thread or the other."""
        ahead = self._polled_ahead.pop(i, None)
        if ahead is not None and ahead[0] is not src:
            ahead = None  # the source was replaced: its batch goes too
        if ahead is not None:
            res = ahead[1].result()
        else:
            t0 = time.perf_counter()
            res = src.poll(self.batch_size)
            cost = time.perf_counter() - t0
        batch, _swm, done = res
        if done or batch is None or not len(batch):
            self._poll_runs.pop(i, None)
            return res
        if ahead is None:
            polls = (
                self._poll_runs.get(i, 0) + 1
                if cost >= self.POLL_AHEAD_MIN_S else 0
            )
            self._poll_runs[i] = polls
            if polls < self.POLL_AHEAD_AFTER or hasattr(src, "state_dict"):
                return res
        self._polled_ahead[i] = (
            src, self._poll_pool.submit(self._poll_ahead, src)
        )
        return res

    # fst:thread-root name=poll-ahead
    def _poll_ahead(self, src: Source):
        with self.telemetry.span("source_poll_ahead"):
            return src.poll(self.batch_size)

    @property
    def _poll_pool(self):
        """One poll thread per job (``_poll``): polls in the order the
        run loop asked for them."""
        import concurrent.futures

        pool = getattr(self, "_poll_pool_", None)
        if pool is None:
            pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="fst-poll"
            )
            # fst:ephemeral lazily-created poll-thread pool; a fresh process rebuilds it
            self._poll_pool_ = pool
        return pool

    def _shed_pending(self) -> None:
        """'drop_oldest' enforcement: shed whole pending batches,
        oldest event time first, until the backlog is within bounds —
        louder than an OOM, cheaper than per-row surgery (a shed may
        overshoot by up to one batch)."""
        total = self._pending_total()
        if total <= self.max_pending_events:
            return
        shed = 0
        while total > self.max_pending_events:
            sid = min(
                (s for s, bs in self._pending.items() if bs),
                key=lambda s: int(self._pending[s][0].timestamps.min())
                if len(self._pending[s][0])
                else MAX_WM,
                default=None,
            )
            if sid is None:
                break
            batch = self._pending[sid].pop(0)
            if not self._pending[sid]:
                del self._pending[sid]
            total -= len(batch)
            shed += len(batch)
        if shed:
            self.shed_events += shed
            self.telemetry.inc("faults.shed_events", shed)
            # journal the burst (rate-collapsed: repeats within the
            # window fold into one entry; exact totals stay above)
            self._frec(
                "fault.shed", events=shed, policy="drop_oldest",
            )
            # rate-limited: under sustained overload a shed happens
            # every cycle — the counters carry the exact total; the
            # log line only needs to keep saying it is still happening
            now = time.monotonic()
            if now - self._shed_warned_at >= 1.0:
                self._shed_warned_at = now
                _LOG.warning(
                    "pending backlog over max_pending_events=%d: shed "
                    "%d oldest events (%d total shed so far); matches "
                    "they would have produced are LOST — raise the "
                    "bound or switch shed_policy to 'block'",
                    self.max_pending_events, shed, self.shed_events,
                )

    def _release_ready(self) -> List[EventBatch]:
        """Watermark gate: release per-stream prefixes with ts <= min
        watermark (processing mode releases everything).

        Event-time extras (docs/event_time.md): the gate watermark is
        MONOTONE (idle-source un-idling cannot drag it back); under the
        'allow' late policy the released horizon is held back by
        ``allowed_lateness_ms`` so rows late by at most the allowance
        still release in order; rows at or below the horizon already
        released are LATE and go to :meth:`_handle_late`. Telemetry:
        ``watermark.lag`` (max event time minus gate watermark) and
        ``gate.residency`` (buffer age of released rows)."""
        if self.time_mode == "processing":
            ready = [
                EventBatch.concat(bs).sort_by_time()
                for bs in self._pending.values()
                if bs
            ]
            # a released batch carries the earliest arrival of the
            # source batches it holds (latency legs)
            for sid in self._pending:
                entries = self._pending_t.get(sid)
                self._ready_arrival[sid] = (
                    entries[0][0] if entries else time.monotonic()
                )
            self._pending.clear()
            self._pending_t.clear()
            return ready
        raw = self._watermark()
        # the MAX end-of-stream sentinel releases everything but is
        # never PERSISTED as gate state: a checkpoint taken at stream
        # end restores into jobs that continue with MORE data (the
        # run-half + restore pattern), and a stored MAX horizon would
        # classify every continuation row late
        if raw != MAX_WM and raw > self._gate_wm:
            self._gate_wm = raw
        wm = MAX_WM if raw == MAX_WM else self._gate_wm
        eff = wm
        if (
            self.late_policy == "allow"
            and self.allowed_lateness_ms > 0
            and wm != MAX_WM
            and wm > MIN_WM
        ):
            # hold the released horizon back by the allowance: an
            # admitted-late row still merges IN ORDER because nothing
            # above (horizon - allowance) has been released yet
            eff = wm - self.allowed_lateness_ms
        tel = self.telemetry
        if (
            tel.enabled
            and self._max_event_ts is not None
            and MIN_WM < wm < MAX_WM
        ):
            tel.record_seconds(
                "watermark.lag",
                max(self._max_event_ts - wm, 0) / 1e3,
            )
        horizon = self._released_wm
        ready: List[EventBatch] = []
        now = time.monotonic()
        for sid in list(self._pending):
            merged = EventBatch.concat(self._pending[sid]).sort_by_time()
            if horizon > MIN_WM:
                # rows at or below the horizon the gate ALREADY
                # released past arrived too late to merge in order
                n_late = int(
                    np.searchsorted(
                        merged.timestamps, horizon, side="right"
                    )
                )
                if n_late:
                    self._handle_late(merged.slice(0, n_late))
                    merged = merged.slice(n_late, len(merged))
            n_ready = int(np.searchsorted(merged.timestamps, eff, side="right"))
            entries = self._pending_t.get(sid)
            if n_ready:
                ready.append(merged.slice(0, n_ready))
                # a released batch carries the earliest arrival of
                # the source batches it holds (latency legs)
                self._ready_arrival[sid] = entries[0][0] if entries else now
                if entries and tel.enabled:
                    # buffer age of the oldest batch still pending at
                    # this release: rows within a batch arrived
                    # together, so this is row-exact at batch
                    # granularity even across partial releases (the
                    # 'allow' holdback keeps rows for the full
                    # allowance, and the histogram must say so)
                    tel.record_seconds(
                        "gate.residency", now - entries[0][0]
                    )
            if entries is not None:
                # retire batches the horizon fully released (all rows
                # of a batch are <= its max ts); a partially-released
                # batch keeps its stamp for the rows it still holds
                while entries and entries[0][1] <= eff:
                    entries.pop(0)
            rest = merged.slice(n_ready, len(merged))
            if len(rest):
                self._pending[sid] = [rest]
            else:
                del self._pending[sid]
                self._pending_t.pop(sid, None)
        if not ready and self._pending and wm != MAX_WM:
            # the gate is holding data it cannot release this cycle —
            # a watermark stall (idle/lagging source, or the 'allow'
            # holdback). Rate-collapsed: a multi-second stall is one
            # journal entry with a repeat count, not one per cycle.
            self._frec(
                "watermark.stall",
                pending=self._pending_total(),
                gate_wm=(
                    int(self._gate_wm)
                    if self._gate_wm > MIN_WM
                    else None
                ),
            )
        if eff != MAX_WM:
            if eff > self._released_wm:
                self._released_wm = eff
        elif (
            self._max_event_ts is not None
            and self._max_event_ts > self._released_wm
        ):
            # end of stream: everything observed has been released, so
            # the max observed event time IS the horizon (exact), and
            # unlike the MAX sentinel it survives checkpoint-restore
            # into a continued stream
            self._released_wm = self._max_event_ts
        return ready

    def _handle_late(self, batch: EventBatch) -> None:
        """Apply the configured late policy to rows below the released
        horizon. Counters are EXACT (the disorder fault-injection tests
        reconcile them against the injected schedule)."""
        n = len(batch)
        self.late_events += n
        # journal the burst (rate-collapsed across repeats; the exact
        # per-policy totals live in the counters below)
        self._frec(
            "fault.late", events=n, policy=self.late_policy,
            stream=batch.stream_id,
        )
        tel = self.telemetry
        if tel.enabled:
            # late share, attributed where attributable: lateness is an
            # INPUT-stream fact, so it maps to a plan only when exactly
            # one live plan consumes the stream (a shared input's late
            # rows stay job-level — splitting them per consumer would
            # double count)
            consumers = [
                member
                for rt in list(self._plans.values())
                if batch.stream_id in rt.plan.spec.stream_codes
                for member in self._scope_plans_of(rt)
            ]
            if len(consumers) == 1:
                tel.scope("plan", consumers[0]).inc("late_events", n)
        if self.late_policy == "side_output":
            tel.inc("faults.late_side_output", n)
            self._emit_late(batch)
            return
        self.late_dropped += n
        tel.inc("faults.late_dropped", n)
        now = time.monotonic()
        if now - self._late_warned_at >= 1.0:
            self._late_warned_at = now
            if self.late_policy == "allow":
                _LOG.warning(
                    "%s: %d rows later than allowed_lateness_ms=%d "
                    "dropped (%d total). Admitting them would require "
                    "window RE-FIRE — retracting and re-emitting "
                    "already-released panes per the Dataflow model's "
                    "accumulation modes (PAPERS.md #5) — which this "
                    "engine rejects by design; see docs/event_time.md. "
                    "Raise allowed_lateness_ms or route them with "
                    "late_policy='side_output'.",
                    batch.stream_id, n, self.allowed_lateness_ms,
                    self.late_dropped,
                )
            else:
                _LOG.warning(
                    "%s: %d late rows dropped below the released "
                    "watermark (%d total; policy 'drop'). Use "
                    "late_policy='side_output' to capture them, or "
                    "'allow' + allowed_lateness_ms to admit bounded "
                    "lateness in order (docs/event_time.md).",
                    batch.stream_id, n, self.late_dropped,
                )

    def _emit_late(self, batch: EventBatch) -> None:
        """'side_output' delivery: the FULL input rows surface on the
        dedicated late channel ``late_stream(stream_id)`` — retained in
        collected[] under that id when retention is on, delivered to
        its sinks either way (ColumnarSink-capable: whole decoded
        column arrays, no per-row tuples for columnar-only consumers).
        """
        sid = late_stream(batch.stream_id)
        schema = batch.schema
        names = list(schema.field_names)
        self.output_fields.setdefault(sid, names)
        self.emitted_counts[sid] = (
            self.emitted_counts.get(sid, 0) + len(batch)
        )
        sinks = self._sinks.get(sid) or []
        col_sinks = [s for s in sinks if hasattr(s, "accept_columns")]
        row_sinks = [s for s in sinks if not hasattr(s, "accept_columns")]
        need_rows = bool(row_sinks) or self.retain_results
        if col_sinks:
            cols: Dict[str, np.ndarray] = {}
            for name in names:
                col = batch.columns[name]
                if schema.field_type(name).is_encoded:
                    cols[name] = np.asarray(
                        schema.string_tables[name].decode(col),
                        dtype=object,
                    )
                else:
                    cols[name] = col
            with self.telemetry.span("sink"):
                for sink in col_sinks:
                    sink.accept_columns(batch.timestamps, cols)
        if not need_rows:
            return
        rows = [
            (int(ts), tuple(rec[n] for n in names))
            for ts, rec in zip(
                batch.timestamps.tolist(), batch.records()
            )
        ]
        if self.retain_results:
            self.collected.setdefault(sid, []).extend(rows)
        if row_sinks:
            with self.telemetry.span("sink"):
                for ts, row in rows:
                    for sink in row_sinks:
                        sink(ts, row)

    def _plan_windows(
        self, rt: _PlanRuntime, ready: List[EventBatch]
    ) -> List[List[EventBatch]]:
        """Split a ready set into the tape windows this plan will step.

        Compile-window cap (wide multi-query stacks): oversized
        micro-batches step in chunks so the compiled program stays at a
        tractable tape width. Single-input plans only — chunking a
        multi-stream merge would need a time-aligned cut per stream
        (stacked groups are single-stream by construction)."""
        plan = rt.plan
        involved = [
            b for b in ready if b.stream_id in plan.spec.stream_codes
        ]
        if not involved:
            return []
        total = sum(len(b) for b in involved)
        limit = plan.tape_capacity_limit
        if limit and total > limit and len(involved) == 1:
            b = involved[0]
            return [
                [b.slice(s, min(s + limit, len(b)))]
                for s in range(0, len(b), limit)
            ]
        return [involved]

    def _step_plan(
        self, rt: _PlanRuntime, ready: List[EventBatch]
    ) -> None:
        for involved in self._plan_windows(rt, ready):
            self._stage_fused(rt, involved)

    def _stage_tape(
        self, rt: _PlanRuntime, involved: List[EventBatch]
    ):
        """Host half of one step: build the wire tape (interning group
        keys as a side effect) and retain lazy-projection columns in the
        ring. Shared by the streaming dispatch path below and the
        bounded-replay pre-stager (runtime/replay.py). The caller is
        responsible for ``plan.grow_state`` before the jitted step."""
        with self.telemetry.span("tape_build"):
            return self._stage_tape_body(rt, involved)

    def _stage_tape_body(
        self, rt: _PlanRuntime, involved: List[EventBatch]
    ):
        plan = rt.plan
        total = sum(len(b) for b in involved)
        rt.tape_capacity = max(rt.tape_capacity, bucket_size(total))
        # lazy-ring retention is decode-side state: a plan NOBODY
        # observes (no sinks, retention off) never decodes ordinals,
        # so retaining projection columns for it is pure memcpy waste.
        # A sink attached later starts a fresh ordinal base (the
        # lazy_base=None adopt-from-device path) — rows produced
        # before the attach are counted-not-delivered by the add_sink
        # contract, so nothing they would have decoded is ever read.
        retain_lazy = (
            getattr(rt, "lazy", None) is not None
            and self._has_consumers(rt)
        )
        tape, _prov = build_wire_tape(
            plan.spec, involved, self._epoch_ms, rt.wire_kinds,
            capacity=rt.tape_capacity,
            # the merged-order provenance map is only consulted by the
            # multi-batch lazy retention below
            want_prov=retain_lazy and len(involved) > 1,
            # nested in tape_build: interning the group keys, expiry
            intern_span=lambda: self.telemetry.span("group_intern"),
        )
        self._count_groups(rt)
        if retain_lazy:
            if rt.lazy_base is None:
                # first step (or first after restore): adopt the device
                # counter so host ring and device ordinals share a base
                rt.lazy_base = int(
                    np.asarray(
                        rt.states[rt.lazy_state_name]["seen"]
                    )
                )
            if rt.lazy_base + total > _LAZY_ORD_WRAP:
                # int32 ordinal space: reset both sides well before the
                # device counter could wrap (undrained in-flight matches
                # from before the reset decode None — one warned event
                # per ~1B processed)
                self._drain_plan(rt)
                states = dict(rt.states)
                sub = dict(states[rt.lazy_state_name])
                sub["seen"] = jnp.zeros((), jnp.int32)
                states[rt.lazy_state_name] = sub
                rt.states = states
                rt.lazy_base = 0
                rt.lazy = _LazyRing(rt.lazy.budget)
                _LOG.warning(
                    "%s: lazy ordinal space reset (wrap horizon)",
                    plan.plan_id,
                )
            # retain the merged-order values of projection-only columns;
            # the device will emit ordinals into this ring's space
            lcols: Dict[str, np.ndarray] = {}
            if len(involved) == 1:
                # single sorted batch: merged order == batch order — a
                # plain copy replaces the provenance gather. The copy is
                # NOT optional: sources may legally reuse column buffers
                # across polls, and event-time releases are views into a
                # larger concat base (aliasing would both corrupt later
                # decodes and break the ring's byte accounting)
                b = involved[0]
                for key in rt.lazy_keys:
                    sid, fname = key.split(".", 1)
                    if b.stream_id == sid:
                        lcols[key] = np.array(b.columns[fname])
                if rt.lazy_ts:
                    lcols["@ts"] = (
                        b.timestamps - self._epoch_ms
                    ).astype(np.int32)
            else:
                for key in rt.lazy_keys:
                    sid, fname = key.split(".", 1)
                    col = None
                    for bi, b in enumerate(involved):
                        if b.stream_id != sid:
                            continue
                        sel = _prov[:, 0] == bi
                        if col is None:
                            col = np.zeros(
                                total, dtype=b.columns[fname].dtype
                            )
                        col[sel] = b.columns[fname][_prov[sel, 1]]
                    if col is not None:
                        lcols[key] = col
                if rt.lazy_ts:
                    tcol = np.zeros(total, dtype=np.int32)
                    for bi, b in enumerate(involved):
                        sel = _prov[:, 0] == bi
                        tcol[sel] = (
                            b.timestamps[_prov[sel, 1]] - self._epoch_ms
                        ).astype(np.int32)
                    lcols["@ts"] = tcol
            rt.lazy.push(rt.lazy_base, lcols)
            rt.lazy_base += total
        return tape

    def _count_groups(self, rt: _PlanRuntime) -> None:
        """The group tables' counters, read from the encoders after a
        batch was interned: ``groups.interned`` (keys given a slot),
        ``groups.slots_reused`` (of them, into a freed slot),
        ``groups.expired`` (slots freed), gauge ``groups.live``."""
        tel = self.telemetry
        encoded = rt.plan.spec.encoded
        if not encoded or not tel.enabled:
            return
        for name in sorted({k for e in encoded for k in e.encoder.stats}):
            total = sum(e.encoder.stats.get(name, 0) for e in encoded)
            seen = rt.group_stats.get(name, 0)
            if total != seen:
                # a key source's own counter carries its full name
                # (``join.left_events``); the table's are ``groups.*``
                tel.inc(name if "." in name else f"groups.{name}",
                        total - seen)
                rt.group_stats[name] = total
        tel.gauge("groups.live", sum(e.encoder.live for e in encoded))

    def _count_merges(self, rt: _PlanRuntime) -> None:
        """One dispatched batch: per blocked sliding-window artifact a
        ``window.merge_steps``, and a ``window.merge_steps_static`` where
        its merge order is the trace-time one (a length window;
        compiler/window_merge.py). The step does not decide, the
        compiled query did, so the host books it."""
        for a in rt.plan.artifacts:
            form = getattr(a, "merge_form", None)
            if form is not None:
                self.telemetry.inc("window.merge_steps")
                if form == "static":
                    self.telemetry.inc("window.merge_steps_static")

    def _grow_states(self, rt: _PlanRuntime) -> None:
        """Host interning may have discovered more group keys than the
        state tables hold: re-bucket them before the jitted step (a
        shape change, so a one-off retrace: ``groups.regrow``)."""
        plan = rt.plan
        grown = plan.grow_count
        rt.states = plan.grow_state(rt.states)
        if plan.grow_count != grown:
            self.telemetry.inc("groups.regrow", plan.grow_count - grown)

    # -- streaming dispatch (scan-of-microbatches segments) ----------------
    def _fused_k(self, rt: _PlanRuntime) -> int:
        """How many batches make a dispatch of this plan: the configured
        K (None, 0 and 1: one), clamped so the accumulator can hold a
        whole segment's emissions (there is no mid-segment drain — the
        bound the bounded replay applies via the drain hint)."""
        k = self.fused_segment_len
        if not k or k <= 1:
            return 1
        hint = self._drain_hints.get(rt.plan.plan_id)
        if hint:
            k = min(k, hint)
        return max(1, k)

    def _stage_fused(
        self, rt: _PlanRuntime, involved: List[EventBatch]
    ) -> None:
        """Stage one micro-batch tape toward the current segment (host
        side only — the segment uploads in one async device_put at
        dispatch, which the in-flight ticket window overlaps with the
        PREVIOUS segment's compute). A structural break (wire kinds
        widened, capacity grew) flushes the shorter segment first so
        one compiled scan shape serves each structure."""
        tape = self._stage_tape(rt, involved)
        # the staging bookkeeping accrues to tape_build (it IS part of
        # building this batch's staged form); the dispatch calls below
        # open their own top-level spans, so they stay outside
        with self.telemetry.span("tape_build"):
            self._update_drain_hint(
                rt.plan, tape.capacity,
                lambda name: rt.states.get(name),
            )
            sig = _wire_sig(tape)
        if rt.seg_pending and rt.seg_pending[0]["sig"] != sig:
            self._dispatch_segment(rt)
        with self.telemetry.span("tape_build"):
            now = time.monotonic()
            rt.seg_pending.append(
                {
                    "tape": tape,
                    "sig": sig,
                    "t": now,  # staged
                    "arrival": self._arrival_of(involved, now),
                    "events": sum(len(b) for b in involved),
                }
            )
            self.telemetry.inc("fusion.batches")
            self._count_merges(rt)
        if len(rt.seg_pending) >= self._fused_k(rt):
            self._dispatch_segment(rt)

    def _dispatch_segment(self, rt: _PlanRuntime) -> None:
        """Upload + dispatch the pending tapes as ONE scanned device
        call. The stacked segment crosses host->device in a single
        async ``jax.device_put`` issued while the previous segment's
        compute is still in flight (the backpressure window keeps >= 2
        segments outstanding), so ingest H2D and device compute
        double-buffer — counted per upload in fusion.h2d_overlapped.
        A partial segment (end of stream, checkpoint boundary,
        structural break) pads with empty tapes to the full segment
        length so the compiled scan stays one shape — padding tapes
        carry zero valid events and are row-inert (the replay's
        proof)."""
        pending = rt.seg_pending
        if not pending:
            return
        rt.seg_pending = []
        wires = [e["tape"] for e in pending]
        k_full = max(self._fused_k(rt), len(wires))
        while len(wires) < k_full:
            wires.append(_empty_wire_like(wires[-1]))
        tel = self.telemetry
        rec, seg_id = self._open_segment(
            rt,
            [e["arrival"] for e in pending],
            [e["t"] for e in pending],
            [e["events"] for e in pending],
        )
        with tel.span("stage.h2d_overlap", seg=seg_id):
            # overlap proof: the upload is issued while the device is
            # still busy with the previous segment — counted, not
            # asserted. The NEWEST ticket is the previous segment's
            # dispatch (tickets retire oldest-first, so checking [0]
            # would undercount overlap whenever an older ticket
            # happened to retire but not yet pop)
            busy = bool(rt.tickets) and not rt.tickets[-1].is_ready()
            seg = jax.device_put(_stack_wires(wires))
        tel.inc("fusion.h2d_uploads")
        if busy:
            tel.inc("fusion.h2d_overlapped")
        plan = rt.plan
        with self._compile_scope(rt), tel.span("dispatch", seg=seg_id):
            t0 = time.monotonic()
            # host interning during staging may have discovered new
            # group keys: grow once per segment, before the scanned
            # call (host-driven re-bucketing = staging-class work)
            with _staging_allow():
                self._grow_states(rt)
            self._issue_step()
            rt.states, rt.acc = rt.jitted_seg(rt.states, rt.acc, seg)
            rt.acc_dirty = True
            if rt.dirty_since is None:
                # backdate to the OLDEST staged tape's staging time:
                # its events have been in hand since then, so the
                # drain deadline (and the schema-gated drain.staleness
                # histogram) must count the staging wait too — else a
                # paced load's visibility is ~2x interval while the
                # histogram reports ~1x
                rt.dirty_since = pending[0]["t"]
            if tel.enabled:
                # per-segment enqueue time, the dispatch's host side (the
                # device wall hides behind the ticket: leg.device, legs.py)
                tel.record_seconds(
                    "dispatch.enqueue", time.monotonic() - t0
                )
                tel.inc("fusion.dispatches")
        # outside the compile-attribution scope (see _ticket_window)
        self._ticket_window(rt, rec, seg_id)
        if plan.has_flush and (
            rt.flush_warm is None
            or rt.flush_warm[0] != self._state_sig(rt.states)
        ):
            self._warm_flush(rt)

    def _issue_step(self) -> None:
        """A step or segment is about to be called: whatever the device
        lacked, it has work from here (the starvation clock's
        ``starved.dispatch`` runs up to this call)."""
        clock = self._starve_clock()
        if clock is not None:
            clock.issue()

    def _arrival_of(self, involved: List[EventBatch], now: float) -> float:
        """The earliest arrival (source pull) behind these released
        batches, as _release_ready left it."""
        return min(
            self._ready_arrival.get(b.stream_id, now) for b in involved
        )

    def _open_segment(
        self, rt: _PlanRuntime, arrival: List[float],
        staged: List[float], events: List[int],
    ) -> Tuple[Optional[SegmentRecord], int]:
        """Number the segment about to be dispatched and, with
        telemetry on, open its latency-leg record (the ``dispatch``
        stamp is taken here, before the upload). Returns the record
        (None with telemetry off) and the ordinal."""
        self._seg_ordinal += 1
        seg = self._seg_ordinal
        if not self.telemetry.enabled:
            return None, seg
        rec = SegmentRecord(seg, arrival, staged, events, time.monotonic())
        rt.seg_open.append(rec)
        return rec, seg

    # device work that may wait behind the dispatch that is running
    MAX_QUEUED_S = 1.0

    def _inflight_depth(self, rt: _PlanRuntime) -> int:
        """Dispatches the ticket window lets wait on the device:
        ``max_inflight_cycles`` of them, and no more than about
        ``MAX_QUEUED_S`` seconds of work, one at the least. A deep
        queue hides the host's jitter behind a fast step (six segments
        of 60 ms); behind a slow one it hides nothing more and delays
        every drain by its whole length: a drain's data slice is
        dispatched when its count prefix is back, so it runs after all
        that was queued meanwhile, the fetch thread takes one drain at a
        time, a swap follows every segment whose accumulator holds no
        more, and at ``MAX_PENDING_DRAINS`` the run loop then waits for
        a fetch that waits for the queue to run dry, and races ahead
        again once it has (six segments of 0.97 s: 6 s without a
        delivery, then 3 s, at start-up and after any hiccup since).
        The device's time for a dispatch is what two waits in a row
        measure; until they have, the first dispatch that finds another
        still running waits for both."""
        if rt.dispatch_s is None:
            return 1 if len(rt.tickets) < 2 else 0
        return max(1, min(
            self.max_inflight_cycles,
            int(self.MAX_QUEUED_S / max(rt.dispatch_s, 1e-4)),
        ))

    def _window_full(self, rt: _PlanRuntime) -> bool:
        """Whether a dispatch now would make the run loop wait in the
        ticket window (never, before the device's pace is known)."""
        if rt.dispatch_s is None:
            return False
        while rt.tickets and rt.tickets[0].is_ready():
            rt.tickets.popleft()
        return len(rt.tickets) >= self._inflight_depth(rt)

    def _ticket_window(
        self, rt: _PlanRuntime, rec: Optional[SegmentRecord], seg_id: int
    ) -> None:
        """Sliding-window backpressure: a tiny non-donated "ticket" is
        derived from the new state at each dispatch; completed tickets
        retire via is_ready polling (free), and only when the device
        is a full window behind does the host genuinely block. Holding
        tickets (fresh jit outputs) never blocks state-buffer
        donation. The ticket is created OUTSIDE the compile-attribution
        scope: the one-shot helper jit (_make_ticket's _noop_jit) is
        process-wide harness plumbing shared by every plan —
        attributing its single lowering to whichever plan happened to
        dispatch first would misattribute it, and would break the fleet
        bootstrap's zero-new-lowerings pin (metrics()["compiles"],
        docs/fleet.md)."""
        tel = self.telemetry
        ticket = self._make_ticket(rt.states)
        rt.tickets.append(ticket)
        while rt.tickets and rt.tickets[0].is_ready():
            rt.tickets.popleft()
        # the depth of the queue the device works through, this segment
        # included: over fusion.dispatches, its mean
        tel.inc("segments.inflight_sum", len(rt.tickets))
        rt.dispatched += 1
        depth = self._inflight_depth(rt)
        while len(rt.tickets) > depth:
            ordinal = rt.dispatched - len(rt.tickets) + 1
            with tel.span("backpressure_wait", seg=seg_id):
                jax.block_until_ready(rt.tickets.popleft())
            now = time.monotonic()
            if rt.waited is not None and rt.waited[0] == ordinal - 1:
                # the device went from the last ticket waited on
                # straight to this one: its time for one dispatch
                rt.dispatch_s = now - rt.waited[1]
                depth = self._inflight_depth(rt)
            rt.waited = (ordinal, now)
            while rt.tickets and rt.tickets[0].is_ready():
                rt.tickets.popleft()
        clock = self._starve_clock()
        if clock is not None:
            # from here the clock's polls retire the ticket and stamp
            # the record's ``complete``; handed over after the wait, in
            # which the queue cannot run dry (starved.backpressure_wait
            # is 0 by construction)
            clock.watch(ticket, rec)

    def _update_drain_hint(self, plan, tape_capacity, state_of) -> None:
        """Capacity-bounding swap cadence: each artifact declares its
        widest per-cycle emission block (joins fan out, patterns carry
        pools, batch windows flush whole grids). A swap resets the
        accumulator to empty, so no overflow requires (k+1)*block <= cap;
        the extra /2 keeps the historical safety margin for in-flight
        cycles dispatched between the hint check and the swap."""
        cap = plan.acc_capacity()

        def cycles(a) -> int:
            if hasattr(a, "safe_cycles"):
                # a block that is wide for one rare step (a window
                # join's closing): the artifact knows what fits
                return a.safe_cycles(tape_capacity, state_of(a.name), cap)
            block = (
                a.emit_block_width(tape_capacity, state_of(a.name))
                if hasattr(a, "emit_block_width")
                else tape_capacity
            )
            return cap // (2 * max(block, 1)) - 1

        cap_cycles = max(1, min(
            (cycles(a) for a in plan.artifacts),
            default=cap // (2 * max(tape_capacity, 1)) - 1,
        ))
        self._drain_hints[plan.plan_id] = cap_cycles

    def _decode_outputs(
        self, plan: CompiledPlan, outputs: Dict, only=None, lookup=None,
        columnar_streams=frozenset(), lookup_np=None,
    ) -> None:
        from ..compiler.output import ColumnBatch

        for a in plan.artifacts:
            if only is not None and a.name not in only:
                continue
            out = outputs[a.name]
            schema = a.output_schema
            columnar = schema.stream_id in columnar_streams
            if a.output_mode == "aligned":
                mask, ts, cols = out
                mask = np.asarray(mask)
                if not mask.any():
                    continue
                if columnar:
                    self._emit_columns(
                        schema,
                        schema.decode_aligned_columns(
                            mask, np.asarray(ts), cols
                        ),
                    )
                    continue
                rows = schema.decode_aligned(mask, np.asarray(ts), cols)
            elif a.output_mode == "packed":
                count, block = out[0], out[1]
                if len(out) > 2 and int(out[2]) > 0:
                    _LOG.warning(
                        "%s: %d emissions dropped (stacked emission "
                        "buffer overflow)", a.name, int(out[2]),
                    )
                    self.telemetry.inc(
                        "faults.emissions_dropped", int(out[2])
                    )
                if int(count) == 0:
                    continue
                block = np.asarray(block)
                if hasattr(a, "decode_packed"):
                    if columnar and hasattr(a, "decode_packed_columns"):
                        decoded = a.decode_packed_columns(
                            int(count), block, lookup_np=lookup_np
                        )
                    elif getattr(a, "wants_lookup", False):
                        decoded = a.decode_packed(
                            int(count), block, lookup=lookup
                        )
                    else:
                        decoded = a.decode_packed(int(count), block)
                    counters = getattr(a, "drain_counters", None)
                    for sch, payload in decoded:
                        if counters is not None and self.telemetry.enabled:
                            # rows that leave outside a drain (a flush)
                            for name, n in counters(payload).items():
                                self.telemetry.inc(name, n)
                        if isinstance(payload, ColumnBatch):
                            self._emit_columns(sch, payload)
                        else:
                            self._emit_rows(sch, payload)
                    continue
                if columnar:
                    self._emit_columns(
                        schema,
                        schema.decode_packed_columns(int(count), block),
                    )
                    continue
                rows = schema.decode_packed_block(int(count), block)
            else:  # buffered
                count, ts, cols = out
                if int(count) == 0:
                    continue
                if columnar:
                    self._emit_columns(
                        schema,
                        schema.decode_columns(
                            int(count), np.asarray(ts), cols
                        ),
                    )
                    continue
                rows = schema.decode_buffered(
                    int(count), np.asarray(ts), cols
                )
            self._emit_rows(schema, rows)

    # -- checkpoint / restore (exceeds the reference: restore of engine
    # state was an abandoned TODO there, AbstractSiddhiOperator.java:341) --
    def _prepare_sink_commits(self) -> None:
        """Phase one of the transactional-sink commit protocol
        (runtime/kafka.py KafkaSink): after the drain surfaced every
        row, each capable sink flushes them into its open transaction
        and stamps the transaction pending, so the snapshot about to
        be captured carries its identity. Sinks without the hook are
        untouched."""
        for sinks in self._sinks.values():
            for s in sinks:
                prep = getattr(s, "prepare_commit", None)
                if prep is not None:
                    prep()

    def commit_sink_transactions(self) -> None:
        """Phase two, driven by the supervisor only once the snapshot
        that will never re-emit the pending rows is durably on disk:
        EndTxn(commit) on every transactional sink. A crash BEFORE
        this call is healed at restore — the snapshot's pending
        identity is resumed; a crash AFTER it finds the transaction
        already closed (INVALID_TXN_STATE, treated as committed)."""
        for sinks in self._sinks.values():
            for s in sinks:
                commit = getattr(s, "commit_transaction", None)
                if commit is not None:
                    commit()

    # fst:runloop-only (drains + reads device state)
    def snapshot(self) -> Dict:
        from .checkpoint import snapshot_job

        # accumulated-but-undrained emissions are not part of the snapshot;
        # surface them to collectors/sinks first so nothing is lost
        self.drain_outputs()
        # transactional sinks: flush the drained rows into the open
        # transaction and stamp it pending BEFORE the capture, so the
        # snapshot carries the transaction identity (checkpoint.py
        # "sinks" block) — the restore side resumes exactly that commit
        self._prepare_sink_commits()
        return snapshot_job(self)

    # fst:runloop-only (drains + captures device state)
    def save_checkpoint(self, path: str, keep: int = 1) -> None:
        """``keep > 1`` retains the K latest checkpoint generations
        (path, path.1, ..; checkpoint.save rotation) so a restore can
        fall back past a checkpoint a crash made unreadable."""
        import os

        from .checkpoint import save

        # same contract as snapshot(): surface accumulated emissions
        # first, then phase one of the transactional-sink protocol
        self.drain_outputs()
        self._prepare_sink_commits()
        # journal BEFORE the state capture: the save event itself is
        # part of the snapshot, so a restored journal shows the save
        # that produced it (exactly once). fspath, not the raw
        # argument: a journaled pathlib.Path would pickle fine but be
        # refused by the restore safelist unpickler — a checkpoint
        # unrestorable exactly when it is needed
        self._frec(
            "checkpoint.save", path=os.fspath(path), keep=int(keep),
            processed_events=int(self.processed_events),
        )
        save(self, path, keep=keep)

    # fst:runloop-only (replaces device state wholesale)
    def restore(self, snapshot_or_path) -> None:
        import os

        from .checkpoint import load, restore_job

        if isinstance(snapshot_or_path, (str, os.PathLike)):
            load(self, os.fspath(snapshot_or_path))
        else:
            restore_job(self, snapshot_or_path)
        # after restore_job adopted the checkpointed journal: the
        # restore event extends it with the next monotone seq
        self._frec(
            "checkpoint.restore",
            processed_events=int(self.processed_events),
            plans=len(self._plans),
        )

    # -- observability ------------------------------------------------------
    # The reference only counts processed events per runtime, logged at
    # shutdown (AbstractSiddhiOperator.java:117,147); this is queryable.
    def metrics(self, drain: bool = False) -> Dict[str, object]:
        """Snapshot of counters. ``drain=False`` (default) reads only
        host-side state — safe to call from another thread (e.g. the REST
        service) while the run loop owns the device; emitted counts are
        then as-of the last drain. ``drain=True`` flushes the device
        accumulators first and must be called from the run-loop thread."""
        if drain:
            self.drain_outputs()
        wm = self._watermark()
        telemetry = self.telemetry.snapshot()
        # per-event trace sampling view (tracing.py): sample rate,
        # stamp/completion counters, and the true end-to-end histogram
        telemetry["trace"] = self.tracer.snapshot()
        return {
            "processed_events": self.processed_events,
            # list() snapshots below: the run-loop thread mutates these
            # dicts concurrently with off-thread metrics readers
            "plans": {
                **{
                    pid: {
                        "enabled": rt.enabled,
                        "tenant": self.tenant_of(pid),
                    }
                    for pid, rt in list(self._plans.items())
                    if not pid.startswith(("@dyn:", "@shr:"))
                },
                **{
                    pid: {
                        "enabled": on,
                        "tenant": self.tenant_of(pid),
                    }
                    for pid, on in list(self._folded_enabled.items())
                },
            },
            # per-tenant rollup (docs/observability.md): plan scopes
            # merged per tenant — counters summed, histograms folded
            # bucket-exactly via LatencyHistogram.merge
            "tenants": self.tenant_rollup(),
            # admitted-vs-measured footprint meter, per runtime
            "footprint": self.footprint_status(),
            "emitted": dict(self.emitted_counts),
            "pending_batches": sum(
                len(b) for b in list(self._pending.values())
            ),
            "watermark": None if wm in (MAX_WM, MIN_WM) else wm,
            # event-time robustness view (docs/event_time.md): per-
            # source watermark + idle state, and the late-row account
            "sources": [
                {
                    "stream_id": getattr(src, "stream_id", None),
                    "watermark": (
                        None if swm in (MAX_WM, MIN_WM) else int(swm)
                    ),
                    "idle": bool(idle),
                    "done": bool(done_),
                }
                for src, swm, idle, done_ in zip(
                    list(self._sources),
                    list(self._source_wm),
                    list(self._source_idle)
                    + [False] * len(self._sources),
                    list(self._source_done),
                )
            ],
            "idle_sources": self.idle_source_ids(),
            "late_events": self.late_events,
            "late_dropped": self.late_dropped,
            "late_policy": self.late_policy,
            # control-plane view (docs/control_plane.md): the control.*
            # counters also land in telemetry["counters"]; this block
            # adds the AOT cache stats and the recent-refusal ring so a
            # refused tenant add is diagnosable from one snapshot
            "control": self.control_status(
                counters=telemetry.get("counters", {})
            ),
            # permanent compile telemetry (telemetry/compile_events.py):
            # per-plan-signature lowering counts + duration histogram
            "compiles": self._compile_sink.snapshot(),
            # serving-fleet view (fleet/, docs/fleet.md): replica
            # identity, warm-store hit/miss/persist counters, commit
            # epoch, last handoff — None outside a fleet
            "fleet": self.fleet_status(),
            # measured limiting-leg attribution over the live stage
            # ledger (telemetry/attribution.py; shares against the
            # attributed total — bench states them against the mode's
            # measured wall-clock window instead)
            "attribution": _attr_limiting_leg(
                telemetry.get("stages", {}),
                None,
                "streaming",
                telemetry.get("histograms", {}),
            ),
            # flight-recorder summary (GET /api/v1/flightrecorder has
            # the filterable journal itself)
            "flight_recorder": {
                "seq": self.flightrec.seq,
                "by_kind": self.flightrec.counts_by_kind(),
            },
            # SLO watchdog view (telemetry/slo.py): per-tenant
            # compliance, burn rates, and the journal-reconciled
            # violation account (GET /api/v1/slo serves it standalone)
            "slo": self.slo.snapshot(),
            # stage-attributed wall clock, latency histograms (drain.*
            # legs at least; jobs under bench add more), counters —
            # an atomic registry snapshot, safe off-thread
            "telemetry": telemetry,
        }

    def control_status(self, counters=None) -> Dict[str, object]:
        """Host-side control-plane snapshot (safe off-thread): the
        control.* counters, AOT cache stats, and recent refusals.
        ``counters`` lets a caller that already holds a telemetry
        snapshot (``metrics()``) avoid taking a second one."""
        if counters is None:
            tel = getattr(self, "telemetry", None)
            counters = (
                tel.snapshot().get("counters", {})
                if tel is not None
                else {}
            )
        with self._rejections_lock:
            rejections = dict(self.control_rejections)
        return {
            "counters": {
                k.split("control.", 1)[1]: v
                for k, v in counters.items()
                if k.startswith("control.")
            },
            "aot_cache": self.aot_cache.stats(),
            "rejections": rejections,
            # shared-subplan table (analysis/share.py): per share key,
            # the producer host + member refcount — what the retire
            # refcounting and the bench's sub-linear-lowerings claim
            # are checked against
            "shared": {
                key: {
                    "host": e["host_id"],
                    "mid": e["mid"],
                    "members": list(e["members"]),
                }
                for key, e in dict(self._shared).items()
            },
        }

    def query_listing(self) -> List[Dict[str, object]]:
        """The whole fleet in one poll (GET /api/v1/queries): id,
        tenant, enabled state, and fold host/slot per live plan. Safe
        off-thread — GIL-atomic snapshots only, same discipline as
        plan_ids."""
        out: List[Dict[str, object]] = []
        folded = dict(self._folded)
        folded_enabled = dict(self._folded_enabled)
        shared_member = dict(self._shared_member)
        for pid in self.plan_ids:
            f = folded.get(pid)
            if f is not None:
                enabled = bool(folded_enabled.get(pid, True))
                fold = {"host": f[0], "slot": int(f[1])}
            else:
                rt = self._plans.get(pid)
                enabled = bool(rt.enabled) if rt is not None else False
                fold = None
            skey = shared_member.get(pid)
            se = self._shared.get(skey) if skey is not None else None
            out.append(
                {
                    "id": pid,
                    "tenant": self.tenant_of(pid),
                    "enabled": enabled,
                    "folded": fold,
                    "shared": (
                        None if se is None
                        else {"host": se["host_id"], "key": skey}
                    ),
                }
            )
        return out

    def plan_metrics(self, plan_id: str) -> Dict[str, object]:
        """One plan's scoped metrics (GET /api/v1/queries/<id>):
        counters/gauges/histograms of its scope, plus — for a folded
        member — the shared host's footprint (the member's state lives
        inside the host's padded group). Safe off-thread."""
        scopes = self.telemetry.scope_map("plan")
        reg = scopes.get(plan_id)
        out: Dict[str, object] = {}
        if reg is not None:
            snap = reg.snapshot()
            out = {
                "counters": snap.get("counters", {}),
                "gauges": snap.get("gauges", {}),
                "histograms": snap.get("histograms", {}),
            }
        f = self._folded.get(plan_id)
        if f is not None:
            host = scopes.get(f[0])
            if host is not None:
                measured = host.gauge_value("footprint.measured_bytes")
                if measured is not None:
                    out["host_footprint"] = {
                        "host": f[0],
                        "measured_bytes": int(measured),
                    }
        return out

    def tenant_rollup(self) -> Dict[str, Dict[str, object]]:
        """metrics()["tenants"]: every tenant's plan scopes rolled up —
        counters summed exactly, drain histograms folded with
        ``LatencyHistogram.merge`` (the same associative primitive the
        sharded decode fold uses), plus the tenant scope's own
        control-path counters (cache traffic, stack joins). Dynamic
        group hosts (shared device state) are excluded; their drain
        legs were already recorded into each member's scope. Safe
        off-thread."""
        reg = self.telemetry
        by_tenant: Dict[str, List[str]] = {}
        plan_scopes = reg.scope_map("plan")
        for pid in plan_scopes:
            if pid.startswith(("@dyn:", "@shr:")):
                continue
            by_tenant.setdefault(self.tenant_of(pid), []).append(pid)
        for pid in self.plan_ids:  # live but not-yet-scoped plans
            ids = by_tenant.setdefault(self.tenant_of(pid), [])
            if pid not in ids:
                ids.append(pid)
        tenant_scopes = reg.scope_map("tenant")
        out: Dict[str, Dict[str, object]] = {}
        for tenant, pids in sorted(by_tenant.items()):
            rows = matches = late = 0
            for pid in pids:
                sreg = plan_scopes.get(pid)
                if sreg is None:
                    continue
                rows += sreg.counter_value("rows_emitted")
                matches += sreg.counter_value("matches")
                late += sreg.counter_value("late_events")
            drain = reg.merged_scope_histogram(
                "plan", pids, "drain.total"
            )
            stale = reg.merged_scope_histogram(
                "plan", pids, "drain.staleness"
            )
            treg = tenant_scopes.get(tenant)
            out[tenant] = {
                "plans": sorted(pids),
                "rows_emitted": rows,
                "matches": matches,
                "late_events": late,
                "drain": drain.snapshot(),
                "drain_staleness": stale.snapshot(),
                "cache_hits": (
                    treg.counter_value("control.cache_hit")
                    if treg is not None else 0
                ),
                "cache_misses": (
                    treg.counter_value("control.cache_miss")
                    if treg is not None else 0
                ),
                "stack_joins": (
                    treg.counter_value("control.stack_join")
                    if treg is not None else 0
                ),
            }
        return out

    def openmetrics(self) -> str:
        """The metrics snapshot as Prometheus text (the
        GET /api/v1/metrics/prometheus body; telemetry/openmetrics.py
        has the mapping). Safe off-thread — same snapshot metrics()
        takes."""
        from ..telemetry.openmetrics import render_openmetrics

        return render_openmetrics(self.metrics())

    # -- results -------------------------------------------------------------
    # fst:runloop-only (drains first)
    def results(self, output_stream: str) -> List[Tuple]:
        self.drain_outputs()
        return [row for _, row in self.collected.get(output_stream, [])]

    # fst:runloop-only (drains first)
    def results_with_ts(self, output_stream: str) -> List[Tuple[int, Tuple]]:
        self.drain_outputs()
        return list(self.collected.get(output_stream, []))
