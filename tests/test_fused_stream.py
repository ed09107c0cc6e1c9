"""Scan-of-microbatches streaming dispatch: row-exact equivalence.

Every batch leaves ``Job`` as an entry of a segment (runtime/executor.py
``_stage_fused``/``_dispatch_segment``): K micro-batches advance in one
lax.scan device call — the bounded replay's proven shape
(runtime/replay.py), fed from live tapes — and ``Job.fused_segment_len``
says how many (None, 0 and 1: one). These tests pin the contract:

* a segment of K == a segment of one, ROW-EXACT, across the
  window zoo (length / timeBatch / unique / sort), pattern chains, and
  multiquery stacks, at segment lengths {1, 3, 16} — 10 micro-batches
  per run, so 3 ends on a partial trailing segment (3+3+3+1) and 16
  never fills a whole one (pure partial, padded with empty tapes);
  a job that never sets the attribute, and None, 0 and 1, are one
  path with one set of counters and one program;
* fused streaming == the per-event reference interpreter
  (``baseline/interp.py``) on its supported surface — row contents at
  f32 tolerance, the ``vs_baseline`` honesty check;
* drain staleness keeps recording under fused dispatch (drains fire
  between segments, not between batches) and its p99 stays bounded at
  segment_len=16;
* checkpoints land on segment boundaries: ``save_checkpoint`` force-
  dispatches the pending partial segment (the supervised-crash
  exactly-once case lives in tests/test_faults.py).

All tier-1, CPU lane; on this lane reverse cummins run in their XLA
form (the kernel-vs-XLA equivalence runs under the Pallas interpreter
in tests/test_pallas_ops.py subprocesses).
"""

import logging
import re

import numpy as np
import pytest

from flink_siddhi_tpu.baseline.workloads import config_cql, make_batches
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

N, BATCH = 40_000, 4096  # 10 micro-batches
SEGMENTS = (1, 3, 16)  # 3 -> partial trailing; 16 -> pure partial


def _schema():
    return StreamSchema(
        [
            ("id", AttributeType.INT),
            ("name", AttributeType.STRING),
            ("price", AttributeType.DOUBLE),
            ("timestamp", AttributeType.LONG),
        ]
    )


CASES = {
    "filter": (
        "from inputStream[id == 2] select id, name, price "
        "insert into out",
        50,
    ),
    "pattern3_within": (
        "from every s1 = inputStream[id == 1] -> "
        "s2 = inputStream[id == 2] -> s3 = inputStream[id == 3] "
        "within 5 sec "
        "select s1.timestamp as t1, s3.timestamp as t3, "
        "s3.price as price insert into out",
        50,
    ),
    "window_groupby": (
        "from inputStream#window.length(100) "
        "select id, sum(price) as total, count() as cnt "
        "group by id insert into out",
        40,
    ),
    "timebatch": (
        "from inputStream#window.timeBatch(3 sec) "
        "select sum(price) as total insert into out",
        50,
    ),
    "unique_window": (
        "from inputStream#window.unique(id) "
        "select id, sum(price) as total, count() as cnt "
        "insert into out",
        20,
    ),
    "sort_window": (
        "from inputStream#window.sort(10, price) "
        "select id, min(price) as mn, max(price) as mx "
        "insert into out",
        20,
    ),
}


_UNSET = object()  # leave Job.fused_segment_len as Job made it


def _run(cql, n_ids, seg=_UNSET, n=N, batch=BATCH):
    schema = _schema()
    plan = compile_plan(
        cql, {"inputStream": schema},
        config=EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    job = Job(
        [plan],
        [BatchSource(
            "inputStream", schema,
            iter(make_batches(n, batch, schema, "inputStream",
                              n_ids)),
        )],
        batch_size=batch, time_mode="processing",
    )
    if seg is not _UNSET:
        job.fused_segment_len = seg
    job.run()
    out = {
        sid: sorted(job.results_with_ts(sid)) for sid in job.collected
    }
    return out, job


@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_matches_per_batch_rowexact(case):
    """The base is the segment of one batch (the attribute unset); the
    oracle independent of both is the interpreter
    (test_fused_matches_baseline_interpreter)."""
    cql, n_ids = CASES[case]
    base, _ = _run(cql, n_ids, None)
    assert base and any(rows for rows in base.values()), case
    for seg in SEGMENTS:
        fused, job = _run(cql, n_ids, seg)
        assert fused.keys() == base.keys(), (case, seg)
        for sid in base:
            assert fused[sid] == base[sid], (
                case, seg, len(fused[sid]), len(base[sid])
            )
        counters = job.telemetry.snapshot()["counters"]
        batches = counters.get("fusion.batches", 0)
        dispatches = counters.get("fusion.dispatches", 0)
        assert batches >= 10
        assert 0 < dispatches <= batches
        # a longer segment collapses dispatches; one batch is one
        assert (dispatches < batches) if seg > 1 else (
            dispatches == batches
        )


@pytest.fixture
def lowered(caplog):
    """The names of the programs lowered while a test runs, from the
    line jax logs as it lowers one (before the persistent cache is
    asked, like telemetry/compile_events.py's event)."""
    def names():
        found = (
            re.match(r"Compiling (\S+) with global shapes", r.getMessage())
            for r in caplog.records
        )
        # jit(seg_scan), as the module is named: jit_seg_scan
        return [
            m.group(1).replace("(", "_").rstrip(")") for m in found if m
        ]

    with caplog.at_level(logging.DEBUG, logger="jax._src.interpreters.pxla"):
        yield names


def test_a_default_job_dispatches_segments_of_one(lowered):
    """A ``Job`` whose ``fused_segment_len`` nobody set: every batch is
    one ``jit_seg_scan`` dispatch with its own explicit upload, and the
    ``fusion.*`` counters the benchmark's dispatch layer reads are
    booked (``dispatches_per_kbatch`` reads 1,000)."""
    out, job = _run(*CASES["window_groupby"])
    assert out["out"]
    snap = job.telemetry.snapshot()
    counters = snap["counters"]
    assert counters["fusion.dispatches"] == counters["fusion.batches"] == 10
    assert counters["fusion.h2d_uploads"] == 10
    assert snap["stages"]["dispatch"]["count"] == 10
    assert job.metrics()["compiles"]["total_lowerings"] > 0
    assert "jit_seg_scan" in lowered()
    assert "jit_step_wire" not in lowered()


def test_a_job_keeps_the_heap_its_large_temporaries_come_from():
    """Once a ``Job`` exists, glibc serves a 16 MB block (a column of
    a delivery) from the heap it keeps, where by default it maps every
    such block anew and hands it back at ``free``; a second ``Job``
    changes nothing. Without glibc's ``mallinfo2`` nothing is held."""
    import ctypes

    class Info(ctypes.Structure):
        _fields_ = [(n, ctypes.c_size_t) for n in (
            "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
            "fsmblks", "uordblks", "fordblks", "keepcost")]

    try:
        libc = ctypes.CDLL("libc.so.6")
        libc.mallinfo2.restype = Info
    except (OSError, AttributeError):
        return
    libc.malloc.restype = ctypes.c_void_p
    libc.free.argtypes = [ctypes.c_void_p]
    for _ in range(2):
        _run(*CASES["window_groupby"])
        mapped = libc.mallinfo2().hblks
        for _ in range(3):
            block = libc.malloc(ctypes.c_size_t(16 << 20))
            assert libc.mallinfo2().hblks == mapped
            libc.free(block)


@pytest.mark.parametrize("seg", [None, 0, 1])
def test_none_zero_and_one_are_one_segment_of_one(seg, lowered):
    """``fused_segment_len`` None, 0 and 1: the rows and counters of
    the job that never set it, and one lowering of ``jit_seg_scan``."""

    def run(*seg):
        out, job = _run(*CASES["pattern3_within"], *seg)
        snap = job.telemetry.snapshot()
        # what does not depend on the device's pace (h2d_overlapped and
        # the ticket window's depth do)
        counted = {k: snap["counters"].get(k) for k in (
            "fusion.batches", "fusion.dispatches", "fusion.h2d_uploads",
            "acc.compactions", "acc.compactions_identity",
        )}
        counted.update({k: snap["stages"][k]["count"] for k in (
            "stage.h2d_overlap", "dispatch",
        )})
        return out, counted

    rows, counted = run(seg)
    assert rows["out"] and counted["fusion.dispatches"] == 10
    assert lowered().count("jit_seg_scan") == 1
    assert (rows, counted) == run()
    assert lowered().count("jit_seg_scan") == 2
    assert "jit_step_wire" not in lowered()


def test_fused_multiquery_stack_rowexact():
    """8 stacked chain queries over one stream: the stacked group
    artifact under the scanned segment dispatch."""
    parts = []
    for q in range(8):
        a, b = q % 5, (q * 3 + 1) % 5
        parts.append(
            f"from every s1 = inputStream[id == {a}] -> "
            f"s2 = inputStream[id == {b}] "
            f"select s1.timestamp as t1, s2.timestamp as t2 "
            f"insert into m{q}"
        )
    cql = "; ".join(parts)
    base, _ = _run(cql, 5, None, n=20_000)
    assert len(base) == 8
    for seg in SEGMENTS:
        fused, _ = _run(cql, 5, seg, n=20_000)
        assert fused.keys() == base.keys()
        for sid in base:
            assert fused[sid] == base[sid], (sid, seg)


def _norm_row(ts, row):
    return (
        int(ts),
        tuple(
            np.float32(v).item() if isinstance(v, float) else v
            for v in row
        ),
    )


@pytest.mark.parametrize("config", ["filter", "headline"])
def test_fused_matches_baseline_interpreter(config):
    """Fused streaming vs the measured-baseline per-event interpreter
    (flink_siddhi_tpu/baseline): identical stream, row contents at f32
    tolerance — the fused dispatch cannot drift from the reference
    semantics either."""
    from flink_siddhi_tpu.baseline import BaselineEngine

    n, batch = 40_000, 4096
    schema = _schema()
    cql = config_cql(config)
    plan = compile_plan(
        cql, {"inputStream": schema},
        config=EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    job = Job(
        [plan],
        [BatchSource("inputStream", schema,
                     iter(make_batches(n, batch, schema,
                                       "inputStream", 50)))],
        batch_size=batch, time_mode="processing", retain_results=False,
    )
    job.fused_segment_len = 3
    eng_rows = []
    for rt in job._plans.values():
        for out_stream in rt.plan.output_streams():
            job.add_sink(
                out_stream,
                lambda ts, row: eng_rows.append(_norm_row(ts, row)),
            )
    job.run()

    eng = BaselineEngine(cql, ["id", "name", "price", "timestamp"])
    base_rows = []
    eng._emit = lambda out, ts, row: base_rows.append(
        _norm_row(ts, row)
    )
    batches = make_batches(n, batch, schema, "inputStream", 50)
    cols = {
        "id": np.concatenate([b.columns["id"] for b in batches]).tolist(),
        "name": ["test_event"] * n,
        "price": np.concatenate(
            [b.columns["price"] for b in batches]
        ).tolist(),
        "timestamp": np.concatenate(
            [b.timestamps for b in batches]
        ).tolist(),
    }
    eng.run_columns(cols, cols["timestamp"])
    assert sorted(eng_rows) == sorted(base_rows)


def test_drain_staleness_bounded_under_fused_dispatch():
    """Satellite: drains fire between segments, not between batches —
    the deadline scheduler's staleness leg must keep recording under
    fused dispatch, and its p99 must stay bounded (~interval + drain
    pipeline time, not the whole run) at segment_len=16."""
    cql, n_ids = CASES["window_groupby"]
    schema = _schema()
    plan = compile_plan(cql, {"inputStream": schema})
    job = Job(
        [plan],
        [BatchSource("inputStream", schema,
                     iter(make_batches(40_000, 2048, schema,
                                       "inputStream", n_ids)))],
        batch_size=2048, time_mode="processing",
    )
    job.fused_segment_len = 16
    job.drain_interval_ms = 25.0
    job.run()
    h = job.telemetry.histogram("drain.staleness")
    assert h.count > 0, "staleness stopped recording under fused mode"
    # bounded: a broken scheduler would show staleness ~= the whole
    # run (tens of seconds when a segment never drains); the budget
    # here is interval + a generous drain+dispatch pipeline allowance
    assert h.percentile_ms(99) < 10_000.0, h.percentile_ms(99)
    counters = job.telemetry.snapshot()["counters"]
    assert counters.get("fusion.dispatches", 0) >= 1


# Named per-shape-bucket compile budget for the tier-1 gate shape
# (constant-cadence stream, one tape bucket, segment 8): the complete
# executable set is init_acc + full-segment scan + padded partial-
# trailing scan + backpressure ticket noop + drain count/pack shapes +
# flush + retrace headroom for jax-version drift. Measured 12 on this
# lane; the sticky-d0 widening regression class (every small-but-
# constant batch widening the wire kind and retracing the segment
# executable) lowers O(n_batches) extra modules and blows straight
# through this.
RETRACE_BUDGET_GATE_SHAPE = 16


def test_retrace_budget_gate_shape():
    """Satellite: count XLA executable builds over an end-to-end run
    of the gate shape via the PERMANENT compile-telemetry surface
    (telemetry/compile_events.py — the lowering event fires before
    the persistent compilation cache is consulted, so a warm
    .jax_cache cannot mask a retrace regression: cache hits skip
    backend_compile, not lowering) and pin them to the named budget.
    Previously this test registered a private jax.monitoring listener
    and tore down with clear_event_listeners(), which clobbered every
    other listener in the process."""
    from flink_siddhi_tpu.telemetry import compile_events

    with compile_events.watch() as w:
        cql, n_ids = CASES["window_groupby"]
        out, job = _run(cql, n_ids, seg=8)
    assert any(rows for rows in out.values())
    counters = job.telemetry.snapshot()["counters"]
    assert counters.get("fusion.dispatches", 0) >= 1
    n = w.count
    assert 0 < n <= RETRACE_BUDGET_GATE_SHAPE, (
        f"{n} executables lowered for ONE shape bucket (budget "
        f"{RETRACE_BUDGET_GATE_SHAPE}) — a retrace leak (sticky "
        "wire-kind widening, unstable jit signatures) is "
        "recompiling the hot loop"
    )
    # the same lowerings land, attributed, in the job's own compile
    # accounting: metrics()["compiles"] with finite durations (the
    # permanent surface the bench and REST readers see). The job sink
    # counts only job-attributed lowerings, so it is bounded by the
    # process-wide watcher count.
    comp = job.metrics()["compiles"]
    assert 0 < comp["total_lowerings"] <= n
    assert comp["total_duration_s"] > 0
    assert comp["by_signature"], "no per-signature attribution"
    assert comp["lowering_duration"]["count"] == comp["total_lowerings"]


def test_checkpoint_forces_segment_boundary(tmp_path):
    """Checkpoints land only at segment boundaries: save_checkpoint
    force-dispatches the staged partial segment, so the snapshot's
    device state covers every event the job has pulled (exactly-once
    depends on this — the supervised crash case is in
    tests/test_faults.py)."""
    cql, n_ids = CASES["window_groupby"]
    schema = _schema()
    plan = compile_plan(cql, {"inputStream": schema})
    job = Job(
        [plan],
        [BatchSource("inputStream", schema,
                     iter(make_batches(N, BATCH, schema,
                                       "inputStream", n_ids)))],
        batch_size=BATCH, time_mode="processing",
    )
    job.fused_segment_len = 16
    for _ in range(3):
        job.run_cycle()
    rt = next(iter(job._plans.values()))
    assert rt.seg_pending, "expected a staged partial segment"
    job.save_checkpoint(str(tmp_path / "ck"))
    assert not rt.seg_pending, (
        "save_checkpoint left staged tapes undispatched — the "
        "checkpoint is not on a segment boundary"
    )
    # and the run completes normally afterwards
    job.run()
    assert job.results_with_ts("out")


def test_fused_h2d_overlap_counters(monkeypatch):
    """The double-buffering accounting: segment k+1's upload (one
    async device_put of the stacked tapes) counts as OVERLAPPED when
    it is issued while segment k's dispatch ticket is still in flight
    (fusion.h2d_overlapped; bench reports the fraction as
    h2d_overlap_frac, gated by schema v5). XLA:CPU retires these
    executions synchronously inside the dispatch call, so the busy
    window cannot be observed live on this lane — the device is
    forced to LOOK busy instead (tickets report in-flight), which
    pins the accounting deterministically; on an async accelerator
    the same counter measures the genuine overlap."""
    cql, n_ids = CASES["pattern3_within"]

    class _Busy:
        def __init__(self, real):
            self._real = real

        def is_ready(self):
            return False

        def block_until_ready(self):
            return self._real.block_until_ready()

    orig = Job._make_ticket
    monkeypatch.setattr(
        Job, "_make_ticket",
        classmethod(lambda cls, states: _Busy(orig(states))),
    )
    schema = _schema()
    plan = compile_plan(
        cql, {"inputStream": schema},
        config=EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    job = Job(
        [plan],
        [BatchSource("inputStream", schema,
                     iter(make_batches(N, BATCH, schema,
                                       "inputStream", n_ids)))],
        batch_size=BATCH, time_mode="processing",
    )
    job.fused_segment_len = 3
    job.max_inflight_cycles = 99  # never hit the forced-block path
    job.run()
    counters = job.telemetry.snapshot()["counters"]
    # uploads count SEGMENTS (one device_put per stacked segment):
    # 10 batches at segment 3 -> 4 dispatches (3+3+3+1 partial)
    assert counters.get("fusion.h2d_uploads", 0) == 4
    # every upload after the first saw in-flight compute, but the one
    # that follows the two waits in which the window measures the
    # device's time for a dispatch (Job._inflight_depth)
    assert counters.get("fusion.h2d_overlapped", 0) == 2


@pytest.mark.parametrize(
    "step_s, depth", [(0.97, 1), (0.4, 2), (0.06, 6), (0.002, 6)],
    ids=["a_second_a_dispatch", "two_fit", "sixty_ms", "host_bound"],
)
def test_ticket_window_holds_about_a_second_of_device_work(
    monkeypatch, step_s, depth
):
    """``Job._inflight_depth``: the window lets ``max_inflight_cycles``
    dispatches wait behind the running one, and no more than about
    ``MAX_QUEUED_S`` of device work. The device here is a clock: it
    takes ``step_s`` a dispatch, in order; the host stages one in 10 ms
    and so runs ahead as far as the window lets it."""
    from flink_siddhi_tpu.runtime import executor

    class _Clock:
        t = 100.0
        free = 0.0  # when the device has done all it was given

    class _Ticket:
        def __init__(self):
            _Clock.free = self.done = max(_Clock.free, _Clock.t) + step_s

        def is_ready(self):
            return _Clock.t >= self.done

        def block_until_ready(self):
            _Clock.t = max(_Clock.t, self.done)

    monkeypatch.setattr(executor.time, "monotonic", lambda: _Clock.t)
    monkeypatch.setattr(
        Job, "_make_ticket", classmethod(lambda cls, states: _Ticket()))
    monkeypatch.setattr(
        executor.jax, "block_until_ready", lambda t: t.block_until_ready())
    cql, _n = CASES["filter"]
    schema = _schema()
    job = Job([compile_plan(cql, {"inputStream": schema})],
              [BatchSource("inputStream", schema, iter(()))],
              batch_size=BATCH, time_mode="processing")
    job.telemetry.enabled = False  # no starvation clock beside this one
    rt, = job._plans.values()
    waiting = []
    for i in range(40):
        _Clock.t += 0.010
        job._ticket_window(rt, None, i)
        waiting.append(len(rt.tickets))
    if step_s < 0.010:
        # the host is the slower side: no dispatch ever finds another
        # running, nothing is measured and nothing waits
        assert rt.dispatch_s is None and max(waiting) == 1
        return
    # measured by the second dispatch (it found the first still running
    # and waited for both), exact to the clock
    assert rt.dispatch_s == pytest.approx(step_s)
    assert waiting[1] == 0 and max(waiting[2:]) == depth
    assert waiting[-1] == depth  # and the host is kept that far ahead
    # a segment that is short of tapes stays open while the window is
    # full (the run loop's age check asks), and goes once it is not
    assert job._window_full(rt)
    _Clock.t = _Clock.free
    assert not job._window_full(rt) and not rt.tickets
    # a device that turns out faster is found out at the next two waits
    step_s /= 4
    for i in range(40, 80):
        _Clock.t += 0.010
        job._ticket_window(rt, None, i)
    assert rt.dispatch_s == pytest.approx(step_s)
