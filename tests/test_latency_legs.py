"""One clock from arrival to sink: the per-segment records and the legs
they close into, the program's spans in a profiler trace, the stable
program and scope names, and the counters for what was only logged."""

import glob
import logging
import re

import numpy as np
import pytest

import jax

from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime import executor
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType
from flink_siddhi_tpu.telemetry.legs import LEGS

SCHEMA = StreamSchema(
    [("id", AttributeType.INT), ("price", AttributeType.DOUBLE)]
)
FILTER = "from s[id == 3] select id, price insert into out"
PATTERN = (
    "from every a = s[id == 1] -> b = s[id == 2] within 5 sec "
    "select a.price as p1, b.price as p2 insert into out"
)
WINDOW = (
    "from s#window.length(100) select id, sum(price) as total "
    "group by id insert into out"
)


def _job(cql=FILTER, n_events=40_000, batch=4_096, fused=0, config=None):
    rng = np.random.default_rng(11)
    batches = []
    for start in range(0, n_events, batch):
        m = min(batch, n_events - start)
        cols = {
            "id": rng.integers(0, 10, m).astype(np.int32),
            "price": rng.random(m) * 50.0,
        }
        ts = 1_000 + start + np.arange(m, dtype=np.int64)
        batches.append(EventBatch("s", SCHEMA, cols, ts))
    plan = compile_plan(
        cql, {"s": SCHEMA}, plan_id="t", config=config or EngineConfig()
    )
    job = Job(
        [plan], [BatchSource("s", SCHEMA, iter(batches))],
        batch_size=batch, time_mode="processing",
    )
    job.fused_segment_len = fused
    return job


def _run(job):
    while not job.finished:
        job.run_cycle()
    job.flush()


@pytest.mark.parametrize("fused", [0, 3], ids=["seg_of_1", "seg_of_3"])
def test_the_five_legs_sum_to_the_total(fused, monkeypatch):
    closed = []
    record_legs = executor.record_legs

    def spy(registry, records, requested, delivered):
        closed.append((list(records), requested, delivered))
        record_legs(registry, records, requested, delivered)

    monkeypatch.setattr(executor, "record_legs", spy)
    job = _job(fused=fused)
    _run(job)
    hists = {leg: job.telemetry.get_histogram("leg." + leg) for leg in LEGS}
    # every event delivered is one sample of every leg
    assert job.processed_events == 40_000
    assert {h.count for h in hists.values()} == {40_000}
    # the identity, to the microsecond
    five = sum(hists[leg].sum for leg in LEGS if leg != "total")
    assert five == hists["total"].sum > 0
    # one record a dispatched segment, each closed once, stamps in order
    segs = [r.seg for records, _q, _d in closed for r in records]
    assert segs == list(range(1, len(segs) + 1))
    # ten batches: ten segments of one, or 3 + 3 + 3 + 1
    assert [len(r.staged) for records, _q, _d in closed for r in records] == (
        [3, 3, 3, 1] if fused else [1] * 10
    )
    for records, requested, delivered in closed:
        assert requested <= delivered
        for r in records:
            assert max(r.staged) <= r.dispatch <= r.complete <= delivered
            assert all(a <= s for a, s in zip(r.arrival, r.staged))
    rt = next(iter(job._plans.values()))
    # nothing waits for a drain; what the next poll will retire is done
    assert not rt.seg_open
    inflight = job.telemetry.stages.starve.inflight
    assert all(r.complete is not None for _ticket, r in inflight)


def test_a_plan_nobody_observes_records_no_legs():
    job = _job(fused=3)
    job.retain_results = False  # no sink either: want=False
    _run(job)
    assert job.telemetry.get_histogram("leg.total") is None
    rt = next(iter(job._plans.values()))
    assert not rt.seg_open


def test_the_run_loop_blocked_on_pending_drains_is_a_span(monkeypatch):
    """Past MAX_PENDING_DRAINS a drain request blocks on the oldest
    pending drain: that wait is the span drain.backlog_wait, nested in
    the drain span that asked."""
    monkeypatch.setattr(Job, "MAX_PENDING_DRAINS", 0)
    job = _job(fused=3)
    _run(job)
    stages = job.telemetry.snapshot()["stages"]
    waits = stages["nested.drain.backlog_wait"]
    assert waits["count"] == job.telemetry.counter_value("drains.completed")
    assert 0 < waits["seconds"] <= stages["drain"]["seconds"]
    assert len(job.results("out")) > 0


def test_telemetry_off_takes_no_stamp_and_enters_no_annotation(monkeypatch):
    entered = []

    class Counting(jax.profiler.TraceAnnotation):
        def __enter__(self):
            entered.append(1)
            return super().__enter__()

    from flink_siddhi_tpu.telemetry import registry, spans

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    monkeypatch.setattr(registry, "TraceAnnotation", Counting)
    opened = []
    monkeypatch.setattr(
        executor, "SegmentRecord",
        lambda *a: opened.append(a) or pytest.fail("a record was opened"),
    )
    job = _job(fused=3)
    job.telemetry.enabled = False
    _run(job)
    assert not entered and not opened
    snap = job.telemetry.snapshot()
    assert not snap["stages"] and not snap["counters"]
    assert all(h["count"] == 0 for h in snap["histograms"].values())
    assert len(job.results("out")) > 0
    # and on again, the same job shape enters them
    monkeypatch.undo()
    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    _run(_job(fused=3))
    assert entered


def _stats(event):
    return {k: v for k, v in event.stats}


def test_a_profiler_trace_holds_the_programs_spans(tmp_path):
    from jax.profiler import ProfileData

    job = _job(fused=3)
    job.run_cycle()  # the compile stays out of the trace
    before = job.telemetry.counter_value("fusion.dispatches")
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run(job)
    finally:
        jax.profiler.stop_trace()
    dispatched = job.telemetry.counter_value("fusion.dispatches") - before
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    events = [
        e
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("fst.")
    ]
    names = {e.name for e in events}
    assert {"fst.tape_build", "fst.dispatch", "fst.stage.h2d_overlap",
            "fst.drain", "fst.drain.fetch", "fst.drain.decode"} <= names
    # one fst.dispatch a dispatched segment, each under its ordinal, the
    # same ordinal as the upload before it
    segs = [_stats(e)["seg"] for e in events if e.name == "fst.dispatch"]
    assert dispatched > 0 and len(segs) == dispatched
    assert segs == list(range(segs[0], segs[0] + dispatched))
    assert segs == [
        _stats(e)["seg"] for e in events if e.name == "fst.stage.h2d_overlap"
    ]
    drains = [_stats(e)["drain"] for e in events if e.name == "fst.drain.fetch"]
    assert drains and drains == [
        _stats(e)["drain"] for e in events if e.name == "fst.drain.decode"
    ]
    assert all(e.duration_ns > 0 for e in events)


@pytest.mark.parametrize(
    "cql, scope",
    [(PATTERN, "fst.pattern_scan"), (WINDOW, "fst.window_fold")],
    ids=["pattern", "window"],
)
def test_the_step_programs_text_holds_scopes_and_names(cql, scope):
    """The names a trace reduction finds the programs and their parts
    by: five program names, three scopes (operation metadata only). A
    segment of one batch and a segment of three are one program name."""
    texts = {}
    for fused in (0, 3):
        job = _job(cql, n_events=8_192, batch=1_024, fused=fused)
        rt = next(iter(job._plans.values()))
        fn = rt.jitted_seg

        def spy(states, acc, seg, fn=fn, key=f"seg_of_{fused or 1}"):
            texts[key] = fn.lower(states, acc, seg).as_text(debug_info=True)
            return fn(states, acc, seg)

        rt.jitted_seg = spy
        job.prewarm_drains([64])
        _run(job)
    rt = next(iter(job._plans.values()))
    texts["init_acc"] = rt.jitted_init_acc.lower().as_text()
    texts["flush"] = rt.jitted_flush.lower(rt.states).as_text()
    texts["pack"] = rt.pack_jits[64].lower(rt.acc).as_text()
    texts["ticket"] = Job._noop_jit.lower(np.zeros(2, np.int32)).as_text()
    module = {k: re.search(r"module @(\w+)", t).group(1)
              for k, t in texts.items()}
    assert module == {
        "seg_of_1": "jit_seg_scan", "seg_of_3": "jit_seg_scan",
        "init_acc": "jit_init_acc", "flush": "jit_flush",
        "pack": "jit_pack", "ticket": "jit_ticket",
    }
    for key in ("seg_of_1", "seg_of_3"):
        assert scope in texts[key] and "fst.acc_append" in texts[key]


def test_the_drop_counter_rises_by_the_number_in_the_warning(caplog):
    # one batch wider than the whole accumulator (65,536 columns at the
    # smallest budget): the rows beyond it are dropped and counted
    job = _job("from s select id, price insert into out", n_events=131_072,
               batch=131_072, config=EngineConfig(acc_budget_bytes=1))
    with caplog.at_level(logging.WARNING, logger="flink_siddhi_tpu"):
        _run(job)
    warned = [
        int(re.search(r"(\d+) emissions dropped", r.getMessage()).group(1))
        for r in caplog.records if "emissions dropped" in r.getMessage()
    ]
    assert warned == [131_072 - 65_536]
    counters = job.metrics()["telemetry"]["counters"]
    assert counters["faults.emissions_dropped"] == sum(warned)
    assert len(job.results("out")) == 65_536
    assert (
        f"fst_faults_emissions_dropped_total {sum(warned)}"
        in job.openmetrics()
    )


def test_the_lazy_eviction_counter_rises_by_the_number_in_the_warning(caplog):
    cql = (
        "from every a = s[id == 1] -> b = s[id == 2] "
        "select a.price as p1, b.price as p2 insert into out"
    )
    job = _job(cql, n_events=4_096, batch=64, config=EngineConfig(
        lazy_projection=True, lazy_ring_budget_bytes=2048))
    with caplog.at_level(logging.WARNING, logger="flink_siddhi_tpu"):
        _run(job)
    warned = [
        int(re.search(r"(\d+) lazy-projected", r.getMessage()).group(1))
        for r in caplog.records if "lazy-projected" in r.getMessage()
    ]
    rt = next(iter(job._plans.values()))
    assert warned and sum(warned) == rt.lazy.missed
    counters = job.metrics()["telemetry"]["counters"]
    assert counters["faults.lazy_evicted"] == sum(warned)
