"""``#window.time`` in its ring (compiler/time_window.py,
docs/time_window.md), at small sizes on the CPU: the system against the
per-event interpreter's ``_TimeWindowGroupBy`` on seeded streams, row
for row, for rings smaller than a batch, equal to it and many times
larger, down the dispatch paths and both sink lanes; ticks that expire
whole, more due members than one stretch, a stalled clock and a jump;
a full ring (counted); a checkpoint; a float argument that a large
value enters and leaves. Nothing here is a rate."""

import numpy as np
import pytest

from flink_siddhi_tpu.baseline import BaselineEngine
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.compiler.time_window import TimeWindowArtifact
from flink_siddhi_tpu.compiler.window import SlidingWindowArtifact
from flink_siddhi_tpu.query.lexer import SiddhiQLError
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

SCHEMA = StreamSchema([
    ("t", AttributeType.INT), ("a", AttributeType.INT),
    ("b", AttributeType.INT), ("v", AttributeType.INT),
    ("x", AttributeType.DOUBLE),
])
FIELDS = ["t", "a", "b", "v", "x"]

INT_CQL = (
    "from S[t == 0]#window.time({span}) "
    "select a, b, v, sum(v) as s, avg(v) as m, count() as n "
    "group by a, b insert into o"
)
FLOAT_CQL = (
    "from S#window.time({span}) "
    "select a, sum(x) as s, avg(x) as m, stddev(x) as d, count() as n "
    "group by a insert into o"
)
PLAIN_CQL = (
    "from S#window.time({span}) select v, sum(v) as s, count() as n "
    "having n >= 2 insert into o"
)


def _stream(seed, n, ts, groups=(5, 3)):
    rng = np.random.default_rng(seed)
    cols = {
        "t": (rng.random(n) < 0.2).astype(np.int32),
        "a": rng.integers(0, groups[0], n).astype(np.int32),
        "b": rng.integers(0, groups[1], n).astype(np.int32),
        "v": rng.integers(-50, 100, n).astype(np.int32),
        "x": np.round(rng.random(n) * 10, 3),
    }
    return cols, np.asarray(ts, dtype=np.int64)


def _steady(seed, n, step_ms=10, **kw):
    return _stream(seed, n, 1_000 + step_ms * np.arange(n), **kw)


def _ticks(seed, n, per_tick, tick_ms=1_000, **kw):
    """A coarse clock: ``per_tick`` events share a stamp."""
    return _stream(seed, n, 5_000 + tick_ms * (np.arange(n) // per_tick), **kw)


def _stalls(seed, n, span):
    """A clock that stands still for stretches and twice jumps by ten
    windows."""
    rng = np.random.default_rng(seed)
    step = rng.choice([0, 0, 0, 1, 7, span // 3], n)
    step[n // 3] = 10 * span
    step[2 * n // 3] = 10 * span
    ts = 2_000 + np.cumsum(step)
    return _stream(seed, n, ts)


def _interpreted(cql, cols, ts, capacity=None):
    eng = BaselineEngine(cql, FIELDS, time_ring_capacity=capacity)
    out = []
    eng._emit = lambda _o, t, row: out.append((t, *row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()}, ts.tolist())
    return out, eng


class _Columns:
    def __init__(self, names):
        self.names, self.rows = names, []

    def accept_columns(self, ts, cols):
        assert all(v.dtype != object for v in cols.values())
        self.rows.extend(zip(
            map(int, ts), *(cols[n].tolist() for n in self.names)))


def _job(cql, cols, ts, batch, lo=0, hi=None, fused=0, lane="rows",
         plan_id="p", **config):
    hi = len(ts) if hi is None else hi
    batches = (
        EventBatch("S", SCHEMA, {k: v[s:s + batch] for k, v in cols.items()},
                   ts[s:s + batch])
        for s in range(lo, hi, batch)
    )
    plan = compile_plan(cql, {"S": SCHEMA}, plan_id=plan_id,
                        config=EngineConfig(**config))
    job = Job([plan], [BatchSource("S", SCHEMA, batches)], batch_size=batch,
              time_mode="processing", retain_results=lane == "rows")
    job.fused_segment_len = fused
    sink = _Columns([f.name for f in plan.artifacts[0].output_schema.fields])
    if lane == "columns":
        job.add_sink("o", sink)
    return job, plan, sink


def _rows(job, sink=None, lane="rows"):
    if lane == "columns":
        return sink.rows
    return [(int(t), *r) for t, r in job.results_with_ts("o")]


def _run(cql, cols, ts, batch, fused=0, lane="rows", **config):
    job, plan, sink = _job(cql, cols, ts, batch, fused=fused, lane=lane,
                           **config)
    job.run()
    return _rows(job, sink, lane), job, plan


def _same(got, want, rtol=1e-5, atol=1e-4):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(b, float) or isinstance(a, float):
                assert a == pytest.approx(b, rel=rtol, abs=atol), (g, w)
            else:
                assert a == b, (g, w)


def _state(job, plan):
    rt = job._plans[plan.plan_id]
    return rt.states[plan.artifacts[0].name]


SCENARIOS = {
    # 256 events a batch, 40 ms of them live: the ring is a quarter of a
    # batch and members join and leave inside one step
    "ring_smaller_than_a_batch": dict(
        cql=INT_CQL.format(span=40), stream=_steady(1, 2_000, 1),
        batch=256, time_ring_capacity=64),
    "ring_equal_to_a_batch": dict(
        cql=INT_CQL.format(span=900), stream=_steady(2, 1_500),
        batch=128, time_ring_capacity=128),
    "ring_many_times_a_batch": dict(
        cql=INT_CQL.format(span="20 sec"), stream=_steady(3, 3_000),
        batch=32, time_ring_capacity=4_096),
    # (stddev is the root of a difference of means: its own tolerance)
    # 100 events share a stamp and leave together, 3 ticks a window
    "a_tick_expires_whole": dict(
        cql=INT_CQL.format(span="3 sec"), stream=_ticks(4, 3_000, 100),
        batch=250, time_ring_capacity=512),
    # 500 members due at one arrival, a tape of 64: eight rounds
    "more_due_than_one_stretch": dict(
        cql=INT_CQL.format(span="2 sec"),
        stream=_ticks(5, 4_000, 500, tick_ms=2_000),
        batch=64, time_ring_capacity=1_024),
    "a_stalled_clock_and_jumps": dict(
        cql=INT_CQL.format(span=300), stream=_stalls(6, 3_000, 300),
        batch=96, time_ring_capacity=2_048),
    "no_group_by_and_having": dict(
        cql=PLAIN_CQL.format(span=70), stream=_steady(7, 1_000),
        batch=64, time_ring_capacity=64),
    "float_sums_and_stddev": dict(
        cql=FLOAT_CQL.format(span=500), stream=_steady(8, 2_000),
        batch=128, time_ring_capacity=256, atol=5e-3),
    "float_sums_in_a_ring_longer_than_a_tape": dict(
        cql=FLOAT_CQL.format(span="5 sec"), stream=_steady(9, 2_500),
        batch=32, time_ring_capacity=1_024, atol=5e-3),
}
PATHS = [(0, "rows"), (0, "columns"), (4, "rows"), (4, "columns")]


@pytest.mark.parametrize("fused, lane", PATHS,
                         ids=[f"seg_of_{f or 1}-{lane}" for f, lane in PATHS])
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_system_equals_the_interpreter_row_for_row(name, fused, lane):
    sc = dict(SCENARIOS[name])
    cql, (cols, ts), batch = sc.pop("cql"), sc.pop("stream"), sc.pop("batch")
    atol = sc.pop("atol", 1e-4)
    got, job, plan = _run(cql, cols, ts, batch, fused, lane, **sc)
    want, _eng = _interpreted(cql, cols, ts)
    assert len(want) > 300
    _same(got, want, atol=atol)
    art = plan.artifacts[0]
    assert isinstance(art, TimeWindowArtifact) and art.merge_form == "ring"
    st = _state(job, plan)
    assert int(st["overflow"]) == 0
    assert st["ring"].shape == (2 + len(art.arg_types),
                                sc["time_ring_capacity"])
    counters = job.telemetry.snapshot()["counters"]
    assert counters.get("window.ring_evicted", 0) == 0
    # every member that is not live at the end left on the clock
    assert counters["window.time_expired"] == len(want_members(
        cql, cols)) - int(st["count"])


def want_members(cql, cols):
    """The events that pass the query's filter: the window's members."""
    n = len(cols["t"])
    return np.flatnonzero(cols["t"] == 0) if "[t == 0]" in cql else range(n)


def test_a_full_ring_loses_its_oldest_and_counts_them():
    """Capacity is a guarantee: a member evicted because the ring is
    full is a wrong answer. It is counted (the ``overflow`` leaf, the
    counter ``window.ring_evicted``), and what the rows then hold is the
    interpreter's answer for a ring of that size."""
    cql = INT_CQL.format(span="10 sec")
    cols, ts = _steady(11, 2_000)
    got, job, plan = _run(cql, cols, ts, 64, time_ring_capacity=100)
    want, eng = _interpreted(cql, cols, ts, capacity=100)
    evicted = eng.handlers[0].evicted
    assert evicted > 500
    _same(got, want)
    free, _ = _interpreted(cql, cols, ts)
    assert free != want
    st = _state(job, plan)
    assert int(st["overflow"]) == evicted
    assert int(st["count"]) == 100
    counters = job.telemetry.snapshot()["counters"]
    assert counters["window.ring_evicted"] == evicted
    assert counters["window.time_expired"] == 0


@pytest.mark.parametrize("ring", [48, 4_096], ids=["small", "large"])
def test_a_checkpoint_restores_the_ring(ring):
    cql = INT_CQL.format(span=400)
    cols, ts = _steady(12, 1_600, 8)
    whole, _job_, _plan = _run(cql, cols, ts, 32, time_ring_capacity=ring)
    cut = 800
    first, plan, _s = _job(cql, cols, ts, 32, 0, cut, plan_id="stops",
                           time_ring_capacity=ring)
    first.run()
    snap = first.snapshot()
    second, _plan2, _s = _job(cql, cols, ts, 32, cut, len(ts),
                              plan_id="stops", time_ring_capacity=ring)
    second.restore(snap)
    second.run()
    assert _rows(first) + _rows(second) == whole
    _same(whole, _interpreted(cql, cols, ts)[0])
    leaves = snap["plans"]["stops"]["states"][plan.artifacts[0].name]
    assert set(leaves) == {"enabled", "ring", "head", "count", "clock",
                           "sums", "overflow", "stepped"}
    assert np.asarray(leaves["ring"]).shape == (3, ring)
    assert int(np.asarray(leaves["count"])) > 20


@pytest.mark.parametrize("ring", [64, 2_048], ids=["rebuilt", "carried"])
def test_a_large_float_enters_and_leaves(ring):
    """1e9 beside 1.5: a float32 sum that is added to and subtracted
    from would be wrong for good once 1e9 has left. Over many batches,
    in a ring no longer than a tape (the sums rebuilt from it) and in a
    longer one (the sums carried as compensated pairs)."""
    cql = ("from S#window.time(200) select a, sum(x) as s, avg(x) as m, "
           "count() as n group by a insert into o")
    cols, ts = _steady(13, 3_000, 5, groups=(2, 1))
    cols["x"] = np.full(len(ts), 1.5)
    cols["x"][100::700] = 1e9
    got, job, plan = _run(cql, cols, ts, 64, time_ring_capacity=ring)
    want, _eng = _interpreted(cql, cols, ts)
    _same(got, want, rtol=2e-6)
    small = [g for g in got if g[2] < 1e6]
    assert len(small) > 2_000
    # a group whose members have all left has no sum left
    quiet = dict(cols)
    quiet_ts = np.concatenate([ts, ts[-1:] + 10_000])
    for k in quiet:
        quiet[k] = np.concatenate([cols[k], cols[k][-1:]])
    _got, job, plan = _run(cql, quiet, quiet_ts, 64, time_ring_capacity=ring)
    sums = _state(job, plan)["sums"]
    live = np.asarray(sums["cnt"]) > 0
    assert live.sum() == 1
    for name in ("s0", "s0c"):
        assert not np.asarray(sums[name])[~live].any()


def test_min_and_max_over_time_keep_the_matrix_path_and_say_so():
    cql = ("from S#window.time(50) select a, min(v) as lo, count() as n "
           "group by a insert into o")
    plan = compile_plan(cql, {"S": SCHEMA}, config=EngineConfig(
        time_ring_capacity=1 << 20))
    art = plan.artifacts[0]
    assert type(art) is SlidingWindowArtifact
    assert art.capacity == 512 and art.merge_form is None
    with pytest.raises(SiddhiQLError, match="time_ring_capacity"):
        compile_plan(cql, {"S": SCHEMA}, config=EngineConfig(
            time_window_capacity=1 << 20))
    with pytest.raises(SiddhiQLError, match="externalTime"):
        compile_plan(
            "from S#window.externalTime(v, 50) select a, count() as n "
            "group by a insert into o", {"S": SCHEMA},
            config=EngineConfig(time_window_capacity=1 << 20))


def test_the_ring_is_sized_by_its_own_field():
    cql = INT_CQL.format(span=50)
    plan = compile_plan(cql, {"S": SCHEMA}, config=EngineConfig(
        time_window_capacity=64, time_ring_capacity=1 << 18))
    art = plan.artifacts[0]
    assert art.capacity == 1 << 18
    assert art.init_state()["ring"].shape == (3, 1 << 18)
    assert EngineConfig().time_ring_capacity == 512


def test_the_accumulator_holds_whole_tapes():
    """``safe_cycles`` is the per-key window's: one method, shared."""
    from flink_siddhi_tpu.compiler.window import (
        AlignedBlocks, PerKeyWindowArtifact)

    assert TimeWindowArtifact.safe_cycles is AlignedBlocks.safe_cycles
    assert PerKeyWindowArtifact.safe_cycles is AlignedBlocks.safe_cycles
    art = compile_plan(INT_CQL.format(span=50), {"S": SCHEMA}).artifacts[0]
    assert art.safe_cycles(1 << 20, {}, 4_793_490) == 4


def test_admission_counts_the_ring_at_its_configured_size():
    from flink_siddhi_tpu.analysis import admit

    def report(ring):
        plan = compile_plan(INT_CQL.format(span="5 min"), {"S": SCHEMA},
                            config=EngineConfig(time_ring_capacity=ring))
        return admit.admit_plan(
            plan, budgets=admit.AdmissionBudgets(max_state_bytes=8 << 20),
            raise_on_reject=False)

    small, large = report(512), report(1 << 20)
    assert large.state_bytes - small.state_bytes == 3 * 4 * ((1 << 20) - 512)
    assert small.admitted and small.residency_ms == 300_000
    # 12.6 MB of ring against 8 MiB of state: refused, by size
    assert [i.rule for i in large.findings] == ["ADM101"]
