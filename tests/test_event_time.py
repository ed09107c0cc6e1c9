"""Event-time robustness under disorder (docs/event_time.md).

The engine's watermark gate used to *consume* watermarks — sources had
to hand perfect ones, late rows slid through the gate and merged out
of order, and one silent source pinned the min watermark forever.
These tests pin the robustness surface end to end:

* watermark GENERATION: ``BoundedDisorderWatermark`` /
  ``PunctuatedWatermark`` strategy units, the ``WatermarkedSource``
  wrapper replacing a source's native claim, per-partition generation
  in ``KafkaSource`` (source wm = min across producing partitions),
  and checkpoint round-trips of all strategy state;
* DISORDER ORACLE: a seeded ``DisorderSchedule`` (bounded-skew
  shuffle + bursty duplicates, runtime/faultinject.py) feeds the
  engine a shuffled stream while the oracle sees the SORTED stream —
  row-exact agreement in streaming, fused-segment, and resident modes
  over a five-query plan (filter, pattern chain, length-window
  group-by, timeBatch, unique), with ``baseline/interp.py`` (the
  measured per-event reference interpreter) as the sorted-stream
  ground truth on its supported surface (filter / chain /
  length-window group-by; the remaining zoo windows are pinned
  engine-sorted vs engine-shuffled — their per-case oracles live in
  tests/test_window_zoo.py);
* LATE POLICY: 'drop' (counted, exact vs the injected schedule),
  'side_output' (full rows on the '<stream>@late' channel, row and
  columnar consumers), 'allow' (in-order admission within
  allowed_lateness_ms);
* IDLE SOURCES: a silent source stops pinning the min watermark
  within its timeout, un-idles on the next event, stays visible in
  metrics, and keeps polling under the 'block' shed policy;
* SUPERVISED RECOVERY: watermark/gate state survives kill->restore
  with 0 duplicate / 0 lost rows against the unfaulted oracle.

Randomized multi-seed sweeps carry @pytest.mark.slow; tier-1 keeps a
fixed-seed deterministic subset (the ~870s budget, ROADMAP.md).
"""

import collections
import glob
import time

import numpy as np
import pytest

from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import (
    MAX_WM,
    Job,
    late_stream,
)
from flink_siddhi_tpu.runtime.faultinject import (
    CrashPlan,
    DisorderSchedule,
    DisorderSource,
    wrap_job,
)
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import (
    BoundedDisorderWatermark,
    CallbackSource,
    ListSource,
    PunctuatedWatermark,
    WatermarkedSource,
    with_watermarks,
)
from flink_siddhi_tpu.runtime.supervisor import Supervisor
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType


def _schema():
    return StreamSchema(
        [
            ("id", AttributeType.INT),
            ("price", AttributeType.DOUBLE),
            ("timestamp", AttributeType.LONG),
        ]
    )


def _stream(n=6000, seed=0, n_ids=5, step_ms=7):
    """Pristine sorted stream. Prices are integer-valued so window
    sums stay EXACT in f32 (no accumulation-order tolerance anywhere
    in these equality assertions)."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n_ids, n)
    prices = rng.integers(0, 50, n).astype(np.float64)
    ts = 1_000 + np.arange(n, dtype=np.int64) * step_ms
    records = [
        (int(i), float(p), int(t))
        for i, p, t in zip(ids, prices, ts)
    ]
    return records, ts.tolist()


# one compile serves five query shapes: stateless filter, 2-step
# chain, sliding length-window group-by, tumbling timeBatch, and the
# unique (per-key latest) window
MULTI_CQL = (
    "from S[id == 2] select id, price insert into o_filter; "
    "from every s1 = S[id == 0] -> s2 = S[id == 1] within 2 sec "
    "select s1.timestamp as t1, s2.timestamp as t2 insert into o_pat; "
    "from S#window.length(50) select id, sum(price) as total, "
    "count() as cnt group by id insert into o_win; "
    "from S#window.timeBatch(3 sec) select sum(price) as total "
    "insert into o_tb; "
    "from S#window.unique(id) select id, sum(price) as total, "
    "count() as cnt insert into o_uni"
)
# the subset the per-event reference interpreter supports
INTERP_CQL = (
    "from S[id == 2] select id, price insert into o_filter; "
    "from every s1 = S[id == 0] -> s2 = S[id == 1] within 2 sec "
    "select s1.timestamp as t1, s2.timestamp as t2 insert into o_pat; "
    "from S#window.length(50) select id, sum(price) as total, "
    "count() as cnt group by id insert into o_win"
)

CHUNK = 300
SKEW_MS = 200


def _norm(ts, row):
    return (
        int(ts),
        tuple(
            np.float32(v).item() if isinstance(v, float) else v
            for v in row
        ),
    )


def _results(job):
    return {
        sid: sorted(_norm(t, r) for t, r in job.results_with_ts(sid))
        for sid in job.collected
    }


def _run_sorted(records, ts, cql=MULTI_CQL, **job_attrs):
    # skew 0 (claims max - 1): the sorted oracle stream may carry
    # duplicates whose ts equals the previous batch's max — the
    # ListSource's native max-ts claim would call those late
    schema = _schema()
    plan = compile_plan(cql, {"S": schema})
    job = Job(
        [plan],
        [with_watermarks(
            ListSource("S", schema, records, timestamps=ts,
                       chunk=CHUNK),
            skew_ms=0,
        )],
        batch_size=CHUNK, time_mode="event",
    )
    for k, v in job_attrs.items():
        setattr(job, k, v)
    job.run()
    assert job.late_events == 0  # the oracle run must be pristine
    return job


def _run_disordered(
    records, ts, schedule, mode="streaming", cql=MULTI_CQL,
    strategy_skew=SKEW_MS, **job_attrs,
):
    schema = _schema()
    plan = compile_plan(cql, {"S": schema})
    src = DisorderSource(
        ListSource("S", schema, records, timestamps=ts, chunk=CHUNK),
        schedule, chunk=CHUNK,
    )
    job = Job(
        [plan],
        [with_watermarks(src, skew_ms=strategy_skew)],
        batch_size=CHUNK, time_mode="event",
    )
    for k, v in job_attrs.items():
        setattr(job, k, v)
    if mode == "fused":
        job.fused_segment_len = 3
        job.run()
    elif mode == "resident":
        rep = ResidentReplay(job)
        rep.stage()
        rep.run()
        job.flush()
    else:
        job.run()
    return job, src


# -- watermark strategy units (no device work) ------------------------------

def test_bounded_disorder_strategy():
    s = BoundedDisorderWatermark(500)
    assert s.current() is None  # unknown until the first event
    s.observe(np.asarray([1_000, 3_000, 2_000]))
    # max - skew - 1: an event AT the bound is still admissible
    assert s.current() == 2_499
    s.observe(np.asarray([2_900]))  # max is sticky, never regresses
    assert s.current() == 2_499
    s.observe(np.asarray([10_000]))
    assert s.current() == 9_499
    clone = s.clone()
    assert clone.skew_ms == 500 and clone.current() is None
    # checkpoint round-trip
    d = s.state_dict()
    fresh = BoundedDisorderWatermark(500)
    fresh.load_state_dict(d)
    assert fresh.current() == 9_499
    with pytest.raises(ValueError):
        BoundedDisorderWatermark(-1)


def test_punctuated_strategy_passes_native_claims():
    s = PunctuatedWatermark()
    s.observe(np.asarray([99_999]))  # event times are ignored
    assert s.current() is None
    s.advance(4_000)
    s.advance(3_000)  # monotone
    assert s.current() == 4_000
    fresh = PunctuatedWatermark()
    fresh.load_state_dict(s.state_dict())
    assert fresh.current() == 4_000


def test_watermarked_source_replaces_native_claim():
    schema = _schema()
    records, ts = _stream(n=10, step_ms=100)
    src = WatermarkedSource(
        ListSource("S", schema, records, timestamps=ts, chunk=5),
        BoundedDisorderWatermark(250),
    )
    batch, wm, done = src.poll(5)
    # ListSource natively claims max(ts); the strategy holds back
    assert len(batch) == 5 and not done
    assert wm == int(batch.timestamps.max()) - 250 - 1
    # checkpoint carries inner position AND strategy state
    d = src.state_dict()
    src2 = WatermarkedSource(
        ListSource("S", schema, records, timestamps=ts, chunk=5),
        BoundedDisorderWatermark(250),
    )
    src2.load_state_dict(d)
    batch2, wm2, done2 = src2.poll(5)
    assert int(batch2.timestamps.min()) == ts[5]
    # the end-of-stream MAX sentinel passes through the strategy
    assert done2 and wm2 == MAX_WM


# -- disorder oracle: shuffled engine == sorted oracle, all modes -----------

_ORACLE_MEMO = {}


def _sorted_with_dups_oracle(records, ts, dup_log, dup_burst, key):
    """The sorted oracle stream carries the SAME duplicates, in sorted
    position. Memoized: the three mode params replay the identical
    schedule, so one oracle run serves all of them (tier-1 budget)."""
    if key not in _ORACLE_MEMO:
        dups = dup_log.tolist()
        allr = list(records) + [
            records[i] for i in dups for _ in range(dup_burst)
        ]
        allt = list(ts) + [
            ts[i] for i in dups for _ in range(dup_burst)
        ]
        order = np.argsort(np.asarray(allt), kind="stable")
        _ORACLE_MEMO[key] = _results(_run_sorted(
            [allr[i] for i in order], [allt[i] for i in order]
        ))
    return _ORACLE_MEMO[key]


@pytest.mark.parametrize("mode", ["streaming", "fused", "resident"])
def test_disorder_rowexact_vs_sorted_oracle(mode):
    """Bounded-skew shuffle + bursty duplicates: the engine fed the
    SHUFFLED stream (watermarking at the disorder bound) must emit
    row-identically to the same engine fed the SORTED stream, across
    all five query shapes, in every execution mode."""
    records, ts = _stream()
    sched = DisorderSchedule(
        seed=42, skew_ms=SKEW_MS, dup_rate=0.005, dup_burst=2
    )
    job, src = _run_disordered(records, ts, sched, mode=mode)
    assert job.late_events == 0  # strategy skew == disorder bound
    want = _sorted_with_dups_oracle(
        records, ts, src.dup_log, sched.dup_burst, "seed42"
    )
    got = _results(job)
    assert got.keys() == want.keys()
    for sid in want:
        assert got[sid] == want[sid], (mode, sid)
    if mode == "streaming":
        # gate telemetry recorded under disorder: watermark lag +
        # reorder-buffer residency histograms are live
        snap = job.telemetry.snapshot()["histograms"]
        assert snap["watermark.lag"]["count"] > 0
        assert snap["gate.residency"]["count"] > 0


def test_disorder_rowexact_vs_baseline_interpreter():
    """The sorted-stream ground truth per the reference interpreter
    (baseline/interp.py): the engine fed the SHUFFLED stream must
    match the per-event interpreter fed the SORTED stream, row-exact,
    on the interpreter's supported surface."""
    from flink_siddhi_tpu.baseline import BaselineEngine

    records, ts = _stream()
    sched = DisorderSchedule(
        seed=7, skew_ms=SKEW_MS, dup_rate=0.005, dup_burst=2
    )
    job, src = _run_disordered(records, ts, sched, cql=INTERP_CQL)
    eng = BaselineEngine(INTERP_CQL, ["id", "price", "timestamp"])
    rows = collections.defaultdict(list)
    eng._emit = lambda out, t, row: rows[out].append(_norm(t, row))
    dups = src.dup_log.tolist()
    allr = list(records) + [
        records[i] for i in dups for _ in range(sched.dup_burst)
    ]
    allt = list(ts) + [
        ts[i] for i in dups for _ in range(sched.dup_burst)
    ]
    order = np.argsort(np.asarray(allt), kind="stable")
    for i in order.tolist():
        rid, price, t = allr[i]
        eng.process(
            {"id": rid, "price": price, "timestamp": t}, allt[i]
        )
    got = _results(job)
    for sid in ("o_filter", "o_pat", "o_win"):
        assert got[sid] == sorted(rows[sid]), sid


@pytest.mark.slow
@pytest.mark.parametrize("seed", [1, 2, 3, 4])
@pytest.mark.parametrize("skew_ms", [50, 500, 3_000])
def test_disorder_rowexact_randomized_sweep(seed, skew_ms):
    """Multi-seed randomized sweep (slow lane): shuffle + duplicates
    at several disorder bounds, streaming + fused, vs the sorted
    engine oracle."""
    records, ts = _stream(n=8000, seed=seed)
    sched = DisorderSchedule(
        seed=seed * 31, skew_ms=skew_ms, dup_rate=0.01, dup_burst=3
    )
    dups_oracle = None
    for mode in ("streaming", "fused"):
        job, src = _run_disordered(
            records, ts, sched, mode=mode, strategy_skew=skew_ms
        )
        assert job.late_events == 0
        if dups_oracle is None:
            dups = src.dup_log.tolist()
            allr = list(records) + [
                records[i] for i in dups for _ in range(3)
            ]
            allt = list(ts) + [ts[i] for i in dups for _ in range(3)]
            order = np.argsort(np.asarray(allt), kind="stable")
            dups_oracle = _results(_run_sorted(
                [allr[i] for i in order], [allt[i] for i in order]
            ))
        assert _results(job) == dups_oracle, (seed, skew_ms, mode)


# -- late-event policy ------------------------------------------------------

FILTER_CQL = "from S[id == 2] select id, price insert into o"


def _filter_oracle(records, ts, indices):
    """Python oracle for FILTER_CQL over the given pristine indices."""
    return sorted(
        (int(ts[i]), (records[i][0], np.float32(records[i][1]).item()))
        for i in indices
        if records[i][0] == 2
    )


def _late_schedule(seed=9):
    return DisorderSchedule(
        seed=seed, skew_ms=SKEW_MS, late_count=12,
        late_release_ms=2_000,
    )


def test_late_policy_drop_counts_exact():
    records, ts = _stream()
    sched = _late_schedule()
    job, src = _run_disordered(
        records, ts, sched, cql=FILTER_CQL, late_policy="drop"
    )
    assert src.injected["late"] == 12
    assert job.late_dropped == 12 == job.late_events
    counters = job.telemetry.snapshot()["counters"]
    assert counters["faults.late_dropped"] == 12
    keep = [i for i in range(len(records)) if i not in
            set(src.late_log.tolist())]
    assert sorted(
        _norm(t, r) for t, r in job.results_with_ts("o")
    ) == _filter_oracle(records, ts, keep)
    # the account is user-visible
    m = job.metrics()
    assert m["late_dropped"] == 12 and m["late_policy"] == "drop"


def test_late_policy_side_output_routes_full_rows():
    records, ts = _stream()
    sched = _late_schedule(seed=13)
    schema = _schema()
    plan = compile_plan(FILTER_CQL, {"S": schema})
    src = DisorderSource(
        ListSource("S", schema, records, timestamps=ts, chunk=CHUNK),
        sched, chunk=CHUNK,
    )
    job = Job(
        [plan], [with_watermarks(src, skew_ms=SKEW_MS)],
        batch_size=CHUNK, time_mode="event",
    )
    job.late_policy = "side_output"
    col_rows = []

    class _ColSink:
        def accept_columns(self, t, cols):
            for k in range(len(t)):
                col_rows.append(
                    (int(t[k]),
                     tuple(cols[n][k] for n in schema.field_names))
                )

    row_rows = []
    job.add_sink(late_stream("S"), _ColSink())
    job.add_sink(late_stream("S"), lambda t, row: row_rows.append(
        (int(t), row)
    ))
    job.run()
    want = sorted(
        (int(ts[i]), records[i]) for i in src.late_log.tolist()
    )
    # full input rows surface on the late channel — identically on
    # the columnar and the per-row sink, and in collected[]
    assert sorted(col_rows) == want
    assert sorted(row_rows) == want
    assert sorted(job.collected[late_stream("S")]) == want
    assert job.late_events == len(want) and job.late_dropped == 0
    counters = job.telemetry.snapshot()["counters"]
    assert counters["faults.late_side_output"] == len(want)
    # nothing late leaked into the query results
    keep = [i for i in range(len(records)) if i not in
            set(src.late_log.tolist())]
    assert sorted(
        _norm(t, r) for t, r in job.results_with_ts("o")
    ) == _filter_oracle(records, ts, keep)


def test_late_policy_allow_admits_within_allowance_in_order():
    """'allow': the gate holds its horizon back by the allowance, so
    stragglers within it still merge IN ORDER — output equals the
    pristine sorted stream's, nothing dropped."""
    records, ts = _stream()
    sched = _late_schedule(seed=17)
    # generous allowance: covers late_release_ms + placement slack
    # (two chunks) + the strategy skew
    job, src = _run_disordered(
        records, ts, sched, cql=FILTER_CQL,
        late_policy="allow", allowed_lateness_ms=15_000,
    )
    assert src.injected["late"] == 12
    assert job.late_dropped == 0 and job.late_events == 0
    assert sorted(
        _norm(t, r) for t, r in job.results_with_ts("o")
    ) == _filter_oracle(records, ts, range(len(records)))


def test_late_policy_allow_beyond_allowance_drops_loudly(caplog):
    """Beyond the allowance 'allow' DROPS, counted, with the
    documented re-fire rejection in the warning — never a silent
    wrong answer."""
    import logging

    records, ts = _stream()
    sched = _late_schedule(seed=21)
    with caplog.at_level(
        logging.WARNING, logger="flink_siddhi_tpu.runtime.executor"
    ):
        job, src = _run_disordered(
            records, ts, sched, cql=FILTER_CQL,
            late_policy="allow", allowed_lateness_ms=100,
        )
    assert job.late_dropped == src.injected["late"] == 12
    assert any("re-fire" in r.message.lower() for r in caplog.records)


# -- idle-source handling ---------------------------------------------------

def test_idle_source_stops_pinning_watermark_and_unidles():
    """One flowing source + one silent source: without idle handling
    the min watermark pins at the silent source and NOTHING releases;
    with idle_timeout_ms the silent source is marked idle within the
    timeout, the backlog releases, and the source un-idles on its
    next event (whose old rows meet the late policy, not the gate)."""
    schema = _schema()
    records, ts = _stream(n=900, step_ms=10)
    quiet = CallbackSource("S", schema)
    flowing = ListSource(
        "S", schema, records, timestamps=ts, chunk=CHUNK
    )
    plan = compile_plan(FILTER_CQL, {"S": schema})
    job = Job(
        [plan], [flowing, quiet], batch_size=CHUNK, time_mode="event"
    )
    job.idle_timeout_ms = 40.0
    deadline = time.monotonic() + 10.0
    while not job.collected.get("o") and time.monotonic() < deadline:
        job.run_cycle()
        job.drain_outputs()
    # the flowing source's rows released despite the silent source
    assert job.collected.get("o"), "idle source still pins the gate"
    assert job.idle_source_ids() == ["S"]
    m = job.metrics()
    assert [s for s in m["sources"] if s["idle"]], m["sources"]
    assert job.telemetry.snapshot()["counters"]["idle.marked"] >= 1
    # un-idle on the next event: its watermark claim rejoins the min
    quiet.emit((2, 1.0, 999_999), timestamp_ms=999_999)
    job.run_cycle()
    assert job.idle_source_ids() == []
    assert (
        job.telemetry.snapshot()["counters"]["idle.unidled"] == 1
    )


def test_idle_source_keeps_polling_under_block_shed_policy():
    """'block' + idle interaction: over the pending bound only
    watermark laggards keep polling — an idle (then un-idling) source
    must stay in that exempt set or the backlog deadlocks."""
    schema = _schema()
    records, ts = _stream(n=1200, step_ms=10)
    quiet = CallbackSource("S", schema)
    flowing = ListSource(
        "S", schema, records, timestamps=ts, chunk=CHUNK
    )
    plan = compile_plan(FILTER_CQL, {"S": schema})
    job = Job(
        [plan], [flowing, quiet], batch_size=CHUNK, time_mode="event"
    )
    job.idle_timeout_ms = 0.0  # first empty poll marks idle
    job.max_pending_events = 2 * CHUNK
    job.shed_policy = "block"
    deadline = time.monotonic() + 10.0
    while not job.collected.get("o") and time.monotonic() < deadline:
        job.run_cycle()
        job.drain_outputs()
    assert job.collected.get("o"), "block policy deadlocked the gate"
    # the silent source was still being polled while idle (that is
    # how it un-idles): feed it and finish the job
    quiet.advance_watermark(10**9)
    quiet.close()
    flowing_done = time.monotonic() + 10.0
    while not job.finished and time.monotonic() < flowing_done:
        job.run_cycle()
    assert job.finished
    expected = _filter_oracle(records, ts, range(len(records)))
    assert sorted(
        _norm(t, r) for t, r in job.results_with_ts("o")
    ) == expected


# -- multi-source join under disorder ---------------------------------------

JOIN_CQL = (
    "from T#window.length(4) as t join Q#window.length(3) as q "
    "on t.sym == q.sym select t.sym, t.price, q.bid insert into oj"
)


def _join_schemas():
    t = StreamSchema(
        [("sym", AttributeType.INT), ("price", AttributeType.DOUBLE)]
    )
    q = StreamSchema(
        [("sym", AttributeType.INT), ("bid", AttributeType.DOUBLE)]
    )
    return t, q


def _join_streams(n=1500, seed=3):
    """Interleaved skewed timestamps: trades on odd ms, quotes on
    even ms — two topics never arrive aligned."""
    rng = np.random.default_rng(seed)
    trades = [
        (int(s), float(p))
        for s, p in zip(rng.integers(0, 4, n),
                        rng.integers(1, 90, n))
    ]
    quotes = [
        (int(s), float(b))
        for s, b in zip(rng.integers(0, 4, n),
                        rng.integers(1, 90, n))
    ]
    t_ts = (1_001 + np.arange(n, dtype=np.int64) * 10).tolist()
    q_ts = (1_006 + np.arange(n, dtype=np.int64) * 10).tolist()
    return trades, t_ts, quotes, q_ts


def _run_join(t_src, q_src):
    ts_schema, qs_schema = _join_schemas()
    plan = compile_plan(JOIN_CQL, {"T": ts_schema, "Q": qs_schema})
    job = Job(
        [plan], [t_src, q_src], batch_size=CHUNK, time_mode="event"
    )
    job.run()
    return sorted(
        _norm(t, r) for t, r in job.results_with_ts("oj")
    )


def test_multi_source_join_under_disorder():
    """The 'honest multi-source joins' pin: two independently
    disordered sources through a windowed join, row-exact vs the same
    join fed both streams sorted."""
    ts_schema, qs_schema = _join_schemas()
    trades, t_ts, quotes, q_ts = _join_streams()
    want = _run_join(
        ListSource("T", ts_schema, trades, timestamps=t_ts,
                   chunk=CHUNK),
        ListSource("Q", qs_schema, quotes, timestamps=q_ts,
                   chunk=CHUNK),
    )
    assert want, "join oracle produced no rows"
    t_dis = DisorderSource(
        ListSource("T", ts_schema, trades, timestamps=t_ts,
                   chunk=CHUNK),
        DisorderSchedule(seed=51, skew_ms=SKEW_MS), chunk=CHUNK,
    )
    q_dis = DisorderSource(
        ListSource("Q", qs_schema, quotes, timestamps=q_ts,
                   chunk=CHUNK),
        DisorderSchedule(seed=52, skew_ms=SKEW_MS), chunk=CHUNK,
    )
    got = _run_join(
        with_watermarks(t_dis, skew_ms=SKEW_MS),
        with_watermarks(q_dis, skew_ms=SKEW_MS),
    )
    assert got == want


# -- kafka: per-partition watermark generation ------------------------------

def test_kafka_per_partition_watermark_min_across_partitions():
    import json

    from tests.fake_kafka import FakeBroker
    from flink_siddhi_tpu.runtime.kafka import KafkaSource

    broker = FakeBroker()
    try:
        broker.create_topic("t", partitions=2)

        def rec(i, t):
            return json.dumps(
                {"id": i, "price": 1.0, "timestamp": t}
            ).encode()

        # partition 0 far ahead of partition 1
        broker.append("t", 0, [rec(1, 10_000), rec(2, 20_000)])
        broker.append("t", 1, [rec(3, 5_000)])
        schema = _schema()
        src = KafkaSource(
            "S", schema, broker.bootstrap, "t",
            ts_field="timestamp",
            watermark=BoundedDisorderWatermark(1_000),
        )
        batch, wm, done = src.poll(64)
        assert len(batch) == 3 and not done
        # min across producing partitions: p0 at 19_999-1, p1 at
        # 5_000-1_000-1
        assert wm == 3_999
        # per-partition state rides the checkpoint
        d = src.state_dict()
        assert set(d["wm"]) == {"0", "1"}
        src2 = KafkaSource(
            "S", schema, broker.bootstrap, "t",
            ts_field="timestamp",
            watermark=BoundedDisorderWatermark(1_000),
        )
        src2.load_state_dict(d)
        assert src2._partition_watermark() == 3_999
        # the lagging partition catches up: the min advances
        broker.append("t", 1, [rec(4, 21_000)])
        batch, wm, done = src.poll(64)
        assert len(batch) == 1
        assert wm == 18_999  # now pinned by partition 0's 20_000
    finally:
        broker.close()


def test_kafka_empty_partition_does_not_pin_watermark():
    import json

    from tests.fake_kafka import FakeBroker
    from flink_siddhi_tpu.runtime.kafka import KafkaSource

    broker = FakeBroker()
    try:
        broker.create_topic("t", partitions=2)
        broker.append("t", 0, [json.dumps(
            {"id": 1, "price": 1.0, "timestamp": 50_000}
        ).encode()])
        # partition 1 never produces
        schema = _schema()
        src = KafkaSource(
            "S", schema, broker.bootstrap, "t",
            ts_field="timestamp",
            watermark=BoundedDisorderWatermark(1_000),
        )
        batch, wm, _ = src.poll(64)
        assert len(batch) == 1
        assert wm == 48_999  # the never-producing partition is absent
    finally:
        broker.close()


def test_kafka_partition_idleness_unpins_min_watermark():
    """One silent PARTITION must stop pinning the source's min claim
    after idle_timeout_ms (0 = the first poll it sits out), un-idle on
    its next record, and carry its idle flag through checkpoints — the
    PR 10 carried item (before this, only the job-level timeout could
    unpin, by silencing the whole SOURCE)."""
    import json

    from tests.fake_kafka import FakeBroker
    from flink_siddhi_tpu.runtime.kafka import KafkaSource
    from flink_siddhi_tpu.telemetry import MetricsRegistry

    broker = FakeBroker()
    try:
        broker.create_topic("t", partitions=2)

        def rec(i, t):
            return json.dumps(
                {"id": i, "price": 1.0, "timestamp": t}
            ).encode()

        broker.append("t", 0, [rec(1, 10_000)])
        broker.append("t", 1, [rec(2, 5_000)])
        schema = _schema()

        def make_src():
            return KafkaSource(
                "S", schema, broker.bootstrap, "t",
                ts_field="timestamp",
                watermark=BoundedDisorderWatermark(1_000),
                idle_timeout_ms=0,
            )

        src = make_src()
        reg = MetricsRegistry()
        src.bind_telemetry(reg)
        _b, wm, _d = src.poll(64)
        assert wm == 3_999  # both produced: plain min across partitions
        # partition 1 goes silent while 0 keeps producing: with the
        # 0ms timeout it idles on the first poll it sits out, and the
        # claim advances to partition 0's alone
        broker.append("t", 0, [rec(3, 30_000)])
        _b, wm, _d = src.poll(64)
        assert src._part_idle[1] and not src._part_idle[0]
        assert wm == 28_999
        assert reg.counter("idle.partition_marked").value == 1
        # the idle FLAG rides the checkpoint
        d = src.state_dict()
        assert d["part_idle"] == {"0": False, "1": True}
        src2 = make_src()
        src2.load_state_dict(d)
        assert src2._part_idle[1]
        assert src2._partition_watermark() == 28_999
        # an all-empty poll idles the remaining partition too (0ms =
        # first sit-out): ALL-idle means the claim HOLDS (None), not
        # jump-to-MAX — idle is "no information", Flink semantics
        _b, wm, done = src.poll(64)
        assert (wm, done) == (None, False)
        assert src._part_idle[0] and src._part_idle[1]
        # un-idles on its next record: the claim is that partition's
        # own again (the source claim may regress; the job's gate
        # watermark is monotone and classifies stragglers by policy)
        broker.append("t", 1, [rec(4, 6_000)])
        _b, wm, _d = src.poll(64)
        assert not src._part_idle[1] and src._part_idle[0]
        assert wm == 4_999
        assert reg.counter("idle.partition_unidled").value == 1
    finally:
        broker.close()


def test_kafka_partition_with_buffered_backlog_is_not_idle():
    """A partition whose records are fetched-but-unconsumed (a
    high-volume sibling can monopolize poll's max_events slice) is NOT
    silent: idling it would misclassify its still-queued rows as late
    once they drain."""
    import json

    from tests.fake_kafka import FakeBroker
    from flink_siddhi_tpu.runtime.kafka import KafkaSource

    broker = FakeBroker()
    try:
        broker.create_topic("t", partitions=2)

        def rec(i, t):
            return json.dumps(
                {"id": i, "price": 1.0, "timestamp": t}
            ).encode()

        broker.append(
            "t", 0, [rec(i, 10_000 + 1_000 * i) for i in range(4)]
        )
        broker.append("t", 1, [rec(9, 5_000)])
        schema = _schema()
        src = KafkaSource(
            "S", schema, broker.bootstrap, "t",
            ts_field="timestamp",
            watermark=BoundedDisorderWatermark(1_000),
            idle_timeout_ms=0,
        )
        # poll(2) consumes only partition 0's head; partition 1's
        # record waits in the fetch buffer — it must not idle even at
        # the 0ms timeout. (The claim is p0's alone for now: a
        # partition that never PRODUCED does not pin the min — the
        # pre-existing PR 10 semantics; idleness must not make that
        # permanent.)
        _b, wm, _d = src.poll(2)
        assert not src._part_idle[1]
        assert wm == 9_999
        # draining the backlog rejoins p1: the true min again (the
        # executor's per-source max keeps the gate monotone)
        _b, wm, _d = src.poll(64)
        assert not src._part_idle[1]
        assert wm == 3_999
    finally:
        broker.close()


# -- checkpoint / supervised recovery ---------------------------------------

def test_gate_watermark_state_survives_checkpoint_roundtrip(tmp_path):
    records, ts = _stream(n=1200)
    schema = _schema()

    def build():
        plan = compile_plan(FILTER_CQL, {"S": schema})
        src = DisorderSource(
            ListSource("S", schema, records, timestamps=ts,
                       chunk=CHUNK),
            DisorderSchedule(seed=2, skew_ms=SKEW_MS), chunk=CHUNK,
        )
        return Job(
            [plan], [with_watermarks(src, skew_ms=SKEW_MS)],
            batch_size=CHUNK, time_mode="event",
        )

    job = build()
    for _ in range(3):
        job.run_cycle()
    path = str(tmp_path / "ckpt")
    job.save_checkpoint(path)
    pre_rows = job.results_with_ts("o")  # emitted before the snapshot
    restored = build()
    restored.restore(path)
    assert restored._released_wm == job._released_wm
    assert restored._gate_wm == job._gate_wm
    assert restored._source_wm == job._source_wm
    assert restored._max_event_ts == job._max_event_ts
    # and the resumed run completes the stream: pre-checkpoint rows +
    # post-restore rows together equal an uninterrupted run's, with no
    # duplicate and no loss (the supervisor's commit protocol handles
    # the crash-suffix case; this pins plain save/restore)
    while not restored.finished:
        restored.run_cycle()
    restored.flush()
    uninterrupted = build()
    uninterrupted.run()
    assert sorted(pre_rows + restored.results_with_ts("o")) == sorted(
        uninterrupted.results_with_ts("o")
    )


def test_supervised_kill_restore_exactly_once_under_disorder(tmp_path):
    """The acceptance pin: watermark state survives supervised
    kill->restore (including a kill mid-checkpoint) with 0 duplicate
    and 0 lost rows vs the unfaulted oracle, under disorder + late
    drops (the late account stays exact across restarts too)."""
    records, ts = _stream(n=3000)
    schema = _schema()
    sched = DisorderSchedule(
        seed=29, skew_ms=SKEW_MS, dup_rate=0.005, dup_burst=2,
        late_count=8, late_release_ms=2_000,
    )
    crash = CrashPlan(at_pulls=(3, 9), at_checkpoints=(2,))

    def factory(armed=True):
        plan = compile_plan(FILTER_CQL, {"S": schema})
        src = DisorderSource(
            ListSource("S", schema, records, timestamps=ts,
                       chunk=CHUNK),
            sched, chunk=CHUNK,
        )
        job = Job(
            [plan], [with_watermarks(src, skew_ms=SKEW_MS)],
            batch_size=CHUNK, time_mode="event", retain_results=False,
        )
        job.late_policy = "drop"
        job._disorder_src = src
        return wrap_job(job, crash) if armed else job

    ckpt = str(tmp_path / "ckpt")
    sup = Supervisor(
        factory, ckpt, checkpoint_every_cycles=2, keep_checkpoints=3,
        max_restarts=10, restart_window_s=3600.0,
    )
    final_job = sup.run()
    assert crash.crashes == 3

    # unfaulted oracle: the same supervised wiring, no crashes
    oracle_job = factory(armed=False)
    rows = collections.defaultdict(list)
    for sid in ("o",):
        oracle_job.add_sink(
            sid, lambda t, row, _s=sid: rows[_s].append((t, row))
        )
    oracle_job.run()
    committed = collections.Counter(sup.results_with_ts("o"))
    oracle = collections.Counter(rows["o"])
    assert sum((committed - oracle).values()) == 0, "duplicate rows"
    assert sum((oracle - committed).values()) == 0, "lost rows"
    # the late account survived restore: exact vs the schedule
    assert final_job.late_dropped == sched.late_count
    assert glob.glob(f"{ckpt}.tmp.*") == []


# -- control backlog drain (the O(n^2) pop(0) fix) --------------------------

def test_control_backlog_applies_in_order_and_gates_on_watermark():
    schema = _schema()
    plan = compile_plan(FILTER_CQL, {"S": schema})
    job = Job(
        [plan],
        [ListSource("S", schema, [(2, 1.0, 1)], timestamps=[1])],
        batch_size=8, time_mode="event",
    )
    applied = []
    job._apply_control = applied.append
    # a long, unsorted backlog behind the watermark gate
    job._control_pending = [
        (t, f"ev{t}") for t in range(500, 0, -1)
    ]
    job._source_wm = [250]  # watermark admits only half
    job._apply_ready_control()
    assert applied == [f"ev{t}" for t in range(1, 251)]
    assert [t for t, _ in job._control_pending] == list(
        range(251, 501)
    )
    # the rest drains when the watermark passes
    job._source_wm = [10_000]
    job._apply_ready_control()
    assert len(applied) == 500
    assert job._control_pending == []
