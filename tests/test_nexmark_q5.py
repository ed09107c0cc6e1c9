"""NEXmark Q5 (hot items) on the normal path, at small sizes on the CPU:
``#window.hop`` with ``windowMax`` against the benchmark's plain
reference at the source's epoch, the hop as the sum of its panes, ties
and partial windows, group slots that expire and are reused (and
round-trip through a checkpoint), and a ``long`` attribute at epoch
scale."""

import importlib.util
import json
import os

import numpy as np
import pytest

from flink_siddhi_tpu import CEPEnvironment, SiddhiCEP
from flink_siddhi_tpu.api.stream import SingleStream
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.query.lexer import SiddhiQLError
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.encoders import GroupEncoder
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
EPOCH = 1436918400000  # the source's base time: 2015-07-15 00:00 UTC


def _module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q5_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(BENCH, "configs", "nexmark_q5.json")) as _f:
    CFG = {**json.load(_f), "event_time_rate": 2_000}
REFERENCE = _module("configs", "nexmark_q5")
Q5 = CFG["cql"]
BATCH, POOL = 1_000, 20_000  # half a second a batch, 10 s a cycle


def _schema():
    return StreamSchema(
        [(name, AttributeType(kind)) for name, kind in CFG["fields"]])


class PoolSource:
    """The pool's stream in batches, from batch ``start`` to ``stop``."""

    stream_id = "nexmark"

    def __init__(self, pool, start, stop):
        self.schema = _schema()
        self.serve = pool.server(BATCH, lambda _f, _v: 0)
        self.next, self.stop = start, stop

    def poll(self, _max_events):
        if self.next >= self.stop:
            return None, np.iinfo(np.int64).max, True
        cols, ts = self.serve(self.next)
        self.next += 1
        return EventBatch("nexmark", self.schema, cols, ts), int(ts[-1]), False


def _job(pool, start, stop, cql=Q5, **config):
    src = PoolSource(pool, start, stop)
    plan = compile_plan(cql, {"nexmark": src.schema}, plan_id="q5",
                        config=EngineConfig(**config))
    job = Job([plan], [src], batch_size=BATCH, time_mode="processing",
              retain_results=True)
    job.fused_segment_len = 2
    return job, plan


def _run(job):
    while not job.finished:
        job.run_cycle()
    job.flush()
    return job


def _rows(job, stream="hot"):
    return [(int(t), int(r[0]), int(r[1]))
            for t, r in job.results_with_ts(stream)]


def _want(pool, n_events):
    w = REFERENCE.expected(pool, 0, n_events)
    return list(zip(w["@ts"].tolist(), w["auction"].tolist(),
                    w["num"].tolist()))


def _pool(seed, **kw):
    return _module("generators", "nexmark").make_pool(
        seed, POOL, {**CFG, **kw})


@pytest.mark.parametrize("seed", [5, 8008, 2_147_483_659])
def test_program_equals_the_plain_reference_at_the_sources_epoch(seed):
    """35 s of event time: partial first windows, full windows, and the
    pool's cycle boundary crossed three times."""
    pool = _pool(seed)
    n_batches = 70
    got = _rows(_run(_job(pool, 0, n_batches)[0]))
    want = _want(pool, n_batches * BATCH)
    # the stream's last window has not closed: no later event came
    assert 15 <= len(got) and len(want) - 1 <= len(got) <= len(want)
    assert got == want[:len(got)]
    assert got[0][0] == EPOCH + 1_999 and got[0][0] % 2_000 == 1_999


def test_a_range_across_the_pools_cycle_boundary():
    pool = _pool(11)
    got = _rows(_run(_job(pool, 0, 60)[0]))
    a, b = POOL - 3_000, POOL + 5_000  # events 17,000 .. 25,000
    part = REFERENCE.expected(pool, a, b)
    lo, hi = int(pool.ts_of(a)), int(pool.ts_of(b - 1))
    mine = [r for r in got if lo <= r[0] <= hi]
    assert len(mine) >= 2
    assert mine == list(zip(part["@ts"].tolist(), part["auction"].tolist(),
                            part["num"].tolist()))


def test_a_hop_window_is_the_sum_of_its_panes():
    """Every group of every window (no ``having``) against the tumbling
    panes the program's own ``externalTimeBatch`` cuts at the same
    epoch-ms attribute."""
    cql = (
        "from nexmark[event_type == 2]#window.hop(dateTime, 10 sec, 2 sec) "
        "select auction, count() as num group by auction insert into hot; "
        "from nexmark[event_type == 2]#window.externalTimeBatch(dateTime, "
        "2 sec) select auction, count() as num group by auction "
        "insert into panes"
    )
    job = _run(_job(_pool(7), 0, 50, cql=cql)[0])
    hop, panes = _rows(job, "hot"), {}
    for t, auction, num in _rows(job, "panes"):
        pane = panes.setdefault((t - EPOCH) // 2_000, {})
        pane[auction] = pane.get(auction, 0) + num
    by_window = {}
    for t, auction, num in hop:
        by_window.setdefault((t + 1 - EPOCH) // 2_000, {})[auction] = num
    assert len(by_window) >= 10
    for q, groups in by_window.items():
        total = {}
        for p in range(q - 5, q):
            for auction, num in panes.get(p, {}).items():
                total[auction] = total.get(auction, 0) + num
        assert groups == total, q
    # within a window the rows leave in auction order
    assert hop == sorted(hop, key=lambda r: (r[0], r[1]))


def _events(rows):
    """(auction, dateTime offset) bids -> the union stream's tuples."""
    return [(2, a, 0, 100, EPOCH + t) for a, t in rows]


def _hot(events, cql=Q5, batch_size=4):
    env = CEPEnvironment(batch_size=batch_size)
    env.register_stream(
        "nexmark", events, [n for n, _ in CFG["fields"]],
        [k for _, k in CFG["fields"]], ts_field="dateTime")
    out = cql.rsplit(" ", 1)[-1]
    job = SingleStream(env, "nexmark").cql(cql).execute()
    return [(int(t) - EPOCH, *map(int, r)) for t, r in
            job.results_with_ts(out)]


def test_ties_partial_first_windows_gaps_and_late_events():
    rows = _hot(_events([
        (7, 100), (5, 200), (9, 300),      # pane 0: a three-way tie
        (5, 2_100),                        # pane 1
        (9, 4_500), (9, 4_600), (7, 4_700),  # pane 2
        (7, 4_100),                        # late: still counts, in pane 2
        (3, 40_000),                       # a gap: every window closes
        (3, 41_000), (4, 42_500),          # pane 20, then pane 21 closes 20
    ]))
    assert rows == [
        (1_999, 5, 1), (1_999, 7, 1), (1_999, 9, 1),  # [-8,000, 2,000)
        (3_999, 5, 2),
        (5_999, 7, 3), (5_999, 9, 3),  # 7: 1 + 2, 9: 1 + 2
        (7_999, 7, 3), (7_999, 9, 3),
        (9_999, 7, 3), (9_999, 9, 3),
        (11_999, 7, 2), (11_999, 9, 2),  # pane 0 has left
        (13_999, 7, 2), (13_999, 9, 2),  # pane 1 has left
        (41_999, 3, 2),  # nothing between: empty windows emit nothing
    ]


def test_rows_past_the_emit_buffer_are_dropped_and_counted():
    """One micro-batch emits at most ``EMIT_ROWS`` rows: a window with
    more groups than that (no ``having`` here, so every group is a row)
    loses the rest, in key order, and says so."""
    from flink_siddhi_tpu.compiler.hop_window import EMIT_ROWS

    n = EMIT_ROWS + 904
    events = _events([(a, 0) for a in range(1, n + 1)] + [(1, 2_100)])
    env = CEPEnvironment(batch_size=8_192)
    env.register_stream(
        "nexmark", events, [f for f, _ in CFG["fields"]],
        [k for _, k in CFG["fields"]], ts_field="dateTime")
    job = SingleStream(env, "nexmark").cql(
        "from nexmark#window.hop(dateTime, 4 sec, 2 sec) select auction, "
        "count() as num group by auction insert into all").execute()
    rows = [(int(t) - EPOCH, *map(int, r))
            for t, r in job.results_with_ts("all")]
    assert rows == [(1_999, a, 1) for a in range(1, EMIT_ROWS + 1)]
    assert job.telemetry.counter_value("faults.emissions_dropped") == 904


def _timestamped(start, cql):
    rows = [(i % 2, start + 400 * i) for i in range(12)]
    return (SiddhiCEP.define("S", rows, ["id", "timestamp"], batch_size=5)
            .cql(cql).returns("o"))


def test_a_time_attribute_keeps_its_value_for_every_other_reader():
    """The window reads its own copy of the attribute, on the job's
    clock; a projection and a second query's filter read the raw
    column, which is a device int32: right where the value fits, and
    refused (not wrapped, not rebased) where it does not."""
    cql = ("from S#window.externalTimeBatch(timestamp, 2 sec) select id, "
           "timestamp as t, count() as n group by id insert into o; "
           "from S[timestamp >= 3200] select id insert into late")
    out = sorted(map(tuple, _timestamped(1_000, cql)))
    assert out == [(0, 2_600, 3), (0, 4_200, 2), (0, 5_000, 1),
                   (1, 2_200, 2), (1, 4_600, 3), (1, 5_400, 1)]
    plan = compile_plan(cql, {"S": StreamSchema(
        [("id", AttributeType.INT), ("timestamp", AttributeType.LONG)])})
    assert plan.spec.time_columns == ("S.timestamp",)
    assert "S.timestamp" in plan.spec.columns
    with pytest.raises(ValueError, match="read as a value"):
        _timestamped(EPOCH + 3_600_000, cql)
    # the window alone reads it: the same rows at any epoch
    counts = ("from S#window.externalTimeBatch(timestamp, 2 sec) select "
              "id, count() as n group by id insert into o")
    assert sorted(map(tuple, _timestamped(EPOCH + 3_600_000, counts))) == \
        sorted(map(tuple, _timestamped(1_000, counts)))
    # a chained stream has no rebased copy: a window over it reads what
    # the producer emitted, under the same key
    chained = ("from S[id >= 0] select id, timestamp insert into mid; "
               "from mid#window.externalTimeBatch(timestamp, 2 sec) select "
               "count() as n insert into o")
    assert sorted(map(tuple, _timestamped(1_000, chained))) == [
        (2,), (5,), (5,)]


@pytest.mark.parametrize("cql, word", [
    ("from nexmark#window.hop(dateTime, 10 sec, 3 sec) select count() as n "
     "insert into o", "multiple of the slide"),
    ("from nexmark#window.hop(dateTime, 14 sec, 7 sec) select count() as n "
     "insert into o", "divide a day"),
    ("from nexmark#window.hop(dateTime, 10 sec) select count() as n "
     "insert into o", "tsAttribute, size, slide"),
    ("from nexmark#window.hop(dateTime, 4 sec, 2 sec) select sum(price) as m "
     "insert into o", "counts its panes"),
    ("from nexmark#window.hop(event_type, 4 sec, 2 sec) select count() as n "
     "insert into o", "needs a long"),
    ("from nexmark#window.hop(dateTime, 4 sec, 2 sec) select bidder, "
     "count() as n group by auction insert into o", "group-by key"),
    ("from nexmark#window.hop(dateTime, 4 sec, 2 sec) select auction "
     "group by auction insert into o", "aggregating"),
])
def test_what_the_hop_window_refuses(cql, word):
    with pytest.raises(SiddhiQLError, match=word):
        compile_plan(cql, {"nexmark": _schema()})


def test_live_slots_stop_growing_while_ids_churn():
    """120 auctions open a second; a window holds 10 s of them."""
    pool = _pool(3)
    job, plan = _job(pool, 0, 120)
    enc = plan.spec.encoded[0].encoder
    sizes, regrows = [], []
    while not job.finished:
        job.run_cycle()
        sizes.append(len(enc))
        regrows.append(job.telemetry.counter_value("groups.regrow"))
    job.flush()
    assert job.processed_events == 120 * BATCH
    third = len(sizes) // 3
    assert sizes[third] == sizes[-1] < 2_048  # the table: flat after 20 s
    assert regrows[third] == regrows[-1]  # ... and no re-bucketing since
    assert enc.stats["interned"] > 3 * len(enc)  # ids kept churning
    counters = job.telemetry.snapshot()["counters"]
    assert counters["groups.slots_reused"] > 0.6 * counters["groups.interned"]
    assert counters["groups.expired"] >= counters["groups.slots_reused"]
    assert job.telemetry.gauge_value("groups.live") <= sizes[-1]
    assert counters["hop.windows_closed"] == len(_rows(job)) == 29
    assert "nested.group_intern" in job.telemetry.snapshot()["stages"]
    # sized from the start, the table never re-buckets at all
    job, _ = _job(pool, 0, 60, hop_group_slots=2_048)
    _run(job)
    assert job.telemetry.counter_value("groups.regrow") == 0
    assert _rows(job) == _want(pool, 60 * BATCH)[:14]


def test_a_checkpoint_with_recycled_slots_restores_the_same_rows():
    pool = _pool(9)
    whole = _rows(_run(_job(pool, 0, 90)[0]))
    first, plan = _job(pool, 0, 55)
    _run(first)
    assert plan.spec.encoded[0].encoder.stats["slots_reused"] > 0
    snap = first.snapshot()
    values = snap["plans"]["q5"]["encoders"]["@group:query_0"]["values"]
    assert None in values  # freed slots travel as holes
    second, plan2 = _job(pool, 55, 90)
    second.restore(snap)
    _run(second)
    assert _rows(first) + _rows(second) == whole
    assert len(plan2.spec.encoded[0].encoder) == len(
        plan.spec.encoded[0].encoder)


def test_group_encoder_expiry_round_trips_and_keeps_append_only_default():
    plain = GroupEncoder()
    keys = np.array([40, 7, 40, 9], dtype=np.int64)
    assert plain.intern_rows([keys], np.ones(4, bool)).tolist() == [2, 0, 2, 1]
    assert plain.value(2) == (40,) and len(plain) == 3
    assert plain.state_dict() == {"values": [(7,), (9,), (40,)]}
    enc = GroupEncoder(retain_ticks=2)
    for tick in range(6):
        keys = np.arange(tick * 2, tick * 2 + 3, dtype=np.int64)
        enc.intern_rows([keys], np.ones(3, bool), np.full(3, tick * 10), 10)
    assert len(enc) == 7 and enc.stats["slots_reused"] == enc.stats["expired"] > 0
    twin = GroupEncoder(retain_ticks=2)
    twin.load_state_dict(enc.state_dict())
    for tick in range(6, 9):
        keys = np.arange(tick * 2, tick * 2 + 3, dtype=np.int64)
        args = ([keys], np.ones(3, bool), np.full(3, tick * 10), 10)
        assert enc.intern_rows(*args).tolist() == twin.intern_rows(
            *args).tolist()
    # several columns go through the dict, expiry included
    pairs = GroupEncoder(retain_ticks=1)
    for tick in range(4):
        a = np.array([tick, tick]), np.array([0.5, 1.5])
        pairs.intern_rows(list(a), np.ones(2, bool), np.full(2, tick), 1)
    assert len(pairs) == 4 and pairs.live == 4 and pairs.value(0) is not None


def test_define_takes_epoch_ms_longs_and_a_time_window_reads_them():
    """``SiddhiCEP.define`` used to raise OverflowError on these rows; an
    ``externalTimeBatch`` over them used to cut panes at garbage."""
    rows = [(i % 2, EPOCH + 400 * i) for i in range(12)]
    out = (
        SiddhiCEP.define("S", rows, ["id", "timestamp"], batch_size=5)
        .cql("from S#window.externalTimeBatch(timestamp, 2 sec) select id, "
             "count() as n group by id insert into o")
        .returns("o")
    )
    assert sorted(map(tuple, out)) == [
        (0, 1), (0, 2), (0, 3), (1, 1), (1, 2), (1, 3)]
    hop = (
        SiddhiCEP.define("S", rows, ["id", "timestamp"], batch_size=5)
        .cql("from S#window.hop(timestamp, 4 sec, 2 sec) select id, "
             "count() as n group by id having n >= windowMax(n) "
             "insert into o")
        .returns("o")
    )
    assert list(map(tuple, hop)) == [(0, 3), (0, 5), (1, 5)]


def test_a_time_attribute_off_the_jobs_clock_is_refused():
    from flink_siddhi_tpu.runtime.tape import build_tape, time_origin

    plan = compile_plan(Q5, {"nexmark": _schema()})
    assert plan.spec.time_columns == ("nexmark.dateTime",)
    cols = {n: np.zeros(2, np.int64) for n, _ in CFG["fields"]}
    cols["dateTime"] = np.array([EPOCH, EPOCH + 5], np.int64)
    batch = EventBatch("nexmark", _schema(), cols,
                       np.array([1_000, 1_001], np.int64))
    with pytest.raises(ValueError, match="job's clock"):
        build_tape(plan.spec, [batch], 1_000)
    batch.timestamps = cols["dateTime"]
    tape, _ = build_tape(plan.spec, [batch], EPOCH + 3_600_000)
    assert time_origin(EPOCH + 3_600_000) == EPOCH
    assert tape.time_off == 3_600_000
    assert tape.cols["@time:nexmark.dateTime"][:2].tolist() == [0, 5]
    # nothing reads dateTime as a value: the raw column is not built
    assert "nexmark.dateTime" not in tape.cols
