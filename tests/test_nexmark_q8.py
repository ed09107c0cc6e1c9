"""NEXmark Q8 (monitor new users) on the normal path, at small sizes on
the CPU: the tumbling-window equi-join keyed on the device against the
per-event interpreter and the benchmark's plain reference, an auction
before its person, a pair split by a window's end, a closing of more
rows than ``#window.hop`` emits a batch, slots that expire and are
reused (and round-trip through a checkpoint), two streams, what the
path refuses, and the joins that stay on the pair-matrix path."""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from flink_siddhi_tpu import CEPEnvironment
from flink_siddhi_tpu.api.stream import SingleStream
from flink_siddhi_tpu.baseline import BaselineEngine
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.join import JoinArtifact
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.compiler.window_join import WindowJoinArtifact
from flink_siddhi_tpu.query.lexer import SiddhiQLError
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.encoders import GroupEncoder
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:  # the reference's control imports bmlib
    sys.path.insert(0, BENCH)
EPOCH = 1436918400000  # the source's base time: 2015-07-15 00:00 UTC


def _module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"q8_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


with open(os.path.join(BENCH, "configs", "nexmark_q8.json")) as _f:
    CFG = {**json.load(_f), "event_time_rate": 2_000}
REFERENCE = _module("configs", "nexmark_q8")
Q8 = CFG["cql"]
FIELDS = [n for n, _ in CFG["fields"]]
BATCH, POOL = 1_000, 40_000  # half a second a batch, two windows a cycle


def _schema():
    return StreamSchema(
        [(name, AttributeType(kind)) for name, kind in CFG["fields"]])


def _pool(seed):
    return _module("generators", "nexmark").make_pool(seed, POOL, CFG)


def _batches(pool, start, stop):
    serve = pool.server(BATCH, lambda _f, _v: 0)
    schema = _schema()
    for j in range(start, stop):
        cols, ts = serve(j)
        yield EventBatch("nexmark", schema, cols, ts)


def _job(pool, start, stop, retain=True, **config):
    schema = _schema()
    plan = compile_plan(Q8, {"nexmark": schema}, plan_id="q8",
                        config=EngineConfig(**config))
    job = Job([plan], [BatchSource("nexmark", schema,
                                   _batches(pool, start, stop))],
              batch_size=BATCH, time_mode="processing",
              retain_results=retain)
    return job, plan


def _rows(job, stream="new_sellers"):
    return [(int(t), int(r[0]), int(r[1]))
            for t, r in job.results_with_ts(stream)]


def _want(pool, n_events):
    w = REFERENCE.expected(pool, 0, n_events)
    return list(zip(w["@ts"].tolist(), w["id"].tolist(),
                    w["auctions"].tolist()))


def _interpreted(pool, n_events):
    cols = pool.columns(0, n_events)
    eng, out = BaselineEngine(Q8, FIELDS), []
    eng._emit = lambda _o, t, row: out.append((t, *row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()},
                    cols["dateTime"].tolist())
    return out


class _Columns:
    """The columnar lane: typed columns, one delivery at a time."""

    def __init__(self):
        self.rows = []

    def accept_columns(self, ts, cols):
        assert all(v.dtype != object for v in cols.values())
        self.rows.extend(zip(
            map(int, ts), cols["id"].tolist(), cols["auctions"].tolist()))


@pytest.mark.parametrize("lane", ["rows", "columns"])
@pytest.mark.parametrize("path", ["per_batch", "fused", "resident"])
@pytest.mark.parametrize("seed", [5, 2_147_483_659])
def test_program_interpreter_and_reference_agree(seed, path, lane):
    """45 s of event time: four windows closed, the pool's cycle crossed
    twice, down every path and both sink lanes."""
    pool = _pool(seed)
    n_batches = 90
    job, _ = _job(pool, 0, n_batches, retain=lane == "rows")
    sink = _Columns()
    if lane == "columns":
        job.add_sink("new_sellers", sink)
    if path == "fused":
        job.fused_segment_len = 4
    if path == "resident":
        ResidentReplay(job).execute()
    else:
        job.run()
    got = _rows(job) if lane == "rows" else sink.rows
    # the stream's fifth window has not closed: no later event came
    want = _want(pool, 80 * BATCH)
    assert got == want == _interpreted(pool, n_batches * BATCH)
    assert len({t for t, _i, _a in got}) == 4 and len(got) > 200
    assert got[0][0] == EPOCH + 9_999 and max(a for _t, _i, a in got) > 1
    counters = job.telemetry.snapshot()["counters"]
    assert counters["join.windows_closed"] == 4
    assert counters["join.rows_emitted"] == len(got)
    kinds = pool.columns(0, n_batches * BATCH, ["event_type"])["event_type"]
    assert counters["join.left_events"] == int((kinds == 0).sum())
    assert counters["join.right_events"] == int((kinds == 1).sum())
    assert job.telemetry.counter_value("faults.emissions_dropped") == 0


def _events(rows):
    """(type, id, seller, dateTime offset) -> the union stream's tuples."""
    return [(k, i, s, EPOCH + t) for k, i, s, t in rows]


def _joined(events, cql=Q8, batch_size=4, out="new_sellers"):
    env = CEPEnvironment(batch_size=batch_size)
    env.register_stream(
        "nexmark", events, FIELDS, [k for _, k in CFG["fields"]],
        ts_field="dateTime")
    job = SingleStream(env, "nexmark").cql(cql).execute()
    rows = [(int(t) - EPOCH, *map(int, r))
            for t, r in job.results_with_ts(out)]
    return rows, job


def test_order_of_arrival_window_ends_and_gaps():
    events = _events([
        (1, 50, 7, 100),      # an auction before its person
        (0, 7, 0, 200),
        (1, 51, 9, 300), (0, 9, 0, 400), (1, 52, 7, 9_000),
        (0, 8, 0, 500),       # a person who sells nothing
        (1, 53, 6, 600),      # a seller who registered long ago
        (0, 11, 0, 9_999),    # a pair split by the window's end:
        (1, 54, 11, 10_000),  # ... the auction is the next window's
        (2, 0, 0, 10_001),    # a bid closes nothing and counts nowhere
        (0, 3, 0, 10_500), (1, 55, 3, 10_600), (1, 56, 3, 10_700),
        (0, 4, 0, 60_000),    # a gap: the open window closes, once
        (1, 57, 4, 60_001), (0, 2, 0, 70_000),
    ])
    rows, _ = _joined(events)
    assert rows == [
        (9_999, 7, 2), (9_999, 9, 1),
        (19_999, 3, 2),
        (69_999, 4, 1),
    ]
    eng, out = BaselineEngine(Q8, FIELDS), []
    eng._emit = lambda _o, t, row: out.append((t - EPOCH, *row))
    for e in events:
        eng.process(dict(zip(FIELDS, e)), e[3])
    assert out == rows


def test_a_closing_of_more_rows_than_a_hop_window_emits_drops_nothing():
    """6,000 persons and their auctions in one window, closed by one
    event: every row leaves, in id order, in one step."""
    from flink_siddhi_tpu.compiler.hop_window import EMIT_ROWS

    n = EMIT_ROWS + 1_904
    events = _events(
        [(0, i, 0, i % 9_000) for i in range(1, n + 1)]
        + [(1, 0, i, 9_500) for i in range(n, 0, -1)]
        + [(1, 0, 5, 9_600), (0, 1, 0, 10_000)])
    rows, job = _joined(events, batch_size=16_384)
    assert len(rows) == n > EMIT_ROWS
    assert rows == [(9_999, i, 2 if i == 5 else 1) for i in range(1, n + 1)]
    assert job.telemetry.counter_value("faults.emissions_dropped") == 0
    assert job.telemetry.counter_value("join.rows_emitted") == n


def test_slots_are_reused_once_their_window_has_closed():
    """40 persons and 120 sellers' auctions open a second; a slot lives
    one window past its key's last event."""
    pool = _pool(3)
    job, plan = _job(pool, 0, 200, hop_group_slots=2_048)
    enc = plan.spec.encoded[0].encoder
    sizes = []
    while not job.finished:
        job.run_cycle()
        sizes.append(len(enc))
    job.flush()
    assert job.processed_events == 200 * BATCH
    assert sizes[len(sizes) // 3] == sizes[-1] < 2_048  # flat after 30 s
    assert job.telemetry.counter_value("groups.regrow") == 0
    counters = job.telemetry.snapshot()["counters"]
    assert enc.stats["interned"] > 3 * len(enc)  # ids kept churning
    assert counters["groups.slots_reused"] > 0.6 * counters["groups.interned"]
    assert counters["groups.expired"] >= counters["groups.slots_reused"]
    assert "nested.group_intern" in job.telemetry.snapshot()["stages"]
    assert _rows(job) == _want(pool, 190 * BATCH)
    # the right key is interned on the host and stays there
    assert "nexmark.seller" not in plan.spec.columns
    assert "nexmark.id" in plan.spec.columns
    assert plan.spec.time_columns == ("nexmark.dateTime",)


def test_a_checkpoint_taken_mid_window_restores_to_the_same_rows():
    pool = _pool(9)
    whole, _ = _job(pool, 0, 130)
    whole.run()
    first, plan = _job(pool, 0, 75)  # 37.5 s: mid-window
    first.run()
    assert plan.spec.encoded[0].encoder.stats["slots_reused"] > 0
    snap = first.snapshot()
    values = snap["plans"]["q8"]["encoders"]["@group:query_0"]["values"]
    assert None in values  # freed slots travel as holes
    second, _ = _job(pool, 75, 130)
    second.restore(snap)
    second.run()
    assert _rows(first) + _rows(second) == _rows(whole)
    assert len(_rows(second)) > 100


def test_one_table_fed_by_two_columns_under_two_filters():
    enc = GroupEncoder(retain_ticks=1)
    ids = np.array([7, 0, 0, 9, 0], dtype=np.int32)
    sellers = np.array([0, 9, 7, 0, 4], dtype=np.int64)
    left = np.array([True, False, False, True, False])
    right = np.array([False, True, True, False, True])
    tick = np.full(5, 3)
    codes = enc.intern_sources(
        [(ids, left, tick, "join.left_events"),
         (sellers, right, tick, "join.right_events")], 10)
    # a key has one slot whichever side brought it
    assert codes[0] == codes[2] and codes[1] == codes[3]
    assert len({int(c) for c in codes}) == 3 and len(enc) == 3
    assert enc.stats["join.left_events"] == 2
    assert enc.stats["join.right_events"] == 3
    # ... and keeps it until a window has passed its last event
    again = enc.intern_sources(
        [(ids, left, tick + 10, None), (sellers, np.zeros(5, bool), None,
                                        None)], 10)
    assert again[0] == codes[0] and again[3] == codes[3]
    # key 4 came in window 0 alone: its slot is freed once a batch of
    # window 1 has been seen, and is the next new key's
    assert enc.stats["expired"] == 0
    last = enc.intern_sources(
        [(ids, np.zeros(5, bool), None, None),
         (np.array([0, 9, 7, 0, 5]), right, tick + 20, None)], 10)
    assert enc.stats["expired"] == enc.stats["slots_reused"] == 1
    assert last[4] == codes[4] and enc.live == len(enc) == 3


def test_two_streams_join_on_their_own_time_attributes():
    """Persons and auctions as two streams: each side's window reads its
    own stream's attribute, the key is one table."""
    people = StreamSchema([("id", AttributeType.LONG),
                           ("dateTime", AttributeType.LONG)])
    sales = StreamSchema([("seller", AttributeType.LONG),
                          ("opened", AttributeType.LONG)])
    cql = (
        "from People#window.hop(dateTime, 10 sec, 10 sec) as p join "
        "Sales[seller > 0]#window.hop(opened, 10 sec, 10 sec) as a "
        "on a.seller == p.id select a.seller as who, count() as n "
        "group by p.id insert into o")
    plan = compile_plan(cql, {"People": people, "Sales": sales})
    assert isinstance(plan.artifacts[0], WindowJoinArtifact)
    # a filter reads the right key on the device: it stays on the wire
    assert "Sales.seller" in plan.spec.columns

    def batch(stream, schema, rows):
        cols = {n: np.array([r[i] for r in rows], np.int64)
                for i, n in enumerate(schema.field_names)}
        return EventBatch(stream, schema, cols, cols[schema.field_names[1]])

    t = EPOCH
    job = Job([plan], [
        BatchSource("People", people, [
            batch("People", people, [(7, t + 100), (8, t + 9_000)]),
            batch("People", people, [(9, t + 12_000), (1, t + 21_000)])]),
        BatchSource("Sales", sales, [
            batch("Sales", sales, [(8, t + 50), (7, t + 9_500),
                                   (8, t + 9_999), (9, t + 9_999)]),
            batch("Sales", sales, [(9, t + 13_000), (0, t + 14_000),
                                   (8, t + 15_000)])]),
    ], batch_size=4, time_mode="processing", retain_results=True)
    job.run()
    assert [(int(ts) - t, *map(int, r))
            for ts, r in job.results_with_ts("o")] == [
        (9_999, 7, 1), (9_999, 8, 2), (19_999, 9, 1)]


HOP = "#window.hop(dateTime, 10 sec, 10 sec)"
P, A = f"nexmark[event_type == 0]{HOP} as P", \
    f"nexmark[event_type == 1]{HOP} as A"
TAIL = "select P.id as id, count() as auctions group by P.id insert into o"


@pytest.mark.parametrize("cql, word", [
    (f"from nexmark[event_type == 0]#window.hop(dateTime, 10 sec, 2 sec) "
     f"as P join {A} on P.id == A.seller {TAIL}", "size has to equal slide"),
    (f"from {P} join nexmark[event_type == 1]#window.length(5) as A "
     f"on P.id == A.seller {TAIL}", "#window.length: both sides need"),
    (f"from {P} join nexmark[event_type == 1] as A on P.id == A.seller "
     f"{TAIL}", "no window: both sides need"),
    (f"from {P} join nexmark[event_type == 1]#window.hop(dateTime, 20 sec, "
     f"20 sec) as A on P.id == A.seller {TAIL}", "windows differ"),
    (f"from nexmark[event_type == 0]#window.hop(dateTime, 7 sec, 7 sec) as "
     "P join nexmark[event_type == 1]#window.hop(dateTime, 7 sec, 7 sec) "
     f"as A on P.id == A.seller {TAIL}", "divide a day"),
    (f"from nexmark[event_type == 0]#window.hop(event_type, 10 sec, 10 sec)"
     f" as P join {A} on P.id == A.seller {TAIL}", "needs a long"),
    (f"from {P} join {A} on P.id > A.seller {TAIL}", "one equality"),
    (f"from {P} join {A} on P.id == A.seller and A.id > 5 {TAIL}",
     "one equality"),
    (f"from {P} join {A} on P.id == P.seller {TAIL}", "one equality"),
    (f"from {P} join {A} {TAIL}", "one equality"),
    (f"from {P} join {A} on P.id == A.seller select P.id as id, "
     "sum(A.seller) as s group by P.id insert into o",
     r"sum\(\) is not supported"),
    (f"from {P} join {A} on P.id == A.seller select P.id as id, A.id as a, "
     "count() as n group by P.id insert into o", "neither the key nor"),
    (f"from {P} join {A} on P.id == A.seller select P.id as id, "
     "count() as n insert into o", "'group by' has to name the key"),
    (f"from {P} join {A} on P.id == A.seller select P.id as id, "
     "count() as n group by P.id, A.id insert into o",
     "'group by' has to name the key"),
    (f"from {P} join {A} on P.id == A.seller select P.id as id, count() "
     "as n group by P.id having n > 1 insert into o", "'having'"),
    (f"from {P} left outer join {A} on P.id == A.seller {TAIL}",
     "inner joins only"),
    (f"from {P} join {A} on P.id == A.seller within 5 sec {TAIL}",
     "'within' is not supported"),
    (f"from nexmark{HOP} as P join nexmark[event_type == 1]{HOP} as A "
     f"on P.id == A.seller {TAIL}", "two different constants"),
    (f"from nexmark[event_type == 1]{HOP} as P join {A} "
     f"on P.id == A.seller {TAIL}", "two different constants"),
])
def test_what_the_window_join_refuses(cql, word):
    with pytest.raises(SiddhiQLError, match=word):
        compile_plan(cql, {"nexmark": _schema()})


def test_the_plan_picks_the_path_from_the_querys_shape():
    """``#window.hop`` sides take the keyed path; ``length`` and ``time``
    sides stay on the pair matrices, aggregated or not."""
    schemas = {"nexmark": _schema()}
    plan = compile_plan(Q8, schemas)
    assert [type(a) for a in plan.artifacts] == [WindowJoinArtifact]
    assert [f.name for f in plan.artifacts[0].output_schema.fields] == [
        "id", "auctions"]
    old = ("from nexmark[event_type == 0]#window.length(4) as P join "
           "nexmark[event_type == 1]#window.time(10 sec) as A "
           "on P.id == A.seller ")
    pairs = compile_plan(old + "select P.id as id, A.id as auction "
                         "insert into o", schemas)
    assert [type(a) for a in pairs.artifacts] == [JoinArtifact]
    # an aggregated pair join is still rewritten into join + aggregate
    chained = compile_plan(old + "select P.id as id, count() as n "
                           "group by P.id insert into o", schemas)
    assert isinstance(chained.artifacts[0], JoinArtifact)
    assert len(chained.artifacts) == 2
    rows, _ = _joined(_events([
        (0, 7, 0, 100), (1, 50, 7, 200), (1, 51, 7, 300), (1, 52, 8, 400),
    ]), cql=old + "select P.id as id, A.id as auction insert into o",
        out="o")
    assert rows == [(200, 7, 50), (300, 7, 51)]
