"""``#window.session`` on the normal path, at small sizes on the CPU: the
vectorised artifact (``compiler/session_window.py``) against the
per-event interpreter (``baseline/interp.py`` ``_SessionWindow``) row
for row on seeded random streams, sessions that close on the stream's
clock though their key never returns, slots that expire and are reused,
a session's start exact at epoch ms, counts exact past 2^24, and what
the path refuses. ``docs/session_window.md`` states the rule."""

import numpy as np
import pytest

from flink_siddhi_tpu.baseline import BaselineEngine
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.compiler.session_window import (
    SessionWindowArtifact,
    _pick,
    _tile_prefix,
    expiry_ticks,
)
from flink_siddhi_tpu.query.lexer import SiddhiQLError
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.runtime.tape import Tape
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

EPOCH = 1436918400000  # NEXmark's base time: far past 2^31 ms
SCHEMA = StreamSchema([
    ("kind", AttributeType.INT), ("user", AttributeType.LONG),
    ("amount", AttributeType.INT), ("price", AttributeType.DOUBLE),
    ("at", AttributeType.LONG),
])
FIELDS = SCHEMA.field_names
KEYED = (
    "from S#window.session(at, 50 ms, user) "
    "select user, count() as n, min(at) as t0, max(at) as t1, "
    "sum(amount) as total, max(amount) as top, min(price) as low, "
    "avg(amount) as mean group by user insert into o"
)
FILTERED = (
    "from S[kind == 2]#window.session(at, 50 ms, user) "
    "select user, count() as n, min(at) as t0 group by user insert into o"
)
OWN_TS = (
    "from S#window.session(50 ms, user) "
    "select user, count() as n, sum(price) as s insert into o"
)
GLOBAL = "from S#window.session(50 ms) select count() as n insert into o"
PARTITIONED = (
    "partition with (user of S) begin from S#window.session(50 ms) "
    "select user, count() as n, max(amount) as top insert into o end"
)


def _stream(seed, n, keys=40, lull=None):
    """``n`` events in time order: keys that return inside the gap and
    after it, several events a millisecond, and (``lull``) silences in
    the stream longer than the gap."""
    rng = np.random.default_rng(seed)
    step = rng.choice([0, 0, 1, 2, 7, 30], n)
    if lull is not None:
        step[rng.integers(1, n, lull)] = 400
    at = EPOCH + 5 + np.cumsum(step)
    return {
        "kind": rng.integers(1, 3, n).astype(np.int32),
        "user": (rng.integers(0, keys, n) * 7 + 2 ** 20).astype(np.int64),
        "amount": rng.integers(-50, 1000, n).astype(np.int32),
        "price": np.round(rng.random(n) * 100, 2),
        "at": at.astype(np.int64),
    }


def _batches(cols, batch):
    n = len(cols["at"])
    ts = cols.get("@ts", cols["at"])
    for s in range(0, n, batch):
        yield EventBatch(
            "S", SCHEMA,
            {k: v[s:s + batch] for k, v in cols.items() if k != "@ts"},
            ts[s:s + batch])


def _job(cql, cols, batch, path="per_batch", slots=64, columnar=False,
         flush=True):
    plan = compile_plan(cql, {"S": SCHEMA},
                        config=EngineConfig(hop_group_slots=slots))
    job = Job([plan], [BatchSource("S", SCHEMA, _batches(cols, batch))],
              batch_size=batch, time_mode="processing",
              retain_results=not columnar)
    got = []
    if columnar:
        class Sink:
            def accept_columns(self, ts, c):
                got.extend(zip(ts.tolist(), zip(*(v.tolist()
                                                  for v in c.values()))))
        job.add_sink("o", Sink())
    if path == "fused":
        job.fused_segment_len = 3
    if path == "resident":
        ResidentReplay(job).execute()
    elif flush:
        job.run()
    else:
        while not job.finished:
            job.run_cycle()
        job.drain_outputs(wait=True)
    return job, (got if columnar else job.results_with_ts("o"))


def _interpreter(cql, cols, flush=True):
    eng = BaselineEngine(cql, FIELDS)
    rows = []
    eng._emit = lambda _o, t, row: rows.append((t, tuple(row)))
    eng.run_columns(
        {k: v.tolist() for k, v in cols.items() if k != "@ts"},
        cols.get("@ts", cols["at"]).tolist())
    if flush:
        eng.flush()
    return rows


def _same(got, want):
    assert len(got) == len(want) > 0
    for (gt, grow), (wt, wrow) in zip(got, want):
        assert gt == wt
        assert len(grow) == len(wrow)
        for g, w in zip(grow, wrow):
            if isinstance(w, float):
                assert g == pytest.approx(w, rel=1e-5, abs=1e-4)
            else:
                assert g == w and isinstance(g, int)


CASES = [
    pytest.param(cql, batch, path, seed, id=f"{name}-{batch}-{path}-{seed}")
    for name, cql in (("keyed", KEYED), ("filtered", FILTERED),
                      ("own_ts", OWN_TS), ("global", GLOBAL),
                      ("partitioned", PARTITIONED))
    for batch, path, seed in (
        (7, "per_batch", 1), (256, "per_batch", 2), (4096, "per_batch", 3),
        (256, "fused", 4), (256, "resident", 5), (1, "per_batch", 6),
    )
]


@pytest.mark.parametrize("cql, batch, path, seed", CASES)
def test_artifact_equals_interpreter_row_for_row(cql, batch, path, seed):
    """Batches of 1, 7, 256 and 4,096 over a stream whose keys return
    inside and after the gap: a batch of 256 spans several gaps, so a
    key has several sessions in one batch; the stream holds silences
    longer than the gap."""
    n = 40 if batch == 1 else 3000
    cols = _stream(seed, n, lull=None if batch == 1 else 6)
    job, got = _job(cql, cols, batch, path)
    _same(got, _interpreter(cql, cols))
    assert isinstance(job._plans["plan"].plan.artifacts[0],
                      SessionWindowArtifact)


@pytest.mark.parametrize("path", ["per_batch", "fused"])
def test_the_columnar_lane_delivers_the_same_rows(path):
    cols = _stream(21, 2000, lull=4)
    _job_, got = _job(KEYED, cols, 128, path, columnar=True)
    _same(got, _interpreter(KEYED, cols))


@pytest.mark.parametrize("cql", [KEYED, OWN_TS])
def test_a_session_closes_on_the_clock_though_its_key_never_returns(cql):
    """Every key comes once or twice and never again; every session but
    those of the stream's last gap is delivered before any flush, in
    (stamp, key) order, each stamped with its last millisecond."""
    n = 2000
    cols = _stream(7, n)
    cols["user"] = (np.arange(n) // 2 + 2 ** 30).astype(np.int64)
    cols["at"] = (EPOCH + np.arange(n) * 3).astype(np.int64)
    _job_, got = _job(cql, cols, 100, flush=False)
    want = _interpreter(cql, cols, flush=False)
    _same(got, want)
    # two events a key 3 ms apart: a session every 6 ms, closed 50 ms on
    assert len(got) == n // 2 - 9
    stamps = [t for t, _ in got]
    assert stamps == sorted(stamps)
    if cql is KEYED:
        for t, row in got:
            assert row[1] == 2 and row[3] - row[2] == 3
            assert t == row[3] + 50 - 1  # its last millisecond


def test_the_boundary_is_the_gap_itself():
    """An event ``gap`` after its key's last opens a session (the clock
    has reached the old one's end); one millisecond earlier joins."""
    at = EPOCH + np.asarray([0, 49, 99, 148, 300], np.int64)
    cols = {
        "kind": np.full(5, 2, np.int32), "user": np.full(5, 9, np.int64),
        "amount": np.arange(5, dtype=np.int32),
        "price": np.zeros(5), "at": at,
    }
    _job_, got = _job(FILTERED, cols, 2)
    assert got == [
        (EPOCH + 49 + 49, (9, 2, EPOCH)),
        (EPOCH + 148 + 49, (9, 2, EPOCH + 99)),
        (EPOCH + 300 + 49, (9, 1, EPOCH + 300)),
    ]
    _same(got, _interpreter(FILTERED, cols))


def test_an_older_event_counts_at_the_clock():
    cols = _stream(31, 600)
    cols["@ts"] = cols["at"].copy()  # the events' own stamps: in order
    late = np.random.default_rng(31).integers(1, 600, 60)
    cols["at"][late] -= 4  # the attribute: behind, in and across batches
    assert (np.diff(cols["at"]) < 0).sum() > 20
    for batch in (16, 600):
        _job_, got = _job(KEYED, cols, batch)
        _same(got, _interpreter(KEYED, cols))


def test_slots_expire_and_a_table_of_64_serves_10000_keys():
    """Keys come and go; a slot is handed on only after the device has
    closed and emitted its session, and the row of a reused slot
    carries the new key."""
    n = 20_000
    cols = _stream(8, n)
    cols["user"] = (np.arange(n) // 2 * 3 + 2 ** 29).astype(np.int64)
    cols["at"] = (EPOCH + np.arange(n) * 5).astype(np.int64)
    job, got = _job(KEYED, cols, 50, slots=64)
    _same(got, _interpreter(KEYED, cols))
    assert len({row[0] for _, row in got}) == 10_000
    art = job._plans["plan"].plan.artifacts[0]
    assert art.encoder.stats["slots_reused"] > 9_000
    assert len(art.encoder) <= 64
    assert job._plans["plan"].plan.grow_count == 0
    counters = job.telemetry.snapshot()["counters"]
    assert counters["groups.slots_reused"] > 9_000
    assert counters.get("groups.regrow", 0) == 0
    assert counters["session.closed"] == counters["session.opened"] == 10_000
    assert counters["session.events"] == n
    # all but the stream's last sessions were closed by the clock
    assert 9_980 <= counters["session.closed_by_clock"] < 10_000


def test_a_checkpoint_taken_mid_stream_restores_to_the_same_rows():
    """Open sessions, the clock, the slots' keys and the encoder's freed
    slots travel in the snapshot."""
    n = 4_000
    cols = _stream(10, n, keys=400, lull=5)
    half = {k: v[:n // 2] for k, v in cols.items()}
    rest = {k: v[n // 2:] for k, v in cols.items()}
    _whole, want = _job(KEYED, cols, 100)
    first, got = _job(KEYED, half, 100, flush=False)
    assert first._plans["plan"].plan.artifacts[0].encoder.stats[
        "slots_reused"] > 0
    snap = first.snapshot()
    plan = compile_plan(KEYED, {"S": SCHEMA},
                        config=EngineConfig(hop_group_slots=64))
    second = Job([plan], [BatchSource("S", SCHEMA, _batches(rest, 100))],
                 batch_size=100, time_mode="processing")
    second.restore(snap)
    second.run()
    assert len(got) > 100
    _same(got + second.results_with_ts("o"), want)


def test_a_table_that_overflows_re_buckets_and_counts_it():
    cols = _stream(9, 3000, keys=300)
    job, got = _job(OWN_TS, cols, 500, slots=64)
    _same(got, _interpreter(OWN_TS, cols))
    assert job._plans["plan"].plan.grow_count >= 1
    assert job.telemetry.snapshot()["counters"]["groups.regrow"] >= 1


def test_expiry_leaves_the_device_time_to_close():
    for gap in (1, 5, 10, 50, 10_000, 86_400_000):
        tick, retain = expiry_ticks(gap)
        assert tick >= 1 and retain * tick >= gap + 2 * tick


def test_a_count_is_exact_past_2_to_the_24():
    """float32 holds integers to 2^24; the count is an int32 all the
    way, from the table to the row."""
    plan = compile_plan(FILTERED, {"S": SCHEMA},
                        config=EngineConfig(hop_group_slots=64))
    art = plan.artifacts[0]
    st = art.init_state()
    big = 2 ** 24 + 1
    st["open"] = st["open"].at[3].set(True)
    st["cnt"] = st["cnt"].at[3].set(big)
    st["first"] = st["first"].at[3].set(10)
    st["last"] = st["last"].at[3].set(20)
    st["key"] = st["key"].at[3].set(77)
    st["clock"], st["started"] = np.int32(20), np.bool_(True)
    n = 4
    tape = Tape(
        ts=np.asarray([30, 40, 60, 200], np.int32),
        stream=np.zeros(n, np.int32), valid=np.ones(n, bool),
        cols={"S.kind": np.full(n, 2, np.int32),
              "S.user": np.full(n, 77, np.int32),
              "@time:S.at": np.asarray([30, 40, 60, 200], np.int32),
              "@group:" + art.name: np.full(n, 3, np.int32)},
    )
    st, (n_out, block) = art.step(st, tape)
    assert int(n_out) == 1
    [(schema, rows)] = art.decode_packed(1, np.asarray(block))
    assert rows == [(60 + 50 - 1, (77, big + 3, 10))]
    assert rows.counters["session.events"] == big + 3
    assert rows.counters["session.closed_by_clock"] == 0  # its key did
    assert int(st["cnt"][3]) == 1 and int(st["first"][3]) == 200


@pytest.mark.parametrize("slots, lanes", [(1, 1), (64, 16), (1024, 100)])
def test_the_close_picks_every_set_slot_in_slot_order(slots, lanes):
    rng = np.random.default_rng(slots)
    for density in (0.0, 0.02, 0.5, 1.0):
        mask = rng.random(slots) < density
        within, count, start = _tile_prefix(mask)
        want = np.flatnonzero(mask)
        assert int(count.sum()) == len(want)
        got = []
        for base in range(0, len(want), lanes):
            idx = np.asarray(_pick(within, count, start, base, lanes))
            got.extend(idx[: len(want) - base].tolist())
        assert got == want.tolist()


def test_what_the_path_refuses():
    for cql, why in (
        ("from S#window.session(price, 50 ms, user) select user, "
         "count() as n insert into o", "long"),
        ("from S#window.session(at, 50 ms, user) select user, "
         "sum(at) as s insert into o", "time attribute"),
        ("from S#window.session(at, 50 ms, user) select user, "
         "count() as n having n > 1 insert into o", "having"),
        ("from S#window.session(at, 50 ms, user, kind) select user, "
         "count() as n insert into o", "needs"),
        ("from S#window.session(at, 0 ms, user) select user, "
         "count() as n insert into o", "positive"),
        ("from S#window.session(50 ms, user) select kind, "
         "count() as n insert into o", "session key"),
    ):
        with pytest.raises(SiddhiQLError, match=why):
            compile_plan(cql, {"S": SCHEMA})


def test_cost_info_states_residency_and_growth():
    plan = compile_plan(KEYED, {"S": SCHEMA})
    info = plan.artifacts[0].cost_info()
    assert info["kind"] == "session_window"
    assert info["residency_ms"] == 50 and info["grows_with"] == "keys"
    assert "grows_with" not in compile_plan(
        GLOBAL, {"S": SCHEMA}).artifacts[0].cost_info()
