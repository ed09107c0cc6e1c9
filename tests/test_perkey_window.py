"""``partition with (k of S) ... #window.length(C)`` with ``min`` /
``max``, ``having`` and ``@purge`` (docs/partition_window.md), at small
sizes on the CPU: the system against the per-event interpreter's
``_PerKeyLengthWindow`` on seeded streams, row for row, down every
execution path and both sink lanes; the purge band's two ends; a table
that stays at ``hop_group_slots`` while more keys pass than it has
slots; what the parser and the plan refuse. Nothing here is a rate."""

import numpy as np
import pytest

from flink_siddhi_tpu.baseline import BaselineEngine
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.compiler.window import (
    PERKEY_RING_MAX,
    PerKeyWindowArtifact,
    purge_ticks,
)
from flink_siddhi_tpu.query.lexer import SiddhiQLError
from flink_siddhi_tpu.query.parser import parse_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.encoders import GroupEncoder
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

SCHEMA = StreamSchema([
    ("t", AttributeType.INT), ("k", AttributeType.LONG),
    ("v", AttributeType.INT), ("w", AttributeType.INT),
    ("x", AttributeType.DOUBLE),
])
FIELDS = ["t", "k", "v", "w", "x"]
PURGE = "@purge(enable='true', interval='30 sec', idle.period='90 sec')\n"


def _query(length=4, filt="", having="having n == {c} and lo == hi",
           purge=""):
    return (
        f"{purge}partition with (k of S) begin "
        f"from S{filt}#window.length({length}) "
        "select k, v, count() as n, min(v) as lo, max(v) as hi, "
        "min(w) as wlo, max(w) as whi "
        f"{having.format(c=length)} insert into o; end"
    )


def _stream(seed, n, n_keys, step_ms=100, values=3):
    """Keys uniform over ``n_keys``, ``v`` over a few values (so runs of
    one value happen), one event every ``step_ms``."""
    rng = np.random.default_rng(seed)
    cols = {
        "t": (rng.random(n) < 0.3).astype(np.int32),
        "k": rng.integers(0, n_keys, n).astype(np.int64) * 1_000_003,
        "v": rng.integers(0, values, n).astype(np.int32),
        "w": rng.integers(-5, 5, n).astype(np.int32),
        "x": np.round(rng.random(n) * 10, 2),
    }
    return cols, 1_000 + np.arange(n, dtype=np.int64) * step_ms


def _churn(seed, n_keys=40, span_s=2_400):
    """Keys that report every few seconds for a while, fall silent for
    under a minute or over 200 s (never inside the purge band), and
    come back; one-report keys pass through all the time, more of them
    over the run than the table has slots."""
    rng = np.random.default_rng(seed)
    times, keys = [], []
    for key in range(n_keys):
        t = float(rng.integers(0, 60))
        while t < span_s:
            for _ in range(int(rng.integers(1, 9))):
                times.append(t)
                keys.append(key)
                t += float(rng.integers(1, 12))
            t += float(rng.integers(1, 55) if rng.random() < 0.5
                       else rng.integers(200, 400))
    for j in range(span_s):  # passers-by: a new key every second
        times.append(j + 0.5)
        keys.append(10_000 + j)
    order = np.argsort(np.asarray(times), kind="stable")
    ts = (np.asarray(times)[order] * 1_000).astype(np.int64) + 5_000
    n = len(ts)
    cols = {
        "t": np.zeros(n, np.int32),
        "k": np.asarray(keys, np.int64)[order],
        "v": rng.integers(0, 2, n).astype(np.int32),
        "w": rng.integers(0, 2, n).astype(np.int32),
        "x": np.round(rng.random(n), 2),
    }
    return cols, ts


def _interpreted(cql, cols, ts):
    eng, out = BaselineEngine(cql, FIELDS), []
    eng._emit = lambda _o, t, row: out.append((t, *row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()}, ts.tolist())
    return out


class _Columns:
    """The columnar lane: typed columns, one delivery at a time."""

    def __init__(self, names):
        self.names, self.rows = names, []

    def accept_columns(self, ts, cols):
        assert all(v.dtype != object for v in cols.values())
        self.rows.extend(zip(
            map(int, ts), *(cols[n].tolist() for n in self.names)))


def _run(cql, cols, ts, batch, path="per_batch", lane="rows", cast=int,
         **config):
    batches = (
        EventBatch("S", SCHEMA, {k: v[s:s + batch] for k, v in cols.items()},
                   ts[s:s + batch])
        for s in range(0, len(ts), batch)
    )
    plan = compile_plan(cql, {"S": SCHEMA}, config=EngineConfig(**config))
    job = Job([plan], [BatchSource("S", SCHEMA, batches)], batch_size=batch,
              time_mode="processing", retain_results=lane == "rows")
    sink = _Columns([f.name for f in plan.artifacts[0].output_schema.fields])
    if lane == "columns":
        job.add_sink("o", sink)
    if path == "fused":
        job.fused_segment_len = 4
    if path == "resident":
        ResidentReplay(job).execute()
    else:
        job.run()
    rows = (
        [(int(t), *map(cast, r)) for t, r in job.results_with_ts("o")]
        if lane == "rows" else sink.rows
    )
    return rows, job


SCENARIOS = {
    # twelve keys in batches of 256: a key comes twenty times a batch,
    # more often than its window is long
    "a_key_many_times_a_batch": dict(
        cql=_query(4), stream=_stream(3, 3_000, 12), batch=256),
    # 400 keys in batches of 32: a key's four rows lie in as many batches
    "a_window_over_many_batches": dict(
        cql=_query(4, having="having n >= 3 and lo == hi"),
        stream=_stream(4, 4_000, 400, values=2), batch=32),
    "a_filter_between_a_keys_events": dict(
        cql=_query(3, filt="[t == 0]"), stream=_stream(5, 3_000, 40),
        batch=128),
    "a_window_of_one_and_no_having": dict(
        cql=_query(1, having=""), stream=_stream(6, 600, 9), batch=64),
    "purged_keys_return_and_slots_are_reused": dict(
        cql=_query(4, having="having n >= 1", purge=PURGE),
        stream=_churn(7), batch=16, hop_group_slots=512),
}
PATHS = [("per_batch", "rows"), ("per_batch", "columns"),
         ("fused", "rows"), ("fused", "columns"),
         ("resident", "rows"), ("resident", "columns")]


@pytest.mark.parametrize("path, lane", PATHS,
                         ids=[f"{p}-{lane}" for p, lane in PATHS])
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_system_equals_the_interpreter_row_for_row(name, path, lane):
    sc = dict(SCENARIOS[name])
    cql, (cols, ts), batch = sc.pop("cql"), sc.pop("stream"), sc.pop("batch")
    got, job = _run(cql, cols, ts, batch, path, lane, **sc)
    want = _interpreted(cql, cols, ts)
    assert len(want) > 50
    assert got == want
    art = job._plans[next(iter(job._plans))].plan.artifacts[0]
    assert isinstance(art, PerKeyWindowArtifact)
    if name.startswith("purged"):
        counters = job.telemetry.snapshot()["counters"]
        # 2,440 keys through 512 slots: freed, reused, never re-bucketed
        assert counters["groups.interned"] > 2_400
        assert counters["groups.slots_reused"] > 1_500
        assert counters["groups.expired"] > 1_500
        assert "groups.regrow" not in counters
        assert len(art.encoder) <= 512
        # keys came back after a purge: their windows began anew
        by_key = {}
        restarts = 0
        for _t, k, _v, n, *_ in want:
            restarts += k < 10_000 and n == 1 and by_key.get(k, 0) > 1
            by_key[k] = n
        assert restarts > 50


def test_the_purge_bands_two_ends():
    """A key that returns 85 s after its last event is remembered (its
    window goes on); one that returns 130 s and a batch after is
    forgotten: its window starts anew in a slot another key has used
    since, and nothing the slot held is read."""
    rng = np.random.default_rng(9)
    t_key = [0, 5, 10, 15, 100, 105, 245, 250]  # seconds: gaps 85, 140
    times = sorted([(t, 77, 5) for t in t_key] + [
        (j + 0.5, 1_000 + j, int(rng.integers(0, 100))) for j in range(300)])
    ts = np.asarray([int(t * 1_000) + 1_000 for t, _k, _v in times])
    n = len(ts)
    cols = {"t": np.zeros(n, np.int32),
            "k": np.asarray([k for _t, k, _v in times], np.int64),
            "v": np.asarray([v for _t, _k, v in times], np.int32),
            "w": np.zeros(n, np.int32), "x": np.zeros(n)}
    cql = _query(4, having="having n >= 1", purge=PURGE)
    got, job = _run(cql, cols, ts, 10, hop_group_slots=64)
    assert got == _interpreted(cql, cols, ts)
    mine = [(n_, lo, hi) for _t, k, _v, n_, lo, hi, *_ in got if k == 77]
    assert [n_ for n_, _lo, _hi in mine] == [1, 2, 3, 4, 4, 4, 1, 2]
    assert all(lo == hi == 5 for _n, lo, hi in mine)
    counters = job.telemetry.snapshot()["counters"]
    assert counters["groups.slots_reused"] > 100
    gauges = job.telemetry.snapshot()["gauges"]
    assert 0 < gauges["groups.live"] <= 256
    assert counters["perkey.rows"] == len(got)


@pytest.mark.parametrize("purge", ["", PURGE], ids=["kept", "purged"])
def test_sums_beside_min_and_max(purge):
    """The float32 prefix path for sums is the one there was: beside the
    ring of raw values, and with slots that start anew."""
    cql = (
        f"{purge}partition with (k of S) begin from S#window.length(3) "
        "select k, sum(x) as s, avg(x) as a, stddev(x) as d, count() as n, "
        "max(v) as hi, min(x) as xlo insert into o; end"
    )
    cols, ts = _churn(11, n_keys=20, span_s=900) if purge else _stream(
        11, 2_000, 25)
    plan_rows, _job = _run(cql, cols, ts, 32, cast=lambda x: x,
                           hop_group_slots=1_024)
    want = _interpreted(cql, cols, ts)
    assert len(plan_rows) == len(want) > 500
    for g, e in zip(plan_rows, want):
        assert (g[0], g[1], g[5], g[6]) == (e[0], e[1], e[5], e[6])
        assert g[2:5] == pytest.approx(e[2:5], rel=2e-4, abs=2e-3)
        assert g[7] == pytest.approx(e[7], rel=1e-6)


def test_the_table_grows_where_the_keys_outgrow_it():
    """Without ``@purge`` the table is append-only: it starts at
    ``hop_group_slots`` and re-buckets as keys come (the rings of raw
    values keep their rows)."""
    cql = _query(3, having="having n >= 2")
    cols, ts = _stream(13, 3_000, 300)
    got, job = _run(cql, cols, ts, 64, hop_group_slots=64)
    assert got == _interpreted(cql, cols, ts)
    assert job.telemetry.snapshot()["counters"]["groups.regrow"] >= 2


def test_a_checkpoint_with_purged_slots_restores_the_same_rows():
    """The table travels with its holes, the stamps and the free list:
    a restored job purges and reuses the slots the first would have, and
    counts a key that returns after the purge from one again."""
    cql = _query(4, having="having n >= 1", purge=PURGE)
    cols, ts = _churn(17, n_keys=30, span_s=1_200)
    whole, _job = _run(cql, cols, ts, 16, hop_group_slots=512)
    cut = len(ts) // 32 * 16

    def job_over(lo, hi):
        batches = (
            EventBatch("S", SCHEMA,
                       {k: v[s:s + 16] for k, v in cols.items()},
                       ts[s:s + 16])
            for s in range(lo, hi, 16))
        plan = compile_plan(cql, {"S": SCHEMA}, plan_id="stops",
                            config=EngineConfig(hop_group_slots=512))
        return Job([plan], [BatchSource("S", SCHEMA, batches)],
                   batch_size=16, time_mode="processing"), plan

    def rows(job):
        return [(int(t), *(int(x) for x in r))
                for t, r in job.results_with_ts("o")]

    first, plan = job_over(0, cut)
    first.run()
    assert plan.spec.encoded[0].encoder.stats["slots_reused"] > 100
    snap = first.snapshot()
    second, plan2 = job_over(cut, len(ts))
    second.restore(snap)
    second.run()
    assert rows(first) + rows(second) == whole
    assert whole == _interpreted(cql, cols, ts)
    assert len(plan2.spec.encoded[0].encoder) <= 512
    leaves = snap["plans"]["stops"]["states"][plan.artifacts[0].name]
    assert set(leaves) == {"enabled", "rec"}
    assert np.asarray(leaves["rec"]).shape == (512 // 8, 128)


def test_a_snapshot_of_the_leaves_before_the_record_is_refused():
    """``cnt`` and ``vals<j>`` (the state until PR 45) do not restore
    into the record table: ``restore`` says so, it does not guess."""
    cql = _query(4, having="having n >= 1", purge=PURGE)
    cols, ts = _churn(19, n_keys=5, span_s=60)

    def job():
        plan = compile_plan(cql, {"S": SCHEMA}, plan_id="stops",
                            config=EngineConfig(hop_group_slots=512))
        batches = iter([EventBatch("S", SCHEMA, cols, ts)])
        return Job([plan], [BatchSource("S", SCHEMA, batches)],
                   batch_size=len(ts), time_mode="processing"), plan

    first, plan = job()
    first.run()
    snap = first.snapshot()
    name = plan.artifacts[0].name
    snap["plans"]["stops"]["states"][name] = {
        "enabled": np.asarray(True), "cnt": np.zeros(512, np.int32),
        "vals0": np.zeros(4 * 512, np.int32),
        "vals1": np.zeros(4 * 512, np.int32)}
    with pytest.raises(ValueError, match="does not match the running plan"):
        job()[0].restore(snap)


# -- the record a slot ---------------------------------------------------------
def _artifact_and_state(job):
    rt, = job._plans.values()
    art, = rt.plan.artifacts
    return art, rt.states[art.name]


def _one_slot_cql(length, args=("v", "w")):
    aggs = ", ".join(f"min({a}) as {a}lo, max({a}) as {a}hi" for a in args)
    return (f"partition with (k of S) begin from S#window.length({length}) "
            f"select k, count() as n, {aggs} insert into o; end")


def _bursts(seed, n=1_500, batch=64):
    """Key 1 comes in bursts longer than any window here within one
    batch, key 2 exactly once a batch, the rest fill in."""
    rng = np.random.default_rng(seed)
    k = rng.integers(3, 12, n)
    at = np.arange(n) % batch
    k[(at >= 5) & (at < 5 + 11)] = 1
    k[at == 40] = 2
    quarter = rng.integers(-8, 9, n) / 4.0  # exact in float32
    cols = {
        "t": np.zeros(n, np.int32), "k": k.astype(np.int64),
        "v": rng.integers(-50, 50, n).astype(np.int32),
        "w": rng.integers(-3, 3, n).astype(np.int32),
        "x": np.where(quarter == 0, -0.0, quarter),
    }
    return cols, 1_000 + np.arange(n, dtype=np.int64) * 100


# 1 + C * A words padded to a power of two: (C, arguments) -> (W, R)
WIDTHS = {
    (1, ("v",)): (2, 64), (3, ("v",)): (4, 32), (7, ("v",)): (8, 16),
    (2, ("v",)): (4, 32), (4, ("v",)): (8, 16), (5, ("v",)): (8, 16),
    (2, ("v", "w")): (8, 16), (4, ("v", "w")): (16, 8),
    (5, ("v", "w")): (16, 8), (5, ("v", "w", "x")): (16, 8),
    (64, ("v", "x")): (256, 1),
}


@pytest.mark.parametrize(
    "length, args", sorted(WIDTHS),
    ids=[f"C{c}-{'+'.join(a)}" for c, a in sorted(WIDTHS)])
def test_a_record_of_any_width_equals_the_interpreter(length, args):
    """A key with more events in one batch than its window holds, one
    with exactly one, widths that fill their padding and widths that do
    not, a record wider than a row."""
    cql = _one_slot_cql(length, args)
    cols, ts = _bursts(length * 10 + len(args))
    got, job = _run(cql, cols, ts, 64, cast=lambda x: x, hop_group_slots=100)
    assert got == _interpreted(cql, cols, ts)
    art, state = _artifact_and_state(job)
    W, R = WIDTHS[length, args]
    assert art._record() == (W, R) and W >= 1 + length * len(args)
    assert set(state) == {"enabled", "rec"}
    rows = -(-100 // R)  # whole rows for the 100 slots asked for
    assert state["rec"].shape == (rows, R * W)
    assert state["rec"].dtype == np.int32


@pytest.mark.parametrize("path", ["per_batch", "fused"])
def test_the_table_is_read_a_block_of_tape_rows_at_a_time(monkeypatch, path):
    """Eight blocks of 128 rows a tape, the last ones without an event
    (a batch is 600 events and the filter drops a third): those are not
    read at all."""
    from flink_siddhi_tpu.compiler import window

    monkeypatch.setattr(window, "_PERKEY_BLOCK", 128)
    cql = _query(4, filt="[t == 0]", having="having n >= 2")
    cols, ts = _stream(29, 6_000, 300)
    got, job = _run(cql, cols, ts, 600, path)
    assert got == _interpreted(cql, cols, ts)
    tape_rows = {rt.tape_capacity for rt in job._plans.values()}
    assert tape_rows == {1_024}


def test_a_float_rides_in_its_record_as_its_bits():
    """``min`` / ``max`` of a double beside an int: negative values, and
    a key that only ever sends -0.0 gets -0.0 back, sign and all."""
    cql = _one_slot_cql(3, ("x", "v"))
    cols, ts = _bursts(23)
    cols["x"][cols["k"] == 2] = -0.0
    got, job = _run(cql, cols, ts, 64, cast=lambda x: x)
    want = _interpreted(cql, cols, ts)
    assert got == want
    zeros = [r for r in got if r[1] == 2]
    assert len(zeros) > 20
    assert all(np.signbit(r[3]) and np.signbit(r[4]) for r in zeros)
    assert min(r[3] for r in got) < 0 < max(r[4] for r in got)
    art, state = _artifact_and_state(job)
    W, _R = art._record()
    slot = int(art.encoder.intern_rows(
        [np.asarray([2], np.int64)], np.ones(1, bool))[0])
    record = np.asarray(state["rec"]).reshape(-1)[slot * W:(slot + 1) * W]
    assert record[0] == len(zeros)
    neg_zero = np.asarray([-0.0], np.float32).view(np.int32)[0]
    assert record[1:4].tolist() == [neg_zero] * 3  # x's ring, bit for bit


def _art_and_tape(cql, keys, v, fresh, slots=64, rows=128):
    """The artifact of ``cql`` and one tape of ``rows`` rows (128: the
    smallest bucket) with ``keys``' events, slot codes as the host would
    intern them (``~slot`` where ``fresh``)."""
    import jax.numpy as jnp

    from flink_siddhi_tpu.runtime.tape import Tape

    plan = compile_plan(cql, {"S": SCHEMA},
                        config=EngineConfig(hop_group_slots=slots))
    art = plan.artifacts[0]
    n = len(keys)

    def column(values, dtype=np.int32):
        out = np.zeros(rows, dtype)
        out[:n] = values
        return jnp.asarray(out)

    code = np.asarray(keys, np.int32)
    cols = {f"S.{f}": column(0, SCHEMA.field_type(f).device_dtype)
            for f in FIELDS}
    cols["S.v"] = column(v)
    cols[art.code_key] = column(np.where(fresh, ~code, code))
    tape = Tape(ts=column(np.arange(n)), stream=column(0),
                valid=column(True, bool), cols=cols)
    return art, tape


@pytest.mark.parametrize("fresh", [True, False], ids=["given_anew", "known"])
def test_a_slot_given_anew_reads_nothing_its_record_held(fresh):
    """A record full of another key's words (count 7, every ring word
    -99): the key that is given the slot (``~slot``) starts at one and
    sees its own values alone; a key the slot already knew goes on from
    what is there."""
    import jax.numpy as jnp

    art, tape = _art_and_tape(
        _one_slot_cql(4, ("v",)), [5, 9, 5], [10, 3, 20], fresh)
    state = art.init_state()
    W, _R = art._record()
    words = np.zeros(state["rec"].size, np.int32)
    words[5 * W:6 * W] = [7] + [-99] * (W - 1)
    state["rec"] = jnp.asarray(words.reshape(state["rec"].shape))
    new_state, (mask, _ts, out) = art.step(state, tape)
    assert np.asarray(mask).tolist() == [True] * 3 + [False] * 125
    rows = list(zip(*(np.asarray(c)[:3].tolist() for c in out)))
    record = np.asarray(new_state["rec"]).reshape(-1)[5 * W:6 * W].tolist()
    if fresh:
        assert rows == [(0, 1, 10, 10), (0, 1, 3, 3), (0, 2, 10, 20)]
        assert record[:3] == [2, 10, 20]
    else:  # ordinals 7 and 8: ring positions 3 and 0
        assert rows == [(0, 4, -99, 10), (0, 1, 3, 3), (0, 4, -99, 20)]
        assert record[:5] == [9, 20, -99, -99, 10]


@pytest.mark.parametrize("rows", [3, 64, 200])
def test_a_tape_that_is_no_whole_tile_is_read_all_the_same(rows):
    """Admission traces a plan on a tape of 64 rows; the read works in
    tiles of 128."""
    art, tape = _art_and_tape(
        _one_slot_cql(2, ("v",)), [5, 9, 5], [10, 3, 20], True, rows=rows)
    _state, (mask, _ts, out) = art.step(art.init_state(), tape)
    assert int(np.asarray(mask).sum()) == 3
    got = list(zip(*(np.asarray(c)[:3].tolist() for c in out)))
    assert got == [(0, 1, 10, 10), (0, 1, 3, 3), (0, 2, 10, 20)]


@pytest.mark.parametrize("slots", [64, 100, 1_000])
def test_a_grown_table_keeps_every_record(slots):
    """Re-bucketing appends whole rows: every slot's record stays at
    ``slot * W`` of the flat view, the new slots read zero."""
    import jax.numpy as jnp

    art, _tape = _art_and_tape(_one_slot_cql(5), [0], [0], False, slots)
    W, R = art._record()
    state = art.init_state()
    assert art.grow_state(state) is state
    G = state["rec"].shape[0] * R
    assert slots <= G < slots + R
    marked = np.arange(G * W, dtype=np.int32).reshape(state["rec"].shape)
    state["rec"] = jnp.asarray(marked)
    art.encoder.intern_rows(
        [np.arange(2 * slots + 1, dtype=np.int64)],
        np.ones(2 * slots + 1, bool))
    grown = art.grow_state(state)
    flat = np.asarray(grown["rec"]).reshape(-1)
    assert grown["rec"].shape[1] == R * W
    assert grown["rec"].shape[0] * R >= 2 * slots + 1
    assert (flat[:G * W] == np.arange(G * W)).all()
    assert not flat[G * W:].any()
    assert art.grow_state(grown) is grown


# -- the encoder ---------------------------------------------------------------
@pytest.mark.parametrize("dense", [True, False], ids=["dense", "sparse"])
def test_mark_new_marks_every_row_of_a_key_the_call_interned(dense):
    enc = GroupEncoder(retain_ticks=2, mark_new=True)
    step = 1 if dense else 10_000
    keys = np.asarray([5, 7, 5, 9, 7, 5], np.int64) * step
    tick = np.zeros(6, np.int64)
    every = np.ones(6, bool)
    first = enc.intern_rows([keys], every, tick, 1)
    assert (first < 0).all() and len(set(first.tolist())) == 3
    again = enc.intern_rows([keys[:3]], every[:3], tick[:3] + 1, 1)
    assert again.tolist() == (~first[:3]).tolist()  # known: the slot itself
    # three ticks on, the slots are freed; the key that returns is new
    # again, in a slot that was used before
    enc.intern_rows([keys[:1] + 1], every[:1], tick[:1] + 4, 1)
    back = enc.intern_rows([keys[:1]], every[:1], tick[:1] + 5, 1)
    assert back[0] < 0 and enc.stats["slots_reused"] >= 1
    assert len(enc) == 4


def test_mark_new_on_the_dict_path():
    enc = GroupEncoder(mark_new=True)
    a = np.asarray([1, 2, 1], np.int64)
    b = np.asarray([1, 1, 1], np.int64)
    codes = enc.intern_rows([a, b], np.ones(3, bool))
    assert codes.tolist() == [~0, ~1, ~0]
    assert enc.intern_rows([a, b], np.ones(3, bool)).tolist() == [0, 1, 0]


def test_purge_ticks_keep_idle_period_and_free_after_the_interval():
    assert purge_ticks(30_000, 90_000) == (30_000, 4)
    assert purge_ticks(60_000, 90_000) == (60_000, 3)


# -- the parser and the plan ------------------------------------------------
def test_a_partitions_annotations_are_in_the_ast():
    q, = parse_plan(
        "@info(name='stops') " + _query(4, purge=PURGE)).queries
    assert q.name == "stops_0"
    a, = q.partition_annotations
    assert a.name == "purge" and dict(a.elements) == {
        "enable": "true", "interval": "30 sec", "idle.period": "90 sec"}
    assert q.partition_purge == (30_000, 90_000)
    off, = parse_plan(_query(4, purge=PURGE.replace("true", "false"))).queries
    assert off.partition_purge is None
    art = compile_plan(_query(4, purge=PURGE), {"S": SCHEMA}).artifacts[0]
    assert art.encoder.retain_ticks == 4 and art.encoder.mark_new
    kept = compile_plan(_query(4), {"S": SCHEMA}).artifacts[0]
    assert kept.encoder.retain_ticks is None and not kept.encoder.mark_new


REFUSED = {
    "an_unknown_annotation": (
        "@async(buffer.size='64') " + _query(4), "@async on a partition"),
    "purge_without_its_times": (
        "@purge(enable='true') " + _query(4), "interval and idle.period"),
    "purge_with_an_unknown_key": (
        "@purge(enable='true', interval='1 sec', idle='2 sec') " + _query(4),
        "takes enable, interval and idle.period"),
    "purge_on_a_partitioned_pattern": (
        PURGE + "partition with (k of S) begin from every a = S[v == 1] -> "
        "b = S[v == 2] select a.k as k insert into o; end",
        "does not expire"),
    "purge_on_a_partitioned_time_window": (
        PURGE + "partition with (k of S) begin from S#window.time(5 sec) "
        "select k, count() as n insert into o; end", "does not expire"),
    "purge_on_a_running_aggregate": (
        PURGE + "partition with (k of S) begin from S select k, "
        "count() as n insert into o; end", "does not expire"),
    "min_over_a_window_longer_than_the_ring": (
        _query(PERKEY_RING_MAX + 1), "at most 64"),
    "a_sequence_in_a_partition": (
        "partition with (k of S) begin from every a = S[v == 1], "
        "b = S[v == 2] select a.k as k insert into o; end",
        "sequences inside 'partition with'"),
    "distinctcount_per_key": (
        "partition with (k of S) begin from S#window.length(4) select k, "
        "distinctCount(v) as d insert into o; end",
        "distinctcount.. is not supported over a per-partition"),
}


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_what_is_refused_is_refused_aloud(name):
    cql, word = REFUSED[name]
    with pytest.raises(SiddhiQLError, match=word):
        compile_plan(cql, {"S": SCHEMA})


def test_annotations_elsewhere_parse_as_they_did():
    plan = parse_plan(
        "@source(type='kafka', @map(type='json')) "
        "define stream S (k long, v int); "
        "@info(name = 'q1') @dist(parallel='4') from S select k "
        "insert into o;")
    assert plan.queries[0].name == "q1"
    assert plan.queries[0].partition_annotations == ()
