"""Columnar sink fast lane: equivalence with the per-row path, the
emission_order side-channel contract, rate-limiter batch accounting,
and the tail-aware drain scheduler's staleness leg.

The per-row ``decode_buffered``/``decode_packed_block`` path is the
compatibility ORACLE (ISSUE 5): every columnar product must carry
identical values, order, and counts. The parametrized job-level test
covers all three device emission layouts (aligned select, buffered
pattern, packed lazy-chain ordinals) plus a rate-limited stream; its
sharded twin holds ShardedJob's columnar lane (ISSUE 25) to its row
lane across a 4-shard CPU mesh.
"""

import functools
import heapq

import numpy as np
import pytest

from flink_siddhi_tpu import (
    AttributeType,
    ColumnarSink,
    EventBatch,
    StreamSchema,
)
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.output import (
    ColumnBatch,
    OutputField,
    OutputSchema,
    emission_order,
)
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job, _OutputRateLimiter
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.strings import StringTable


# -- emission_order: the side-channel desync contract ----------------------


def test_emission_order_is_stable_by_timestamp():
    """THE permutation (compiler/output.py:120-125 contract): stable
    sort by timestamp — equal timestamps keep slot order, so artifacts
    reordering side-channel rows with the same helper stay aligned."""
    rng = np.random.default_rng(11)
    for trial in range(200):
        n = int(rng.integers(1, 64))
        # heavy duplication on purpose: stability only matters for ties
        ts = rng.integers(0, 8, size=n).astype(np.int64)
        order = emission_order(ts, n)
        # brute-force oracle: sort (ts, original index) pairs
        expect = sorted(range(n), key=lambda i: (ts[i], i))
        assert order.tolist() == expect, (trial, ts.tolist())


def test_emission_order_keeps_side_channel_rows_paired():
    """Fuzz the slot-NFA-mbits / join-missing-side pattern: a packed
    block whose extra row (past the schema columns) is reordered by the
    SAME emission_order call must stay paired with its data row."""
    schema = OutputSchema(
        "s",
        (
            OutputField("a", AttributeType.INT),
            OutputField("b", AttributeType.DOUBLE),
        ),
    )
    rng = np.random.default_rng(7)
    for trial in range(100):
        n = int(rng.integers(1, 48))
        ts = rng.integers(0, 6, size=n).astype(np.int32)
        a = np.arange(n, dtype=np.int32)  # unique: identifies the row
        b = rng.random(n).astype(np.float32)
        side = a * 3 + 1  # the side-channel marker, keyed to its row
        block = np.stack(
            [ts, a, b.view(np.int32), side.astype(np.int32)]
        )
        rows = schema.decode_packed_block(n, block[:3])
        markers = np.asarray(block[3, :n])[emission_order(block[0], n)]
        assert len(rows) == n
        for (row_ts, row), m in zip(rows, markers.tolist()):
            # the marker must still belong to ITS data row
            assert m == row[0] * 3 + 1, (trial, rows, markers)
        # and the columnar twin applies the identical permutation
        cb = schema.decode_packed_columns(n, block[:3])
        assert cb.ts.tolist() == [t for t, _ in rows]
        assert cb.cols["a"].tolist() == [r[0] for _, r in rows]


def test_side_channel_desync_without_the_helper():
    """Negative control: a permutation that breaks ties differently
    (sort by timestamp, LATEST slot first) is NOT emission_order — the
    desync bug class the contract pins."""
    ts = np.array([3, 1, 1, 0], dtype=np.int64)
    n = 4
    good = emission_order(ts, n)
    reversed_ties = np.array(
        sorted(range(n), key=lambda i: (ts[i], -i)), dtype=np.int64
    )
    assert not np.array_equal(good, reversed_ties)


# -- whole-column decode equivalence ---------------------------------------


def _schema_with_strings():
    table = StringTable()
    for v in ("alpha", "beta", "gamma"):
        table.intern(v)
    return (
        OutputSchema(
            "s",
            (
                OutputField("i", AttributeType.INT),
                OutputField("f", AttributeType.DOUBLE),
                OutputField("s", AttributeType.STRING, table=table),
                OutputField("b", AttributeType.BOOL),
            ),
        ),
        table,
    )


def test_decode_columns_matches_decode_buffered():
    schema, table = _schema_with_strings()
    rng = np.random.default_rng(3)
    for trial in range(50):
        n = int(rng.integers(0, 40))
        cap = n + int(rng.integers(0, 8))
        ts = rng.integers(0, 10, size=cap).astype(np.int32)
        cols = [
            rng.integers(-5, 5, size=cap).astype(np.int32),
            rng.random(cap).astype(np.float32),
            rng.integers(-1, len(table) + 1, size=cap).astype(np.int32),
            rng.integers(0, 2, size=cap).astype(np.int32),
        ]
        rows = schema.decode_buffered(n, ts, cols)
        cb = schema.decode_columns(n, ts, cols)
        assert len(cb) == len(rows) == n
        assert cb.rows() == rows  # values, order, AND types-on-tolist


def test_decode_aligned_columns_matches_decode_aligned():
    schema, table = _schema_with_strings()
    rng = np.random.default_rng(5)
    for _ in range(30):
        cap = int(rng.integers(1, 40))
        mask = rng.integers(0, 2, size=cap).astype(bool)
        ts = rng.integers(0, 9, size=cap).astype(np.int32)
        cols = [
            rng.integers(0, 9, size=cap).astype(np.int32),
            rng.random(cap).astype(np.float32),
            rng.integers(0, len(table), size=cap).astype(np.int32),
            rng.integers(0, 2, size=cap).astype(np.int32),
        ]
        rows = schema.decode_aligned(mask, ts, cols)
        cb = schema.decode_aligned_columns(mask, ts, cols)
        assert cb.rows() == rows


def test_decode_column_np_out_of_range_codes_decode_none():
    schema, table = _schema_with_strings()
    f = schema.fields[2]
    arr = np.array([0, 99, -1, 2], dtype=np.int32)
    assert f.decode_column_np(arr).tolist() == [
        "alpha", None, None, "gamma",
    ]
    assert f.decode_column_np(arr).tolist() == f.decode_column(arr)


# -- rate limiter: batch accounting parity ---------------------------------


def _cb_of(ts_vals):
    ts = np.asarray(ts_vals, dtype=np.int64)
    return ColumnBatch(ts, {"v": ts * 10})


class _Rate:
    def __init__(self, mode, which, n_events=1, ms=0.0):
        self.mode, self.which = mode, which
        self.n_events, self.ms = n_events, ms


@pytest.mark.parametrize("which", ["all", "first", "last"])
def test_feed_columns_matches_feed_events_mode(which):
    rng = np.random.default_rng(13)
    for chunk in (1, 3, 5):
        lim_r = _OutputRateLimiter(_Rate("events", which, chunk))
        lim_c = _OutputRateLimiter(_Rate("events", which, chunk))
        t = 0
        out_r, out_c = [], []
        for _ in range(20):
            m = int(rng.integers(0, 7))
            ts = list(range(t, t + m))
            t += m
            rows = [(x, (x * 10,)) for x in ts]
            out_r.extend(lim_r.feed(rows))
            for part in lim_c.feed_columns(_cb_of(ts)):
                out_c.extend(
                    (int(a), (int(v),))
                    for a, v in zip(
                        part.ts.tolist(), part.cols["v"].tolist()
                    )
                )
        # end-of-stream flush parity too
        out_r.extend(lim_r.flush())
        for part in lim_c.flush():
            out_c.extend(
                (int(a), (int(v),))
                for a, v in zip(
                    part.ts.tolist(), part.cols["v"].tolist()
                )
            )
        assert out_c == out_r, (which, chunk)


@pytest.mark.parametrize("which", ["all", "first", "last"])
def test_feed_columns_matches_feed_time_mode(which):
    """Deterministic time-mode check: a far deadline (nothing flushes
    mid-run), then flush() — row and columnar lanes release identical
    output."""
    lim_r = _OutputRateLimiter(_Rate("time", which, ms=60_000.0))
    lim_c = _OutputRateLimiter(_Rate("time", which, ms=60_000.0))
    out_r, out_c = [], []
    t = 0
    for m in (2, 0, 4, 1):
        ts = list(range(t, t + m))
        t += m
        out_r.extend(lim_r.feed([(x, (x * 10,)) for x in ts]))
        for part in lim_c.feed_columns(_cb_of(ts)):
            out_c.extend(part.rows())
    out_r.extend(lim_r.flush())
    for part in lim_c.flush():
        out_c.extend(
            (int(a), (int(v),))
            for a, v in zip(part.ts.tolist(), part.cols["v"].tolist())
        )
    out_r2 = [(int(a), (int(v),)) for a, (v,) in out_r]
    out_c2 = [(int(a), (int(v),)) for a, (v,) in out_c]
    assert out_c2 == out_r2


# -- job-level equivalence: ColumnarSink vs row sink on the same job -------


def _make_batches(schema, n=4000, chunk=1000, n_ids=5, seed=0):
    name_code = schema.string_tables["name"].intern("ev")
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n_ids, n).astype(np.int32)
    prices = rng.random(n)
    ts = np.arange(n, dtype=np.int64) + 1_000
    out = []
    for i in range(0, n, chunk):
        out.append(
            EventBatch(
                "s",
                schema,
                {
                    "id": ids[i:i + chunk],
                    "name": np.full(
                        len(ids[i:i + chunk]), name_code, np.int32
                    ),
                    "price": prices[i:i + chunk],
                },
                ts[i:i + chunk],
            )
        )
    return out


class _Recorder(ColumnarSink):
    """Records whatever lane delivers, normalized to (ts, row) pairs."""

    def __init__(self, names):
        self.names = names
        self.rows = []
        self.batches = 0

    def accept_columns(self, ts, cols):
        self.batches += 1
        lists = [cols[n].tolist() for n in self.names]
        for t, *vals in zip(ts.tolist(), *lists):
            self.rows.append((int(t), tuple(vals)))


CASES = {
    # aligned layout (stateless select), string decode included
    "aligned_select": (
        "from s[id == 2] select id, name, price insert into out",
        EngineConfig(),
    ),
    # buffered layout (pattern match buffer)
    "buffered_pattern": (
        "from every e1 = s[id == 1] -> e2 = s[id == 2] "
        "select e1.price as p1, e2.price as p2 insert into out",
        EngineConfig(),
    ),
    # packed lazy-ordinal layout: projection-only columns resolve
    # through the host ring (lookup_np on the columnar lane)
    "packed_lazy": (
        "from s[id == 2] select id, name, price insert into out",
        EngineConfig(lazy_projection=True, pred_pushdown=True),
    ),
    # rate-limited stream: the limiter accounts column batches
    "rate_limited": (
        "from s[id == 2] select id, price "
        "output all every 7 events insert into out",
        EngineConfig(),
    ),
}


def _schema():
    return StreamSchema(
        [
            ("id", AttributeType.INT),
            ("name", AttributeType.STRING),
            ("price", AttributeType.DOUBLE),
        ]
    )


@pytest.mark.parametrize("case", sorted(CASES))
def test_columnar_and_row_sinks_observe_identical_data(case):
    cql, cfg = CASES[case]

    def run(columnar_only):
        schema = _schema()
        plan = compile_plan(cql, {"s": schema}, config=cfg)
        job = Job(
            [plan],
            [BatchSource("s", schema, iter(_make_batches(schema)))],
            batch_size=1000,
            retain_results=False,
        )
        names = plan.output_streams()["out"][0].field_names
        col_sink = _Recorder(names)
        row_rows = []
        job.add_sink("out", col_sink)
        if not columnar_only:
            job.add_sink(
                "out", lambda ts, row: row_rows.append((ts, tuple(row)))
            )
        job.run()
        return col_sink, row_rows

    # fast lane: columnar-only consumers -> zero row tuples in engine
    col_fast, _ = run(columnar_only=True)
    # mixed consumers: the stream decodes row-wise; the columnar sink
    # gets the converted batches, the row sink the tuples
    col_mixed, row_rows = run(columnar_only=False)

    assert col_fast.rows, case  # the query actually emitted
    assert col_fast.rows == col_mixed.rows == row_rows, case


# -- ShardedJob: the same lane across shards --------------------------------

SHARDED_CASES = {
    # (a) keyed group-by: every group's state on one shard, one row out
    # per event, merged across shards by timestamp
    "keyed_group_by": dict(
        cql="from s select id, sum(price) as total, count() as cnt "
        "group by id insert into out",
    ),
    # (b) four events share each timestamp (no timestamp spans two
    # batches) and land on different shards: ties keep shard order, as
    # heapq.merge gives
    "equal_timestamps": dict(
        cql="from s select id, sum(price) as total, count() as cnt "
        "group by id insert into out",
        ts_div=4,
    ),
    # (c) events-mode limiter: feed_columns thins the merged batch
    "rate_limited": dict(
        cql="from s select id, sum(price) as total group by id "
        "output last every 7 events insert into out",
    ),
}


@functools.lru_cache(maxsize=None)  # the columnar-only run, once a case
def _run_sharded(case, columnar_sink, row_sink):
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh

    spec = SHARDED_CASES[case]
    schema = _schema()
    plan = compile_plan(spec["cql"], {"s": schema})
    batches = _make_batches(schema, n_ids=16)
    div = spec.get("ts_div")
    if div:
        for b in batches:
            b.timestamps[:] = 1_000 + (b.timestamps - 1_000) // div
    job = ShardedJob(
        [plan],
        [BatchSource("s", schema, iter(batches))],
        mesh=make_cep_mesh(4),
        batch_size=1000,
        retain_results=False,
    )
    job.drain_every_cycles = 1  # a drain a batch: chunks span drains
    # ShardedJob.add_plan does not register the plan's output rates
    # (Job.add_plan does; ROADMAP queue 1 item 11): install them as Job
    # would, so the drain's limiter paths run on both lanes
    for sid, rate in plan.output_rates.items():
        job._rate_limiters[sid] = _OutputRateLimiter(
            rate, plan.snapshot_keys.get(sid, ())
        )
    col_sink = _Recorder(plan.output_streams()["out"][0].field_names)
    row_rows = []
    if columnar_sink:
        job.add_sink("out", col_sink)
    if row_sink:
        job.add_sink(
            "out", lambda ts, row: row_rows.append((ts, tuple(row)))
        )
    job.run()
    m = job.metrics()["telemetry"]
    routed = m["gauges"]["route.cumulative_per_shard"][plan.plan_id]
    assert sum(1 for r in routed if r) >= 3, routed  # really sharded
    c = m["counters"]
    assert c["drains.completed"] >= 4
    return col_sink.rows, row_rows, (
        c.get("drain.rows_columnar", 0), c.get("drain.rows", 0),
        m["trace"]["completed"],
    )


@pytest.mark.parametrize("sinks", ["row_sink", "mixed_sinks"])
@pytest.mark.parametrize("case", sorted(SHARDED_CASES))
def test_sharded_columnar_and_row_lanes_deliver_identical_data(case, sinks):
    """ShardedJob's drain takes the columnar lane for exactly the
    streams Job would (ISSUE 25): the same timestamps, columns and order
    as the row lane (decode to tuples, heapq.merge, _emit_rows), which
    any non-columnar consumer keeps the stream on — (d), and
    ``drain.rows_columnar`` says which lane ran — (e)."""
    fast, _, (fast_col, fast_all, traced) = _run_sharded(case, True, False)
    assert fast, case  # the query actually emitted
    # (e) every row of the columnar-only job left through _emit_columns
    assert fast_col == fast_all > 0
    assert fast_all == 4000  # one row per event, counted pre-limiter
    assert len(fast) == (4000 if case != "rate_limited" else 4000 // 7)
    ts = [t for t, _ in fast]
    assert ts == sorted(ts)
    if case == "equal_timestamps":
        assert len(set(ts)) < len(ts) / 2

    mixed, rows, (slow_col, slow_all, slow_traced) = _run_sharded(
        case, sinks == "mixed_sinks", True
    )
    # (d) a callable sink anywhere on the stream: the row lane, whole
    assert slow_col == 0 and slow_all == fast_all
    # sampled events' traces complete the same on either lane
    assert traced == slow_traced
    assert (traced > 0) == (case != "rate_limited")  # thinned: no stop
    assert rows == fast, case
    if sinks == "mixed_sinks":
        assert mixed == fast, case


def _queued_or_blocking(lane, queued):
    """A mesh-4 keyed group-by with a drain a cycle. ``queued``: the
    job's one fetch thread is held, so the first five drains are all
    pending at once (swapped out, queued, not fetched) before any is
    fetched, decoded, merged and emitted; else every drain is the
    blocking form, complete before the next cycle."""
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh
    from tests.faults import HeldFetchThread

    schema = _schema()
    plan = compile_plan(
        SHARDED_CASES["keyed_group_by"]["cql"], {"s": schema},
        config=EngineConfig(acc_budget_bytes=1 << 20),
    )
    job = ShardedJob(
        [plan],
        [BatchSource(
            "s", schema, iter(_make_batches(schema, chunk=500, n_ids=16))
        )],
        mesh=make_cep_mesh(4),
        batch_size=500,
        retain_results=False,
    )
    delivered = []
    if lane == "columnar":
        sink = _Recorder(plan.output_streams()["out"][0].field_names)
        delivered = sink.rows
        job.add_sink("out", sink)
    else:
        job.add_sink(
            "out", lambda ts, row: delivered.append((ts, tuple(row)))
        )
    (rt,) = job._plans.values()
    if queued:
        with HeldFetchThread(job):
            for k in range(1, 6):
                job.run_cycle()
                job.drain_outputs(wait=False)
                assert len(rt.drain_q) == k
            assert not delivered  # five drains pending, none emitted
    while not job.finished:
        job.run_cycle()
        job.drain_outputs(wait=not queued)
    job.flush()
    assert not rt.drain_q
    return delivered, job.metrics()["telemetry"]["counters"]


@pytest.mark.parametrize("lane", ["columnar", "row"])
def test_queued_sharded_drain_delivers_what_a_blocking_one_does(lane):
    """ShardedJob drains through Job's drain queue and fetch thread
    (ISSUE 30): with several drains pending, the rows and their order
    are those of blocking drains on either lane, every drain was
    fetched, decoded and merged off the run loop, and the lane counters
    say which lane ran."""
    got, c = _queued_or_blocking(lane, queued=True)
    want, _ = _queued_or_blocking(lane, queued=False)
    assert len(want) == 4000  # one row per event
    assert got == want
    assert [t for t, _ in got] == sorted(t for t, _ in got)
    assert c["drains.fetched_off_loop"] == c["drains.completed"] >= 8
    assert c["drain.rows"] == 4000
    assert c.get("drain.rows_columnar", 0) == (
        4000 if lane == "columnar" else 0
    )


def test_merge_by_ts_is_heapq_merge_ties_included():
    rng = np.random.default_rng(7)
    parts, row_parts = [], []
    for s in range(4):
        ts = np.sort(rng.integers(0, 40, 100 + s)).astype(np.int64)
        v = np.arange(ts.size, dtype=np.int64) + 1000 * s
        parts.append(ColumnBatch(ts, {"v": v}))
        row_parts.append(parts[-1].rows())
    want = list(heapq.merge(*row_parts, key=lambda p: p[0]))
    assert ColumnBatch.merge_by_ts(parts).rows() == want
    assert ColumnBatch.merge_by_ts(parts[:1]) is parts[0]


def test_columnar_lane_requires_all_columnar_consumers():
    """A stream with any row sink decodes row-wise (the fallback), and
    retained-results jobs never go columnar — _columnar_streams gate."""
    schema = _schema()
    plan = compile_plan(
        "from s[id == 2] select id, price insert into out",
        {"s": schema},
    )
    job = Job(
        [plan],
        [BatchSource("s", schema, iter(_make_batches(schema)))],
        batch_size=1000,
        retain_results=True,  # retention on: rows must exist
    )
    sink = _Recorder(["id", "price"])
    job.add_sink("out", sink)
    rt = next(iter(job._plans.values()))
    assert job._columnar_streams(rt) == frozenset()
    job.run()
    # the columnar sink still observed every row via the fallback
    assert sink.rows == [
        (ts, row) for ts, row in job.collected["out"]
    ]


def test_tail_scheduler_records_staleness_and_deadline_drains():
    """The deadline drain scheduler: a consumer job records the
    drain.staleness leg (age of the oldest undrained match at
    completion), and it is bounded by interval + drain time at this
    scale (CPU lane: generous 10x headroom against scheduler jitter)."""
    schema = _schema()
    plan = compile_plan(
        "from s[id == 2] select id, price insert into out",
        {"s": schema},
    )
    job = Job(
        [plan],
        [BatchSource("s", schema, iter(_make_batches(schema)))],
        batch_size=1000,
        retain_results=False,
    )
    job.drain_interval_ms = 20.0
    sink = _Recorder(["id", "price"])
    job.add_sink("out", sink)
    import time as _time

    while not job.finished:
        job.run_cycle()
        _time.sleep(0.005)  # give deadlines a chance to arrive
    job.flush()
    h = job.telemetry.histogram("drain.staleness")
    assert h.count > 0
    assert h.percentile_ms(99) < 10 * (20.0 + 1000.0)
    assert sink.rows


@pytest.mark.parametrize("which", ["all", "last"])
def test_limiter_survives_lane_switch_mid_chunk(which):
    """A stream can change lanes mid-flight (add_sink of a row sink
    drops it off the columnar lane; the gate re-resolves per drain).
    Buffered fragments from the other lane are normalized, so chunk
    accounting continues exactly — oracle: one limiter fed all rows."""

    def norm(parts):
        out = []
        for p in parts:
            if isinstance(p, ColumnBatch):
                out.extend(
                    (int(a), (int(v),))
                    for a, v in zip(
                        p.ts.tolist(), p.cols["v"].tolist()
                    )
                )
            else:
                a, (v,) = p
                out.append((int(a), (int(v),)))
        return out

    for chunk in (3, 7):
        # columnar -> row: feed_columns leaves a partial chunk buffered,
        # then the row path takes over
        lim = _OutputRateLimiter(_Rate("events", which, chunk))
        got = norm(lim.feed_columns(_cb_of(list(range(10)))))
        got += norm(lim.feed([(x, (x * 10,)) for x in range(10, 20)]))
        got += norm(lim.flush())
        # row -> columnar: the buffered row tuples get lifted
        lim2 = _OutputRateLimiter(_Rate("events", which, chunk))
        got2 = norm(lim2.feed([(x, (x * 10,)) for x in range(10)]))
        got2 += norm(lim2.feed_columns(_cb_of(list(range(10, 20)))))
        got2 += norm(lim2.flush())
        # oracle: all 20 rows through the row path alone
        ora = _OutputRateLimiter(_Rate("events", which, chunk))
        want = norm(ora.feed([(x, (x * 10,)) for x in range(20)]))
        want += norm(ora.flush())
        assert got == want, (which, chunk, "columnar->row")
        assert got2 == want, (which, chunk, "row->columnar")
