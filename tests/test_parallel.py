"""Sharded execution over a virtual 8-device mesh.

The analog of the reference's MiniCluster integration tests
(SiddhiCEPITCase.java:63 — real multi-subtask pipelines in one process):
every test runs the same plan on a 1-device path (plain Job) and on an
8-shard ShardedJob over the CPU mesh from conftest, asserting result
equivalence. Routing exactness contract: group-by streams are key-routed
(exact), pattern/join streams are owner-pinned (exact), stateless filters
are shuffle-routed (exact up to order).
"""

import dataclasses

import jax
import pytest

from flink_siddhi_tpu import CEPEnvironment
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.parallel import Router, ShardedJob, make_cep_mesh
from flink_siddhi_tpu.query.planner import StreamPartition
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.schema.batch import EventBatch
from tests.faults import HeldFetchThread


@dataclasses.dataclass
class Event:
    id: int
    name: str
    price: float
    timestamp: int


FIELDS = ["id", "name", "price", "timestamp"]


def make_events(n, start_ts=1000, id_mod=7, step=100):
    return [
        Event(i % id_mod, f"name_{i % 5}", float(i), start_ts + step * i)
        for i in range(n)
    ]


def build_job(cql, streams, sharded, batch_size=512):
    """streams: {stream_id: events}. Returns a fresh Job/ShardedJob."""
    env = CEPEnvironment(batch_size=batch_size)
    for sid, events in streams.items():
        env.register_stream(sid, events, FIELDS)
    plan = compile_plan(
        cql,
        {sid: env.schemas[sid] for sid in streams},
        extensions=env.extensions,
    )
    sources = [env.sources[sid] for sid in plan.input_stream_ids]
    if sharded:
        return ShardedJob(
            [plan], sources, mesh=make_cep_mesh(8), batch_size=batch_size
        )
    return Job([plan], sources, batch_size=batch_size)


def run_both(cql, streams, batch_size=512):
    single = build_job(cql, streams, sharded=False, batch_size=batch_size)
    single.run()
    sharded = build_job(cql, streams, sharded=True, batch_size=batch_size)
    sharded.run()
    out_stream = next(iter(single.collected), None)
    if out_stream is None:
        out_stream = next(iter(sharded.collected), "out")
    return (
        single.results_with_ts(out_stream),
        sharded.results_with_ts(out_stream),
    )


def test_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    assert make_cep_mesh(8).devices.size == 8


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_filter_sharded_equivalence():
    # stateless filter: shuffle routing, union of shards == global
    events = make_events(500)
    cql = (
        "from inputStream[id == 2] select id, name, price "
        "insert into out"
    )
    single, sharded = run_both(cql, {"inputStream": events})
    assert sorted(single) == sorted(sharded)
    assert len(single) == len([e for e in events if e.id == 2])


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_groupby_cumulative_sharded_equivalence():
    # keyed aggregation state lives on exactly one shard per group -> exact
    events = make_events(600, id_mod=13)
    cql = (
        "from inputStream select id, sum(price) as total, count() as cnt "
        "group by id insert into out"
    )
    single, sharded = run_both(cql, {"inputStream": events})
    assert sorted(single) == sorted(sharded)


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_groupby_time_window_sharded_equivalence():
    # time-window eviction boundaries are key-independent -> per-group rows
    # identical under key routing
    events = make_events(400, id_mod=9)
    cql = (
        "from inputStream#window.time(2 sec) "
        "select id, sum(price) as total group by id insert into out"
    )
    single, sharded = run_both(cql, {"inputStream": events})
    assert sorted(single) == sorted(sharded)


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_pattern_sharded_equivalence():
    # pattern streams are owner-pinned: the NFA sees the full stream once
    s1 = [Event(i % 50, "a", 0.0, 1000 + 1000 * i) for i in range(50)]
    s2 = [Event(i % 50, "b", 0.0, 1500 + 1000 * i) for i in range(50)]
    cql = (
        "from every s1 = inputStream1[id == 2] -> s2 = inputStream2[id == 3]"
        " select s1.id as id_1, s2.id as id_2 insert into out"
    )
    streams = {"inputStream1": s1, "inputStream2": s2}
    single, sharded = run_both(cql, streams)
    assert single == sharded
    assert len(sharded) == 1


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_join_sharded_equivalence():
    # equi-join: both sides key-routed on the join key -> exact. Time
    # windows are used because their eviction boundary is key-independent;
    # length windows are shard-local by design (reference parity: Flink
    # subtask-local window state).
    s1 = [Event(i % 10, "l", float(i), 1000 + 100 * i) for i in range(200)]
    s2 = [Event(i % 10, "r", float(i), 1000 + 100 * i) for i in range(200)]
    cql = (
        "from inputStream1#window.time(1 sec) as a "
        "join inputStream2#window.time(1 sec) as b on a.id == b.id "
        "select a.id as id, a.price as lp, b.price as rp insert into out"
    )
    streams = {"inputStream1": s1, "inputStream2": s2}
    single, sharded = run_both(cql, streams)
    assert sorted(single) == sorted(sharded)


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_multi_query_plan_sharded():
    # one plan, several queries with different partition needs
    events = make_events(300, id_mod=6)
    cql = (
        "from inputStream[price > 100.0] select id, price insert into big; "
        "from inputStream select id, count() as cnt group by id "
        "insert into counts"
    )
    single = build_job(cql, {"inputStream": events}, sharded=False)
    single.run()
    sharded = build_job(cql, {"inputStream": events}, sharded=True)
    sharded.run()
    for out in ("big", "counts"):
        assert sorted(single.results_with_ts(out)) == sorted(
            sharded.results_with_ts(out)
        )


# -------------------------------------------------------------------------
# router unit behavior
# -------------------------------------------------------------------------

def _batch(events):
    env = CEPEnvironment()
    env.register_stream("s", events, FIELDS)
    src = env.sources["s"]
    batch, _, _ = src.poll(10_000)
    return batch


def test_router_groupby_consistency():
    events = make_events(200, id_mod=11)
    batch = _batch(events)
    r = Router(8, {"s": StreamPartition("groupby", ("id",))})
    pieces = r.route(batch)
    total = sum(len(p) for p in pieces if p is not None)
    assert total == len(events)
    # same key always lands on the same shard
    key_shard = {}
    for s, p in enumerate(pieces):
        if p is None:
            continue
        for v in p.columns["id"]:
            assert key_shard.setdefault(int(v), s) == s


def test_router_shuffle_balance_and_broadcast_pin():
    events = make_events(160)
    batch = _batch(events)
    r = Router(8, {})
    pieces = r.route(batch)
    assert [len(p) for p in pieces] == [20] * 8
    rb = Router(8, {"s": StreamPartition("broadcast")})
    pieces = rb.route(batch)
    assert len(pieces[0]) == len(events)
    assert all(p is None for p in pieces[1:])


@pytest.mark.parametrize("n_shards", [1, 3, 4, 8])
@pytest.mark.parametrize(
    "kind", ["groupby", "shuffle", "broadcast", "replicate", "segment"]
)
def test_select_names_the_rows_route_all_copies(kind, n_shards):
    """``Router.select`` is ``route_all`` without the copies: for every
    shard the same rows in the order ``build_tape`` merges the shard's
    pieces in (by timestamp, ties by arrival), the same counts and the
    same cursors, over three cycles of two streams whose stamps
    interleave and tie."""
    import numpy as np

    keys = ("id",) if kind == "groupby" else ()
    parts = {
        "a": StreamPartition(kind, keys), "b": StreamPartition(kind, keys)
    }
    one, twin = Router(n_shards, parts), Router(n_shards, parts)
    a = _batch(make_events(90, id_mod=11, step=100))
    b = _batch(make_events(75, start_ts=1050, id_mod=5, step=150))
    a.stream_id, b.stream_id = "a", "b"
    for lo in (0, 30, 60):
        cycle = [a.slice(lo, lo + 30), b.slice(lo // 2, lo // 2 + 25)]
        rows = one.select(cycle)
        shards = twin.route_all(cycle)
        ts = np.concatenate([p.timestamps for p in cycle])
        ids = np.concatenate([p.columns["id"] for p in cycle])
        src = np.repeat([0, 1], [len(p) for p in cycle])
        assert rows.offsets[0] == 0 and len(rows.offsets) == n_shards + 1
        for s, pieces in enumerate(shards):
            got = rows.order[rows.offsets[s]:rows.offsets[s + 1]]
            if not pieces:
                assert len(got) == 0
                continue
            p_ts = np.concatenate([p.timestamps for p in pieces])
            merge = np.argsort(p_ts, kind="stable")
            want = [
                np.concatenate([p.columns["id"] for p in pieces])[merge],
                p_ts[merge],
                np.concatenate([
                    np.full(len(p), p.stream_id == "b") for p in pieces
                ])[merge],
            ]
            for exp, col in zip(want, (ids, ts, src)):
                np.testing.assert_array_equal(col[got], exp)
        assert list(one.routed) == list(twin.routed)
        assert one.state_dict() == twin.state_dict()


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_sharded_stacked_chain_group():
    """A plan whose chain queries auto-stack must run under ShardedJob
    (regression: the stacked packed output is a 3-tuple)."""
    import numpy as np

    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh
    from flink_siddhi_tpu.runtime.sources import BatchSource
    from flink_siddhi_tpu.schema.batch import EventBatch
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    schema = StreamSchema(
        [("id", AttributeType.INT), ("timestamp", AttributeType.LONG)]
    )
    n = 256
    ids = (np.arange(n) % 6).astype(np.int32)
    ts = 1000 + np.arange(n, dtype=np.int64)
    batch = EventBatch("S", schema, {"id": ids, "timestamp": ts}, ts)
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] "
        "select s1.timestamp as t1, s2.timestamp as t2 insert into o1; "
        "from every s1 = S[id == 3] -> s2 = S[id == 4] "
        "select s1.timestamp as a, s2.timestamp as b insert into o2"
    )
    plan = compile_plan(cql, {"S": schema}, plan_id="p")
    assert len(plan.artifacts) == 1  # stacked
    mesh = make_cep_mesh(4)
    job = ShardedJob(
        [plan], [BatchSource("S", schema, iter([batch]))],
        mesh=mesh, batch_size=128,
    )
    job.run()
    assert len(job.results("o1")) > 0
    assert len(job.results("o2")) > 0


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_nonequi_time_join_replicated_scales():
    # VERDICT round-2 item 7: a non-equi TIME-window join must use more
    # than one shard (replicate-one-side routing) and still match the
    # single-device results exactly
    evs_l = make_events(64, id_mod=7)
    evs_r = [
        Event(i % 5, f"name_{i}", 1000.0 + i, 1050 + 100 * i)
        for i in range(64)
    ]
    cql = (
        # 300ms windows keep the pair count under the per-batch join
        # output cap (out_factor * E) so BOTH paths are lossless
        "from L#window.time(300 millisec) as a "
        "join R#window.time(300 millisec) as b "
        "on a.price < b.price "
        "select a.id, b.id as rid, a.price, b.price as rprice "
        "insert into out"
    )
    single, sharded = run_both(cql, {"L": evs_l, "R": evs_r})
    assert sorted(single) == sorted(sharded)
    # and the left side genuinely spreads: the router sends L rows to
    # more than one shard while R replicates everywhere
    from flink_siddhi_tpu.query.planner import infer_stream_partitions
    from flink_siddhi_tpu.query.parser import parse_plan

    parts = infer_stream_partitions(parse_plan(cql).queries)
    assert parts["L"].kind == "shuffle"
    assert parts["R"].kind == "replicate"


def test_nonequi_length_join_stays_pinned():
    # length windows are global last-n state: spreading a side would
    # change membership, so the planner keeps the owner-pinned instance
    from flink_siddhi_tpu.query.planner import infer_stream_partitions
    from flink_siddhi_tpu.query.parser import parse_plan

    cql = (
        "from L#window.length(4) as a join R#window.length(4) as b "
        "on a.price < b.price select a.id insert into out"
    )
    parts = infer_stream_partitions(parse_plan(cql).queries)
    assert parts["L"].kind == "broadcast"
    assert parts["R"].kind == "broadcast"


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_unkeyed_pattern_segment_parallel():
    # VERDICT round-2 item 7: an unkeyed 3-step every-chain must use
    # more than one shard (time-segment routing + partial-match handoff)
    # and still match single-device results exactly
    evs = [
        Event(i % 9, f"n{i}", float(i), 1000 + 37 * i) for i in range(300)
    ]
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] -> s3 = S[id == 3] "
        "select s1.timestamp as t1, s2.timestamp as t2, s3.timestamp as t3 "
        "insert into out"
    )
    from flink_siddhi_tpu.query.parser import parse_plan
    from flink_siddhi_tpu.query.planner import infer_stream_partitions

    parts = infer_stream_partitions(parse_plan(cql).queries)
    assert parts["S"].kind == "segment"
    single, sharded = run_both(cql, {"S": evs}, batch_size=128)
    assert sorted(single) == sorted(sharded)
    assert len(single) > 0


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_unkeyed_pattern_segment_within():
    # within-deadline must hold across segment boundaries (the global
    # batch max gates expiry, partial handoff preserves start ts)
    evs = [
        Event(i % 11, f"n{i}", float(i), 1000 + 311 * i) for i in range(200)
    ]
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] within 2 sec "
        "select s1.timestamp as t1, s2.timestamp as t2 insert into out"
    )
    single, sharded = run_both(cql, {"S": evs}, batch_size=64)
    assert sorted(single) == sorted(sharded)
    assert len(single) > 0


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_unkeyed_pattern_segment_midchain_absence():
    # mid-chain absence guards must kill partials wherever the guard
    # event lands — including a different segment than the partial
    evs = [
        Event(i % 13, f"n{i}", float(i), 1000 + 53 * i) for i in range(260)
    ]
    cql = (
        "from every s1 = S[id == 1] -> not S[id == 7] -> s2 = S[id == 2] "
        "select s1.timestamp as t1, s2.timestamp as t2 insert into out"
    )
    single, sharded = run_both(cql, {"S": evs}, batch_size=128)
    assert sorted(single) == sorted(sharded)


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_replicate_does_not_duplicate_coconsumer_output():
    # review regression: a plain query reading the replicated side of a
    # non-equi join must emit each row ONCE (the mixed requirement
    # degrades to owner-pinning)
    evs_l = make_events(32)
    evs_r = [
        Event(i % 5, f"n{i}", 1000.0 + i, 1050 + 100 * i)
        for i in range(32)
    ]
    cql = (
        "from R select id, price insert into rcopy; "
        "from L#window.time(300 millisec) as a "
        "join R#window.time(300 millisec) as b on a.price < b.price "
        "select a.id, b.id as rid insert into out"
    )
    single = build_job(cql, {"L": evs_l, "R": evs_r}, sharded=False)
    single.run()
    sharded = build_job(cql, {"L": evs_l, "R": evs_r}, sharded=True)
    sharded.run()
    assert sorted(single.results_with_ts("rcopy")) == sorted(
        sharded.results_with_ts("rcopy")
    )
    assert sorted(single.results_with_ts("out")) == sorted(
        sharded.results_with_ts("out")
    )


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_segment_plus_nonsegmentable_pattern_compiles():
    # review regression: a segmentable chain and a quantified chain on
    # the same stream must still compile (requirements merge to
    # broadcast instead of raising)
    evs = [Event(i % 5, "x", float(i), 1000 + 100 * i) for i in range(60)]
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] "
        "select s1.timestamp as t1 insert into o1; "
        "from every a1 = S[id == 1]<2:3> -> a2 = S[id == 2] "
        "select a1[0].timestamp as t1 insert into o2"
    )
    single = build_job(cql, {"S": evs}, sharded=False)
    single.run()
    sharded = build_job(cql, {"S": evs}, sharded=True)
    sharded.run()
    for out in ("o1", "o2"):
        assert sorted(single.results_with_ts(out)) == sorted(
            sharded.results_with_ts(out)
        )


# -------------------------------------------------------------------------
# tape staging: one build a cycle, one sharded put (mesh-4, tier-1)
# -------------------------------------------------------------------------

_GROUPBY_CQL = (
    "from S select id, sum(price) as total, count() as cnt "
    "group by id insert into out"
)
_EPOCH = 1_700_000_000_000  # epoch ms, for plans whose windows read a long


def _mesh4_job(cql, events, batch_size=64):
    env = CEPEnvironment(batch_size=batch_size)
    env.register_stream("S", events, FIELDS)
    plan = compile_plan(
        cql, {"S": env.schemas["S"]}, extensions=env.extensions
    )
    return ShardedJob(
        [plan], [env.sources["S"]], mesh=make_cep_mesh(4),
        batch_size=batch_size,
    )


def _events_case(cql, events, kind):
    """A case that steps: 300 events of ``S`` in batches of 64."""
    return lambda: (_mesh4_job(cql, events), {"S": kind}, True)


def _cycles_job(cql, fields, cycles):
    """A mesh-4 job over prebuilt batches, stream by stream and cycle
    by cycle: ``cycles[i][sid]`` is ``(columns, timestamps)`` of what
    ``sid`` brings to cycle ``i`` (processing time releases what it
    pulled), the columns in the host's widths (a ``long`` int64, a
    ``double`` float64). Only the staging runs: the step is a stub, so a plan a
    mesh cannot step (a hop window) stages all the same."""
    import numpy as np

    from flink_siddhi_tpu.runtime.sources import BatchSource
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema

    schemas = {sid: StreamSchema(f) for sid, f in fields.items()}
    plan = compile_plan(cql, schemas)

    def batches(sid):
        for cyc in cycles:
            cols, ts = cyc.get(sid) or (
                {n: np.zeros(0, np.int64)
                 for n in schemas[sid].field_names}, []
            )
            yield EventBatch(sid, schemas[sid], dict(cols), ts)

    job = ShardedJob(
        [plan],
        [BatchSource(sid, schemas[sid], batches(sid)) for sid in fields],
        mesh=make_cep_mesh(4), batch_size=512, time_mode="processing",
    )
    (rt,) = job._plans.values()
    rt.jitted_acc = lambda states, acc, tape: (states, acc)
    return job


_LONG2 = [("id", "long"), ("t", "long")]
_IPT = [("id", "int"), ("price", "double"), ("timestamp", "long")]


def _replicate_case():
    import numpy as np

    def side(i, off, n=40):
        ts = 1000 + off + 100 * (np.arange(n) + i * n)
        return ({"id": np.arange(n) % 7, "price": ts / 8.0,
                 "timestamp": ts}, ts)

    cql = (
        "from L#window.time(300 millisec) as a "
        "join R#window.time(300 millisec) as b on a.price < b.price "
        "select a.id, b.id as rid insert into out"
    )
    cycles = [{"L": side(i, 0), "R": side(i, 50)} for i in range(5)]
    return (_cycles_job(cql, {"L": _IPT, "R": _IPT}, cycles),
            {"L": "shuffle", "R": "replicate"}, False)


def _interleaved_case():
    """Two keyed streams whose stamps alternate inside every cycle (the
    merge is no concatenation), each under its own time attribute, one
    slot table fed by both with slots that expire."""
    import numpy as np

    def side(i, off, n=48):
        ts = _EPOCH + off + 700 * (np.arange(n) + i * n)
        return ({"id": (np.arange(n) * 5 + i) % 11, "t": ts}, ts)

    cql = (
        "from People#window.hop(t, 10 sec, 10 sec) as p join "
        "Sales[id > 0]#window.hop(t, 10 sec, 10 sec) as a "
        "on a.id == p.id select a.id as who, count() as n "
        "group by p.id insert into out"
    )
    cycles = [{"People": side(i, 0), "Sales": side(i, 350)}
              for i in range(5)]
    return (_cycles_job(cql, {"People": _LONG2, "Sales": _LONG2}, cycles),
            {"People": "groupby", "Sales": "groupby"}, False)


def _time_bool_double_case():
    """A ``long`` read as time (rebased, its slots expiring tick by
    tick), a ``bool`` and a ``double`` device column."""
    import numpy as np

    def cyc(i, n=90):
        k = np.arange(n) + i * n
        ts = _EPOCH + 130 * k
        return {"S": ({"id": k % 17, "ok": k % 3 != 0,
                       "price": k / 4.0, "timestamp": ts}, ts)}

    cql = (
        "from S[ok == true and price > 1.5]"
        "#window.hop(timestamp, 4 sec, 2 sec) "
        "select id, count() as n group by id insert into out"
    )
    fields = {"S": [("id", "int"), ("ok", "bool"), ("price", "double"),
                    ("timestamp", "long")]}
    return (_cycles_job(cql, fields, [cyc(i) for i in range(6)]),
            {"S": "groupby"}, False)


def _random_cycles_case(n_cycles=500, seed=41):
    """Seeded cycles of one to three streams (keyed, round-robin and
    owner-pinned in one plan), 0 to 300 events each, stamps drawn so
    that the streams interleave and tie."""
    import numpy as np

    rng = np.random.default_rng(seed)
    cql = (
        "from A select id, sum(price) as total group by id insert into oa; "
        "from B[id == 2] select id, price insert into ob; "
        "from every c1 = C[id == 1]<2:3> -> c2 = C[id == 2] "
        "select c1[0].price as p1, c2.price as p2 insert into oc"
    )
    cycles, t0 = [], 1000
    for _ in range(n_cycles):
        cyc = {}
        for sid in rng.permutation(["A", "B", "C"])[: rng.integers(1, 4)]:
            n = int(rng.integers(0, 301))
            ts = t0 + np.sort(rng.integers(0, 400, n))
            cyc[sid] = ({"id": rng.integers(0, 40, n),
                         "price": rng.random(n) * 100,
                         "timestamp": ts}, ts)
        cycles.append(cyc)
        t0 += 400
    return (_cycles_job(cql, {"A": _IPT, "B": _IPT, "C": _IPT}, cycles),
            {"A": "groupby", "B": "shuffle", "C": "broadcast"}, False)


_STAGING_CASES = {
    # name: () -> (job, partition kinds the planner must have chosen,
    # whether the step runs)
    "groupby": _events_case(
        _GROUPBY_CQL, make_events(300, id_mod=13), "groupby"
    ),
    "shuffle": _events_case(
        "from S[id == 2] select id, name, price insert into out",
        make_events(300), "shuffle",
    ),
    # a quantified chain does not split by time: owner-pinned
    "broadcast": _events_case(
        "from every a1 = S[id == 1]<2:3> -> a2 = S[id == 2] "
        "select a1[0].price as p1, a2.price as p2 insert into out",
        make_events(300), "broadcast",
    ),
    "segment": _events_case(
        "from every s1 = S[id == 2] -> s2 = S[id == 3] "
        "select s1.price as p1, s2.price as p2 insert into out",
        make_events(300), "segment",
    ),
    # one key: one shard gets every event of every cycle, three none
    "groupby_empty_shard": _events_case(
        _GROUPBY_CQL, make_events(300, id_mod=1), "groupby"
    ),
    "replicate": _replicate_case,
    "two_streams_interleaved": _interleaved_case,
    "time_bool_double": _time_bool_double_case,
    "random_500_cycles": _random_cycles_case,
}


@pytest.mark.parametrize("case", sorted(_STAGING_CASES))
def test_staged_tape_is_the_four_tapes_one_row_per_device(case):
    """What the step is called on: row ``s`` of every staged leaf is
    ``build_tape`` of what ``Router.route_all`` hands shard ``s``, bit
    for bit and already on shard ``s``'s device. The oracle is a twin:
    a router in the same cursor state and a copy of the spec with its
    own encoders, fed the same cycles shard after shard, so the group
    codes are held to the order the per-shard builds interned in."""
    import copy

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_siddhi_tpu.parallel.mesh import SHARD_AXIS
    from flink_siddhi_tpu.runtime.tape import build_tape

    job, kinds, steps = _STAGING_CASES[case]()
    (rt,) = job._plans.values()
    router = job._routers[rt.plan.plan_id]
    assert {
        sid: router.partition_of(sid).kind for sid in kinds
    } == kinds
    twin_spec = copy.deepcopy(rt.plan.spec)
    twin = Router(4, router.partitions)
    want = NamedSharding(job.mesh, P(SHARD_AXIS))
    devices = list(job.mesh.devices.flat)
    stage, cycles = job._stage_tapes, []

    def staged_and_checked(rt_, involved, rows):
        tape = stage(rt_, involved, rows)
        shards = twin.route_all(involved)
        cycles.append((involved, [sum(map(len, sh)) for sh in shards]))
        cap = tape.capacity
        refs = [
            build_tape(twin_spec, sh, job._epoch_ms, cap)[0]
            for sh in shards
        ]
        assert tape.time_off == refs[0].time_off
        assert sorted(tape.cols) == sorted(refs[0].cols)
        got_leaves = jax.tree.leaves(tape)
        for got in got_leaves:
            assert got.shape == (4, cap)
            assert got.sharding.is_equivalent_to(want, got.ndim)
        for s, ref in enumerate(refs):
            ref_leaves = jax.tree.leaves(ref)
            assert len(got_leaves) == len(ref_leaves)
            for got, exp in zip(got_leaves, ref_leaves):
                assert got.dtype == exp.dtype
                row = {
                    sh.index[0].start: sh for sh in got.addressable_shards
                }[s]
                assert row.device == devices[s]
                np.testing.assert_array_equal(np.asarray(row.data)[0], exp)
        assert list(router.routed) == list(twin.routed)
        assert router.state_dict() == twin.state_dict()
        return tape

    job._stage_tapes = staged_and_checked
    job.run()
    if steps:
        assert len(cycles) >= 4  # 300 events in batches of 64
    counts = [c for _, c in cycles]
    if case == "groupby_empty_shard":
        assert all(sorted(c)[:3] == [0, 0, 0] for c in counts)
    if case == "replicate":
        assert all(min(c) >= 40 for c in counts)  # R whole, everywhere
    if case == "two_streams_interleaved":
        # no concatenation of a cycle's batches is in time order
        for involved, _ in cycles:
            ts = np.concatenate([b.timestamps for b in involved])
            assert len(involved) == 2 and (np.diff(ts) < 0).any()
    if case == "time_bool_double":
        spec = rt.plan.spec
        assert spec.time_columns == ("S.timestamp",)
        assert {
            k: np.dtype(spec.column_types[k].device_dtype)
            for k in spec.built_columns()
        } == {"S.id": np.int32, "S.ok": np.bool_, "S.price": np.float32}
        # slots expired on the way: the encoder's ticks are each tape's
        assert spec.encoded[0].encoder.stats["expired"] > 0
    if case == "random_500_cycles":
        sizes = {len(inv) for inv, _ in cycles}
        assert len(cycles) > 400 and sizes == {1, 2, 3}
        assert any(0 in c for c in counts)


def test_a_cycle_is_built_once_inside_tape_build():
    """``shard.tape_builds`` counts the builds of a tape's columns: one
    a cycle (``n_shards`` a cycle would mean per-shard builds were
    back), under the nested span ``shard_build``, which with
    ``shard_put`` makes up ``tape_build``."""
    job = _mesh4_job(_GROUPBY_CQL, make_events(300, id_mod=13))
    job.run()
    telemetry = job.metrics()["telemetry"]
    counters, stages = telemetry["counters"], telemetry["stages"]
    cycles = counters["shard.cycles"]
    assert counters["shard.tape_builds"] == cycles > 0
    assert stages["nested.shard_build"]["count"] == cycles
    assert stages["tape_build"]["count"] == cycles
    assert "shard_build" not in stages  # never a top-level span
    inside = (stages["nested.shard_build"]["seconds"]
              + stages["nested.shard_put"]["seconds"])
    assert inside <= stages["tape_build"]["seconds"]


def test_sharded_run_under_transfer_guard_puts_once_a_cycle(monkeypatch):
    """The dispatch site allows nothing: under the hot-loop transfer
    guard a ShardedJob runs clean with the guard still at "disallow"
    when the step is called, and every cycle that dispatched made
    exactly one explicit sharded upload."""
    from flink_siddhi_tpu.runtime import executor

    monkeypatch.setattr(executor, "HOTLOOP_TRANSFER_GUARD", True)
    events = make_events(300, id_mod=13)
    job = _mesh4_job(_GROUPBY_CQL, events)
    (rt,) = job._plans.values()
    step, guard_at_dispatch = rt.jitted_acc, []

    def guarded_step(*args):
        guard_at_dispatch.append(
            jax.config.jax_transfer_guard_host_to_device
        )
        return step(*args)

    rt.jitted_acc = guarded_step
    job.run()
    single = build_job(_GROUPBY_CQL, {"S": events}, sharded=False)
    single.run()
    assert sorted(job.results_with_ts("out")) == sorted(
        single.results_with_ts("out")
    )
    telemetry = job.metrics()["telemetry"]
    cycles = telemetry["counters"]["shard.cycles"]
    assert telemetry["counters"]["shard.tape_puts"] == cycles > 0
    assert guard_at_dispatch == ["disallow"] * cycles
    assert telemetry["stages"]["nested.shard_put"]["count"] == cycles
    # nested in tape_build, never a top-level span of the run loop
    assert "shard_put" not in telemetry["stages"]


# -------------------------------------------------------------------------
# the queued drain: Job's drain queue and fetch thread on a mesh (mesh-4)
# -------------------------------------------------------------------------


def _queued_job(n_events=640, batch_size=64):
    """A mesh-4 keyed group-by whose accumulator is the smallest there
    is (65,536 slots a shard), rows retained: the row lane."""
    from flink_siddhi_tpu.compiler.config import EngineConfig

    env = CEPEnvironment(batch_size=batch_size)
    events = make_events(n_events, id_mod=13)
    env.register_stream("S", events, FIELDS)
    plan = compile_plan(
        _GROUPBY_CQL, {"S": env.schemas["S"]}, extensions=env.extensions,
        config=EngineConfig(acc_budget_bytes=1 << 20),
    )
    job = ShardedJob(
        [plan], [env.sources["S"]], mesh=make_cep_mesh(4),
        batch_size=batch_size,
    )
    return job, next(iter(job._plans.values()))


def _queue_drains(job, rt, n):
    for k in range(1, n + 1):
        assert job.run_cycle() > 0
        job.drain_outputs(wait=False)
        assert len(rt.drain_q) == k


def test_blocking_drain_and_flush_are_barriers_over_pending_drains():
    """drain_outputs(wait=True) and flush() complete every pending
    sharded drain, in order, before they return: results() reads all
    rows of the events stepped so far, none twice."""
    job, rt = _queued_job()
    with HeldFetchThread(job) as held:
        _queue_drains(job, rt, 3)
        assert not job.collected.get("out")
        held.release_after(0.2)
        job.drain_outputs(wait=True)
        assert not rt.drain_q
        assert len(job.collected["out"]) == 3 * 64
    with HeldFetchThread(job) as held:
        _queue_drains(job, rt, 3)
        held.release_after(0.2)
        job.flush()
        assert not rt.drain_q
        assert len(job.collected["out"]) == 6 * 64
    job.run()
    got = job.results_with_ts("out")
    single = build_job(
        _GROUPBY_CQL, {"S": make_events(640, id_mod=13)}, sharded=False
    )
    single.run()
    assert got == sorted(single.results_with_ts("out"), key=lambda p: p[0])
    c = job.metrics()["telemetry"]["counters"]
    assert c["drains.fetched_off_loop"] == c["drains.completed"] >= 7


def test_more_pending_sharded_drains_than_the_bound_is_the_backlog_span():
    """The seventh pending drain blocks the run loop on the oldest:
    the span drain.backlog_wait, as on one chip."""
    job, rt = _queued_job()
    assert job.MAX_PENDING_DRAINS == 6
    with HeldFetchThread(job) as held:
        _queue_drains(job, rt, 6)
        assert "nested.drain.backlog_wait" not in (
            job.telemetry.snapshot()["stages"]
        )
        held.release_after(0.2)
        job.run_cycle()
        job.drain_outputs(wait=False)  # the seventh: waits for the first
        assert len(rt.drain_q) <= 6
    waits = job.telemetry.snapshot()["stages"]["nested.drain.backlog_wait"]
    assert waits["count"] == 1 and waits["seconds"] > 0.1
    job.run()
    assert len(job.results("out")) == 640


def test_an_exception_on_the_fetch_thread_surfaces_on_the_run_loop():
    job, rt = _queued_job(n_events=128)

    def broken(*args, **kw):
        raise RuntimeError("decode fell over")

    object.__setattr__(rt.plan, "drain_decode", broken)
    job.run_cycle()
    job.drain_outputs(wait=False)  # queued: the fetch thread raises
    with pytest.raises(RuntimeError, match="decode fell over"):
        job.drain_outputs(wait=True)


def test_after_prewarm_a_steady_sharded_run_lowers_no_program():
    """prewarm_drains compiles the sharded slice program of every fetch
    width between ShardedJob.MIN_FETCH_WIDTH and the capacity; after it
    and the first cycles, a run with a drain a cycle lowers nothing."""
    from flink_siddhi_tpu.telemetry import compile_events

    job, rt = _queued_job()
    with compile_events.watch() as warm:
        job.prewarm_drains()
    assert sorted(rt.pack_jits) == [1 << 14, 1 << 15, 1 << 16]
    assert warm.count == 3
    for _ in range(2):
        job.run_cycle()
        job.drain_outputs(wait=True)
    before = job.metrics()["compiles"]["total_lowerings"]
    with compile_events.watch() as steady:
        while not job.finished:
            job.run_cycle()
            job.drain_outputs(wait=False)
        job.drain_outputs(wait=True)
    assert steady.count == 0, steady.durations
    assert job.metrics()["compiles"]["total_lowerings"] == before
    assert len(job.results("out")) == 640
    c = job.metrics()["telemetry"]["counters"]
    assert c["drains.fetched_off_loop"] == c["drains.completed"] >= 10
