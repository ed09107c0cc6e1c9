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
# tape staging: host stack, one sharded put (mesh-4, tier-1)
# -------------------------------------------------------------------------

_GROUPBY_CQL = (
    "from S select id, sum(price) as total, count() as cnt "
    "group by id insert into out"
)
_STAGING_CASES = {
    # name: (cql, events, partition kind the planner must have chosen)
    "groupby": (_GROUPBY_CQL, make_events(300, id_mod=13), "groupby"),
    "shuffle": (
        "from S[id == 2] select id, name, price insert into out",
        make_events(300),
        "shuffle",
    ),
    # a quantified chain does not split by time: owner-pinned
    "broadcast": (
        "from every a1 = S[id == 1]<2:3> -> a2 = S[id == 2] "
        "select a1[0].price as p1, a2.price as p2 insert into out",
        make_events(300),
        "broadcast",
    ),
    "segment": (
        "from every s1 = S[id == 2] -> s2 = S[id == 3] "
        "select s1.price as p1, s2.price as p2 insert into out",
        make_events(300),
        "segment",
    ),
    # one key: three of the four shards receive no event in any cycle
    "groupby_empty_shard": (
        _GROUPBY_CQL, make_events(300, id_mod=1), "groupby",
    ),
}


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


@pytest.mark.parametrize("case", sorted(_STAGING_CASES))
def test_staged_tape_is_the_four_tapes_one_row_per_device(case):
    """What the step is called on: the per-shard ``build_tape`` results,
    leaf for leaf and row for row, already laid one row per device."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flink_siddhi_tpu.parallel.mesh import SHARD_AXIS
    from flink_siddhi_tpu.runtime.tape import build_tape

    cql, events, kind = _STAGING_CASES[case]
    job = _mesh4_job(cql, events)
    (rt,) = job._plans.values()
    assert job._routers[rt.plan.plan_id].partition_of("S").kind == kind
    staged = []
    stage = job._stage_tapes

    def spy(rt_, shards):
        out = stage(rt_, shards)
        staged.append((shards, out))
        return out

    job._stage_tapes = spy
    job.run()
    assert len(staged) >= 4  # 300 events in batches of 64
    if case == "groupby_empty_shard":
        assert all(
            sorted(map(len, shards))[:3] == [0, 0, 0]
            for shards, _ in staged
        )
    want = NamedSharding(job.mesh, P(SHARD_AXIS))
    devices = list(job.mesh.devices.flat)
    for shards, tape in staged:
        cap = tape.capacity
        refs = [
            build_tape(rt.plan.spec, sh, job._epoch_ms, cap)[0]
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
