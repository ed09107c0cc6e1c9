"""Checkpoint / restore equivalence: run-half + restore == full run.

The reference never restored engine state (AbstractSiddhiOperator.java:341
TODO); these tests pin that this engine restores EVERYTHING: window rings,
partial NFA matches, group tables, string dictionaries, event tables."""

import dataclasses

import pytest

from flink_siddhi_tpu import CEPEnvironment, SiddhiCEP


@dataclasses.dataclass
class Event:
    id: int
    name: str
    price: float
    timestamp: int


FIELDS = ["id", "name", "price", "timestamp"]


def make_events(n, start_ts=1000):
    return [
        Event(i % 4, f"name_{i % 3}", float(i), start_ts + 1000 * i)
        for i in range(n)
    ]


def run_full(events, cql, out="out"):
    env = CEPEnvironment(batch_size=5)
    return (
        SiddhiCEP.define("S", events, FIELDS, env=env).cql(cql).returns(out)
    )


def run_split(events, cql, k, out="out"):
    """Run the first k events, snapshot, then resume in a fresh process
    analog: a new environment over the SAME stream, where the restored
    source position skips the already-consumed prefix."""
    env1 = CEPEnvironment(batch_size=5)
    es1 = SiddhiCEP.define("S", events[:k], FIELDS, env=env1).cql(cql)
    job1 = es1.execute()
    snap = job1.snapshot()

    env2 = CEPEnvironment(batch_size=5)
    es2 = SiddhiCEP.define("S", events[:k] + events[k:], FIELDS, env=env2).cql(cql)
    job2 = es2.job
    job2.restore(snap)
    job2.run()
    return job1.results(out) + job2.results(out)


CASES = [
    # sliding window ring must survive
    "from S#window.length(6) select sum(price) as t, min(price) as lo "
    "insert into out",
    # cumulative group table + encoder
    "from S select id, sum(price) as t, count() as c group by id "
    "insert into out",
    # string-keyed groups: dictionary + encoder round-trip
    "from S select name, count() as c group by name insert into out",
    # partial pattern matches must survive the boundary
    "from every s1 = S[id == 2] -> s2 = S[id == 3] "
    "select s1.price as p1, s2.price as p2 insert into out",
    # tumbling window carry
    "from S#window.lengthBatch(7) select sum(price) as t insert into out",
]


@pytest.mark.parametrize("cql", CASES)
@pytest.mark.parametrize("k", [9, 13])
def test_restore_equivalence(cql, k):
    events = make_events(30)
    assert run_split(events, cql, k) == run_full(events, cql)


def test_restore_event_table():
    events = make_events(20)
    cql = (
        "define table T (tid int, total double);"
        "from S[id == 0] select id as tid, price as total insert into T;"
        "from S[id == 1] join T on S.id == T.tid + 1 "
        "select S.price, T.total insert into out"
    )
    assert run_split(events, cql, 11) == run_full(events, cql)


def test_save_load_file(tmp_path):
    events = make_events(24)
    cql = "from S#window.length(5) select sum(price) as t insert into out"
    env1 = CEPEnvironment(batch_size=5)
    es1 = SiddhiCEP.define("S", events[:12], FIELDS, env=env1).cql(cql)
    job1 = es1.execute()
    path = str(tmp_path / "ckpt.bin")
    job1.save_checkpoint(path)

    env2 = CEPEnvironment(batch_size=5)
    es2 = SiddhiCEP.define("S", events, FIELDS, env=env2).cql(cql)
    job2 = es2.job
    job2.restore(path)
    job2.run()
    assert job1.results("out") + job2.results("out") == run_full(
        events, cql
    )


def test_restore_rejects_changed_plan():
    events = make_events(10)
    env1 = CEPEnvironment(batch_size=5)
    job1 = (
        SiddhiCEP.define("S", events, FIELDS, env=env1)
        .cql("from S#window.length(5) select sum(price) as t insert into out")
        .execute()
    )
    snap = job1.snapshot()

    env2 = CEPEnvironment(batch_size=5)
    es2 = SiddhiCEP.define("S", events, FIELDS, env=env2).cql(
        "from every s1 = S[id == 2] -> s2 = S[id == 3] "
        "select s1.price as p insert into out"
    )
    with pytest.raises(ValueError):
        es2.job.restore(snap)


def test_restore_rejects_changed_window_size():
    # same pytree structure, different ring capacity -> must be rejected
    # (shape validation, not just key paths)
    events = make_events(12)
    env1 = CEPEnvironment(batch_size=5)
    job1 = (
        SiddhiCEP.define("S", events, FIELDS, env=env1)
        .cql("from S#window.length(5) select sum(price) as t insert into out")
        .execute()
    )
    snap = job1.snapshot()

    env2 = CEPEnvironment(batch_size=5)
    es2 = SiddhiCEP.define("S", events, FIELDS, env=env2).cql(
        "from S#window.length(9) select sum(price) as t insert into out"
    )
    with pytest.raises(ValueError, match="shape|dtype|CQL"):
        es2.job.restore(snap)


def test_restore_rejects_time_mode_mismatch():
    events = make_events(12)
    env1 = CEPEnvironment(batch_size=5)
    cql = "from S select id, price insert into out"
    job1 = SiddhiCEP.define("S", events, FIELDS, env=env1).cql(cql).execute()
    snap = job1.snapshot()

    env2 = CEPEnvironment(batch_size=5, time_mode="processing")
    es2 = SiddhiCEP.define("S", events, FIELDS, env=env2).cql(cql)
    with pytest.raises(ValueError, match="time mode"):
        es2.job.restore(snap)


def test_restore_accepts_pathlike(tmp_path):
    events = make_events(12)
    cql = "from S#window.length(5) select sum(price) as t insert into out"
    env1 = CEPEnvironment(batch_size=5)
    job1 = SiddhiCEP.define("S", events, FIELDS, env=env1).cql(cql).execute()
    path = tmp_path / "ckpt.bin"  # pathlib.Path, not str
    job1.save_checkpoint(str(path))

    env2 = CEPEnvironment(batch_size=5)
    es2 = SiddhiCEP.define("S", events, FIELDS, env=env2).cql(cql)
    es2.job.restore(path)


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_sharded_job_checkpoint_roundtrip():
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh

    events = make_events(40)
    cql = (
        "from S select id, sum(price) as total, count() as c "
        "group by id insert into out"
    )

    def build(evs):
        env = CEPEnvironment(batch_size=8)
        env.register_stream("S", evs, FIELDS)
        plan = compile_plan(
            cql, {"S": env.schemas["S"]}, extensions=env.extensions
        )
        return ShardedJob(
            [plan], [env.sources["S"]], mesh=make_cep_mesh(8), batch_size=8
        )

    full = build(events)
    full.run()

    j1 = build(events[:20])
    j1.run()
    snap = j1.snapshot()
    j2 = build(events)
    j2.restore(snap)
    # skip the consumed prefix (source position was restored)
    j2.run()
    assert sorted(j1.results_with_ts("out") + j2.results_with_ts("out")) == sorted(
        full.results_with_ts("out")
    )


def test_sharded_job_double_recovery_roundtrip(tmp_path):
    """Checkpoint -> kill -> restore -> SECOND kill -> SECOND restore:
    two full generations of file-based recovery on a ShardedJob (the
    second restore starts from a checkpoint written by an
    already-restored job, so restored state must itself checkpoint
    losslessly), with row-exact oracle agreement across all three
    lifetimes. The save path runs with keep=2 rotation, so the round
    trip also pins that rotated generations stay readable.

    Mesh 4, deliberately: this test stays in the tier-1 lane, and on
    the 2-core CPU lane a mesh-8 shard_map compile costs minutes (the
    mesh-8 suites carry @pytest.mark.slow)."""
    import glob
    import os

    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh

    events = make_events(48)
    cql = (
        "from S select id, sum(price) as total, count() as c "
        "group by id insert into out"
    )

    def build(evs):
        env = CEPEnvironment(batch_size=8)
        env.register_stream("S", evs, FIELDS)
        plan = compile_plan(
            cql, {"S": env.schemas["S"]}, extensions=env.extensions
        )
        return ShardedJob(
            [plan], [env.sources["S"]], mesh=make_cep_mesh(4), batch_size=8
        )

    full = build(events)
    full.run()
    oracle = sorted(full.results_with_ts("out"))

    path = str(tmp_path / "ckpt")

    # lifetime 1: consume a third, checkpoint, "die"
    j1 = build(events[:16])
    j1.run()
    j1.save_checkpoint(path, keep=2)

    # lifetime 2: restore, consume to two-thirds, checkpoint, "die".
    # This save rotates lifetime 1's checkpoint to ckpt.1.
    j2 = build(events[:32])
    j2.restore(path)
    j2.run()
    j2.save_checkpoint(path, keep=2)
    assert os.path.exists(f"{path}.1")  # the rotated generation

    # lifetime 3: restore the SECOND-generation checkpoint, finish
    j3 = build(events)
    j3.restore(path)
    j3.run()

    got = sorted(
        j1.results_with_ts("out")
        + j2.results_with_ts("out")
        + j3.results_with_ts("out")
    )
    assert got == oracle  # no loss, no duplicates, across two recoveries
    assert glob.glob(f"{path}.tmp.*") == []  # no temp debris left

    # the ROTATED generation is itself restorable (the fallback the
    # supervisor walks when the newest file is unreadable): restoring
    # ckpt.1 replays lifetime 2 exactly
    j2b = build(events[:32])
    j2b.restore(f"{path}.1")
    j2b.run()
    assert sorted(j2b.results_with_ts("out")) == sorted(
        j2.results_with_ts("out")
    )


def test_sharded_shuffle_cursor_survives_a_checkpoint():
    """A round-robin stream's cursor is router state: restored from a
    snapshot taken mid-turn (batches of 7 over 4 shards), the second
    life deals the next event to the shard the first would have, so the
    two lives place every event where one uninterrupted run does."""
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh

    events = make_events(49)
    cql = "from S[id == 2] select id, name, price insert into out"

    def build(evs):
        env = CEPEnvironment(batch_size=7)
        env.register_stream("S", evs, FIELDS)
        plan = compile_plan(
            cql, {"S": env.schemas["S"]}, extensions=env.extensions
        )
        job = ShardedJob(
            [plan], [env.sources["S"]], mesh=make_cep_mesh(4), batch_size=7
        )
        (router,) = job._routers.values()
        assert router.partition_of("S").kind == "shuffle"
        return job, router

    full, full_router = build(events)
    full.run()
    j1, r1 = build(events[:21])
    j1.run()
    snap = j1.snapshot()
    assert r1.state_dict() == {"rr": {"S": 21 % 4}}
    j2, r2 = build(events)
    j2.restore(snap)
    assert r2.state_dict() == r1.state_dict()
    j2.run()
    assert list(r1.routed + r2.routed) == list(full_router.routed)
    assert r2.state_dict() == full_router.state_dict()
    assert sorted(
        j1.results_with_ts("out") + j2.results_with_ts("out")
    ) == sorted(full.results_with_ts("out"))


def test_sharded_checkpoint_with_drains_pending_is_a_barrier():
    """A snapshot taken while sharded drains are pending (swapped out,
    queued behind the fetch thread, not yet emitted) completes them
    first: across the restore no row is lost and none is doubled."""
    from flink_siddhi_tpu.compiler.config import EngineConfig
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh
    from tests.faults import HeldFetchThread

    events = make_events(48)
    cql = (
        "from S select id, sum(price) as total, count() as c "
        "group by id insert into out"
    )

    def build(evs):
        env = CEPEnvironment(batch_size=8)
        env.register_stream("S", evs, FIELDS)
        plan = compile_plan(
            cql, {"S": env.schemas["S"]}, extensions=env.extensions,
            config=EngineConfig(acc_budget_bytes=1 << 20),
        )
        return ShardedJob(
            [plan], [env.sources["S"]], mesh=make_cep_mesh(4), batch_size=8
        )

    full = build(events)
    full.run()

    j1 = build(events[:24])
    (rt,) = j1._plans.values()
    with HeldFetchThread(j1) as held:
        while not j1.finished:
            j1.run_cycle()
            j1.drain_outputs(wait=False)
        assert len(rt.drain_q) == 3 and not j1.collected.get("out")
        held.release_after(0.2)
        snap = j1.snapshot()
    assert not rt.drain_q and len(j1.collected["out"]) == 24

    j2 = build(events)
    j2.restore(snap)
    j2.run()
    assert sorted(
        j1.results_with_ts("out") + j2.results_with_ts("out")
    ) == sorted(full.results_with_ts("out"))
