"""Late materialization (EngineConfig.lazy_projection): projection-only
columns never ship to the device — the chain matcher emits event
ordinals and decode resolves them from host-retained batches.

Every event crosses the host->device link once; this cuts the headline
pattern's bytes over it to the predicate column + timestamp deltas. Values decode at full host precision (float64),
strictly better than the device's float32 round-trip.
"""

import numpy as np
import pytest

from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

SCHEMA = StreamSchema(
    [
        ("id", AttributeType.INT),
        ("name", AttributeType.STRING),
        ("price", AttributeType.DOUBLE),
        ("timestamp", AttributeType.LONG),
    ]
)

CQL = (
    "from every s1 = S[id == 1] -> s2 = S[id == 2] -> s3 = S[id == 3] "
    "within 5 sec "
    "select s1.timestamp as t1, s3.timestamp as t3, s3.price as price, "
    "s3.name as n3 insert into matches"
)


def make_batches(n=2000, batch=64, seed=7):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 6, n).astype(np.int32)
    prices = np.round(rng.random(n) * 100, 3)
    names = rng.integers(0, 3, n)
    ts = (1000 + np.arange(n)).astype(np.int64)
    tbl = SCHEMA.string_tables["name"]
    codes = np.array([tbl.intern(f"nm{i}") for i in range(3)], np.int32)
    return [
        EventBatch(
            "S", SCHEMA,
            {
                "id": ids[s:s + batch],
                "name": codes[names[s:s + batch]],
                "price": prices[s:s + batch],
                "timestamp": ts[s:s + batch],
            },
            ts[s:s + batch],
        )
        for s in range(0, n, batch)
    ]


def run(cfg, batch=64):
    plan = compile_plan(CQL, {"S": SCHEMA}, config=cfg)
    job = Job(
        [plan], [BatchSource("S", SCHEMA, iter(make_batches(batch=batch)))],
        batch_size=batch, time_mode="processing",
    )
    job.run()
    return plan, sorted(job.results("matches"))


def test_lazy_matches_eager_results():
    plan_e, eager = run(EngineConfig())
    plan_l, lazy = run(EngineConfig(lazy_projection=True))
    # the predicate column is the only one left on the wire
    assert plan_l.spec.device_columns == ("S.id",)
    a = plan_l.artifacts[0]
    assert set(a.lazy_pairs) == {
        (0, "timestamp"), (2, "name"), (2, "price"), (2, "timestamp")
    }
    assert len(eager) == len(lazy) > 0
    for (t1e, t3e, pe, ne), (t1l, t3l, pl, nl) in zip(eager, lazy):
        assert (t1e, t3e, ne) == (t1l, t3l, nl)
        # lazy decodes the ORIGINAL float64; eager went through f32
        assert pl == pytest.approx(pe, rel=1e-6)


def test_lazy_partials_across_batch_boundaries():
    # a partial started in one batch completes several batches later:
    # its lazy ordinals resolve against older ring entries
    _, lazy = run(EngineConfig(lazy_projection=True), batch=16)
    _, eager = run(EngineConfig(), batch=16)
    assert len(lazy) == len(eager) > 0


def test_computed_projection_is_not_lazy():
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] "
        "select s1.timestamp as t1, s2.price * 2.0 as p2 insert into o"
    )
    plan = compile_plan(
        cql, {"S": SCHEMA}, config=EngineConfig(lazy_projection=True)
    )
    a = plan.artifacts[0]
    # price feeds a computed expression -> must stay on the device
    assert (1, "price") not in a.lazy_pairs
    assert "S.price" in (plan.spec.device_columns or ())


def test_ring_eviction_decodes_none():
    from flink_siddhi_tpu.runtime.executor import _LazyRing

    ring = _LazyRing(budget_bytes=64)
    ring.push(0, {"S.x": np.arange(8, dtype=np.float64)})  # 64 B
    ring.push(8, {"S.x": np.arange(8, dtype=np.float64) + 100})
    # first entry evicted (budget); its ordinals miss
    vals = ring.lookup("S.x", np.array([2, 9]))
    assert vals[0] is None
    assert vals[1] == 101.0
    assert ring.missed == 1


def test_lazy_survives_checkpoint_restore(tmp_path):
    # post-restore matches must decode real values: the host ring base
    # re-syncs from the restored device ordinal counter
    plan = compile_plan(
        CQL, {"S": SCHEMA}, config=EngineConfig(lazy_projection=True)
    )
    batches = make_batches(n=512, batch=64)
    job = Job(
        [plan], [BatchSource("S", SCHEMA, iter(batches[:4]))],
        batch_size=64, time_mode="processing",
    )
    job.run(max_cycles=4)
    p = tmp_path / "c.bin"
    job.save_checkpoint(str(p))

    plan2 = compile_plan(
        CQL, {"S": SCHEMA}, config=EngineConfig(lazy_projection=True)
    )
    job2 = Job(
        [plan2], [BatchSource("S", SCHEMA, iter(batches[4:]))],
        batch_size=64, time_mode="processing",
    )
    job2.restore(str(p))
    job2.run()
    rows = job2.results("matches")
    post = [r for r in rows if r[2] is not None]
    # brand-new post-restore matches carry real values (only partials
    # carried ACROSS the restore may decode None)
    assert post, f"all post-restore matches decoded None: {rows[:5]}"


def test_lazy_plan_not_folded_dynamically():
    plan = compile_plan(
        CQL, {"S": SCHEMA}, config=EngineConfig(lazy_projection=True)
    )
    job = Job(
        [],
        [BatchSource("S", SCHEMA, iter(make_batches(n=256)))],
        batch_size=64, time_mode="processing",
    )
    job.add_plan(plan, dynamic=True)
    # lazy plans keep their own runtime (no parametric group wrap)
    assert list(job._plans) == [plan.plan_id]
    job.run()
    assert all(
        r[2] is not None for r in job.results("matches")
    )


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_sharded_job_auto_disables_lazy():
    # VERDICT round-2 item 8: a lazy-compiled plan must not make
    # ShardedJob refuse — it recompiles without lazy projection and
    # still matches the single-device results
    from flink_siddhi_tpu.parallel import ShardedJob

    plan = compile_plan(
        CQL, {"S": SCHEMA}, config=EngineConfig(lazy_projection=True)
    )
    assert any(getattr(a, "lazy_pairs", ()) for a in plan.artifacts)
    job = ShardedJob(
        [plan],
        [BatchSource("S", SCHEMA, iter(make_batches(n=512)))],
        n_shards=8, batch_size=64, time_mode="processing",
    )
    rt = next(iter(job._plans.values()))
    assert not any(
        getattr(a, "lazy_pairs", ()) for a in rt.plan.artifacts
    )
    job.run()
    single = Job(
        [compile_plan(CQL, {"S": SCHEMA})],
        [BatchSource("S", SCHEMA, iter(make_batches(n=512)))],
        batch_size=64, time_mode="processing",
    )
    single.run()
    assert sorted(job.results("matches")) == sorted(
        single.results("matches")
    )


# -- lazy stateless select/filter (round-4: the filter bench was wire-
# bound at 7 B/event because select plans always shipped every projected
# column; lazy select drops the wire to predicate column + ts deltas) --

SELECT_CQL = (
    "from S[id == 2] select id, name, price insert into out"
)


def run_select(cfg, cql=SELECT_CQL, batch=64, n=2000):
    plan = compile_plan(cql, {"S": SCHEMA}, config=cfg)
    job = Job(
        [plan],
        [BatchSource("S", SCHEMA, iter(make_batches(n=n, batch=batch)))],
        batch_size=batch, time_mode="processing",
    )
    job.run()
    return plan, job.results("out")


def test_lazy_select_matches_eager():
    plan_e, eager = run_select(EngineConfig())
    plan_l, lazy = run_select(EngineConfig(lazy_projection=True))
    # only the predicate column ships; name/price resolve host-side
    assert plan_l.spec.device_columns == ("S.id",)
    a = plan_l.artifacts[0]
    assert set(a.lazy_pairs) == {"S.name", "S.price"}
    assert len(eager) == len(lazy) > 0
    for (ide, ne, pe), (idl, nl, pl) in zip(eager, lazy):
        assert (ide, ne) == (idl, nl)
        # lazy decodes the ORIGINAL float64; eager went through f32
        assert pl == pytest.approx(pe, rel=1e-6)


def test_lazy_select_no_filter_ships_nothing():
    # a projection-only query's wire is just the timestamp deltas
    cql = "from S select name, price insert into out"
    plan_l, lazy = run_select(EngineConfig(lazy_projection=True), cql=cql)
    assert plan_l.spec.device_columns == ()
    _, eager = run_select(EngineConfig(), cql=cql)
    assert len(lazy) == len(eager) == 2000
    for (ne, pe), (nl, pl) in zip(eager, lazy):
        assert ne == nl
        assert pl == pytest.approx(pe, rel=1e-6)


def test_lazy_select_computed_expr_stays_on_device():
    cql = "from S[id == 2] select price * 2.0 as p2, name insert into out"
    plan_l, lazy = run_select(EngineConfig(lazy_projection=True), cql=cql)
    a = plan_l.artifacts[0]
    assert a.lazy_pairs == ("S.name",)
    assert "S.price" in plan_l.spec.device_columns
    _, eager = run_select(EngineConfig(), cql=cql)
    assert lazy == eager and len(lazy) > 0


def test_lazy_select_survives_checkpoint_restore(tmp_path):
    plan = compile_plan(
        SELECT_CQL, {"S": SCHEMA},
        config=EngineConfig(lazy_projection=True),
    )
    batches = make_batches(n=512, batch=64)
    job = Job(
        [plan], [BatchSource("S", SCHEMA, iter(batches[:4]))],
        batch_size=64, time_mode="processing",
    )
    job.run()
    path = str(tmp_path / "ck")
    job.save_checkpoint(path)
    plan2 = compile_plan(
        SELECT_CQL, {"S": SCHEMA},
        config=EngineConfig(lazy_projection=True),
    )
    job2 = Job(
        [plan2], [BatchSource("S", SCHEMA, iter(batches[4:]))],
        batch_size=64, time_mode="processing",
    )
    job2.restore(path)
    job2.run()
    for row in job2.results("out"):
        assert row[1] is not None and row[2] is not None


def test_ring_eviction_warns_at_drain(caplog):
    """Round-5 verdict item 9: horizon-evicted Nones in user rows must
    not be silent — the drain that surfaces them logs the miss count."""
    import logging

    plan = compile_plan(
        CQL, {"S": SCHEMA},
        config=EngineConfig(
            lazy_projection=True, lazy_ring_budget_bytes=2048
        ),
    )
    job = Job(
        [plan],
        [BatchSource("S", SCHEMA, iter(make_batches(batch=16)))],
        batch_size=16, time_mode="processing",
    )
    with caplog.at_level(
        logging.WARNING, logger="flink_siddhi_tpu.runtime.executor"
    ):
        job.run()
        rows = job.results("matches")
    rt = next(iter(job._plans.values()))
    assert rt.lazy.missed > 0, "tiny budget must evict live entries"
    assert any(None in r for r in rows)
    assert any(
        "evicted past the ring horizon" in rec.message
        for rec in caplog.records
    )
