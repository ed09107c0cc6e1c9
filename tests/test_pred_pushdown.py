"""Wire predicate pushdown (EngineConfig.pred_pushdown): host-evaluable
predicates are computed on the ingest host with numpy and ship as ONE
packed BIT per event; their raw columns drop off the device tape.

Also covers the wire kinds the bench relies on: 'b1' (bit-packed bools)
and 'd0' (constant-cadence timestamps, zero wire bytes).
"""

import numpy as np
import pytest

from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.runtime.tape import build_wire_tape
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


def make_batches(n=2000, batch=64, seed=11, step_ms=1):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 6, n).astype(np.int32)
    prices = np.round(rng.random(n) * 100, 3)
    names = rng.integers(0, 3, n)
    ts = (1000 + step_ms * np.arange(n)).astype(np.int64)
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


def run(cql, cfg, batch=64, n=2000):
    plan = compile_plan(cql, {"S": SCHEMA}, config=cfg)
    job = Job(
        [plan],
        [BatchSource("S", SCHEMA, iter(make_batches(n=n, batch=batch)))],
        batch_size=batch, time_mode="processing",
    )
    job.run()
    return plan, job


EAGER = EngineConfig()
PUSH = EngineConfig(pred_pushdown=True)
PUSH_LAZY = EngineConfig(pred_pushdown=True, lazy_projection=True)


def test_select_pushdown_matches_eager():
    cql = "from S[id == 2] select name, price insert into out"
    plan_e, job_e = run(cql, EAGER)
    plan_p, job_p = run(cql, PUSH)
    # the predicate column drops off the wire; the mask ships instead
    assert plan_p.spec.host_preds and plan_p.spec.host_preds[0].out_key == "@p:0"
    assert "S.id" not in plan_p.spec.device_columns
    eager, push = job_e.results("out"), job_p.results("out")
    assert len(eager) == len(push) > 0
    for (ne, pe), (np_, pp) in zip(eager, push):
        assert ne == np_
        assert pp == pytest.approx(pe, rel=1e-6)


def test_select_pushdown_skipped_when_nothing_freed():
    # id is also projected (non-lazy): pushing would free nothing, so
    # the predicate stays on the device and no mask ships
    cql = "from S[id == 2] select id, name, price insert into out"
    plan_p, _ = run(cql, PUSH, n=200)
    assert plan_p.spec.host_preds == ()
    assert plan_p.spec.device_columns is None


def test_select_pushdown_plus_lazy_ships_only_bits():
    cql = "from S[id == 2] select id, name, price insert into out"
    plan, job = run(cql, PUSH_LAZY)
    # with lazy projection the pred column becomes ordinal-decodable,
    # so pushdown fires and NOTHING but the mask ships
    assert plan.spec.device_columns == ()
    assert [h.out_key for h in plan.spec.host_preds] == ["@p:0"]
    _, job_e = run(cql, EAGER)
    eager, push = job_e.results("out"), job.results("out")
    assert len(eager) == len(push) > 0
    for (ie, ne, pe), (ip, np_, pp) in zip(eager, push):
        assert (ie, ne) == (ip, np_)
        assert pp == pytest.approx(pe, rel=1e-6)


def test_chain_pushdown_matches_eager():
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] -> s3 = S[id == 3] "
        "within 5 sec "
        "select s1.timestamp as t1, s3.timestamp as t3, s3.price as p "
        "insert into m"
    )
    plan_e, job_e = run(cql, EAGER)
    plan_p, job_p = run(cql, PUSH_LAZY)
    a = plan_p.artifacts[0]
    assert a.pushed_preds == (0, 1, 2)
    assert plan_p.spec.device_columns == ()
    assert len(plan_p.spec.host_preds) == 3
    eager, push = sorted(job_e.results("m")), sorted(job_p.results("m"))
    assert len(eager) == len(push) > 0
    for (t1e, t3e, pe), (t1p, t3p, pp) in zip(eager, push):
        assert (t1e, t3e) == (t1p, t3p)
        assert pp == pytest.approx(pe, rel=1e-6)


def test_chain_pushdown_string_and_float_preds():
    cql = (
        "from every s1 = S[name == 'nm1'] -> s2 = S[price > 50.0] "
        "select s1.timestamp as t1, s2.timestamp as t2 insert into m"
    )
    _, job_e = run(cql, EAGER)
    plan_p, job_p = run(cql, PUSH_LAZY)
    assert plan_p.artifacts[0].pushed_preds == (0, 1)
    # host predicates see f64: results must still agree with the oracle
    # (the bench literals are f32-exact; here > keeps them comparable)
    assert sorted(job_e.results("m")) == sorted(job_p.results("m"))
    assert len(job_p.results("m")) > 0


def test_cross_element_filters_not_pushed():
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[price > s1.price] "
        "select s1.timestamp as t1, s2.timestamp as t2 insert into m"
    )
    plan_p, job_p = run(cql, PUSH_LAZY)
    # the cross filter must never be host-pushed (it reads captures);
    # this pattern compiles to the slot engine, which skips pushdown
    # entirely — either way no host pred may read a capture-dependent
    # filter, and results must match the eager oracle
    assert getattr(plan_p.artifacts[0], "pushed_preds", ()) == ()
    assert plan_p.spec.host_preds == ()
    _, job_e = run(cql, EAGER)
    assert sorted(job_p.results("m")) == sorted(job_e.results("m"))
    assert len(job_p.results("m")) > 0


def test_pushdown_dynamic_add_keeps_own_runtime():
    # a pushed plan cannot fold into a parametric dynamic group (its
    # tape lacks the raw columns); it must keep its own runtime
    plan = compile_plan(
        "from every s1 = S[id == 1] -> s2 = S[id == 2] "
        "select s1.timestamp as t1, s2.timestamp as t2 insert into m",
        {"S": SCHEMA}, config=PUSH_LAZY,
    )
    job = Job(
        [], [BatchSource("S", SCHEMA, iter(make_batches(n=256)))],
        batch_size=64, time_mode="processing",
    )
    job.add_plan(plan, dynamic=True)
    assert list(job._plans) == [plan.plan_id]
    job.run()
    assert len(job.results("m")) > 0


# -- wire kind unit coverage ------------------------------------------------


def _wire_for(batch_events, cfg=PUSH_LAZY, cql=None, step_ms=1):
    cql = cql or "from S[id == 2] select name, price insert into out"
    plan = compile_plan(cql, {"S": SCHEMA}, config=cfg)
    batches = make_batches(n=batch_events, batch=batch_events,
                           step_ms=step_ms)
    return plan, build_wire_tape(
        plan.spec, batches[:1], 1000, {}, capacity=None
    )[0]


def test_b1_bitpack_roundtrip():
    import jax

    plan, wire = _wire_for(8192)
    assert dict(wire.kinds)["@p:0"] == "b1"
    packed = wire.cols["@p:0"]
    assert packed.dtype == np.uint8 and packed.nbytes == 8192 // 8
    tape = jax.jit(lambda w: w.expand().cols["@p:0"])(wire)
    ids = np.concatenate(
        [b.columns["id"] for b in make_batches(n=8192, batch=8192)]
    )
    np.testing.assert_array_equal(np.asarray(tape)[:8192], ids == 2)


def test_d0_constant_cadence_ships_zero_ts_bytes():
    import jax

    plan, wire = _wire_for(8192, step_ms=7)
    assert wire.ts_kind == "d0"
    assert wire.ts.size == 0
    assert wire.capacity == 8192
    ts = np.asarray(jax.jit(lambda w: w.expand().ts)(wire))
    assert ts[0] == 0 and ts[1] == 7  # rebased to epoch, step 7
    assert ts[8191] == 7 * 8191


def test_d0_degrades_to_deltas_on_irregular_batch():
    plan = compile_plan(
        "from S[id == 2] select name, price insert into out",
        {"S": SCHEMA}, config=PUSH_LAZY,
    )
    sticky = {}
    regular = make_batches(n=8192, batch=8192)
    build_wire_tape(plan.spec, regular[:1], 1000, sticky, capacity=8192)
    assert sticky["__ts__"] == "d0"
    # irregular cadence: widen, never narrow back
    irr = make_batches(n=8192, batch=8192)
    irr[0].columns["timestamp"][5] += 3
    irr[0].timestamps[5] += 3
    build_wire_tape(plan.spec, irr[:1], 1000, sticky, capacity=8192)
    assert sticky["__ts__"] in ("d8", "d16")
    build_wire_tape(plan.spec, regular[:1], 1000, sticky, capacity=8192)
    assert sticky["__ts__"] in ("d8", "d16")


def test_small_batches_never_pick_d0():
    plan = compile_plan(
        "from S[id == 2] select name, price insert into out",
        {"S": SCHEMA}, config=PUSH_LAZY,
    )
    sticky = {}
    build_wire_tape(
        plan.spec, make_batches(n=64, batch=64)[:1], 1000, sticky,
        capacity=64,
    )
    assert sticky["__ts__"] != "d0"


@pytest.mark.parametrize("gaps, kind", [
    ((0, 1, 100), "d8"), ((0, 3, 30_000), "d16"), ((0, 5, 40_000), "i32"),
    ((0,), "d8"),
], ids=["d8", "d16", "i32", "one_tick"])
@pytest.mark.parametrize("n", [1, 2, 700], ids=["n1", "n2", "n700"])
def test_delta_stamps_expand_to_the_tapes(gaps, kind, n):
    """Irregular stamps travel as deltas in the narrowest type that
    holds the widest gap; a tape shorter than its capacity repeats its
    last stamp in the padding."""
    import jax

    plan = compile_plan(
        "from S[id == 2] select name, price insert into out",
        {"S": SCHEMA}, config=PUSH_LAZY,
    )
    rng = np.random.default_rng(n)
    b = make_batches(n=n, batch=n)[0]
    ts = 1000 + np.cumsum(rng.choice(gaps, n)).astype(np.int64)
    if n > 2:
        ts[1:3] = ts[0] + np.asarray([gaps[-1], gaps[-1]])
        ts = np.sort(ts)
    b.columns["timestamp"][:], b.timestamps[:] = ts, ts
    sticky = {}
    wire, _ = build_wire_tape(plan.spec, [b], 1000, sticky, capacity=1024)
    if n > 2:
        assert wire.ts_kind == kind and sticky["__ts__"] == kind
    got = np.asarray(jax.jit(lambda w: w.expand().ts)(wire))
    want = np.full(1024, ts[-1] - 1000)
    want[:n] = ts - 1000
    np.testing.assert_array_equal(got, want)
