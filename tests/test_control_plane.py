"""The dynamic query control plane (flink_siddhi_tpu/control/):
epoch-boundary admit/retire, incremental multi-query stacking, the
shape-keyed AOT executable cache, admission gating on the REST/control
path, control-in-replay epoch parity, and control-event checkpointing.

docs/control_plane.md states the contracts these tests pin."""

import json
import urllib.request

import numpy as np
import pytest

from flink_siddhi_tpu.analysis.admit import STRICT_BUDGETS
from flink_siddhi_tpu.app.service import (
    ControlQueueSource,
    QueryControlService,
)
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.control import (
    AdmissionGate,
    ControlPlane,
    ControlRejected,
    MetadataControlEvent,
    OperationControlEvent,
    control_event_from_json,
    control_event_to_json,
)
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import (
    BatchSource,
    CallbackSource,
    ControlListSource,
)
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

SCHEMA = StreamSchema(
    [
        ("id", AttributeType.INT),
        ("price", AttributeType.DOUBLE),
        ("timestamp", AttributeType.LONG),
    ]
)

# the hostile-zoo unbounded-residency query (analysis/zoo.py
# hostile_pattern_no_within): plancheck-clean, refused under the
# strict multi-tenant budgets by exactly ADM110
HOSTILE_CQL = (
    "from every s1 = S[id == 1] -> s2 = S[id == 2] "
    "select s1.price as p1, s2.price as p2 insert into out"
)


class Rec:
    def __init__(self, id, price, timestamp):
        self.id, self.price, self.timestamp = id, price, timestamp


def compiler(cql, pid):
    return compile_plan(cql, {"S": SCHEMA}, plan_id=pid)


def chain_cql(a, b, out="out"):
    return (
        f"from every s1 = S[id == {a}] -> s2 = S[id == {b}] "
        "within 60 sec "
        f"select s1.timestamp as t1, s2.timestamp as t2 "
        f"insert into {out}"
    )


def make_job(src, ctrl, **kw):
    return Job(
        [], [src], batch_size=64, time_mode="processing",
        control_sources=[ctrl], plan_compiler=compiler, **kw,
    )


def feed(src, lo, hi):
    for i in range(lo, hi):
        src.emit(Rec(i % 4, float(i), 1000 + i), 1000 + i)


# -- admit / stack-join / retire-reclaim / status ---------------------------


def test_admit_stack_join_retire_reclaim_slot():
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    plane = ControlPlane(job, ctrl, gate=AdmissionGate(compiler))

    plane.admit(chain_cql(1, 2), plan_id="q1", tenant="acme")
    feed(src, 0, 8)
    job.run_cycle()
    assert job.results("out") == [(1001, 1002), (1005, 1006)]

    # second, constants-only tenant variant: joins the padded stack as
    # a data update (stack_join), not a new runtime
    plane.admit(chain_cql(2, 3), plan_id="q2")
    job.run_cycle()
    assert len(job._plans) == 1
    st = plane.status()
    assert st["counters"]["admitted"] == 2
    assert st["counters"]["stack_join"] == 1
    assert st["plans"]["q1"]["folded"]["slot"] == 0
    assert st["plans"]["q2"]["folded"]["slot"] == 1

    # retire q1: its slot goes row-inert; a later admit RECLAIMS it
    plane.retire("q1")
    n_before = len(job.results("out"))
    feed(src, 8, 16)
    job.run_cycle()
    rows = job.results("out")
    # only q2 (2 -> 3) matches land: (1010,1011), (1014,1015)
    assert rows[n_before:] == [(1010, 1011), (1014, 1015)]
    plane.admit(chain_cql(3, 0), plan_id="q3")
    job.run_cycle()
    st = plane.status()
    assert st["plans"]["q3"]["folded"]["slot"] == 0  # reclaimed
    assert st["counters"]["retired"] == 1
    assert st["counters"]["stack_join"] == 2


def test_ownership_guard_catches_off_thread_mutation():
    """The dynamic half of fstrace FST201 (docs/static_analysis.md):
    conftest flips RUNLOOP_OWNERSHIP_GUARD for this file, the first
    run_cycle stamps this thread as the run-loop owner, and a DIRECT
    Job mutation from another thread must raise OwnershipViolation —
    while the same intent routed through the control queue (the
    documented contract) applies cleanly at the next boundary."""
    import threading

    from flink_siddhi_tpu.runtime import executor as executor_mod
    from flink_siddhi_tpu.runtime.executor import OwnershipViolation

    assert executor_mod.RUNLOOP_OWNERSHIP_GUARD  # conftest lane flip
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    plane = ControlPlane(job, ctrl, gate=AdmissionGate(compiler))
    plane.admit(chain_cql(1, 2), plan_id="q1")
    feed(src, 0, 8)
    job.run_cycle()  # stamps the run-loop owner = this thread
    assert job.results("out") == [(1001, 1002), (1005, 1006)]

    caught: list = []

    def rogue():
        try:
            job.set_plan_enabled("q1", False)  # bypasses the queue
        except OwnershipViolation as e:
            caught.append(e)

    t = threading.Thread(target=rogue)
    t.start()
    t.join()
    assert len(caught) == 1
    msg = str(caught[0])
    assert "owns Job state" in msg and "control event" in msg
    # the rogue write never landed: q1 still emits
    feed(src, 8, 12)
    job.run_cycle()
    assert job.results("out")[-1] == (1009, 1010)

    # the sanctioned route from the same foreign thread: push a
    # disable CONTROL EVENT (plane.set_enabled), applied by the run
    # loop at the next micro-batch boundary
    t2 = threading.Thread(
        target=plane.set_enabled, args=("q1", False)
    )
    t2.start()
    t2.join()
    feed(src, 12, 20)
    n_before = len(job.results("out"))
    job.run_cycle()
    assert len(job.results("out")) == n_before  # disabled, no new rows

    # and the owner itself keeps full mutation rights
    job.set_plan_enabled("q1", True)


def test_aot_cache_hit_on_constants_variant_readmit():
    """The acceptance criterion: after full retire drops the group
    host, re-admitting a constants-only variant re-forms it from the
    AOT executable cache — a measured cache HIT with ZERO new XLA
    lowerings, counted via the PERMANENT compile-telemetry surface
    (telemetry/compile_events.py; the lowering event fires at the
    jaxpr->MLIR stage, so a warm persistent cache cannot mask it).
    Previously this test registered a private jax.monitoring listener
    and tore down with clear_event_listeners() — the footgun the
    surface replaced. The same pin now also rides
    ``Job.metrics()["compiles"]``: the first admit records >= 1
    attributed lowering with finite duration, the cache-hit re-admit
    adds ZERO."""
    from flink_siddhi_tpu.telemetry import compile_events

    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    plane = ControlPlane(job, ctrl)

    plane.admit(chain_cql(1, 2), plan_id="q1")
    feed(src, 0, 8)
    job.run_cycle()
    job.drain_outputs()
    assert job.aot_cache.stats()["misses"] == 1
    # first admit of the shape class: the permanent surface recorded
    # its compiles — attributed to the 'dyn:' signature label, with a
    # finite lowering-duration distribution
    comp0 = job.metrics()["compiles"]
    assert comp0["total_lowerings"] >= 1
    assert comp0["total_duration_s"] > 0
    assert any(
        label.startswith("dyn:") for label in comp0["by_signature"]
    ), comp0["by_signature"]

    plane.retire("q1")
    job.run_cycle()
    assert not job._plans  # host dropped; executables stay cached

    with compile_events.watch() as w:
        plane.admit(chain_cql(2, 3), plan_id="q2")
        feed(src, 8, 16)
        job.run_cycle()
        job.drain_outputs()
    assert job.results("out")[-2:] == [(1010, 1011), (1014, 1015)]
    assert w.count == 0, (
        f"{w.count} executables lowered on a cache-hit re-admit — "
        "the AOT cache is not serving the shape class"
    )
    # the job's own accounting agrees: zero new attributed lowerings
    comp1 = job.metrics()["compiles"]
    assert comp1["total_lowerings"] == comp0["total_lowerings"]
    stats = job.aot_cache.stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    # and the cache traffic is journaled: one miss then one hit for
    # the same shape-class signature (telemetry/flightrec.py)
    hits = job.flightrec.events(kind="aotcache.hit")
    misses = job.flightrec.events(kind="aotcache.miss")
    assert len(hits) == 1 and len(misses) == 1
    assert hits[0]["signature"] == misses[0]["signature"]


def test_cache_eviction_is_bounded_and_counted():
    from flink_siddhi_tpu.control.aotcache import (
        AOTExecutableCache,
        CachedExecutables,
    )

    cache = AOTExecutableCache(max_entries=2)
    mk = lambda: CachedExecutables(*([None] * 5))  # noqa: E731
    cache.insert(("exact", "a"), mk())
    cache.insert(("exact", "b"), mk())
    assert cache.lookup(("exact", "a")) is not None  # a now MRU
    cache.insert(("exact", "c"), mk())  # evicts b (LRU)
    assert cache.lookup(("exact", "b")) is None
    assert cache.lookup(("exact", "a")) is not None
    assert cache.stats()["evictions"] == 1
    assert len(cache) == 2


# -- admission gating: REST boundary + executor apply time ------------------


def test_hostile_refused_by_rule_id_rest_and_apply_time():
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    job.admission_budgets = STRICT_BUDGETS
    gate = AdmissionGate(compiler, budgets=STRICT_BUDGETS)
    svc = QueryControlService(
        ctrl, job=job, admission=gate
    ).start()
    try:
        base = f"http://127.0.0.1:{svc.port}/api/v1"
        # REST boundary: 422 with the exact ADM rule id
        req = urllib.request.Request(
            f"{base}/queries",
            data=json.dumps({"cql": HOSTILE_CQL}).encode(),
            method="POST",
        )
        try:
            urllib.request.urlopen(req)
            raise AssertionError("hostile add returned 2xx")
        except urllib.error.HTTPError as e:
            assert e.code == 422
            body = json.loads(e.read())
            assert body["rules"] == ["ADM110"]
        # the boundary refusal is recorded too (source="service"):
        # observable from /health and per-query status even after the
        # 422 response is gone
        boundary_id = body["id"]
        assert (
            job.control_rejections[boundary_id]["source"] == "service"
        )
        with urllib.request.urlopen(
            f"{base}/queries/{boundary_id}"
        ) as resp:
            status = json.loads(resp.read())
        assert status["state"] == "rejected"
        assert status["rules"] == ["ADM110"]
        # a well-behaved add passes the same gate and applies
        req = urllib.request.Request(
            f"{base}/queries",
            data=json.dumps(
                {"cql": chain_cql(1, 2), "tenant": "acme"}
            ).encode(),
            method="POST",
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 201
            created = json.loads(resp.read())
        assert created["admission"]["admitted"] is True
        assert created["admission"]["signature"]
        feed(src, 0, 4)
        job.run_cycle()
        assert created["id"] in job.plan_ids

        # defense in depth: an event injected PAST the service (raw
        # control queue) is refused at apply time, counted, and
        # observable via /health and per-query status
        b = MetadataControlEvent.builder()
        hostile_id = b.add_execution_plan(
            HOSTILE_CQL, plan_id="hostile-1"
        )
        ctrl.push(b.build())
        job.run_cycle()
        assert hostile_id not in job.plan_ids
        rej = job.control_rejections[hostile_id]
        assert rej["rules"] == ["ADM110"]
        with urllib.request.urlopen(f"{base}/health") as resp:
            health = json.loads(resp.read())
        assert (
            health["control"]["counters"]["admission_rejected"] >= 1
        )
        assert hostile_id in health["control"]["rejections"]
        with urllib.request.urlopen(
            f"{base}/queries/{hostile_id}"
        ) as resp:
            status = json.loads(resp.read())
        assert status["state"] == "rejected"
        assert status["rules"] == ["ADM110"]
    finally:
        svc.stop()


def test_unparsable_cql_refused_not_fatal():
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    b = MetadataControlEvent.builder()
    bad_id = b.add_execution_plan("this is not siddhi ql at all")
    ctrl.push(b.build())
    feed(src, 0, 4)
    job.run_cycle()  # must not raise
    assert bad_id not in job.plan_ids
    assert job.control_rejections[bad_id]["rules"] == ["CQL000"]


def test_engine_error_on_add_is_not_reported_as_bad_cql():
    # a compiler/device failure on a dynamic add is recorded under its
    # own rule id — never as the "unparsable query" CQL000
    def broken_compiler(cql, plan_id):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of device memory")

    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = Job(
        [], [src], batch_size=64, time_mode="processing",
        control_sources=[ctrl], plan_compiler=broken_compiler,
    )
    b = MetadataControlEvent.builder()
    pid = b.add_execution_plan(chain_cql(1, 2))
    ctrl.push(b.build())
    feed(src, 0, 4)
    job.run_cycle()  # the other tenants keep running
    assert pid not in job.plan_ids
    rej = job.control_rejections[pid]
    assert rej["rules"] == ["ENG000"]
    assert "RESOURCE_EXHAUSTED" in rej["findings"][0]


def test_gate_rejects_before_event_ever_pushed():
    ctrl = ControlQueueSource()
    plane = ControlPlane(
        None, ctrl, gate=AdmissionGate(compiler, budgets=STRICT_BUDGETS)
    )
    with pytest.raises(ControlRejected) as ei:
        plane.admit(HOSTILE_CQL)
    assert ei.value.rules == ["ADM110"]
    assert ctrl.poll(16)[0] == []  # nothing reached the stream


# -- service-level sustained load (tier-1 dryrun subset; see the slow
# sweep below for the full-scale version) -----------------------------------


def _sustained_streaming(n_queries, cycles_between, events_per_cycle):
    """Admit/disable/enable/retire through the REST service while the
    load keeps flowing; returns (job, fed, per-cycle seconds)."""
    import time as _t

    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    # batch_size must cover one cycle's feed, or unpulled events linger
    # in the source and the fed==processed reconciliation lies
    job = Job(
        [], [src], batch_size=4096, time_mode="processing",
        control_sources=[ctrl], plan_compiler=compiler,
        retain_results=False,
    )
    svc = QueryControlService(
        ctrl, job=job, admission=AdmissionGate(compiler)
    ).start()
    fed = 0
    cyc = []
    try:
        base = f"http://127.0.0.1:{svc.port}/api/v1"

        def post(path, body=None):
            req = urllib.request.Request(
                f"{base}/{path}",
                data=json.dumps(body).encode() if body else None,
                method="POST",
            )
            with urllib.request.urlopen(req) as resp:
                return json.loads(resp.read())

        def run_cycles(n):
            nonlocal fed
            for _ in range(n):
                feed(src, fed, fed + events_per_cycle)
                fed += events_per_cycle
                t0 = _t.perf_counter()
                job.run_cycle()
                cyc.append(_t.perf_counter() - t0)

        ids = []
        for q in range(n_queries):
            ids.append(
                post("queries", {"cql": chain_cql(q % 4, (q + 1) % 4)})[
                    "id"
                ]
            )
            run_cycles(cycles_between)
        post(f"queries/{ids[0]}/disable")
        run_cycles(cycles_between)
        post(f"queries/{ids[0]}/enable")
        req = urllib.request.Request(
            f"{base}/queries/{ids[1]}", method="DELETE"
        )
        urllib.request.urlopen(req).read()
        run_cycles(cycles_between)
        assert set(job.plan_ids) == set(ids) - {ids[1]}
    finally:
        svc.stop()
    return job, fed, cyc


def test_service_sustained_load_zero_drops_bounded_latency():
    job, fed, cyc = _sustained_streaming(
        n_queries=6, cycles_between=3, events_per_cycle=256
    )
    # ZERO dropped events across every mutation boundary
    assert job.processed_events == fed
    assert job.shed_events == 0 and job.late_dropped == 0
    # bounded added latency: admit cycles pay compile/fold work, but
    # steady cycles between mutations must stay far under a second
    steady = sorted(cyc)[: int(len(cyc) * 0.5)]
    assert max(steady) < 1.0, steady[-5:]
    st = job.control_status()
    assert st["counters"]["admitted"] == 6
    assert st["counters"]["retired"] == 1
    assert st["counters"]["stack_join"] >= 5


@pytest.mark.slow
def test_service_sustained_load_full_sweep():
    """The O(100s)-of-queries sweep (slow lane): 24 tenants across 3
    group hosts, heavier per-cycle load, same zero-drop contract."""
    job, fed, cyc = _sustained_streaming(
        n_queries=24, cycles_between=4, events_per_cycle=2048
    )
    assert job.processed_events == fed
    assert job.shed_events == 0 and job.late_dropped == 0
    st = job.control_status()
    assert st["counters"]["admitted"] == 24
    assert st["aot_cache"]["hits"] >= 1  # hosts 2..N from the cache


# -- resident mode: control at replay-epoch boundaries ----------------------


def _mk_batches(n, start):
    ids = (np.arange(n) % 4).astype(np.int64)
    ts = (start + np.arange(n) * 1000).astype(np.int64)
    return EventBatch(
        "S", SCHEMA,
        {"id": ids, "price": np.arange(n, dtype=np.float64),
         "timestamp": ts},
        ts,
    )


def _control_timeline():
    b = MetadataControlEvent.builder()
    b.add_execution_plan(chain_cql(1, 2), plan_id="qa")
    b2 = MetadataControlEvent.builder()
    b2.add_execution_plan(chain_cql(2, 3), plan_id="qb")
    drop = MetadataControlEvent.builder()
    drop.remove_execution_plan("qa")
    return [
        (0, b.build()),
        (9_500, b2.build()),
        (15_500, OperationControlEvent.disable_query("qb")),
        (20_500, OperationControlEvent.enable_query("qb")),
        (25_500, drop.build()),
    ]


def _run_mode(mode):
    batches = [_mk_batches(8, s) for s in (1000, 9000, 17000, 25000)]
    job = Job(
        [], [BatchSource("S", SCHEMA, iter(batches))], batch_size=8,
        time_mode="event",
        control_sources=[ControlListSource(_control_timeline())],
        plan_compiler=compiler,
    )
    if mode == "resident":
        ResidentReplay(job).execute()
    else:
        job.run()
    return job


def test_resident_epoch_control_parity_with_streaming():
    """Admit / stack-join / disable / enable / retire applied at
    replay-epoch boundaries produce row-for-row the SAME output a
    streaming run applies at micro-batch boundaries — the control-in-
    replay contract (docs/control_plane.md)."""
    a = _run_mode("streaming")
    b = _run_mode("resident")
    rows_a = sorted(a.results_with_ts("out"))
    rows_b = sorted(b.results_with_ts("out"))
    assert rows_a and rows_a == rows_b
    assert a.processed_events == b.processed_events
    # the replay really went through the control plane's counters too
    st = b.control_status()
    assert st["counters"]["admitted"] == 2
    assert st["counters"]["retired"] == 1


def test_resident_live_control_queue_drains_and_completes():
    """A live (service-fed) ControlQueueSource works in resident mode:
    events already pushed apply at their epoch boundary; an empty live
    queue never holds the data watermark (its documented contract), so
    the replay drains and completes."""
    src = BatchSource("S", SCHEMA, iter([_mk_batches(8, 1000)]))
    ctrl = ControlQueueSource()
    b = MetadataControlEvent.builder()
    b.add_execution_plan(chain_cql(1, 2), plan_id="qy")
    ctrl.push(b.build(), timestamp_ms=0)
    job = Job(
        [], [src], batch_size=8, time_mode="event",
        control_sources=[ctrl], plan_compiler=compiler,
    )
    ResidentReplay(job).execute()
    assert job.plan_ids == ["qy"]
    assert job.results("out") == [(2000, 3000), (6000, 7000)]


# -- checkpoint/restore: a pending control event survives exactly once ------


def test_checkpoint_mid_admit_applies_exactly_once():
    """Kill->restore with the admit still PENDING behind the event-time
    watermark: the restored job applies it exactly once — not lost
    (the query runs) and not doubled (one slot, one runtime)."""
    def build(events_batches, control):
        return Job(
            [],
            [BatchSource("S", SCHEMA, iter(events_batches))],
            batch_size=8, time_mode="event",
            control_sources=[ControlListSource(control)],
            plan_compiler=compiler,
        )

    b = MetadataControlEvent.builder()
    b.add_execution_plan(chain_cql(1, 2), plan_id="qx")
    # the admit sits at ts 9500; the source stays OPEN (CallbackSource
    # not closed), so the watermark holds below it: at snapshot time
    # the admit is still PENDING — the mid-admit kill point
    src1 = CallbackSource("S", SCHEMA)
    job1 = Job(
        [], [src1], batch_size=8, time_mode="event",
        control_sources=[ControlListSource([(9_500, b.build())])],
        plan_compiler=compiler,
    )
    for i in range(8):
        src1.emit(Rec(i % 4, float(i), 1000 + i * 1000), 1000 + i * 1000)
    job1.run_cycle()
    assert job1.plan_ids == []  # not applied yet
    snap = job1.snapshot()
    assert snap["control_pending"], "admit was not captured pending"

    # fresh process analog: second half of the stream only (the first
    # half's rows ride the snapshot's reorder buffer), control source
    # already consumed — the event lives in the snapshot now
    job2 = build([_mk_batches(8, 9000)], [])
    job2.restore(snap)
    job2.run()
    assert job2.plan_ids == ["qx"]
    assert len(job2._plans) == 1
    # applied exactly once: matches exist and are unique
    rows = job2.results_with_ts("out")
    assert rows == sorted(set(rows)) and rows

    # and the post-apply checkpoint does NOT double-apply on restore:
    snap2 = job2.snapshot()
    job3 = build([_mk_batches(8, 17000)], [])
    job3.restore(snap2)
    job3.run()
    assert job3.plan_ids == ["qx"]
    assert len(job3._plans) == 1
    rows3 = job3.results_with_ts("out")
    assert rows3 == sorted(set(rows3))


# -- control-event wire format: new fields ----------------------------------


def test_tenant_field_json_round_trip():
    b = MetadataControlEvent.builder()
    pid = b.add_execution_plan(chain_cql(1, 2), plan_id="fixed-id")
    ev = b.build()
    ev.tenant = "acme"
    ev2 = control_event_from_json(control_event_to_json(ev))
    assert ev2.tenant == "acme"
    assert pid == "fixed-id" and ev2.added_plans == ev.added_plans

    op = OperationControlEvent.disable_query("abc")
    op.tenant = "zorg"
    op2 = control_event_from_json(control_event_to_json(op))
    assert (op2.action, op2.plan_id, op2.tenant) == (
        "disable", "abc", "zorg",
    )
    # absent tenant stays None (backward compatible with old wires)
    op3 = control_event_from_json(
        json.dumps(
            {"type": "operation", "action": "enable", "plan_id": "p"}
        )
    )
    assert op3.tenant is None
