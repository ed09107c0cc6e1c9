"""Per-tenant SLO watchdog (telemetry/slo.py) + the serving
observability surface around it: violation/recovery state transitions
with exact journal parity, multi-window burn rates, the measurement
layer's missing-data honesty, the ``/api/v1/slo`` route and compact
``/health`` slo block, the flight recorder's ``?tenant=`` filter, the
prometheus exposition's consistency under mid-scrape churn, and the
carried-verdict preclear path on the control apply (the run loop skips
the redundant deep re-analysis the service gate already ran —
observable as ``control.preclear``).

These are the deterministic unit/route versions of the serving
contracts, read off the REST plane.
"""

import json
import re
import time
import urllib.request

import pytest

from flink_siddhi_tpu.analysis.admit import STRICT_BUDGETS
from flink_siddhi_tpu.app.service import (
    ControlQueueSource,
    QueryControlService,
)
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.control import ControlPlane, MetadataControlEvent
from flink_siddhi_tpu.control.plane import AdmissionGate
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import CallbackSource
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType
from flink_siddhi_tpu.telemetry import FlightRecorder, MetricsRegistry
from flink_siddhi_tpu.telemetry.slo import SLOPolicy, SLOWatchdog

SCHEMA = StreamSchema(
    [
        ("id", AttributeType.INT),
        ("price", AttributeType.DOUBLE),
        ("timestamp", AttributeType.LONG),
    ]
)


def compiler(cql, pid):
    return compile_plan(cql, {"S": SCHEMA}, plan_id=pid)


def filter_cql(v, out="out"):
    return f"from S[id == {v}] select id, price insert into {out}"


def chain_cql(a, b):
    return (
        f"from every s1 = S[id == {a}] -> s2 = S[id == {b}] "
        "within 60 sec select s1.timestamp as t1, s2.timestamp as t2 "
        "insert into out"
    )


class Rec:
    def __init__(self, id, price, timestamp):
        self.id, self.price, self.timestamp = id, price, timestamp


def make_job(src, ctrl, **kw):
    return Job(
        [], [src], batch_size=64, time_mode="processing",
        control_sources=[ctrl], plan_compiler=compiler, **kw,
    )


# -- unit: watchdog against a stub job --------------------------------------


class _StubJob:
    """The exact surface SLOWatchdog._measure reads, no runtime."""

    def __init__(self):
        self.telemetry = MetricsRegistry()
        self.flightrec = FlightRecorder(registry=self.telemetry)
        self._plan_tenant = {}
        self._max_event_ts = None
        self._gate_wm = -(2 ** 62)
        self.late_dropped = 0
        self.shed_events = 0
        self.processed_events = 0

    def tenant_of(self, pid):
        return self._plan_tenant.get(pid, "default")


def _record_drain_ms(job, pid, ms, n=50):
    # LatencyHistogram's native unit is microseconds
    h = job.telemetry.scope("plan", pid).histogram("drain.total")
    for _ in range(n):
        h.record(int(ms * 1e3))


def test_violation_recovery_transitions_and_journal_parity():
    """Sustained breach -> one rate-collapsed journal entry whose full
    count matches the watchdog's tally; the transition back journals
    ONE discrete recovery; snapshot()['reconciled'] asserts the two
    accounts agree."""
    job = _StubJob()
    job._plan_tenant["q1"] = "t0"
    wd = SLOWatchdog(job, min_interval_s=0.0)
    wd.set_policy(SLOPolicy(tenant="t0", p99_ms=10.0, budget=0.5,
                            windows_s=(100.0,)))
    _record_drain_ms(job, "q1", ms=50.0)

    t_base = time.monotonic()
    for i in range(3):
        wd.evaluate(now=t_base + i)
    snap = wd.snapshot()
    t0 = snap["tenants"]["t0"]
    assert t0["compliant"] is False
    assert t0["breaches"] == ["p99_ms"]
    assert t0["measured"]["p99_ms"] > 10.0
    assert t0["violations"] == snap["violations_total"] == 3
    # the sustained breach occupies O(1) journal slots but counts in
    # full — and the watchdog's tally matches the journal replay
    evs = job.flightrec.events(kind="slo.violation")
    assert len(evs) == 1 and evs[0]["collapsed"] == 2
    assert snap["journal"]["violations"] == 3
    assert snap["reconciled"] is True
    assert snap["active_violations"] == 1
    assert snap["worst_burning_tenant"] == "t0"
    # violating 100% of evaluations against a 0.5 budget: burn rate 2
    assert t0["burn_rates"]["100s"] == pytest.approx(2.0)

    # raising the objective heals the tenant: one discrete recovery
    wd.set_policy(SLOPolicy(tenant="t0", p99_ms=10_000.0))
    wd.evaluate(now=t_base + 10.0)
    snap = wd.snapshot()
    assert snap["tenants"]["t0"]["compliant"] is True
    assert snap["recoveries_total"] == 1
    assert len(job.flightrec.events(kind="slo.recovered")) == 1
    assert snap["journal"]["recoveries"] == 1
    assert snap["reconciled"] is True
    assert snap["active_violations"] == 0


def test_missing_data_is_not_a_breach():
    """Objectives nothing has measured yet are OMITTED, not breached:
    no drain samples, a pre-first-event watermark, and a zero-served
    loss account all stay silent."""
    job = _StubJob()
    wd = SLOWatchdog(job, min_interval_s=0.0)
    wd.set_policy(SLOPolicy(
        tenant="t9", p99_ms=1.0, freshness_s=0.001, loss_ratio=1e-9,
    ))
    wd.evaluate(now=0.0)
    snap = wd.snapshot()
    t9 = snap["tenants"]["t9"]
    assert t9["compliant"] is True
    assert t9["measured"] == {}
    assert snap["violations_total"] == 0


def test_loss_and_freshness_objectives_measure_the_gate():
    job = _StubJob()
    job.late_dropped, job.shed_events = 5, 5
    job.processed_events = 990
    job._max_event_ts = 10_000
    job._gate_wm = 7_500
    wd = SLOWatchdog(job, min_interval_s=0.0)
    wd.set_policy(SLOPolicy(
        tenant="t0", loss_ratio=0.005, freshness_s=3.0,
    ))
    wd.evaluate(now=0.0)
    t0 = wd.snapshot()["tenants"]["t0"]
    # loss 10/1000 = 0.01 breaches the 0.005 budget; the 2.5 s
    # watermark lag stays inside the 3 s freshness objective
    assert t0["breaches"] == ["loss_ratio"]
    assert t0["measured"]["loss_ratio"] == pytest.approx(0.01)
    assert t0["measured"]["freshness_s"] == pytest.approx(2.5)


def test_burn_rates_are_per_window_fractions_over_budget():
    # 4 evaluations in the short window (2 violating), 8 in the long
    # (2 violating): short window burns 0.5/0.1 = 5x budget, long 2.5x
    history = [(float(t), t >= 6) for t in range(8)]
    rates = SLOWatchdog._burn_rates(
        history, windows_s=(3.0, 10.0), budget=0.1, now=7.0,
    )
    assert rates["3s"] == pytest.approx(5.0)
    assert rates["10s"] == pytest.approx(2.5)


def test_evaluate_rate_limit_and_policy_less_noop():
    job = _StubJob()
    wd = SLOWatchdog(job, min_interval_s=1.0)
    wd.evaluate(now=0.0)  # no policies: nothing counted
    assert wd.snapshot()["evaluations"] == 0
    wd.set_policy(SLOPolicy(tenant="t0", p99_ms=1.0))
    wd.evaluate(now=2.0)
    wd.evaluate(now=2.5)  # inside min_interval_s: dropped
    wd.evaluate(now=3.5)
    assert wd.snapshot()["evaluations"] == 2


# -- the REST surface: /api/v1/slo, /health, ?tenant= filter ----------------


def _get(base, path):
    with urllib.request.urlopen(f"{base}{path}") as resp:
        body = resp.read()
    try:
        return json.loads(body)
    except ValueError:
        return body.decode()


def test_slo_route_health_block_and_tenant_filter():
    """A live job with a breaching tenant: GET /api/v1/slo serves the
    reconciled snapshot, /health carries the compact alertable block,
    and GET /api/v1/flightrecorder?tenant= narrows the journal to one
    tenant's story."""
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    plane = ControlPlane(job, ctrl)
    plane.admit(filter_cql(1), plan_id="q1", tenant="t0")
    plane.admit(filter_cql(2), plan_id="q2", tenant="t1")
    job.slo.min_interval_s = 0.0
    job.slo.set_policy(SLOPolicy(tenant="t0", p99_ms=1e-4))  # breaches
    job.slo.set_policy(SLOPolicy(tenant="t1", p99_ms=1e9))  # never
    for cycle in range(3):
        for i in range(8):
            src.emit(Rec(1 + (i % 2), float(i), 1000 + i), 1000 + i)
        job.run_cycle()
    job.drain_outputs()
    # drain.total records at drain time: one more epoch boundary so
    # the watchdog evaluates against the recorded samples
    job.run_cycle()

    svc = QueryControlService(ctrl, job=job).start()
    try:
        base = f"http://127.0.0.1:{svc.port}/api/v1"
        slo = _get(base, "/slo")
        assert slo["policies"] == 2
        assert slo["reconciled"] is True
        assert slo["tenants"]["t0"]["compliant"] is False
        assert slo["tenants"]["t0"]["breaches"] == ["p99_ms"]
        assert slo["tenants"]["t1"]["compliant"] is True
        assert slo["violations_total"] == slo["journal"]["violations"]
        assert slo["worst_burning_tenant"] == "t0"
        # the violation entry is cross-linked into the journal
        seq = slo["tenants"]["t0"]["last_violation_seq"]
        assert isinstance(seq, int) and seq >= 1

        health = _get(base, "/health")
        blk = health["slo"]
        assert blk["policies"] == 2
        assert blk["active_violations"] == 1
        assert blk["worst_burning_tenant"] == "t0"
        assert blk["violations_total"] >= 1
        # compact means compact: no per-tenant detail rides /health
        assert "tenants" not in blk

        # ?tenant= narrows to one tenant's journal (admit + breaches);
        # entries without the label never match a set filter
        t0_evs = _get(base, "/flightrecorder?tenant=t0")["events"]
        assert t0_evs and all(e["tenant"] == "t0" for e in t0_evs)
        kinds = {e["kind"] for e in t0_evs}
        assert "control.admit" in kinds and "slo.violation" in kinds
        t1_evs = _get(base, "/flightrecorder?tenant=t1")["events"]
        assert all(e["tenant"] == "t1" for e in t1_evs)
        assert not any(e["kind"] == "slo.violation" for e in t1_evs)
        # composed with a kind filter
        both = _get(
            base, "/flightrecorder?tenant=t0&kind=slo",
        )["events"]
        assert both and all(
            e["kind"].startswith("slo") and e["tenant"] == "t0"
            for e in both
        )
    finally:
        svc.stop()


# -- prometheus exposition stays consistent mid-churn -----------------------

_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?:\{(?P<labels>[^}]*)\})?\s+(?P<value>\S+)'
)
_LABEL_RE = re.compile(r'(\w+)="([^"]*)"')


def _prom_parse(text):
    samples = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparsable exposition line: {line!r}"
        labels = dict(_LABEL_RE.findall(m.group("labels") or ""))
        samples.append((m.group("name"), labels, float(m.group("value"))))
    return samples


def test_prometheus_exposition_consistent_under_churn():
    """Scrapes interleaved with admit/disable/enable/retire mutations:
    every exposition parses, carries no duplicate (name, labelset)
    sample, keeps the job-wide processed counter monotone, and the
    tenant families follow the churn — the serving benchmark's scrape
    loop relies on exactly this."""
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    plane = ControlPlane(job, ctrl)
    plane.admit(filter_cql(1), plan_id="q1", tenant="t0")

    svc = QueryControlService(ctrl, job=job).start()
    try:
        base = f"http://127.0.0.1:{svc.port}/api/v1"

        def feed(n=8):
            for i in range(n):
                src.emit(
                    Rec(1 + (i % 3), float(i), 1000 + i), 1000 + i
                )
            job.run_cycle()
            job.drain_outputs()

        def scrape():
            samples = _prom_parse(_get(base, "/metrics/prometheus"))
            keys = [
                (n, tuple(sorted(l.items()))) for n, l, _ in samples
            ]
            assert len(keys) == len(set(keys)), (
                "duplicate sample in one exposition"
            )
            processed = [
                v for n, l, v in samples
                if n == "fst_processed_events_total"
                and "plan" not in l and "tenant" not in l
            ]
            assert len(processed) == 1
            tenants = {
                l["tenant"] for n, l, _ in samples if "tenant" in l
            }
            return processed[0], tenants

        feed()
        p0, tenants = scrape()
        assert "t0" in tenants

        # churn: admit a second tenant mid-stream, scrape between
        # every mutation
        plane.admit(filter_cql(2), plan_id="q2", tenant="t1")
        feed()
        p1, tenants = scrape()
        assert p1 >= p0 and {"t0", "t1"} <= tenants

        plane.set_enabled("q2", False)
        feed()
        p2, tenants = scrape()
        assert p2 >= p1 and "t1" in tenants  # history survives pause

        plane.set_enabled("q2", True)
        feed()
        plane.retire("q2")
        feed()
        p3, tenants = scrape()
        # a retired tenant's cumulative account must NOT vanish from
        # the exposition (counters are forever), and the job total
        # never moves backwards across any mutation
        assert p3 >= p2 and {"t0", "t1"} <= tenants
    finally:
        svc.stop()


# -- the carried-verdict preclear on the control apply ----------------------


def test_carried_verdict_preclears_deep_reanalysis():
    """An add whose event carries the service gate's PASSING verdict
    (with footprint bytes) skips the run-loop's deep eval_shape pass —
    counted as ``control.preclear`` and journaled — while a raw event
    with no carried verdict keeps the full defense-in-depth path. Both
    adds end up admitted with a footprint denominator."""
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = make_job(src, ctrl)
    job.admission_budgets = STRICT_BUDGETS  # arms the deep tier
    gate = AdmissionGate(compiler, budgets=STRICT_BUDGETS)
    plane = ControlPlane(job, ctrl, gate=gate)

    plane.admit(chain_cql(1, 2), plan_id="q1", tenant="t0")
    job.run_cycle()
    assert job.telemetry.counter_value("control.preclear") == 1
    evs = job.flightrec.events(kind="control.preclear")
    assert len(evs) == 1 and evs[0]["plan"] == "q1"
    assert evs[0]["tenant"] == "t0"
    # the footprint meter's denominator comes from the carried bytes
    assert job._plan_admitted_bytes["q1"] > 0
    assert "q1" in job.plan_ids

    # a raw control event (no gate, no carried verdict) still runs
    # the deep tier: no preclear counted, fresh prediction stamped
    b = MetadataControlEvent.builder()
    b.add_execution_plan(chain_cql(2, 3), plan_id="q2")
    ctrl.push(b.build())
    job.run_cycle()
    assert job.telemetry.counter_value("control.preclear") == 1
    assert len(job.flightrec.events(kind="control.preclear")) == 1
    assert job._plan_admitted_bytes["q2"] > 0
    assert "q2" in job.plan_ids

    # a REJECTING carried verdict is never precleared past apply time:
    # the hostile add is refused at the gate already (ControlRejected
    # surfaces before any event is pushed), so push the event shape an
    # attacker would: verdict admitted=False carried on a raw event
    b = MetadataControlEvent.builder()
    b.add_execution_plan(
        chain_cql(3, 4).replace(" within 60 sec", ""),
        admission={"admitted": False,
                   "findings": [{"rule": "ADM110", "message": "x"}]},
        plan_id="q3",
    )
    ctrl.push(b.build())
    job.run_cycle()
    assert "q3" not in job.plan_ids
    assert job.control_rejections["q3"]["source"] == "carried-verdict"
    assert job.telemetry.counter_value("control.preclear") == 1
