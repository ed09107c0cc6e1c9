"""Per-tenant observability (PR: scoped metric attribution, the
admitted-vs-measured footprint meter, OpenMetrics exposition).

Contracts pinned here (docs/observability.md):

* **Conservation** — across a full admit / stack-join / disable /
  enable / retire control timeline, per-plan ``rows_emitted`` scopes
  sum EXACTLY to the job-level emitted total, in streaming, fused, and
  resident modes, and the per-plan split agrees across all three modes
  row-for-row.
* **Footprint meter** — for every legit zoo plan the measured device
  footprint stays within the admission-time ADM101/102 prediction; a
  deliberately under-admitted plan trips the loud
  ``footprint.overruns`` counter; the meter is metadata-only (runs
  clean under ``HOTLOOP_TRANSFER_GUARD`` inside the guarded hot loop).
* **OpenMetrics** — ``Job.openmetrics()`` / the
  ``GET /api/v1/metrics/prometheus`` route parse with a STANDALONE
  text-format checker (no client library) and carry ``plan`` and
  ``tenant`` labels on the scoped series.
* **Tenant rollup** — ``metrics()["tenants"]`` merges plan scopes per
  tenant (counters summed, histograms bucket-merged), and AOT-cache /
  stack-join traffic is attributable per tenant.
"""

import json
import math
import re
import urllib.request

import numpy as np
import pytest

from flink_siddhi_tpu.analysis.admit import analyze_plan
from flink_siddhi_tpu.app.service import (
    ControlQueueSource,
    QueryControlService,
)
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.control import (
    ControlPlane,
    MetadataControlEvent,
    OperationControlEvent,
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
    """The PR 12 parity timeline (tests/test_control_plane.py), with
    tenants on the adds: admit qa (acme) -> stack-join qb (bobcorp) ->
    disable/enable qb -> retire qa."""
    b = MetadataControlEvent.builder()
    b.add_execution_plan(chain_cql(1, 2), plan_id="qa")
    ev_a = b.build()
    ev_a.tenant = "acme"
    b2 = MetadataControlEvent.builder()
    b2.add_execution_plan(chain_cql(2, 3), plan_id="qb")
    ev_b = b2.build()
    ev_b.tenant = "bobcorp"
    drop = MetadataControlEvent.builder()
    drop.remove_execution_plan("qa")
    return [
        (0, ev_a),
        (9_500, ev_b),
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
    if mode == "fused":
        job.fused_segment_len = 2
    if mode == "resident":
        ResidentReplay(job).execute()
    else:
        job.run()
    return job


# one timeline run per mode, shared by the conservation / rollup /
# exposition tests below (the engine work is identical to the PR 12
# parity tests, so the XLA executables are persistent-cache-warm)
_JOBS = {}


def _job_for(mode):
    if mode not in _JOBS:
        _JOBS[mode] = _run_mode(mode)
    return _JOBS[mode]


def _per_plan_rows(job):
    return {
        pid: reg.counter_value("rows_emitted")
        for pid, reg in job.telemetry.scope_map("plan").items()
        if not pid.startswith("@dyn:")
    }


def _job_total(job):
    return sum(
        n
        for sid, n in job.emitted_counts.items()
        if not sid.endswith("@late")
    )


# -- conservation across the control timeline, all three modes --------------


@pytest.mark.parametrize("mode", ["streaming", "fused", "resident"])
def test_rows_emitted_conserve_across_control_timeline(mode):
    """Per-plan emitted-row scopes sum EXACTLY to job-level emitted
    rows across admit/stack-join/disable/enable/retire — including the
    retired plan, whose scope persists. The two members share ONE
    output stream and one dynamic-group host, so this pins the
    per-slot decode attribution, not just per-stream bookkeeping."""
    job = _job_for(mode)
    per_plan = _per_plan_rows(job)
    total = _job_total(job)
    assert total > 0
    assert sum(per_plan.values()) == total, (per_plan, total)
    # both tenants' queries really contributed (qa retired mid-stream)
    assert per_plan.get("qa", 0) > 0
    assert per_plan.get("qb", 0) > 0
    # matches (pre-rate-limit) agree with rows here: no limiter thins
    scopes = job.telemetry.scope_map("plan")
    for pid, n in per_plan.items():
        assert scopes[pid].counter_value("matches") == n


@pytest.mark.parametrize("mode", ["fused", "resident"])
def test_per_plan_attribution_parity_with_streaming(mode):
    """The per-plan split itself (not only the sum) is identical in
    all three modes — the control-in-replay / fused-boundary row
    parity of PR 12, now holding per ATTRIBUTED plan."""
    assert _per_plan_rows(_job_for(mode)) == _per_plan_rows(
        _job_for("streaming")
    )


def test_tenant_rollup_merges_plan_scopes():
    job = _job_for("streaming")
    m = job.metrics()
    tenants = m["tenants"]
    assert tenants["acme"]["plans"] == ["qa"]
    assert tenants["bobcorp"]["plans"] == ["qb"]
    per_plan = _per_plan_rows(job)
    assert tenants["acme"]["rows_emitted"] == per_plan["qa"]
    assert tenants["bobcorp"]["rows_emitted"] == per_plan["qb"]
    # rollup conservation: tenant sums cover the whole job total
    assert (
        sum(t["rows_emitted"] for t in tenants.values())
        == _job_total(job)
    )
    # drain histograms merged bucket-exactly: counts add up
    assert tenants["acme"]["drain"]["count"] >= 1
    # plans carry their tenant in the live listing too
    assert m["plans"]["qb"]["tenant"] == "bobcorp"


def test_tenant_cache_and_stack_attribution():
    """A tenant's AOT-cache traffic and stack-joins land in ITS scope:
    acme's first admit is the compile (cache_miss), bobcorp's
    constants-only variant is a pure data update (stack_join, no cache
    traffic)."""
    src = CallbackSource("S", SCHEMA)
    ctrl = ControlQueueSource()
    job = Job(
        [], [src], batch_size=64, time_mode="processing",
        control_sources=[ctrl], plan_compiler=compiler,
    )
    plane = ControlPlane(job, ctrl)
    plane.admit(chain_cql(1, 2), plan_id="c1", tenant="acme")
    plane.admit(chain_cql(2, 3), plan_id="c2", tenant="bobcorp")
    for i in range(8):
        src.emit(Rec(i % 4, float(i), 1000 + i), 1000 + i)
    job.run_cycle()
    job.run_cycle()
    t = job.metrics()["tenants"]
    assert t["acme"]["cache_misses"] == 1
    assert t["acme"]["stack_joins"] == 0
    assert t["bobcorp"]["stack_joins"] == 1
    assert t["bobcorp"]["cache_misses"] == 0
    # the scoped counters also surface in the registry snapshot
    scopes = job.telemetry.snapshot()["scopes"]["tenant"]
    assert scopes["acme"]["counters"]["control.cache_miss"] == 1
    assert scopes["bobcorp"]["counters"]["control.stack_join"] == 1


def test_query_listing_one_poll_shows_fleet():
    job = _job_for("streaming")
    listing = {q["id"]: q for q in job.query_listing()}
    # qa was retired: only qb remains live, with tenant + fold info
    assert "qa" not in listing
    qb = listing["qb"]
    assert qb["tenant"] == "bobcorp"
    assert qb["enabled"] is True
    assert qb["folded"]["host"].startswith("@dyn:")
    assert isinstance(qb["folded"]["slot"], int)


# -- conservation UNDER SUBPLAN SHARING (PR 20 satellite) --------------------
#
# Two structurally-distinct tenants ride ONE shared @shr: prefix host
# across an admit / retire / re-admit timeline. The PR 14 gate must
# hold EXACTLY: the host is measured-only bookkeeping, every emitted
# row is attributed to a member tenant, in all three modes.

_SHR_A = "from S[price > 2.0][id == 1] select id, price insert into oa"
_SHR_B = ("from S[price > 2.0]#window.lengthBatch(2) "
          "select sum(price) as tot insert into ob")


def _share_timeline():
    def add(pid, cql, t, tenant):
        b = MetadataControlEvent.builder()
        b.add_execution_plan(cql, plan_id=pid)
        ev = b.build()
        ev.tenant = tenant
        return (t, ev)

    def drop(pid, t):
        b = MetadataControlEvent.builder()
        b.remove_execution_plan(pid)
        return (t, b.build())

    # sa+sb share a host; sa retires (host survives on sb), then a
    # re-admit sa2 rejoins the still-live host — the slot-reclaim path
    return [
        add("sa", _SHR_A, 0, "acme"),
        add("sb", _SHR_B, 100, "bobcorp"),
        drop("sa", 9_500),
        add("sa2", _SHR_A, 17_500, "acme"),
    ]


def _run_share_mode(mode):
    batches = [_mk_batches(8, s) for s in (1000, 9000, 17000, 25000)]
    job = Job(
        [], [BatchSource("S", SCHEMA, iter(batches))], batch_size=8,
        time_mode="event",
        control_sources=[ControlListSource(_share_timeline())],
        plan_compiler=compiler,
    )
    job.share_subplans = True
    if mode == "fused":
        job.fused_segment_len = 2
    if mode == "resident":
        from flink_siddhi_tpu.runtime.replay import ResidentReplay

        ResidentReplay(job).execute()
    else:
        job.run()
    return job


_SHARE_JOBS = {}


def _share_job_for(mode):
    if mode not in _SHARE_JOBS:
        _SHARE_JOBS[mode] = _run_share_mode(mode)
    return _SHARE_JOBS[mode]


def _per_plan_rows_shared(job):
    """Per-plan scopes excluding BOTH host kinds (@dyn: groups and
    @shr: prefix hosts) — only tenant-attributed scopes may count."""
    return {
        pid: reg.counter_value("rows_emitted")
        for pid, reg in job.telemetry.scope_map("plan").items()
        if not pid.startswith(("@dyn:", "@shr:"))
    }


@pytest.mark.parametrize("mode", ["streaming", "fused", "resident"])
def test_rows_conserve_under_subplan_sharing(mode):
    job = _share_job_for(mode)
    # the share really formed, and survived sa's retire on refcount
    assert job.control_status()["counters"]["subplan_share"] == 3
    per_plan = _per_plan_rows_shared(job)
    total = _job_total(job)
    assert total > 0
    assert sum(per_plan.values()) == total, (per_plan, total)
    # every phase of the timeline really contributed rows
    assert per_plan.get("sa", 0) > 0      # pre-retire
    assert per_plan.get("sb", 0) > 0      # rides the host throughout
    assert per_plan.get("sa2", 0) > 0     # post-readmit
    # and no @shr: scope leaked rows_emitted attribution
    assert all(
        reg.counter_value("rows_emitted") == 0
        for pid, reg in job.telemetry.scope_map("plan").items()
        if pid.startswith("@shr:")
    )


@pytest.mark.parametrize("mode", ["fused", "resident"])
def test_shared_attribution_parity_with_streaming(mode):
    assert _per_plan_rows_shared(
        _share_job_for(mode)
    ) == _per_plan_rows_shared(_share_job_for("streaming"))


def test_shared_tenant_rollup_conserves():
    """The tenant rollup covers the whole job total with the @shr host
    mapped onto its members (tenant 'shared' never owns rows)."""
    job = _share_job_for("streaming")
    tenants = job.metrics()["tenants"]
    assert (
        sum(t["rows_emitted"] for t in tenants.values())
        == _job_total(job)
    )
    assert sorted(tenants["acme"]["plans"]) == ["sa", "sa2"]
    assert tenants["bobcorp"]["plans"] == ["sb"]
    assert tenants.get("shared", {}).get("rows_emitted", 0) == 0


# -- the admitted-vs-measured footprint meter --------------------------------


def _meter_job(plan, admitted=None):
    job = Job([plan], [], batch_size=64)
    if admitted is not None:
        job.set_admitted_footprint(plan.plan_id, admitted)
    job.drain_outputs()  # the meter polls at drain boundaries
    return job


def test_footprint_measured_within_admitted_for_legit_zoo():
    """Every legit zoo plan's LIVE device bytes stay within the
    admission analyzer's worst-case prediction (the soundness
    direction ADM101 promises), and none trips the overrun counter."""
    from flink_siddhi_tpu.analysis.zoo import compile_zoo

    for name, plan in compile_zoo():
        report = analyze_plan(plan, deep=True)
        assert report.state_bytes is not None, name
        admitted = int(report.state_bytes + report.acc_bytes)
        job = _meter_job(plan, admitted)
        fp = job.footprint_status()[plan.plan_id]
        assert 0 < fp["measured_bytes"] <= admitted, (name, fp)
        assert fp["utilization"] <= 1.0 + 1e-9, (name, fp)
        assert (
            job.telemetry.counter_value("footprint.overruns") == 0
        ), name


def test_under_admitted_plan_trips_overrun_counter():
    plan = compiler(chain_cql(1, 2), "tiny")
    job = _meter_job(plan, admitted=1024)  # deliberately under-admitted
    fp = job.footprint_status()["tiny"]
    assert fp["utilization"] > 1.0
    assert job.telemetry.counter_value("footprint.overruns") >= 1
    sc = job.telemetry.scope_map("plan")["tiny"]
    assert sc.counter_value("footprint.overruns") >= 1


def test_footprint_meter_clean_under_transfer_guard(monkeypatch):
    """The meter reads leaf nbytes (aval metadata) only: polling it at
    drain boundaries inside the guarded hot loop must raise no
    transfer-guard violation and no overrun for a correctly-admitted
    plan."""
    from flink_siddhi_tpu.runtime import executor as _executor

    plan = compiler(chain_cql(1, 2), "guarded")
    report = analyze_plan(plan, deep=True)
    src = BatchSource(
        "S", SCHEMA,
        iter([_mk_batches(8, 1000), _mk_batches(8, 17000)]),
    )
    job = Job([plan], [src], batch_size=8, time_mode="event")
    job.set_admitted_footprint(
        "guarded", int(report.state_bytes + report.acc_bytes)
    )
    job.drain_interval_ms = 0.0  # meter polls on every cycle's drain
    monkeypatch.setattr(_executor, "HOTLOOP_TRANSFER_GUARD", True)
    job.run()
    fp = job.footprint_status()["guarded"]
    assert fp["measured_bytes"] > 0
    assert job.telemetry.counter_value("footprint.overruns") == 0
    assert len(job.results("out")) > 0  # the run really computed


# -- OpenMetrics exposition ---------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(\{[^{}]*\})?"
    r" (-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?)$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
_VALID_TYPES = ("counter", "gauge", "summary", "histogram", "untyped")


def check_prometheus_text(text):
    """Standalone Prometheus text-format (0.0.4) checker — no client
    dependency. Every line must be blank, a comment, or a parsable
    ``name{labels} value`` sample; every sample's family must have
    exactly one TYPE declared before its samples; counter values
    non-negative; all values finite. Returns (n_samples, types)."""
    types = {}
    n_samples = 0
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4, f"line {ln}: malformed TYPE"
            name, mtype = parts[2], parts[3]
            assert mtype in _VALID_TYPES, f"line {ln}: {mtype!r}"
            assert name not in types, (
                f"line {ln}: duplicate TYPE for {name}"
            )
            types[name] = mtype
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"line {ln}: unparsable sample {line!r}"
        name, labels, value = m.group(1), m.group(2), m.group(3)
        family = name
        for suffix in ("_count", "_sum"):
            base = name[: -len(suffix)] if name.endswith(suffix) else None
            if base and base in types:
                family = base
        assert family in types, (
            f"line {ln}: sample {name} has no TYPE declaration"
        )
        v = float(value)
        assert math.isfinite(v), f"line {ln}: non-finite {value}"
        if types[family] == "counter":
            assert v >= 0, f"line {ln}: negative counter"
        if labels:
            body = labels[1:-1]
            pairs = _LABEL_RE.findall(body)
            rebuilt = ",".join(f'{k}="{v2}"' for k, v2 in pairs)
            assert rebuilt == body, (
                f"line {ln}: malformed labels {labels!r}"
            )
        n_samples += 1
    return n_samples, types


def test_openmetrics_renders_and_parses_with_scoped_labels():
    job = _job_for("streaming")
    text = job.openmetrics()
    n_samples, types = check_prometheus_text(text)
    assert n_samples > 20
    # scoped series carry plan AND tenant labels
    assert re.search(
        r'fst_rows_emitted_total\{plan="qa",tenant="acme"\} \d+', text
    ), text[:2000]
    assert re.search(
        r'fst_rows_emitted_total\{plan="qb",tenant="bobcorp"\} \d+',
        text,
    )
    # histogram summaries render in seconds with quantile labels
    assert 'quantile="0.99"' in text
    assert types.get("fst_drain_total_seconds") == "summary"
    # the pre-merged tenant rollup series are present
    assert 'fst_tenant_rows_emitted_total{tenant="acme"}' in text
    # scoped sample values agree with the scoped counters they render
    per_plan = _per_plan_rows(job)
    m = re.search(
        r'fst_rows_emitted_total\{plan="qb",tenant="bobcorp"\} (\d+)',
        text,
    )
    assert int(m.group(1)) == per_plan["qb"]


def test_prometheus_route_serves_text_format():
    job = _job_for("streaming")
    svc = QueryControlService(ControlQueueSource(), job=job).start()
    try:
        base = f"http://127.0.0.1:{svc.port}/api/v1"
        with urllib.request.urlopen(
            f"{base}/metrics/prometheus"
        ) as resp:
            assert resp.status == 200
            ctype = resp.headers.get("Content-Type", "")
            assert ctype.startswith("text/plain")
            text = resp.read().decode("utf-8")
        n_samples, _ = check_prometheus_text(text)
        assert n_samples > 0
        assert 'plan="qb"' in text and 'tenant="bobcorp"' in text
        # the richer per-query status rides the same service: live
        # scoped metrics + tenant in one GET
        with urllib.request.urlopen(f"{base}/queries/qb") as resp:
            q = json.loads(resp.read())
        assert q["tenant"] == "bobcorp"
        assert q["metrics"]["counters"]["rows_emitted"] > 0
        assert "host_footprint" in q["metrics"]
        # and the fleet listing is one poll
        with urllib.request.urlopen(f"{base}/queries") as resp:
            listing = json.loads(resp.read())["queries"]
        assert listing and all(
            {"id", "tenant", "enabled", "folded"} <= set(q2)
            for q2 in listing
        )
    finally:
        svc.stop()


def test_build_info_gauge_present_and_parses():
    """Satellite (ISSUE 15): the exposition carries the standard
    *_info gauge — package version, jax version, backend as labels,
    value 1 — and the whole document still parses
    under the standalone text-format checker."""
    import jax

    import flink_siddhi_tpu as pkg

    job = _job_for("streaming")
    text = job.openmetrics()
    n_samples, types = check_prometheus_text(text)
    assert n_samples > 0
    assert types.get("fst_build_info") == "gauge"
    m = re.search(r"^fst_build_info\{([^}]*)\} 1$", text, re.M)
    assert m, "fst_build_info sample missing"
    labels = dict(_LABEL_RE.findall(m.group(1)))
    assert labels["package_version"] == pkg.__version__
    assert labels["jax_version"] == jax.__version__
    assert labels["backend"] == "cpu"


def test_checker_rejects_malformed_text():
    """The checker itself must actually check (a checker that accepts
    anything proves nothing)."""
    with pytest.raises(AssertionError):
        check_prometheus_text("fst_x_total 1\n")  # sample w/o TYPE
    with pytest.raises(AssertionError):
        check_prometheus_text(
            "# TYPE fst_x_total counter\nfst_x_total oops\n"
        )
    with pytest.raises(AssertionError):
        check_prometheus_text(
            "# TYPE fst_x gauge\nfst_x{bad-label=\"v\"} 1\n"
        )
