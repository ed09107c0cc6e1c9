"""scripts/check_bench_schema.py in the tier-1 lane: the BENCH JSON
schema gate (stage_breakdown present and attributing >= 95% of elapsed
wall-clock; schema v3: all three execution modes present, each with a
finite out-of-process prober p99 next to the telemetry p99) validates
synthetic documents, the repo's real BENCH_*.json harvest files, AND a
live ``bench.py --dryrun`` — the dryrun must stay schema-complete:
three modes + a real prober child process, under the tier-1 timeout."""

import importlib.util
import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checker():
    spec = importlib.util.spec_from_file_location(
        "check_bench_schema",
        os.path.join(REPO, "scripts", "check_bench_schema.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CHECK = _checker()


def _v2_doc(coverage=0.97, elapsed=10.0, extra_stages=None):
    stages = {
        "plan_compile": 0.5,
        "stage.compile": elapsed * coverage - 1.0,
        "replay.dispatch": 0.3,
        "drain": 0.1,
        "flush": 0.1,
        "nested.sink": 0.05,  # drill-down: excluded from the sum
    }
    if extra_stages:
        stages.update(extra_stages)
    top = CHECK._stage_names()
    attributed = sum(v for k, v in stages.items() if k in top)
    return {
        "metric": "events/sec (headline, 1000 events)",
        "value": 1234.5,
        "unit": "events/sec",
        "vs_baseline": 2.0,
        "schema_version": 2,
        "stage_breakdown": {
            "telemetry": "on",
            "window": "build_job..final_flush",
            "elapsed_s": elapsed,
            "attributed_s": round(attributed, 3),
            "coverage": round(attributed / elapsed, 4),
            "stages": stages,
        },
    }


def test_valid_v2_doc_passes():
    errors = []
    CHECK.validate_doc(_v2_doc(), errors, "doc")
    assert errors == []


def test_v2_without_stage_breakdown_fails():
    doc = _v2_doc()
    del doc["stage_breakdown"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("stage_breakdown" in e for e in errors)


def test_low_coverage_fails():
    doc = _v2_doc(coverage=0.80)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("unattributed off-clock" in e for e in errors)


def test_declared_coverage_must_match_stages():
    doc = _v2_doc()
    doc["stage_breakdown"]["coverage"] = 0.99  # lies about the stages
    doc["stage_breakdown"]["stages"]["stage.compile"] = 1.0
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors


def test_unknown_stage_names_fail():
    doc = _v2_doc(extra_stages={"mystery_stage": 1.0})
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("unknown stage names" in e for e in errors)


def test_telemetry_off_run_is_exempt():
    doc = _v2_doc()
    doc["stage_breakdown"] = {"telemetry": "off"}
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []


def test_legacy_doc_passes_without_stages():
    doc = {
        "metric": "events/sec (headline, 10000000 events)",
        "value": 16881096.6,
        "unit": "events/sec",
    }
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []
    errors = []
    CHECK.validate_doc(doc, errors, "doc", require_stages=True)
    assert errors  # unless the caller demands the new contract


# -- schema v3: multi-mode + out-of-process prober contract ---------------


def _v3_latency(**over):
    lat = {
        "telemetry_p50_ms": 60.0,
        "telemetry_p99_ms": 95.0,
        "telemetry_source": "trace_histogram (paced latency job)",
        "prober_p50_ms": 76.0,
        "prober_p99_ms": 122.0,
        "prober_pid": 4242,
        "prober_parent_pid": 4241,
        "prober_n_sent": 120,
        "prober_n_received": 119,
        "prober_lost": 1,
        "prober_clock": "child-monotonic",
        "prober_path": "paced-socket-ingest",
        "discrepancy_ratio": 1.284,
    }
    lat.update(over)
    return lat


def _v3_doc(**over):
    base = _v2_doc()
    sb = base["stage_breakdown"]
    modes = {}
    for name in ("resident", "streaming", "sink"):
        modes[name] = {
            "events": 200_000,
            "elapsed_s": 1.0,
            "events_per_sec": 200_000.0,
            "vs_baseline": 0.4,
            "stage_breakdown": json.loads(json.dumps(sb)),
            "latency": _v3_latency(),
        }
    base["schema_version"] = 3
    base["modes"] = modes
    base.update(over)
    return base


def test_valid_v3_doc_passes():
    errors = []
    CHECK.validate_doc(_v3_doc(), errors, "doc")
    assert errors == []


def test_v3_requires_all_three_modes():
    doc = _v3_doc()
    del doc["modes"]["streaming"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("modes.streaming missing" in e for e in errors)


def test_v3_partial_subset_fails():
    doc = _v3_doc(partial=True)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("partial" in e for e in errors)


def test_v3_missing_or_nonfinite_prober_fields_fail():
    for bad in (
        {"prober_p99_ms": None},
        {"prober_p99_ms": float("nan")},
        {"prober_p50_ms": None},
        {"telemetry_p99_ms": None},
        {"discrepancy_ratio": None},
        {"discrepancy_ratio": float("inf")},
    ):
        doc = _v3_doc()
        doc["modes"]["sink"]["latency"] = _v3_latency(**bad)
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert errors, bad


def test_v3_same_pid_means_no_separate_process():
    doc = _v3_doc()
    doc["modes"]["resident"]["latency"] = _v3_latency(
        prober_pid=7, prober_parent_pid=7
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("separate OS process" in e for e in errors)


def test_v3_mode_coverage_still_enforced():
    doc = _v3_doc()
    doc["modes"]["sink"]["stage_breakdown"]["stages"][
        "stage.compile"
    ] = 1.0
    doc["modes"]["sink"]["stage_breakdown"]["coverage"] = 0.5
    doc["modes"]["sink"]["stage_breakdown"]["attributed_s"] = 5.0
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "modes.sink" in e and "unattributed off-clock" in e
        for e in errors
    )


def test_v3_telemetry_off_exempts_internal_half_only():
    """A BENCH_TELEMETRY=0 overhead-A/B run has no in-process
    histograms, but the prober is external: its fields stay
    mandatory."""
    doc = _v3_doc()
    sec = doc["modes"]["streaming"]
    sec["stage_breakdown"] = {"telemetry": "off"}
    sec["latency"] = _v3_latency(
        telemetry_p50_ms=None,
        telemetry_p99_ms=None,
        discrepancy_ratio=None,
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []
    sec["latency"]["prober_p99_ms"] = None
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors


def test_v3_prober_contradiction_fails():
    doc = _v3_doc(
        prober_contradiction="prober p99 5000ms > 3x internal claims"
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("contradicts" in e for e in errors)


def test_v3_reports_discrepancy_ratio():
    CHECK.INFO.clear()
    errors = []
    CHECK.validate_doc(_v3_doc(), errors, "doc")
    assert errors == []
    assert any("discrepancy ratio" in n for n in CHECK.INFO)


# -- schema v4: columnar sink + tail-aware drain contract ------------------


def _v4_doc(**over):
    doc = _v3_doc()
    doc["schema_version"] = 4
    doc["modes"]["sink"].update(
        rows_materialized_ev_s=200_000.0,
        rows_emitted=4096,
        rows_per_sec=4096.0,
        columnar=True,
    )
    doc["p99_target"] = {
        "p99_ms": 120.0,
        "offered_load_events_per_sec": 1_000_000,
        "p99_le_500ms_at_1M": True,
        "p99_le_2x_prober": True,
        "prober_p99_ms": 122.0,
        "verdict": "p99_le_500ms",
    }
    doc["drain_staleness"] = {
        "p50_ms": 80.0, "p99_ms": 140.0, "count": 33,
    }
    doc.update(over)
    return doc


def test_valid_v4_doc_passes():
    errors = []
    CHECK.validate_doc(_v4_doc(), errors, "doc")
    assert errors == []


def test_v4_requires_rows_materialized_and_columnar():
    for strip in (
        "rows_materialized_ev_s", "rows_emitted", "columnar",
    ):
        doc = _v4_doc()
        del doc["modes"]["sink"][strip]
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert errors, strip
    doc = _v4_doc()
    doc["modes"]["sink"]["columnar"] = False  # row fallback: rejected
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("columnar" in e for e in errors)


def test_v4_missed_verdict_fails_loudly():
    doc = _v4_doc()
    doc["p99_target"]["verdict"] = "missed"
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("fails BOTH latency targets" in e for e in errors)
    doc = _v4_doc()
    del doc["p99_target"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("p99_target" in e for e in errors)


def test_v4_requires_finite_drain_staleness():
    doc = _v4_doc()
    del doc["drain_staleness"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("drain_staleness" in e for e in errors)
    doc = _v4_doc()
    doc["drain_staleness"]["p99_ms"] = None
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("drain_staleness.p99_ms" in e for e in errors)


def test_v3_era_docs_unaffected_by_v4_gate():
    """BENCH_r01..r05 harvests predate v4; the new requirements apply
    from schema_version 4 only."""
    errors = []
    CHECK.validate_doc(_v3_doc(), errors, "doc")
    assert errors == []


# -- schema v5: fused dispatch + streaming-vs-resident contract ------------


def _v5_fusion(**over):
    fu = {
        "segment_len": 8,
        "dispatches": 13,
        "batches": 100,
        "dispatches_per_1k_batches": 130.0,
        "h2d_overlap_frac": 0.75,
    }
    fu.update(over)
    return fu


def _v5_doc(**over):
    doc = _v4_doc()
    doc["schema_version"] = 5
    for name in ("resident", "streaming", "sink"):
        doc["modes"][name]["fusion"] = _v5_fusion()
    doc["modes"]["resident"]["fusion"].update(
        h2d_overlap_frac=0.0, prestaged=True
    )
    doc["streaming_vs_resident_ratio"] = 1.0
    doc["fusion_target"] = {
        "streaming_ev_s": 200_000.0,
        "resident_ev_s": 200_000.0,
        "basis": "best of 2 ABBA rounds",
        "rounds": 2,
        "resident_runs_s": [0.1, 0.12, 0.11, 0.1],
        "streaming_runs_s": [0.1, 0.12, 0.11, 0.1],
        "ratio": 1.0,
        "target": 0.8,
        "segment_len": 8,
        "verdict": "met",
    }
    doc.update(over)
    return doc


def test_valid_v5_doc_passes():
    errors = []
    CHECK.validate_doc(_v5_doc(), errors, "doc")
    assert errors == []


def test_v5_requires_fusion_block_per_mode():
    doc = _v5_doc()
    del doc["modes"]["streaming"]["fusion"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "modes.streaming" in e and "fusion block missing" in e
        for e in errors
    )


def test_v5_fusion_field_bounds():
    for bad in (
        {"segment_len": 0},
        {"segment_len": None},
        {"dispatches_per_1k_batches": None},
        {"dispatches_per_1k_batches": -1.0},
        {"h2d_overlap_frac": 1.5},
        {"h2d_overlap_frac": None},
    ):
        doc = _v5_doc()
        doc["modes"]["sink"]["fusion"] = _v5_fusion(**bad)
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert errors, bad
    # a fused segment that did NOT collapse dispatches is a lie
    doc = _v5_doc()
    doc["modes"]["streaming"]["fusion"] = _v5_fusion(
        segment_len=8, dispatches_per_1k_batches=1001.0
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("did not collapse" in e for e in errors)


def test_v5_requires_consistent_ratio():
    doc = _v5_doc()
    del doc["streaming_vs_resident_ratio"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("streaming_vs_resident_ratio" in e for e in errors)
    # the declared ratio must match a recompute from the mode sections
    doc = _v5_doc(streaming_vs_resident_ratio=0.5)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("recomputed" in e for e in errors)


def test_v5_fusion_target_missed_fails_loudly():
    doc = _v5_doc()
    doc["fusion_target"]["verdict"] = "missed"
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("still dispatch-bound" in e for e in errors)
    doc = _v5_doc()
    del doc["fusion_target"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("fusion_target" in e for e in errors)


def test_v5_telemetry_off_fusion_exempt():
    doc = _v5_doc()
    doc["modes"]["streaming"]["fusion"] = {
        "telemetry": "off", "segment_len": 8,
    }
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []


def test_v4_era_docs_unaffected_by_v5_gate():
    """BENCH files predating v5 carry no fusion blocks; the new
    requirements apply from schema_version 5 only."""
    errors = []
    CHECK.validate_doc(_v4_doc(), errors, "doc")
    assert errors == []


# -- schema v6: the event-time disorder contract ---------------------------

def _v6_run(skew, **over):
    run = {
        "skew_ms": skew,
        "events": 60_000,
        "events_per_sec": 45_000.0,
        "p99_ms": 3.2,
        "p50_ms": 0.4,
        "elapsed_s": 1.3,
        "injected": {
            "duplicates": 124, "late": 20,
            "idle_gaps": 2, "idle_polls": 4,
        },
        "late_dropped": 20,
        "idle_marked": 2,
        "processed_events": 60_000 + 124 - 20,
        "counts_exact": True,
    }
    run.update(over)
    return run


def _v6_doc(**over):
    doc = _v5_doc()
    doc["schema_version"] = 6
    doc["disorder"] = {
        "config": "headline",
        "late_policy": "drop",
        "watermark": "BoundedDisorderWatermark(skew)",
        "runs": [_v6_run(s) for s in (0, 1_000, 10_000)],
    }
    doc.update(over)
    return doc


def test_valid_v6_doc_passes():
    errors = []
    CHECK.validate_doc(_v6_doc(), errors, "doc")
    assert errors == []


def test_v6_requires_disorder_block():
    doc = _v6_doc()
    del doc["disorder"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("disorder block missing" in e for e in errors)


def test_v6_requires_all_three_skews():
    doc = _v6_doc()
    doc["disorder"]["runs"] = doc["disorder"]["runs"][:2]  # drop 10s
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("missing skew" in e for e in errors)


def test_v6_requires_finite_throughput_and_p99():
    for bad in (
        {"events_per_sec": None},
        {"events_per_sec": 0},
        {"p99_ms": None},
        {"p99_ms": float("nan")},
    ):
        doc = _v6_doc()
        doc["disorder"]["runs"][1] = _v6_run(1_000, **bad)
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert errors, bad


def test_v6_accounting_must_match_injected_schedule():
    # late counter drifted from the injected stragglers
    doc = _v6_doc()
    doc["disorder"]["runs"][0] = _v6_run(0, late_dropped=19)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("late account drifted" in e for e in errors)
    # idle marks drifted from the injected gaps
    doc = _v6_doc()
    doc["disorder"]["runs"][2] = _v6_run(10_000, idle_marked=1)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("idle" in e and "never marked" in e for e in errors)
    # duplicate reconciliation: processed != events + dups - late
    doc = _v6_doc()
    doc["disorder"]["runs"][0] = _v6_run(0, processed_events=60_000)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("duplicate accounting drifted" in e for e in errors)
    # a declared counts_exact=false is itself a failure
    doc = _v6_doc()
    doc["disorder"]["runs"][0] = _v6_run(0, counts_exact=False)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("counts_exact" in e for e in errors)


def test_v5_era_docs_unaffected_by_v6_gate():
    """BENCH files predating v6 carry no disorder block; the
    requirement applies from schema_version 6 only — but a disorder
    block PRESENT in an older line is still held to its contract."""
    errors = []
    CHECK.validate_doc(_v5_doc(), errors, "doc")
    assert errors == []
    doc = _v5_doc()
    doc["disorder"] = {"runs": [_v6_run(0, late_dropped=1)]}
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("late account drifted" in e for e in errors)


# -- schema v7: the dynamic-control-plane contract --------------------------


def _control_blk(**over):
    blk = {
        "concurrent_queries": 23,
        "queries_admitted": 24,
        "queries_retired": 1,
        "admission_rejected": 1,
        "hostile_refused_rule": "ADM110",
        "stack_joins": 21,
        "admit_wall_ms": 940.0,
        "admit_rate_qps": 25.5,
        "steady_state_events_per_sec": 120_000,
        "events": 104_448,
        "dropped_events": 0,
        "baseline_p99_ms": 7.0,
        "added_latency_p99_ms": 940.0,
        "cache": {"entries": 1, "hits": 2, "misses": 1,
                  "evictions": 0},
        "dryrun": True,
    }
    blk.update(over)
    return blk


def _v7_doc(**over):
    doc = _v6_doc()
    doc["schema_version"] = 7
    doc["control"] = _control_blk()
    doc.update(over)
    return doc


def test_valid_v7_doc_passes():
    errors = []
    CHECK.validate_doc(_v7_doc(), errors, "doc")
    assert errors == []


def test_v7_requires_control_block():
    doc = _v7_doc()
    del doc["control"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("control block missing" in e for e in errors)


def test_v7_admit_rate_must_be_measured():
    for bad in (None, 0, -1.0, float("inf")):
        errors = []
        CHECK.validate_doc(
            _v7_doc(control=_control_blk(admit_rate_qps=bad)),
            errors, "doc",
        )
        assert any("admit_rate_qps" in e for e in errors), bad


def test_v7_dropped_events_gated_zero():
    errors = []
    CHECK.validate_doc(
        _v7_doc(control=_control_blk(dropped_events=3)), errors, "doc"
    )
    assert any("dropped_events" in e for e in errors)


def test_v7_hostile_must_be_refused_by_rule_id():
    errors = []
    CHECK.validate_doc(
        _v7_doc(control=_control_blk(admission_rejected=0)),
        errors, "doc",
    )
    assert any("not refused" in e for e in errors)
    errors = []
    CHECK.validate_doc(
        _v7_doc(control=_control_blk(hostile_refused_rule="nope")),
        errors, "doc",
    )
    assert any("rule id" in e for e in errors)


def test_v7_cache_counters_required():
    errors = []
    blk = _control_blk()
    del blk["cache"]
    CHECK.validate_doc(_v7_doc(control=blk), errors, "doc")
    assert any("cache block missing" in e for e in errors)
    errors = []
    CHECK.validate_doc(
        _v7_doc(control=_control_blk(cache={"hits": -1, "misses": 0})),
        errors, "doc",
    )
    assert any("cache." in e for e in errors)


def test_v6_era_docs_unaffected_by_v7_gate():
    """Pre-v7 lines need no control block, but one present is held to
    its contract (same exemption shape as the disorder block)."""
    errors = []
    CHECK.validate_doc(_v6_doc(), errors, "doc")
    assert errors == []
    doc = _v6_doc()
    doc["control"] = _control_blk(dropped_events=7)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("dropped_events" in e for e in errors)


# -- schema v8: per-plan attribution + footprint meter ----------------------


def _attribution_blk(**over):
    blk = {
        "plans": {
            "q0": {"tenant": "tenant0", "rows_emitted": 120,
                   "matches": 120},
            "q1": {"tenant": "tenant1", "rows_emitted": 80,
                   "matches": 80},
            "flat": {"tenant": "tenant0", "rows_emitted": 300,
                     "matches": 300},
        },
        "rows_emitted_total": 500,
        "conserved": True,
        "footprint": {
            "@dyn:q0": {"measured_bytes": 134_217_728},
            "flat": {
                "measured_bytes": 100_000_000,
                "admitted_bytes": 100_663_296,
                "utilization": 0.993,
            },
        },
    }
    blk.update(over)
    return blk


def _v8_doc(**att_over):
    doc = _v7_doc()
    doc["schema_version"] = 8
    doc["control"]["attribution"] = _attribution_blk(**att_over)
    return doc


def test_valid_v8_doc_passes():
    errors = []
    CHECK.validate_doc(_v8_doc(), errors, "doc")
    assert errors == []


def test_v8_requires_attribution_block():
    doc = _v8_doc()
    del doc["control"]["attribution"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("attribution block missing" in e for e in errors)


def test_v8_rows_must_conserve():
    # scoped sum != job total: attribution dropped rows
    errors = []
    CHECK.validate_doc(
        _v8_doc(rows_emitted_total=501), errors, "doc"
    )
    assert any("do not CONSERVE" in e for e in errors)
    # a declared conserved=false is itself a failure
    errors = []
    CHECK.validate_doc(_v8_doc(conserved=False), errors, "doc")
    assert any("conserved must be true" in e for e in errors)
    # empty plans map measures nothing
    errors = []
    CHECK.validate_doc(
        _v8_doc(plans={}, rows_emitted_total=0), errors, "doc"
    )
    assert any("plans missing/empty" in e for e in errors)


def test_v8_footprint_utilization_must_be_finite_and_compared():
    # a non-finite utilization is a failed claim
    errors = []
    CHECK.validate_doc(
        _v8_doc(footprint={
            "flat": {
                "measured_bytes": 1, "admitted_bytes": 1,
                "utilization": float("inf"),
            },
        }),
        errors, "doc",
    )
    assert any("utilization" in e for e in errors)
    # measured-only everywhere = the meter never compared anything
    errors = []
    CHECK.validate_doc(
        _v8_doc(footprint={"@dyn:q0": {"measured_bytes": 7}}),
        errors, "doc",
    )
    assert any("never compared" in e for e in errors)
    # an empty meter is a missing meter
    errors = []
    CHECK.validate_doc(_v8_doc(footprint={}), errors, "doc")
    assert any("footprint map missing/empty" in e for e in errors)
    # measured bytes must be positive finite
    errors = []
    CHECK.validate_doc(
        _v8_doc(footprint={
            "x": {"measured_bytes": 0},
            "flat": {
                "measured_bytes": 1, "admitted_bytes": 2,
                "utilization": 0.5,
            },
        }),
        errors, "doc",
    )
    assert any("measured_bytes" in e for e in errors)


def test_v7_era_docs_unaffected_by_v8_gate():
    """Pre-v8 lines need no attribution block, but one present is
    held to its contract (same exemption shape as disorder/control)."""
    errors = []
    CHECK.validate_doc(_v7_doc(), errors, "doc")
    assert errors == []
    doc = _v7_doc()
    doc["control"]["attribution"] = _attribution_blk(conserved=False)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("conserved must be true" in e for e in errors)


# -- schema v9: the measured limiting-leg verdict ---------------------------


def _limiting_leg_blk(mode="streaming", **over):
    blk = {
        "mode": mode,
        "elapsed_s": 10.0,
        "coverage": 0.98,
        "legs": {
            "setup": {"seconds": 1.0, "share": 0.1,
                      "overlapped": False, "stages": ["prewarm"]},
            "host_staging": {"seconds": 2.0, "share": 0.2,
                             "overlapped": False,
                             "stages": ["ingest", "tape_build"]},
            "h2d": {"seconds": 0.3, "share": 0.03,
                    "overlapped": False,
                    "stages": ["stage.h2d_overlap"]},
            "dispatch": {"seconds": 5.0, "share": 0.5,
                         "overlapped": False, "stages": ["dispatch"]},
            "device_compute": {"seconds": 0.5, "share": 0.05,
                               "overlapped": False,
                               "stages": ["backpressure_wait"]},
            "drain_fetch": {"seconds": 1.0, "share": 0.1,
                            "overlapped": False, "stages": ["drain"]},
            "decode": {"seconds": 0.4, "share": 0.04,
                       "overlapped": True,
                       "stages": ["drain.decode (histogram mass)"]},
            "sink": {"seconds": 0.1, "share": 0.01,
                     "overlapped": True, "stages": ["sink"]},
        },
        "limiting_leg": "dispatch",
        "limiting_share": 0.5,
        "basis": "test fixture",
    }
    blk.update(over)
    return blk


def _v9_doc(**over):
    doc = _v8_doc()
    doc["schema_version"] = 9
    for name, sec in doc["modes"].items():
        sec["limiting_leg"] = _limiting_leg_blk(mode=name)
    doc.update(over)
    return doc


def test_valid_v9_doc_passes():
    errors = []
    CHECK.validate_doc(_v9_doc(), errors, "doc")
    assert errors == []


def test_v9_requires_limiting_leg_per_mode():
    doc = _v9_doc()
    del doc["modes"]["streaming"]["limiting_leg"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "modes.streaming: limiting_leg block missing" in e
        for e in errors
    )


def test_v9_named_leg_must_be_argmax():
    """A verdict contradicting its own published seconds is rejected —
    the gate re-derives the argmax, a declared name cannot lie."""
    doc = _v9_doc()
    doc["modes"]["sink"]["limiting_leg"]["limiting_leg"] = (
        "host_staging"  # dispatch measured 5.0s, host_staging 2.0s
    )
    doc["modes"]["sink"]["limiting_leg"]["limiting_share"] = 0.2
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("is not the argmax" in e for e in errors)
    # setup and the overlapped legs are never nameable, however large
    doc = _v9_doc()
    doc["modes"]["sink"]["limiting_leg"]["limiting_leg"] = "setup"
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("not a candidate leg" in e for e in errors)


def test_v9_cover_must_reach_95_percent():
    blk = _limiting_leg_blk()
    blk["legs"]["dispatch"]["seconds"] = 1.0  # cover drops to 58%
    blk["coverage"] = 0.58
    blk["limiting_leg"] = "host_staging"
    blk["limiting_share"] = 0.2
    doc = _v9_doc()
    doc["modes"]["resident"]["limiting_leg"] = blk
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("attributes only" in e for e in errors)
    # and a declared coverage that disagrees with the per-leg seconds
    blk2 = _limiting_leg_blk(coverage=0.99)
    blk2["legs"]["dispatch"]["seconds"] = 4.0
    doc = _v9_doc()
    doc["modes"]["resident"]["limiting_leg"] = blk2
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("declared coverage" in e for e in errors)


def test_v9_overlapped_legs_outside_cover():
    """decode/sink (fetch-lane) seconds must not rescue a failing
    cover: only non-overlapped legs sum into coverage."""
    blk = _limiting_leg_blk()
    blk["legs"]["dispatch"]["seconds"] = 1.0
    blk["legs"]["decode"]["seconds"] = 6.0  # overlapped: not cover
    blk["coverage"] = 0.58
    blk["limiting_leg"] = "host_staging"
    blk["limiting_share"] = 0.2
    doc = _v9_doc()
    doc["modes"]["streaming"]["limiting_leg"] = blk
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("attributes only" in e for e in errors)


def test_v9_telemetry_off_exempt():
    doc = _v9_doc()
    doc["modes"]["sink"]["stage_breakdown"] = {"telemetry": "off"}
    doc["modes"]["sink"]["limiting_leg"] = {"telemetry": "off"}
    # the latency block keeps only the external half under
    # telemetry-off (same exemption as v3)
    doc["modes"]["sink"]["latency"].pop("telemetry_p99_ms", None)
    doc["modes"]["sink"]["latency"]["discrepancy_ratio"] = None
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []


def test_v8_era_docs_unaffected_by_v9_gate():
    """Pre-v9 lines need no limiting_leg, but a present one is held
    to its contract (same exemption shape as disorder/control)."""
    errors = []
    CHECK.validate_doc(_v8_doc(), errors, "doc")
    assert errors == []
    doc = _v8_doc()
    doc["modes"]["streaming"]["limiting_leg"] = _limiting_leg_blk(
        limiting_leg="h2d", limiting_share=0.03
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("is not the argmax" in e for e in errors)


# -- optional recovery block (bench.py --fault) ----------------------------


def _recovery_block(**over):
    rec = {
        "events": 40_000,
        "crash_pulls": [2, 6],
        "kill_mid_checkpoint": True,
        "crashes": 3,
        "restarts": 3,
        "checkpoints": 3,
        "recovery_time_ms": 412.7,
        "events_replayed": 24_576,
        "rows_discarded_uncommitted": 8_192,
        "rows_emitted": 40_000,
        "duplicate_rows": 0,
        "lost_rows": 0,
        "exactly_once": True,
        "stale_tmp_swept": True,
        "elapsed_s": 9.3,
    }
    rec.update(over)
    return rec


def test_recovery_block_valid_passes():
    errors = []
    CHECK.validate_doc(_v5_doc(recovery=_recovery_block()), errors, "doc")
    assert errors == []


def test_recovery_block_absent_is_fine():
    """--fault is optional: a line without the block validates."""
    errors = []
    CHECK.validate_doc(_v4_doc(), errors, "doc")
    assert errors == []


def test_recovery_duplicates_or_losses_fail():
    for key in ("duplicate_rows", "lost_rows"):
        doc = _v4_doc(recovery=_recovery_block(**{key: 3}))
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert any(
            key in e and "exactly-once violated" in e for e in errors
        ), key


def test_recovery_time_must_be_measured():
    for bad in (None, 0, -1.0, float("nan")):
        doc = _v4_doc(
            recovery=_recovery_block(recovery_time_ms=bad)
        )
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert any("recovery_time_ms" in e for e in errors), bad


def test_recovery_requires_a_real_crash_and_clean_tmp():
    doc = _v4_doc(recovery=_recovery_block(crashes=0))
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("measures nothing" in e for e in errors)
    doc = _v4_doc(recovery=_recovery_block(stale_tmp_swept=False))
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("stale_tmp_swept" in e for e in errors)


# -- v10: transactional sub-block of the recovery block --------------------


def _txn_block(**over):
    txn = {
        "events": 8_192,
        "crash_pulls": [3],
        "kill_mid_checkpoint": True,
        "kill_mid_transaction": True,
        "crashes": 3,
        "restarts": 3,
        "recovery_time_ms": 101.4,
        "rows_emitted": 8_192,
        "read_committed_duplicates": 0,
        "read_committed_lost": 0,
        "exactly_once": True,
        "read_uncommitted_rows": 9_001,
        "aborted_rows_invisible": True,
        "elapsed_s": 4.2,
    }
    txn.update(over)
    return txn


def _v10_doc(**over):
    doc = _v9_doc()
    doc["schema_version"] = 10
    doc.update(over)
    return doc


def test_valid_v10_doc_passes():
    """v10 without --fault is fine (the block stays optional), and
    with the full recovery + transactional pair it validates."""
    errors = []
    CHECK.validate_doc(_v10_doc(), errors, "doc")
    assert errors == []
    errors = []
    CHECK.validate_doc(
        _v10_doc(
            recovery=_recovery_block(transactional=_txn_block())
        ),
        errors, "doc",
    )
    assert errors == []


def test_v10_recovery_requires_transactional_subblock():
    """From v10, a recovery block that only diffed INTERNAL results is
    an incomplete exactly-once claim — the external read-committed
    boundary must be measured."""
    doc = _v10_doc(recovery=_recovery_block())
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "transactional sub-block" in e and "read-committed" in e
        for e in errors
    )


def test_v9_era_recovery_exempt_but_present_txn_block_validated():
    """Pre-v10 lines need no transactional sub-block, but one that IS
    present is held to its contract (the disorder/control exemption
    shape)."""
    errors = []
    CHECK.validate_doc(
        _v9_doc(recovery=_recovery_block()), errors, "doc"
    )
    assert errors == []
    doc = _v9_doc(
        recovery=_recovery_block(
            transactional=_txn_block(read_committed_duplicates=2)
        )
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("read_committed_duplicates" in e for e in errors)


def test_txn_subblock_external_duplicates_or_losses_fail():
    for key in ("read_committed_duplicates", "read_committed_lost"):
        doc = _v10_doc(
            recovery=_recovery_block(
                transactional=_txn_block(**{key: 1})
            )
        )
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert any(
            key in e and "external boundary" in e for e in errors
        ), key


def test_txn_subblock_must_be_a_real_measurement():
    """recovery_time_ms must be finite-positive, the
    kill-mid-transaction must actually have fired, and the aborted
    debris must have stayed invisible — otherwise the block measured
    nothing (or worse, leaked)."""
    for bad in (None, 0, -1.0, float("nan")):
        doc = _v10_doc(
            recovery=_recovery_block(
                transactional=_txn_block(recovery_time_ms=bad)
            )
        )
        errors = []
        CHECK.validate_doc(doc, errors, "doc")
        assert any(
            "transactional" in e and "recovery_time_ms" in e
            for e in errors
        ), bad
    doc = _v10_doc(
        recovery=_recovery_block(
            transactional=_txn_block(kill_mid_transaction=False)
        )
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("kill_mid_transaction" in e for e in errors)
    doc = _v10_doc(
        recovery=_recovery_block(
            transactional=_txn_block(aborted_rows_invisible=False)
        )
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("aborted_rows_invisible" in e for e in errors)
    doc = _v10_doc(
        recovery=_recovery_block(transactional=_txn_block(crashes=0))
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "transactional" in e and "measures nothing" in e
        for e in errors
    )


# -- schema v11: the serving observatory ------------------------------------


def _serving_blk(**over):
    """A self-consistent serving block modeled on a real
    ``bench.py --serve --dryrun`` line (victim ratios, max_ratio and
    every verdict re-derivable from the numbers published next to
    them)."""
    blk = {
        "tenants": 4,
        "offered_events_per_sec": 1200.0,
        "sustained_events_per_sec": 1176.6,
        "seconds": 6.0,
        "search": {
            "mode": "fixed",
            "rates_tried": [[1200.0, True]],
            "sustained_rate_ev_s": 1200.0,
        },
        "per_tenant_p99_ms": {
            "t0": 61.2, "t1": 58.6, "t2": 69.8, "t3": 55.2,
        },
        "isolation": {
            "storm_tenant": "t0",
            "window": "storm",
            "gate_ratio": 4.0,
            "victims": {
                "t1": {"pre_ms": 49.3, "post_ms": 58.6,
                       "ratio": 1.189},
                "t2": {"pre_ms": 50.0, "post_ms": 69.8,
                       "ratio": 1.396},
                "t3": {"pre_ms": 48.0, "post_ms": 55.2,
                       "ratio": 1.15},
            },
            "max_ratio": 1.396,
            "verdict": "pass",
        },
        "slo": {
            "policies": 4,
            "violations_total": 45,
            "recoveries_total": 4,
            "journal_violations": 45,
            "journal_recoveries": 4,
            "reconciled": True,
            "active_violations": 4,
            "worst_burning_tenant": "t0",
        },
        "sustainable": {
            "lag_p90_s": 0.674,
            "lag_budget_s": 2.5,
            "lag_ok": True,
            "loss_ratio": 0.0017,
            "loss_budget": 0.005,
            "loss_ok": True,
            "probe_p99_ms": 1519.3,
            "telemetry_p99_ms": 946.2,
            "probe_tolerance": 4.0,
            "probe_slack_ms": 500.0,
            "probe_ok": True,
            "health_ok": True,
            "verdict": True,
        },
        "limiting_leg": _limiting_leg_blk(mode="serve"),
        "churn": {
            "admitted": 1, "retired": 1, "disabled": 1, "enabled": 1,
            "hostile_refused_rules": ["ADM110"],
        },
        "scrapes": {
            "count": 21, "failures": 0, "cadence_s": 0.35,
            "source": "rest",
        },
    }
    blk.update(over)
    return blk


def _v11_doc(**over):
    doc = {
        "metric": "events/sec (serving mix, 4 tenants, open-loop)",
        "value": 1176.6,
        "unit": "events/sec",
        "schema_version": 11,
        "serving": _serving_blk(),
    }
    doc.update(over)
    return doc


def test_valid_v11_serving_only_doc_passes():
    """A --serve line carries ``serving`` INSTEAD of ``modes``: the
    replay-mode contracts (stage_breakdown through the v10 recovery
    requirement) must NOT fire against it — errors == [] proves the
    early-return, not just the serving gate."""
    errors = []
    CHECK.validate_doc(_v11_doc(), errors, "doc")
    assert errors == []


def test_v11_isolation_ratios_rederived():
    # a declared victim ratio that disagrees with its own pre/post
    doc = _v11_doc()
    doc["serving"]["isolation"]["victims"]["t2"]["ratio"] = 1.05
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("!= recomputed" in e and "t2" in e for e in errors)
    # a declared max_ratio that is not the max of its victims
    doc = _v11_doc()
    doc["serving"]["isolation"]["max_ratio"] = 1.15
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("max_ratio" in e and "recomputed" in e for e in errors)


def test_v11_isolation_verdict_cannot_lie_and_fail_fails():
    # verdict "pass" contradicting a gate the numbers blow through
    doc = _v11_doc()
    doc["serving"]["isolation"]["gate_ratio"] = 1.2
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("contradicts its own numbers" in e for e in errors)
    # an HONEST fail verdict still fails the line — the serving claim
    # requires isolation to hold, not merely to be reported
    doc = _v11_doc()
    doc["serving"]["isolation"]["gate_ratio"] = 1.2
    doc["serving"]["isolation"]["verdict"] = "fail"
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "verdict 'fail'" in e and "blew victims" in e for e in errors
    )


def test_v11_slo_account_must_reconcile_with_journal():
    # watchdog counters drifting from the flight-recorder replay
    doc = _v11_doc()
    doc["serving"]["slo"]["journal_violations"] = 44
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "journal replay" in e and "drifted" in e for e in errors
    )
    doc = _v11_doc()
    doc["serving"]["slo"]["reconciled"] = False
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("reconciled must be true" in e for e in errors)


def test_v11_sustainable_verdict_rederived_from_inputs():
    # a declared lag_ok=True contradicting the published lag vs budget
    doc = _v11_doc()
    doc["serving"]["sustainable"]["lag_p90_s"] = 3.1
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "lag_ok" in e and "contradicts its own inputs" in e
        for e in errors
    )
    # verdict false = not sustained = the line's headline is a lie
    doc = _v11_doc()
    doc["serving"]["sustainable"]["verdict"] = False
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("verdict must be true" in e for e in errors)
    # missing inputs: the check cannot be re-derived, so it fails
    doc = _v11_doc()
    del doc["serving"]["sustainable"]["probe_p99_ms"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("cannot re-derive" in e and "probe_ok" in e
               for e in errors)


def test_v11_churn_really_happened_with_rule_ids():
    doc = _v11_doc()
    doc["serving"]["churn"]["admitted"] = 0
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "admitted=0" in e and "really must have happened" in e
        for e in errors
    )
    doc = _v11_doc()
    doc["serving"]["churn"]["hostile_refused_rules"] = []
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("hostile_refused_rules" in e for e in errors)
    doc = _v11_doc()
    doc["serving"]["churn"]["hostile_refused_rules"] = ["nope"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("exact rule ids" in e for e in errors)


def test_v11_requires_limiting_leg_and_rest_scrapes():
    doc = _v11_doc()
    del doc["serving"]["limiting_leg"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "limiting_leg block missing" in e and "bottleneck" in e
        for e in errors
    )
    doc = _v11_doc()
    doc["serving"]["scrapes"]["source"] = "in-process"
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("public REST surface" in e for e in errors)
    doc = _v11_doc()
    doc["serving"]["scrapes"]["count"] = 2
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("scraped series" in e for e in errors)


def test_v11_search_ledger_required():
    doc = _v11_doc()
    doc["serving"]["search"]["rates_tried"] = []
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("rates_tried" in e and "ledger" in e for e in errors)
    doc = _v11_doc()
    doc["serving"]["search"]["sustained_rate_ev_s"] = 0.0
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("sustained_rate_ev_s" in e for e in errors)


def test_v10_era_docs_unaffected_by_v11_gate():
    """Replay-mode lines need no serving block, but one attached to a
    modes-carrying line is held to its contract AND the replay
    contracts still apply (no early-return when modes is present) —
    same exemption shape as disorder/control/attribution."""
    errors = []
    CHECK.validate_doc(_v10_doc(), errors, "doc")
    assert errors == []
    doc = _v10_doc()
    doc["serving"] = _serving_blk()
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []
    doc = _v10_doc()
    doc["serving"] = _serving_blk()
    doc["serving"]["slo"]["reconciled"] = False
    del doc["modes"]["streaming"]["limiting_leg"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("reconciled must be true" in e for e in errors)
    assert any(
        "modes.streaming: limiting_leg block missing" in e
        for e in errors
    )


def test_v11_serving_line_recovery_block_still_gated():
    """The early-return exempts a --serve line from the replay
    contracts, NOT from the recovery contract: an attached recovery
    block is still validated (at v11 that includes the transactional
    sub-block requirement)."""
    doc = _v11_doc(
        recovery=_recovery_block(transactional=_txn_block())
    )
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []
    doc = _v11_doc(recovery=_recovery_block(transactional=None))
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("transactional" in e for e in errors)


def test_fault_block_live_and_gate_accepts():
    """The live --fault contract: bench._fault_recovery_block runs the
    supervised crash schedule (two pull-kills + one
    kill-mid-checkpoint) at dryrun scale and the resulting block — a
    MEASURED recovery_time_ms, replayed events, and oracle-diffed
    exactly-once counts — passes the schema gate attached to a v4
    line. Run in a SUBPROCESS, not in-process: bench's supervised
    jobs sharing this pytest process's XLA runtime corrupted later
    sharded tests' device state nondeterministically (garbage
    accumulator values); process isolation is the same boundary
    ``bench.py --fault`` itself runs behind. (A full ``bench.py
    --dryrun --fault`` subprocess line was gate-validated when this
    landed; this test keeps the block's producer and validator honest
    against each other at a fraction of a full dryrun's cost.)"""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import json, bench; "
            "print(json.dumps(bench._fault_recovery_block(True)))",
        ],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    block = json.loads(proc.stdout.splitlines()[-1])
    assert block["crashes"] >= 2  # pull kills + mid-checkpoint kill
    assert block["kill_mid_checkpoint"] is True
    assert math.isfinite(block["recovery_time_ms"])
    assert block["recovery_time_ms"] > 0
    assert block["events_replayed"] > 0
    assert block["duplicate_rows"] == 0
    assert block["lost_rows"] == 0
    assert block["exactly_once"] is True
    assert block["stale_tmp_swept"] is True
    # v10: the transactional leg rode the same producer run — its
    # exactly-once numbers are EXTERNAL (read-committed topic vs
    # oracle) and the kill-mid-transaction really fired
    txn = block["transactional"]
    assert txn["kill_mid_transaction"] is True
    assert txn["crashes"] >= 2
    assert math.isfinite(txn["recovery_time_ms"])
    assert txn["recovery_time_ms"] > 0
    assert txn["read_committed_duplicates"] == 0
    assert txn["read_committed_lost"] == 0
    assert txn["exactly_once"] is True
    assert txn["read_uncommitted_rows"] > txn["rows_emitted"]
    assert txn["aborted_rows_invisible"] is True
    errors = []
    CHECK.validate_doc(_v4_doc(recovery=block), errors, "doc")
    assert errors == []
    # and attached to a v10 line it satisfies the REQUIRED contract
    errors = []
    CHECK.validate_doc(_v10_doc(recovery=block), errors, "doc")
    assert errors == []


def test_dryrun_emits_schema_complete_v13(tmp_path):
    """The live contract: ``bench.py --dryrun`` (small events, one
    replay, short paced phase) exercises resident + streaming + sink,
    the out-of-process prober, the small-skew disorder sweep, the
    control-plane sustained-load run (with the v8 per-plan
    attribution block), AND the v9 measured limiting-leg verdict per
    mode, and its JSON line passes the schema gate — in the tier-1
    lane, under its timeout. (The --fault recovery block — which v10
    gates the transactional sub-block inside of — has its own live
    subprocess test above, and the v11 serving line has its own
    --serve --dryrun test below; this replay line stays at its
    historical cost and simply stamps the current schema version.)"""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        # the production batch shape scaled down: per-event staging
        # amortizes as it does at 10M/524k, so the gated
        # streaming_vs_resident_ratio measures dispatch overhead, not
        # tiny-batch fixed costs; ~0.4s per measured run keeps the
        # shared host's ±20ms scheduler jitter at the few-percent
        # level instead of flipping the verdict
        BENCH_EVENTS="2097152",
        BENCH_BATCH="65536",
        # 32 micro-batches -> 4 fused segments per run
        BENCH_SEGMENT="8",
        BENCH_LAT_SECONDS="1.0",
        BENCH_RUNS="3",
        # the gated ratio is the median of ABBA rounds (resident,
        # streaming, streaming, resident — linear host drift cancels
        # out of each round's quotient)
        BENCH_PAIR_ROUNDS="2",
    )
    out = tmp_path / "BENCH_dryrun.json"
    for attempt in (1, 2):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--dryrun"],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=560,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out.write_text(proc.stdout)
        errors = CHECK.validate_file(str(out))
        # ONE retry, only when the sole failure is the perf-ratio
        # verdict: the fusion target is a hardware measurement on a
        # shared 2-core host whose round quotients still spread under
        # co-tenant load even with the drift-cancelling ABBA design —
        # a second independent window distinguishes "engine regressed"
        # (fails twice) from "the box was busy" (passes clean)
        if attempt == 1 and errors and all(
            "fusion_target" in e for e in errors
        ):
            continue
        break
    assert errors == []
    doc = [
        json.loads(l)
        for l in proc.stdout.splitlines()
        if l.strip().startswith("{")
    ][-1]
    assert doc["schema_version"] == 13
    assert set(doc["modes"]) == {"resident", "streaming", "sink"}
    for name, sec in doc["modes"].items():
        lat = sec["latency"]
        # the prober demonstrably ran out of process, and its numbers
        # are real finite measurements
        assert lat["prober_pid"] != lat["prober_parent_pid"]
        assert math.isfinite(lat["prober_p99_ms"])
        assert math.isfinite(lat["telemetry_p99_ms"])
        assert math.isfinite(lat["discrepancy_ratio"])
        assert sec["stage_breakdown"]["coverage"] >= 0.95
        # the v9 additions: the LIVE limiting-leg block — coverage,
        # a named leg that is the argmax of its own published
        # numbers, and the overlapped decode/sink detail legs
        ll = sec["limiting_leg"]
        assert ll["mode"] == name
        assert ll["coverage"] >= 0.95
        cands = {
            k: v["seconds"]
            for k, v in ll["legs"].items()
            if not v["overlapped"] and k != "setup"
        }
        assert ll["limiting_leg"] == max(cands, key=cands.get)
        assert {"decode", "sink"} <= set(ll["legs"])
        assert all(
            ll["legs"][k]["overlapped"] for k in ("decode", "sink")
        )
    assert "prober_contradiction" not in doc
    # the v4 additions ride the same dryrun line: the columnar sink
    # lane really materialized rows, the latency verdict passed one of
    # the two targets, and the deadline scheduler recorded staleness
    sink = doc["modes"]["sink"]
    assert sink["columnar"] is True
    assert sink["rows_materialized_ev_s"] > 0
    assert sink["rows_emitted"] > 0
    assert doc["p99_target"]["verdict"] in (
        "p99_le_500ms", "p99_le_2x_prober",
    )
    assert math.isfinite(doc["drain_staleness"]["p99_ms"])
    # the v5 additions: fused dispatch really collapsed the streaming
    # dispatch chain, H2D uploads really overlapped in-flight compute,
    # and streaming reached the gated >= 80%-of-resident target
    for name in ("resident", "streaming", "sink"):
        fu = doc["modes"][name]["fusion"]
        assert fu["segment_len"] >= 1
        assert math.isfinite(fu["dispatches_per_1k_batches"])
    stream_fu = doc["modes"]["streaming"]["fusion"]
    assert stream_fu["segment_len"] > 1
    assert stream_fu["dispatches_per_1k_batches"] < 1000.0
    # on the 2-core CPU lane segment compute retires inside the
    # dispatch call itself, so the between-dispatch overlap fraction
    # can honestly be 0 here; the busy-window overlap proof is the
    # heavy-stack unit test (tests/test_fused_stream.py)
    assert 0.0 <= stream_fu["h2d_overlap_frac"] <= 1.0
    assert math.isfinite(doc["streaming_vs_resident_ratio"])
    assert doc["fusion_target"]["verdict"] == "met"
    # the v6 additions: the disorder sweep really ran at all three
    # skews in event-time mode with EXACT late/dup/idle accounting
    runs = {r["skew_ms"]: r for r in doc["disorder"]["runs"]}
    assert set(runs) == {0, 1_000, 10_000}
    for skew, run in runs.items():
        assert run["counts_exact"] is True, (skew, run)
        assert run["late_dropped"] == run["injected"]["late"] > 0
        assert run["idle_marked"] == run["injected"]["idle_gaps"] > 0
        assert run["events_per_sec"] > 0
        assert math.isfinite(run["p99_ms"])
    # the v7 additions: the control plane really admitted a stack of
    # tenant queries at epoch boundaries under load, refused the
    # hostile one by rule id, dropped nothing, and the AOT executable
    # cache served hosts 2..N without recompiling
    ctrl = doc["control"]
    assert ctrl["dropped_events"] == 0
    assert ctrl["concurrent_queries"] >= 8
    assert ctrl["stack_joins"] > 0
    assert ctrl["hostile_refused_rule"].startswith("ADM")
    assert ctrl["cache"]["hits"] >= 1
    assert math.isfinite(ctrl["admit_rate_qps"])
    assert ctrl["admit_rate_qps"] > 0
    # the v8 additions: per-plan scoped row counts really conserve
    # against the job total, every plan carries its tenant, and the
    # footprint meter compared at least one admission prediction to
    # live device bytes (see also the unit v8 cases above)
    att = ctrl["attribution"]
    assert att["conserved"] is True
    assert sum(
        p["rows_emitted"] for p in att["plans"].values()
    ) == att["rows_emitted_total"] > 0
    assert all("tenant" in p for p in att["plans"].values())
    assert any(
        math.isfinite(ent.get("utilization", float("nan")))
        for ent in att["footprint"].values()
    )
    # the v13 additions: the shared-vs-unshared fleet A/B really ran —
    # hosts formed, each serving >= 2 members with sub-linear compile
    # spend, attribution conserved with tenants riding shared prefixes,
    # and neither side shed load (the gate re-derives the speedup and
    # holds the dryrun fleet to its regression backstop)
    shr = doc["subplan_share"]
    assert shr["tenants"] >= 12
    assert shr["dryrun"] is True
    assert shr["shared"]["conserved"] is True
    assert shr["shared"]["subplan_shares"] >= shr["tenants"]
    assert shr["unshared"]["dropped_events"] == 0
    assert shr["shared"]["dropped_events"] == 0
    for h in shr["shared"]["hosts"].values():
        assert h["members"] >= 2
        assert h["lowerings"] < h["members"]


def test_serve_dryrun_emits_valid_serving_line(tmp_path):
    """The live --serve contract: ``bench.py --serve --dryrun`` runs
    ONE fixed-load open-loop pass of the full serving observatory —
    mixed-tenant stack over shared ingest, disorder, mid-run broker
    faults, admit/retire churn, the noisy-neighbor storm, the
    out-of-process prober, the SLO watchdog — with every verdict read
    off the public REST surface, and its serving-only JSON line
    passes the v11 schema gate in the tier-1 lane."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    out = tmp_path / "BENCH_serve_dryrun.json"
    for attempt in (1, 2):
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py"),
             "--serve", "--dryrun"],
            env=env, cwd=REPO, capture_output=True, text=True,
            timeout=560,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        out.write_text(proc.stdout)
        errors = CHECK.validate_file(str(out))
        # ONE retry, only when every failure is a serving-block
        # verdict: the isolation ratios and sustainability gates are
        # hardware measurements of tail latency on a shared 2-core
        # host — a second independent window distinguishes "the
        # observatory regressed" (fails twice) from "the box was
        # busy" (passes clean)
        if attempt == 1 and errors and all(
            ":serving" in e for e in errors
        ):
            continue
        break
    assert errors == []
    doc = [
        json.loads(l)
        for l in proc.stdout.splitlines()
        if l.strip().startswith("{")
    ][-1]
    assert doc["schema_version"] == 13
    srv = doc["serving"]
    # the headline number is the measured aggregate, sustained
    assert doc["value"] == srv["sustained_events_per_sec"] > 0
    assert srv["tenants"] >= 2
    assert srv["search"]["mode"] == "fixed"
    assert srv["search"]["rates_tried"] == [
        [srv["search"]["sustained_rate_ev_s"], True]
    ]
    # every tenant published a finite positive tail
    assert len(srv["per_tenant_p99_ms"]) == srv["tenants"]
    assert all(
        math.isfinite(v) and v > 0
        for v in srv["per_tenant_p99_ms"].values()
    )
    # the verdicts the gate re-derived really came out green
    assert srv["isolation"]["verdict"] == "pass"
    assert srv["sustainable"]["verdict"] is True
    assert srv["slo"]["reconciled"] is True
    assert srv["slo"]["policies"] >= srv["tenants"]
    # churn really happened mid-measurement, hostile refused by rule
    churn = srv["churn"]
    assert all(
        churn[k] >= 1
        for k in ("admitted", "retired", "disabled", "enabled")
    )
    assert churn["hostile_refused_rules"]
    # the mix's shared-prefix family (two structurally distinct
    # residues behind one exact bracket) was admitted AND actually
    # rode the subplan-share path under churn/faults — real coverage
    # of the share ladder rung on the serving line, no new gate
    assert srv["mix"].get("shared") == 2
    assert churn["subplan_shares"] >= 2
    # the prober ran out of process under serving load
    sus = srv["sustainable"]
    assert math.isfinite(sus["probe_p99_ms"])
    assert math.isfinite(sus["telemetry_p99_ms"])
    # the verdicts were read off the REST plane, as a series
    assert srv["scrapes"]["source"] == "rest"
    assert srv["scrapes"]["count"] >= 3
    assert srv["scrapes"]["failures"] == 0
    # the serving line names its measured bottleneck
    assert srv["limiting_leg"]["limiting_leg"] in srv[
        "limiting_leg"
    ]["legs"]


@pytest.mark.slow
def test_serve_full_binary_search_publishes_rate_ladder(tmp_path):
    """The full (non-dryrun) --serve mode: binary search on the
    open-loop offered rate. Scaled down via the BENCH_SERVE_* knobs
    so it terminates in minutes, but the search itself is real: the
    published ledger must show more than one rate tried, the mode
    must be "binary", and the sustained rate must be the highest
    rate whose pass verdict was true."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        # dryrun-scale passes: the disorder schedule needs rate *
        # seconds events to span several 2048-event chunks so its
        # stragglers have room to release before the stream ends
        BENCH_SERVE_RATE="1200",
        BENCH_SERVE_SECONDS="6.0",
        BENCH_SERVE_PASSES="3",
        BENCH_SERVE_TENANTS="4",
    )
    out = tmp_path / "BENCH_serve.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--serve"],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out.write_text(proc.stdout)
    assert CHECK.validate_file(str(out)) == []
    doc = [
        json.loads(l)
        for l in proc.stdout.splitlines()
        if l.strip().startswith("{")
    ][-1]
    search = doc["serving"]["search"]
    assert search["mode"] == "binary"
    assert len(search["rates_tried"]) > 1
    passed = [r for r, ok in search["rates_tried"] if ok]
    assert passed, search["rates_tried"]
    assert search["sustained_rate_ev_s"] == max(passed)


def test_wrapper_format_extraction(tmp_path):
    inner = json.dumps(_v2_doc())
    wrapper = json.dumps(
        {"n": 6, "cmd": "python bench.py", "rc": 0,
         "tail": "WARNING: noise\n" + inner + "\n"}
    )
    p = tmp_path / "BENCH_x.json"
    p.write_text(wrapper)
    assert CHECK.validate_file(str(p)) == []
    # and a broken inner doc is caught through the wrapper
    bad = _v2_doc(coverage=0.5)
    p.write_text(
        json.dumps({"rc": 0, "tail": json.dumps(bad)})
    )
    assert CHECK.validate_file(str(p))
    # a wrapper whose run crashed before printing its JSON line
    # (noise-only / empty tail) must fail, not trivially validate
    p.write_text(json.dumps({"rc": 1, "tail": "Traceback ...\n"}))
    assert any(
        "no bench JSON lines" in e for e in CHECK.validate_file(str(p))
    )


# -- schema v12: the fleet block (bench.py --fleet) --------------------------


def _fleet_doc():
    """A valid fleet-only v12 line (the shape ``bench.py --fleet
    --dryrun`` prints; numbers from a real run)."""
    return {
        "metric": "cold-start to first row (warm store, 8 tenants)",
        "value": 1.65,
        "unit": "seconds",
        "schema_version": 12,
        "fleet": {
            "tenants": 8,
            "events_per_boot": 200,
            "store_namespace": "cpu-cpu-n1-jax0.4.37",
            "cold": {
                "first_row_s": 4.68, "ready_s": 0.03, "compiles": 1,
                "warm_hits": 0, "warm_misses": 2, "persists": 3,
                "store_errors": 0,
            },
            "warm": {
                "first_row_s": 1.65, "ready_s": 0.03, "compiles": 0,
                "warm_hits": 3, "warm_misses": 0, "persists": 0,
                "store_errors": 0,
            },
            "cold_to_warm_speedup": 2.84,
            "handoff": {
                "replica": "fleet-warm", "reason": "drain",
                "boundary": "final_checkpoint",
            },
            "committed": {
                "rows": 798, "epochs": 8, "duplicate_epochs": 0,
                "lost": 0,
            },
            "wall_seconds": 9.8,
        },
    }


def test_fleet_block_valid_line_passes(tmp_path):
    p = tmp_path / "BENCH_fleet.json"
    p.write_text(json.dumps(_fleet_doc()) + "\n")
    assert CHECK.validate_file(str(p)) == []


def test_fleet_line_exempt_from_replay_contracts(tmp_path):
    """A --fleet line carries ``fleet`` INSTEAD of ``modes``: the v2
    stage_breakdown .. v10 recovery-requirement contracts must not
    fire on it (same early-return shape as the serving exemption)."""
    doc = _fleet_doc()
    assert "modes" not in doc and "stage_breakdown" not in doc
    p = tmp_path / "BENCH_fleet.json"
    p.write_text(json.dumps(doc) + "\n")
    errors = CHECK.validate_file(str(p))
    assert errors == []


@pytest.mark.parametrize(
    "mutate, needle",
    [
        (lambda f: f["warm"].__setitem__("compiles", 2),
         "warm.compiles must be 0"),
        (lambda f: f["warm"].__setitem__("warm_misses", 1),
         "warm.warm_misses must be 0"),
        (lambda f: f["warm"].__setitem__("warm_hits", 0),
         "warm.warm_hits missing/<1"),
        (lambda f: f["warm"].__setitem__("first_row_s", 9.9),
         "must beat cold.first_row_s"),
        (lambda f: f["cold"].__setitem__("persists", 0),
         "cold.persists missing/<1"),
        (lambda f: f["cold"].pop("first_row_s"),
         "cold.first_row_s missing"),
        (lambda f: f.pop("warm"), "warm boot block missing"),
        (lambda f: f["committed"].__setitem__("duplicate_epochs", 1),
         "duplicate_epochs must be 0"),
        (lambda f: f["committed"].__setitem__("lost", 5),
         "committed.lost must be 0"),
        (lambda f: f["committed"].__setitem__("rows", 0),
         "committed.rows missing/<1"),
        (lambda f: f.pop("committed"), "committed block missing"),
        (lambda f: f.__setitem__("tenants", 1), "tenants missing"),
    ],
)
def test_fleet_block_rejects_broken_claims(tmp_path, mutate, needle):
    doc = _fleet_doc()
    mutate(doc["fleet"])
    p = tmp_path / "BENCH_fleet.json"
    p.write_text(json.dumps(doc) + "\n")
    errors = CHECK.validate_file(str(p))
    assert errors, "mutation should have failed the gate"
    assert any(needle in e for e in errors), errors


def test_fleet_block_validated_on_old_versions_when_present(tmp_path):
    """Pre-v12 exemption shape: an old line need not carry the block,
    but one that IS present is held to its contract regardless of the
    stamped version."""
    doc = _fleet_doc()
    doc["schema_version"] = 11
    doc["fleet"]["warm"]["compiles"] = 3
    p = tmp_path / "BENCH_fleet.json"
    p.write_text(json.dumps(doc) + "\n")
    assert any(
        "warm.compiles must be 0" in e
        for e in CHECK.validate_file(str(p))
    )


def test_fleet_dryrun_emits_valid_v12_fleet_line(tmp_path):
    """The live --fleet contract: ``bench.py --fleet --dryrun`` boots
    a replica subprocess cold behind the key-hash router, admits the
    tenant stack through the fan-out control plane, rolling-restarts
    it into a warm successor booted from the persistent store + the
    supervisor checkpoint, and the fleet-only JSON line passes the v12
    gate in the tier-1 lane: warm first-row beats cold, the warm boot
    lowered NOTHING, and the commit-log exactly-once account across
    the handoff is clean."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    out = tmp_path / "BENCH_fleet_dryrun.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"),
         "--fleet", "--dryrun"],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    out.write_text(proc.stdout)
    assert CHECK.validate_file(str(out)) == []
    doc = [
        json.loads(l)
        for l in proc.stdout.splitlines()
        if l.strip().startswith("{")
    ][-1]
    assert doc["schema_version"] == 13
    flt = doc["fleet"]
    # the headline number is the WARM boot's cold-start-to-first-row
    assert doc["value"] == flt["warm"]["first_row_s"] > 0
    assert flt["warm"]["first_row_s"] < flt["cold"]["first_row_s"]
    # the successor lowered nothing: every executable came off disk
    assert flt["warm"]["compiles"] == 0
    assert flt["warm"]["warm_hits"] >= 1
    assert flt["warm"]["warm_misses"] == 0
    assert flt["cold"]["persists"] >= 1
    # the handoff was journaled and the committed account is exact
    assert flt["handoff"]["reason"] == "drain"
    assert flt["committed"]["rows"] >= 1
    assert flt["committed"]["duplicate_epochs"] == 0
    assert flt["committed"]["lost"] == 0


# -- schema v13: the subplan_share block (cross-tenant sharing A/B) ----------


def _share_blk(**over):
    """A valid v13 ``subplan_share`` block (the shape bench.py's
    replay line carries; numbers from a real dryrun)."""
    blk = {
        "tenants": 12,
        "families": 2,
        "members_per_family": 6,
        "mix": "non-constants-only structurally-distinct suffixes",
        "unshared": {
            "events_per_sec": 100_000, "events": 196_608,
            "concurrent_plans": 12, "lowerings": 11,
            "dropped_events": 0, "stack_joins": 1,
        },
        "shared": {
            "events_per_sec": 180_000, "events": 196_608,
            "concurrent_plans": 12, "lowerings": 14,
            "dropped_events": 0,
            "hosts": {
                "@shr:aaaa0000aaaa0000": {"members": 6, "lowerings": 1},
                "@shr:bbbb1111bbbb1111": {"members": 6, "lowerings": 1},
            },
            "subplan_shares": 12,
            "conserved": True,
            "rows_emitted_total": 27_258,
        },
        "speedup": 1.8,
        "dryrun": False,
    }
    blk.update(over)
    return blk


def _v13_doc(**over):
    doc = _v10_doc()
    doc["schema_version"] = 13
    doc["subplan_share"] = _share_blk(**over)
    return doc


def test_valid_v13_doc_passes():
    errors = []
    CHECK.validate_doc(_v13_doc(), errors, "doc")
    assert errors == []


def test_v13_requires_subplan_share_block():
    doc = _v13_doc()
    del doc["subplan_share"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("subplan_share block missing" in e for e in errors)


def test_pre_v13_exempt_but_present_block_validated():
    # a v12-era replay line need not carry the block...
    doc = _v10_doc()
    doc["schema_version"] = 12
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []
    # ...but one that IS present is held to its contract
    doc["subplan_share"] = _share_blk(speedup=9.9)
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("does not re-derive" in e for e in errors)


def test_v13_speedup_must_rederive_from_sides():
    errors = []
    CHECK.validate_doc(_v13_doc(speedup=2.5), errors, "doc")
    assert any("does not re-derive" in e for e in errors)


def test_v13_sharing_must_not_lose():
    # a full-fleet line below 1.0 fails outright
    doc = _v13_doc()
    doc["subplan_share"]["unshared"]["events_per_sec"] = 200_000
    doc["subplan_share"]["speedup"] = 0.9
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("must not lose" in e for e in errors)
    # the dryrun fleet gets the 0.8 regression backstop: 0.9 passes...
    doc["subplan_share"]["dryrun"] = True
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []
    # ...but the broken-coalescing regime (<= 0.5) still fails
    doc["subplan_share"]["unshared"]["events_per_sec"] = 400_000
    doc["subplan_share"]["speedup"] = 0.45
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("must not lose" in e for e in errors)


def test_v13_shared_side_must_conserve():
    doc = _v13_doc()
    doc["subplan_share"]["shared"]["conserved"] = False
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("conserved must be true" in e for e in errors)


def test_v13_host_lowerings_must_be_sublinear():
    # one lowering per member is exactly the unshared cost: rejected
    doc = _v13_doc()
    doc["subplan_share"]["shared"]["hosts"][
        "@shr:aaaa0000aaaa0000"]["lowerings"] = 6
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("sub-linear" in e for e in errors)
    # a host nobody shares proves nothing
    doc = _v13_doc()
    doc["subplan_share"]["shared"]["hosts"][
        "@shr:aaaa0000aaaa0000"]["members"] = 1
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("shares nothing" in e for e in errors)


def test_v13_dropped_events_fail_either_side():
    doc = _v13_doc()
    doc["subplan_share"]["shared"]["dropped_events"] = 17
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("cheating" in e for e in errors)


def test_v13_nonfinite_throughput_rejected():
    doc = _v13_doc()
    doc["subplan_share"]["shared"]["events_per_sec"] = float("nan")
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "shared.events_per_sec missing/non-positive" in e
        for e in errors
    )
    doc = _v13_doc()
    del doc["subplan_share"]["unshared"]["events_per_sec"]
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any(
        "unshared.events_per_sec missing/non-positive" in e
        for e in errors
    )


def test_v13_missing_hosts_rejected():
    doc = _v13_doc()
    doc["subplan_share"]["shared"]["hosts"] = {}
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert any("hosts missing/empty" in e for e in errors)


@pytest.mark.slow
def test_subplan_share_block_live_and_gate_accepts():
    """The live producer: bench._subplan_share_block(True) runs the
    real shared-vs-unshared A/B (two families x six structurally-
    distinct members over one Job each) and the resulting block
    passes the v13 gate. Subprocess-isolated like the --fault live
    test, and slow-marked: the block also rides the main --dryrun
    line, whose live test gate-validates it in the tier-1 lane — this
    test exists to debug the producer in isolation."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [
            sys.executable, "-c",
            "import json, bench; "
            "print(json.dumps(bench._subplan_share_block(True)))",
        ],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    block = json.loads(proc.stdout.splitlines()[-1])
    assert block["tenants"] >= 12
    assert block["shared"]["conserved"] is True
    assert block["shared"]["subplan_shares"] >= block["tenants"]
    for h in block["shared"]["hosts"].values():
        assert h["members"] >= 2
        assert h["lowerings"] < h["members"]
    # attached to a v13 replay line the REQUIRED contract holds
    doc = _v10_doc()
    doc["schema_version"] = 13
    doc["subplan_share"] = block
    errors = []
    CHECK.validate_doc(doc, errors, "doc")
    assert errors == []
