"""``linear_road_lav5m``: its plain reference against the repo's
per-event interpreter on the same seeded events, a range in the middle
against the whole, the generator's parameters against
``linear_road_rows4``'s, the sample ranges the cell draws (none empty:
each holds a tick's end), the lower-precision control, and the cell's
functions end to end at a tiny size on the CPU, traced and untraced. The
tiny sizes live here, not in ``conftest.py``."""

import json

import numpy as np
import pytest
from conftest import TINY

from bmlib.cell import load_json, load_module, make_pool, run_cell
from bmlib.compare import compare_range
from bmlib.sink import SampleRanges

CELL = "linear_road_lav5m.replay"
# one expressway at 16 reports a second: a tick of 1 s holds 16 events, a
# round of 30 s 480, a batch 5 s (as in the cell), the pool eight rounds
# (240 s, shorter than the window's 300 s, as in the cell); 300 ticks x
# 16 = 4,800 members at most, in a ring of 8,192
SHAPES = {
    "expressways": 1, "reports_per_s_per_xway": 16,
    "trip_reports_min": 3, "trip_reports_max": 9,
    "accident_every_s": 120, "accident_reports": 6,
}
ROUND, POOL = 480, 3_840
TINY_LAV = {
    **TINY, **SHAPES, "batch": 80, "pool_batches": 48, "pool_events": POOL,
    "engine_config": {"time_ring_capacity": 8_192,
                      "acc_budget_bytes": 1 << 20},
    "fused_segment_len": 4, "sample_length_per_batch": 0.25,
}
REF = load_module("configs", "linear_road_lav5m")


def _cfg(**kw):
    return {**load_json("configs", "linear_road_lav5m"), **SHAPES, **kw}


def _pool(seed, n=POOL, **kw):
    return make_pool(_cfg(**kw), seed, n)


def test_the_two_linear_road_files_make_one_stream():
    mine = load_json("configs", "linear_road_lav5m")
    theirs = load_json("configs", "linear_road_rows4")
    gen = load_module("generators", "linear_road")
    for k in ("stream", "fields", "generator", "batch", "time_mode",
              "fused_segment_len", *gen.PARAMS):
        assert mine[k] == theirs[k], k
    assert mine["engine_config"] == {
        "time_ring_capacity": 33_554_432, "acc_budget_bytes": 134_217_728}
    assert mine["reduced"] == ["queries"]
    cell = load_json("cells", CELL)
    assert cell["params"] == {
        "pool_batches": 48, "sample_ranges": 3,
        "sample_length_per_batch": 0.25, "trace_seconds": 16.0}
    assert cell["chips"] == 1


@pytest.mark.parametrize("seed", [11, 2_147_483_659])
def test_reference_equals_interpreter(seed):
    from flink_siddhi_tpu.baseline import BaselineEngine

    cfg = _cfg()
    n = 25 * ROUND  # 750 s: the window fills at 300 s and slides on
    pool = _pool(seed)
    cols = pool.columns(0, n)
    eng = BaselineEngine(cfg["cql"], [name for name, _ in cfg["fields"]])
    out_ts, rows = [], []
    eng._emit = lambda _o, t, row: (out_ts.append(t), rows.append(row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()},
                    cols["time"].tolist())
    got = REF.expected(pool, 0, n)
    assert len(rows) == len(got["@ts"]) == int((cols["type"] == 0).sum())
    assert got["@ts"].tolist() == out_ts
    exact = ("vid", "xway", "dir", "seg")
    assert list(zip(*(got[c].tolist() for c in exact))) == [
        tuple(r[:4]) for r in rows]
    assert got["n"].tolist() == [r[5] for r in rows]
    assert np.allclose(got["lav"], [r[4] for r in rows], rtol=1e-12, atol=0)
    assert got["n"].max() > 40 and got["n"][-200:].min() >= 1
    assert np.array_equal(
        got["@idx"], pool.index_of(got["@ts"]))
    # a range in the middle reads five minutes back, whether or not it is
    # cut on a tick; a block that ends inside the history changes nothing
    for a, b in ((15 * ROUND, 17 * ROUND), (15 * ROUND + 7, 17 * ROUND - 3)):
        part = REF.expected(pool, a, b)
        keep = (got["@idx"] >= a) & (got["@idx"] < b)
        assert keep.sum() > 700
        for key in part:
            assert np.array_equal(part[key], got[key][keep]), key
    block = REF.BLOCK
    try:
        REF.BLOCK = 100
        small = REF.expected(pool, 15 * ROUND + 7, 17 * ROUND - 3)
    finally:
        REF.BLOCK = block
    for key in part:
        assert np.array_equal(part[key], small[key]), key


def test_no_sample_range_of_the_cell_is_empty():
    """A tick's rows are all due at its last event, so a range shorter
    than a tick can hold no row at all, and the harness scores a float
    column of such a range as ``inf`` (PERF.md section 7). At the cell's
    parameters every range holds a tick's end or two, over any seed."""
    cfg = load_json("configs", "linear_road_lav5m")
    params = load_json("cells", CELL)["params"]
    batch = cfg["batch"]
    per_tick = (cfg["expressways"] * cfg["reports_per_s_per_xway"]
                * cfg["tick_ms"] // 1000)
    length = int(params["sample_length_per_batch"] * batch)
    assert length == 136_000 > per_tick == 108_800
    period = params["pool_batches"] * batch
    seeds = list(range(300)) + [1_954_407_471, 2 ** 31 + 7]
    for seed in seeds:
        ranges = SampleRanges(seed, period, batch, length,
                              params["sample_ranges"])
        assert len(ranges.offsets) == params["sample_ranges"]
        for o in ranges.offsets:
            last = np.arange(o // per_tick, (o + length) // per_tick + 1
                             ) * per_tick + per_tick - 1
            inside = ((last >= o) & (last < o + length)).sum()
            assert inside in (1, 2), (seed, o)
    # at an eighth of a batch (PR 49's value) some are empty
    short = [
        o for seed in seeds
        for o in SampleRanges(seed, period, batch, batch // 8, 3).offsets
        if (o + batch // 8 - 1 - (per_tick - 1)) // per_tick
        < -(-(o - (per_tick - 1)) // per_tick)
    ]
    assert len(short) > 100


def test_the_lower_precision_control_fails_lav_alone():
    """A segment's sum of speeds kept in bfloat16 is off by parts in a
    thousand: ``lav`` fails its 1e-6, every other number holds."""
    cfg = load_json("configs", "linear_road_lav5m")
    pool = _pool(12)
    a, b = 20 * ROUND, 21 * ROUND
    want = REF.expected(pool, a, b)
    assert len(want["@idx"]) > 400
    assert all(
        v == 0 for v in compare_range(want, want, cfg["compare"]).values())
    low = compare_range(REF.expected(pool, a, b, "bf16"), want,
                        cfg["compare"])
    assert low.pop("lav_err_over_tol") > 100
    assert all(v == 0 for v in low.values())
    # float32 holds it: the program's own arithmetic
    f32 = dict(want)
    f32["lav"] = want["lav"].astype(np.float32)
    assert compare_range(f32, want, cfg["compare"])["lav_err_over_tol"] < 0.1


# -- the cell ------------------------------------------------------------------
def _run(trace, **kw):
    lines = []
    out = run_cell(CELL, 1_954_407_471, 3.0, trace, overrides=dict(TINY_LAV),
                   say=lines.append, **kw)
    return out, lines


def test_cell_runs_and_is_correct():
    out, lines = _run(False, control=True)
    assert list(out)[-1] == "compared"
    json.dumps(out)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}
    assert out["compared"]["ranges"] > 0 and out["compared"]["rows"] > 0
    assert all(v <= lim for v, lim in out["compared"]["numbers"].values())
    assert 0 < out["compared"]["numbers"]["lav_err_over_tol"][0] < 1
    control = json.loads(
        next(x for x in lines if x.startswith("[bench] control"))[21:])
    assert control["correct"] is False
    failing = [k for k, (v, lim) in control["numbers"].items() if v > lim]
    assert failing == ["lav_err_over_tol"]


def test_traced_run_reports_the_windows_metrics():
    out, _ = _run(True)
    assert out["correct"] is True and out["failed"] == 0
    m = out["metrics"]
    assert {"tape_build_ms_per_batch", "group_intern_ms_per_batch",
            "group_regrow_per_kbatch", "time_expired_per_batch",
            "ring_evicted_per_kbatch", "static_merge_share",
            "compact_identity_share", "dispatches_per_kbatch",
            "h2d_overlap_share", "drain_busy_share",
            "source_pull_ms_per_batch", "trace_stamp_ms_per_batch",
            "starved_share", "backpressure_wait_share",
            "runloop_unattributed_share", "drain_backlog_wait_share",
            "drain_request_ms_per_batch", "fetch_ms_per_mrow",
            "decode_ms_per_mrow", "drain_emit_ms_per_mrow",
            "trace_complete_ms_per_mrow"} <= set(m)
    assert m["static_merge_share"]["value"] == 0.0
    assert m["ring_evicted_per_kbatch"]["value"] == 0.0
    # 79 reports a batch of 80 leave, once the window is full
    assert 60 < m["time_expired_per_batch"]["value"] < 100
    assert abs(m["dispatches_per_kbatch"]["value"] - 250.0) < 5


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_cell_is_correct_with_the_source_polled_ahead(monkeypatch, trace):
    """At the cell's own size the generator's 13 ms a batch put its
    polls on the poll thread (``Job._poll``); here the thresholds are
    lowered so that a tiny pool's do: the same rows, all compared."""
    from flink_siddhi_tpu.runtime.executor import Job

    monkeypatch.setattr(Job, "POLL_AHEAD_AFTER", 2)
    monkeypatch.setattr(Job, "POLL_AHEAD_MIN_S", 0.0)
    ahead = []
    poll_ahead = Job._poll_ahead
    monkeypatch.setattr(
        Job, "_poll_ahead",
        lambda self, src: (ahead.append(1), poll_ahead(self, src))[1])
    out, _ = _run(trace)
    assert len(ahead) > 20
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["ranges"] > 0 and out["compared"]["rows"] > 0
    assert all(v <= lim for v, lim in out["compared"]["numbers"].values())
    if trace:
        assert out["metrics"]["source_pull_ms_per_batch"]["value"] >= 0
