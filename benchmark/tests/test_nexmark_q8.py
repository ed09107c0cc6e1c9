"""``nexmark_q8``: its plain reference against the repo's per-event
interpreter on the same seeded events, the lower-precision control, and
the cell's functions end to end at a tiny size on the CPU (the other
cells' are in ``test_references.py``, ``test_cells_cpu.py`` and
``test_nexmark_q5.py``)."""

import json

import numpy as np
import pytest
from conftest import TINY

from bmlib.cell import load_json, load_module, make_pool, run_cell
from bmlib.compare import compare_range

CELL = "nexmark_q8.replay"
# two events a millisecond: 1 s of event time a batch, a window is ten
# batches and the pool two windows; sample ranges of 12 batches
TINY_Q8 = {
    **TINY, "event_time_rate": 2000, "batch": 2000, "pool_batches": 20,
    "pool_events": 40_000, "engine_config": {"hop_group_slots": 4096},
    "fused_segment_len": 2, "sample_length_per_batch": 12,
}


def _tiny_cfg(**kw):
    cfg = load_json("configs", "nexmark_q8")
    cfg.update(event_time_rate=2000, **kw)
    return cfg


@pytest.mark.parametrize("seed", [11, 12])
def test_reference_equals_interpreter(seed):
    from flink_siddhi_tpu.baseline import BaselineEngine

    cfg = _tiny_cfg()
    ref = load_module("configs", "nexmark_q8")
    n = 70_000  # 35 s of event time
    pool = make_pool(cfg, seed, 20_000)  # one window: the pool cycles
    cols = pool.columns(0, n)
    eng = BaselineEngine(cfg["cql"], [name for name, _ in cfg["fields"]])
    out_ts, rows = [], []
    eng._emit = lambda _o, t, row: (out_ts.append(t), rows.append(row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()},
                    cols["dateTime"].tolist())
    # the interpreter has not closed the stream's last window
    got = ref.expected(pool, 0, 60_000)
    assert len(out_ts) == len(got["@ts"]) > 30
    assert got["@ts"].tolist() == out_ts
    assert list(zip(got["id"].tolist(), got["auctions"].tolist())) \
        == [tuple(r) for r in rows]
    assert len(set(out_ts)) == 3 and got["auctions"].max() > 1
    # a range in the middle needs only its own window
    a, b = 23_000, 41_000
    part = ref.expected(pool, a, b)
    keep = (got["@idx"] >= a) & (got["@idx"] < b)
    assert keep.sum() > 0
    for key in part:
        assert np.array_equal(part[key], got[key][keep]), key


def test_a_later_cycles_rows_are_cycle_ones_moved():
    """``expected`` counts a range of cycle 2 or later once, in cycle 1,
    and moves it; the direct count says the same. A pool that is no
    whole number of windows is counted directly every time."""
    cfg = _tiny_cfg(first_event_number=50_000)
    ref = load_module("configs", "nexmark_q8")
    pool = make_pool(cfg, 13, 40_000)  # 20 s a cycle: two windows
    for a, b in ((86_000, 107_000), (118_000, 141_000), (200_500, 226_500)):
        moved, direct = ref.expected(pool, a, b), ref._direct(pool, a, b)
        assert len(direct["@idx"]) >= 2
        for key in direct:
            assert np.array_equal(moved[key], direct[key]), (a, key)
    assert len(ref._MEMO) == 3
    odd = make_pool(cfg, 13, 30_000)  # 15 s a cycle
    rows = ref.expected(odd, 95_000, 125_000)
    assert len(rows["@idx"]) > 0 and len(ref._MEMO) == 3
    for key, col in ref._direct(odd, 95_000, 125_000).items():
        assert np.array_equal(rows[key], col), key


def test_the_lower_precision_control_fails_the_limits():
    """An id held in bfloat16 keeps eight bits: ids past 256 come back
    rounded, and the rows come out as not correct. The counts would
    pass: a seller's auctions stay under 256."""
    cfg = load_json("configs", "nexmark_q8")
    cfg["event_time_rate"] = 100_000
    ref = load_module("configs", "nexmark_q8")
    pool = make_pool(cfg, 12, 2_000_000)
    want = ref.expected(pool, 0, 2_000_000)
    assert len(want["@idx"]) > 10_000 and want["auctions"].max() < 256
    assert all(
        v == 0 for v in compare_range(want, want, cfg["compare"]).values())
    low = compare_range(
        ref.expected(pool, 0, 2_000_000, "bf16"), want, cfg["compare"])
    assert low["id_mismatches"] > 0 and low["auctions_mismatches"] == 0


def _run(trace):
    lines = []
    out = run_cell(CELL, 2_147_483_659, 3.0, trace, overrides=dict(TINY_Q8),
                   say=lines.append)
    return out, lines


def test_cell_runs_and_is_correct():
    out, lines = _run(False)
    assert list(out)[-1] == "compared"
    json.dumps(out)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"events_per_s", "setup_s"}
    assert out["compared"]["ranges"] > 0 and out["compared"]["rows"] > 0
    assert all(v <= lim for v, lim in out["compared"]["numbers"].values())


def test_traced_run_reports_the_group_and_join_metrics():
    out, _ = _run(True)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"tape_build_ms_per_batch", "group_intern_ms_per_batch",
            "group_slot_reuse_share", "group_regrow_per_kbatch",
            "join_windows_per_kbatch", "join_rows_per_window",
            "dispatches_per_kbatch", "h2d_overlap_share",
            "backpressure_wait_share", "drain_busy_share",
            "drain_backlog_wait_share"} <= set(m)
    assert m["group_intern_ms_per_batch"]["value"] \
        <= m["tape_build_ms_per_batch"]["value"]
    # a window is ten batches here; slots are reused once the first
    # windows have closed, and the table never re-buckets
    assert 50 <= m["join_windows_per_kbatch"]["value"] <= 150
    assert m["join_rows_per_window"]["value"] > 10
    assert m["group_slot_reuse_share"]["value"] > 50
    assert m["group_regrow_per_kbatch"]["value"] == 0
