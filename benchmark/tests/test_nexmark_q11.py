"""``nexmark_q11``: its plain reference against the repo's per-event
interpreter on the same seeded events, the moved rows of a later cycle
against the direct count, the lower-precision control, and the cell's
functions end to end at a tiny size on the CPU (the other cells' are in
``test_references.py``, ``test_cells_cpu.py``, ``test_nexmark_q5.py``
and ``test_nexmark_q8.py``)."""

import json

import numpy as np
import pytest
from conftest import TINY

from bmlib.cell import load_json, load_module, make_pool, run_cell
from bmlib.compare import compare_range

CELL = "nexmark_q11.replay"
# two events a millisecond: 1 s of event time a batch, the 10 s gap is
# ten batches and the pool 20 s; sample ranges of two batches. A person
# is among the newest 1,000 for 25 s here, so bidders come back
TINY_Q11 = {
    **TINY, "event_time_rate": 2000, "batch": 2000, "pool_batches": 20,
    "pool_events": 40_000, "engine_config": {"hop_group_slots": 4096},
    "fused_segment_len": 2, "sample_length_per_batch": 2,
}


def _tiny_cfg(**kw):
    cfg = load_json("configs", "nexmark_q11")
    cfg.update(event_time_rate=2000, **kw)
    return cfg


@pytest.mark.parametrize("seed", [11, 12])
def test_reference_equals_interpreter(seed):
    from flink_siddhi_tpu.baseline import BaselineEngine

    cfg = _tiny_cfg()
    ref = load_module("configs", "nexmark_q11")
    n = 70_000  # 35 s of event time
    pool = make_pool(cfg, seed, 20_000)  # 10 s: the pool cycles
    cols = pool.columns(0, n)
    eng = BaselineEngine(cfg["cql"], [name for name, _ in cfg["fields"]])
    out_ts, rows = [], []
    eng._emit = lambda _o, t, row: (out_ts.append(t), rows.append(row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()},
                    cols["dateTime"].tolist())
    # the sessions that the stream's own clock closed: no flush
    got = ref.expected(pool, 0, n)
    assert len(out_ts) == len(got["@ts"]) > 1_000
    assert got["@ts"].tolist() == out_ts
    assert list(zip(got["bidder"].tolist(), got["bid_count"].tolist(),
                    got["starttime"].tolist())) == [tuple(r) for r in rows]
    # bidders come back after the gap, and the hot one bids often
    assert len(set(got["bidder"].tolist())) < len(out_ts)
    assert got["bid_count"].max() > 256
    # a range in the middle reads back as far as its sessions reach
    a, b = 43_000, 51_000
    part = ref.expected(pool, a, b)
    keep = (got["@idx"] >= a) & (got["@idx"] < b)
    assert keep.sum() > 50
    for key in part:
        assert np.array_equal(part[key], got[key][keep]), key


def test_a_later_cycles_rows_are_cycle_ones_moved():
    """``expected`` counts a range of cycle 2 or later once, in cycle 1,
    and moves it; the direct count says the same."""
    cfg = load_json("configs", "nexmark_q11")
    cfg["event_time_rate"] = 100_000  # a person bids for half a second
    ref = load_module("configs", "nexmark_q11")
    pool = make_pool(cfg, 13, 2_000_000)  # 20 s a cycle
    for a, b in ((4_300_000, 4_400_000), (9_950_000, 10_050_000)):
        moved, (direct, _w) = ref.expected(pool, a, b), ref._direct(
            pool, a, b)
        assert len(direct["@idx"]) >= 1_000
        for key in direct:
            assert np.array_equal(moved[key], direct[key]), (a, key)
    assert len(ref._MEMO) == 2
    assert all(whole for _rows, whole in ref._MEMO.values())
    # cycle 0 and cycle 1 are counted directly
    ref.expected(pool, 1_500_000, 1_600_000)
    ref.expected(pool, 2_500_000, 2_600_000)
    assert len(ref._MEMO) == 2
    # at two events a millisecond a session lasts as long as its bidder
    # is among the newest 1,000 (25 s): cycle 1's rows lean on the
    # stream's start, and a later cycle's are counted directly
    slow = make_pool(_tiny_cfg(), 13, 40_000)
    a, b = 86_000, 90_000
    rows, (direct, _w) = ref.expected(slow, a, b), ref._direct(slow, a, b)
    assert len(rows["@idx"]) > 20
    assert not ref._MEMO[(id(slow), a - 40_000, b - 40_000)][1]
    for key in direct:
        assert np.array_equal(rows[key], direct[key]), key


def test_the_lower_precision_control_fails_the_limits():
    """A count held in bfloat16 stops at 256: at the cell's rate the hot
    bidder of every 5 ms bids some 3,450 times in its one session, and
    those rows come out as not correct."""
    cfg = load_json("configs", "nexmark_q11")
    ref = load_module("configs", "nexmark_q11")
    pool = make_pool(cfg, 12, 500_000)
    want = ref.expected(pool, 0, 2_000_000)
    assert len(want["@idx"]) == 0  # 2 s of stream: no clock past a gap
    want = ref.expected(pool, 10_100_000, 10_400_000)
    assert 5_500 < len(want["@idx"]) < 6_500  # 20,000 a second
    assert want["bid_count"].max() > 3_000
    assert all(
        v == 0 for v in compare_range(want, want, cfg["compare"]).values())
    low = compare_range(
        ref.expected(pool, 10_100_000, 10_400_000, "bf16"), want,
        cfg["compare"])
    assert 40 < low["bid_count_mismatches"] < 80  # 200 a second
    assert low["bidder_mismatches"] == low["starttime_mismatches"] == 0


def _run(trace):
    lines = []
    out = run_cell(CELL, 2_147_483_659, 3.0, trace, overrides=dict(TINY_Q11),
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


def test_traced_run_reports_the_group_and_session_metrics():
    out, _ = _run(True)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"tape_build_ms_per_batch", "group_intern_ms_per_batch",
            "group_slot_reuse_share", "group_regrow_per_kbatch",
            "session_closes_per_batch", "session_events_per_close",
            "session_timer_close_share", "fetch_ms_per_mrow",
            "decode_ms_per_mrow", "dispatches_per_kbatch",
            "h2d_overlap_share", "backpressure_wait_share",
            "drain_busy_share", "drain_backlog_wait_share"} <= set(m)
    assert m["group_intern_ms_per_batch"]["value"] \
        <= m["tape_build_ms_per_batch"]["value"]
    # a batch is a second here: some 40 persons register in it and about
    # as many sessions close; a bidder of this tiny stream does come
    # back, so not every session is closed by the clock alone
    assert 10 <= m["session_closes_per_batch"]["value"] <= 200
    assert m["session_events_per_close"]["value"] > 5
    assert 20 <= m["session_timer_close_share"]["value"] <= 100
    assert m["group_regrow_per_kbatch"]["value"] == 0
