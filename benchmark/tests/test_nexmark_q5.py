"""``nexmark_q5``: its plain reference against the repo's per-event
interpreter on the same seeded events, the lower-precision control, and
the cell's functions end to end at a tiny size on the CPU (the other
cells' are in ``test_references.py`` and ``test_cells_cpu.py``)."""

import json

import numpy as np
import pytest
from conftest import TINY

from bmlib.cell import load_json, load_module, make_pool, run_cell
from bmlib.compare import compare_range

CELL = "nexmark_q5.replay"
# two events a millisecond, 1 s of event time a batch, 20 s a cycle
TINY_Q5 = {
    **TINY, "event_time_rate": 2000, "batch": 2000, "pool_batches": 20,
    "pool_events": 40_000, "engine_config": {}, "fused_segment_len": 2,
}


def _tiny_cfg(**kw):
    cfg = load_json("configs", "nexmark_q5")
    cfg.update(event_time_rate=2000, **kw)
    return cfg


@pytest.mark.parametrize("seed", [11, 12])
def test_reference_equals_interpreter(seed):
    from flink_siddhi_tpu.baseline import BaselineEngine

    cfg = _tiny_cfg()
    ref = load_module("configs", "nexmark_q5")
    n = 60_000  # 30 s of event time
    pool = make_pool(cfg, seed, 20_000)  # shorter than n: the pool cycles
    cols = pool.columns(0, n)
    eng = BaselineEngine(cfg["cql"], [name for name, _ in cfg["fields"]])
    out_ts, rows = [], []
    eng._emit = lambda _o, t, row: (out_ts.append(t), rows.append(row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()},
                    cols["dateTime"].tolist())
    got = ref.expected(pool, 0, n)
    # the interpreter has not closed the stream's last window
    k = len(out_ts)
    assert 0 < k <= len(got["@ts"]) <= k + 2
    assert got["@ts"][:k].tolist() == out_ts
    assert list(zip(got["auction"][:k].tolist(), got["num"][:k].tolist())) \
        == [tuple(r) for r in rows]
    # a range in the middle needs only its own history
    a, b = 23_000, 41_000
    part = ref.expected(pool, a, b)
    keep = (got["@idx"] >= a) & (got["@idx"] < b)
    assert keep.sum() > 0
    for key in part:
        assert np.array_equal(part[key], got[key][keep]), key


def test_a_later_cycles_rows_are_cycle_ones_moved():
    """``expected`` counts a range of cycle 2 or later once, in cycle 1,
    and moves it; the direct count says the same."""
    cfg = _tiny_cfg(first_event_number=50_000)
    ref = load_module("configs", "nexmark_q5")
    pool = make_pool(cfg, 13, 40_000)  # 20 s a cycle
    for a, b in ((86_000, 97_000), (118_000, 131_000), (200_500, 216_500)):
        moved, direct = ref.expected(pool, a, b), ref._direct(
            pool, a, b, "f64")
        assert len(direct["@idx"]) >= 2
        for key in direct:
            assert np.array_equal(moved[key], direct[key]), (a, key)
    assert len(ref._MEMO) == 3


def test_the_lower_precision_control_fails_the_limits():
    """A count held in bfloat16 stops at 256: every auction past it ties
    at the top, and the rows come out as not correct."""
    cfg = load_json("configs", "nexmark_q5")
    cfg["event_time_rate"] = 100_000
    ref = load_module("configs", "nexmark_q5")
    pool = make_pool(cfg, 12, 400_000)
    want = ref.expected(pool, 200_000, 1_000_000)
    assert len(want["@idx"]) > 0 and want["num"].min() > 256
    assert all(
        v == 0 for v in compare_range(want, want, cfg["compare"]).values())
    low = compare_range(
        ref.expected(pool, 200_000, 1_000_000, "bf16"), want, cfg["compare"]
    )
    assert low["rows_lost_or_extra"] > 0


def _run(trace):
    lines = []
    out = run_cell(CELL, 2_147_483_659, 3.0, trace, overrides=dict(TINY_Q5),
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


def test_traced_run_reports_the_group_and_hop_metrics():
    out, _ = _run(True)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"tape_build_ms_per_batch", "group_intern_ms_per_batch",
            "group_slot_reuse_share", "group_regrow_per_kbatch",
            "hop_windows_per_kbatch", "dispatches_per_kbatch",
            "h2d_overlap_share", "backpressure_wait_share",
            "drain_busy_share", "drain_backlog_wait_share"} <= set(m)
    assert m["group_intern_ms_per_batch"]["value"] \
        <= m["tape_build_ms_per_batch"]["value"]
    # one slide is two batches here; slots are reused once the first
    # windows have closed
    assert 300 <= m["hop_windows_per_kbatch"]["value"] <= 700
    assert m["group_slot_reuse_share"]["value"] > 50
