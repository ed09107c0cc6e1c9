"""``linear_road_rows4``: the generator's contract and the stream's
shapes, its plain reference against the repo's per-event interpreter on
the same seeded events, a range in the middle against the whole, the
rows per tick that repeat with the pool (what ``bmlib/compare.py``'s
``rows_due`` counts on), the lower-precision control, and the cell's
functions end to end at a tiny size on the CPU. The tiny sizes live
here, not in ``conftest.py``."""

import json

import numpy as np
import pytest
from conftest import TINY

from bmlib.cell import load_json, load_module, make_pool, run_cell
from bmlib.compare import compare_range, rows_due

CELL = "linear_road_rows4.replay"
# one expressway at 16 reports a second: a tick of 1 s holds 16 events, a
# round of 30 s 480, a batch 5 s (as in the cell), the pool eight rounds.
# Trips of 3-9 reports, so vids die, their slots are purged (90 s + 30 s)
# and reused within a few rounds; accidents of 6 reports every two
# minutes, two a pool period, three rows a vehicle
SHAPES = {
    "expressways": 1, "reports_per_s_per_xway": 16,
    "trip_reports_min": 3, "trip_reports_max": 9,
    "accident_every_s": 120, "accident_reports": 6,
}
ROUND, POOL = 480, 3_840
TINY_LR = {
    **TINY, **SHAPES, "batch": 80, "pool_batches": 48, "pool_events": POOL,
    "engine_config": {"hop_group_slots": 1_024}, "fused_segment_len": 4,
    "sample_length_per_batch": 8,
}
REF = load_module("configs", "linear_road_rows4")


def _cfg(**kw):
    return {**load_json("configs", "linear_road_rows4"), **SHAPES, **kw}


def _pool(seed, n=POOL, **kw):
    return make_pool(_cfg(**kw), seed, n)


# -- the generator -----------------------------------------------------------
def test_same_seed_same_events_and_batches_equal_columns():
    pool, again, other = _pool(8008), _pool(8008), _pool(8009)
    whole = pool.columns(0, 3 * POOL)
    for k, v in again.columns(0, 3 * POOL).items():
        assert np.array_equal(v, whole[k]) and v.dtype == whole[k].dtype
    assert any(not np.array_equal(v, whole[k])
               for k, v in other.columns(0, 3 * POOL).items())
    serve = pool.server(80, lambda _f, _v: 0)
    for j in (0, 1, 47, 48, 2 * 48 + 3, 600):
        cols, ts = serve(j)
        want = pool.columns(j * 80, (j + 1) * 80)
        assert list(cols) == [name for name, _ in _cfg()["fields"]]
        for k in cols:
            assert np.array_equal(cols[k], want[k]), (j, k)
            assert cols[k].dtype == want[k].dtype
        assert ts is cols["time"]
    # a field list cut down, in any order of asking
    some = pool.columns(100, 900, ("pos", "vid"))
    assert set(some) == {"vid", "pos"}
    assert np.array_equal(some["pos"], whole["pos"][100:900])


def test_the_event_clock_both_ways():
    pool = _pool(3)
    i = np.arange(0, 3 * POOL)
    ts = pool.ts_of(i)
    assert np.all(np.diff(ts) >= 0) and ts[0] == 3_600_000
    assert np.array_equal(ts, pool.columns(0, 3 * POOL, ("time",))["time"])
    # a tick's 16 events share a stamp; index_of gives the tick's last
    assert np.array_equal(pool.index_of(ts), i // 16 * 16 + 15)
    assert pool.index_of(ts[0] - 1) == -1
    assert int(pool.index_of(int(ts[16]) + 999)) == 31


def test_vehicles_trips_requests_and_accidents():
    pool = _pool(21)
    n_rounds = 40
    c = pool.columns(0, n_rounds * ROUND)
    slot = np.arange(n_rounds * ROUND) % ROUND
    report = c["type"] == 0
    # about 1% of a round's slots are requests of types 2-4, with no
    # position; the others report every round
    asks = ~report
    assert 0 < asks[:ROUND].sum() <= 15 and set(c["type"][asks]) <= {2, 3, 4}
    assert np.all(c["pos"][asks] == -1) and np.all(c["seg"][asks] == -1)
    assert np.array_equal(asks, np.tile(asks[:ROUND], n_rounds))
    # the record is the source's fifteen fields wide: a position report
    # holds the null in the requests' six, a request its type's own
    own = ("qid", "sinit", "send", "dow", "tod", "day")
    assert len(c) == 15 and all(np.all(c[k][report] == -1) for k in own)
    assert np.all(c["qid"][asks] >= 0)
    assert len(set(c["qid"][asks])) == asks.sum()  # a new one each time
    for kind, has in ((2, ()), (3, ("day", "xway")),
                      (4, ("sinit", "send", "dow", "tod", "xway"))):
        rows = c["type"] == kind
        for k in own[1:] + ("xway",):
            assert np.all((c[k][rows] >= 0) == (k in has)), (kind, k)
    assert c["sinit"].max() <= 99 and c["tod"].max() <= 1_440
    assert c["dow"].max() <= 7 and c["day"].max() <= 69
    # a request carries the vid of a vehicle that is reporting
    assert set(c["vid"][asks]) <= set(c["vid"][report])
    assert np.all(c["seg"][report] == c["pos"][report] // 5_280)
    assert c["pos"][report].min() >= 0 and c["pos"][report].max() <= 527_999
    assert set(c["lane"][report]) <= {1, 2, 3} and c["xway"].max() == 0
    stopped = 0
    for s in np.flatnonzero(report[:ROUND]):
        vid, pos = c["vid"][slot == s], c["pos"][slot == s]
        # a slot's vids only grow, each trip a new one, 3-9 reports long
        # (an accident's slot: 10, then 6)
        assert np.all(np.diff(vid) >= 0)
        cuts = np.flatnonzero(np.diff(vid)) + 1
        assert len(cuts) >= 3
        assert set(np.diff(cuts)) <= set(range(3, 11))
        still = (np.diff(pos) == 0) & (np.diff(vid) == 0)
        if pool.res_of[s] < 0:
            assert not still.any()  # a moving vehicle repeats no position
        else:
            stopped += 1
            assert still.sum() >= 5 * 2  # six reports at one place, twice
    assert stopped == len(pool.res_slot) == 2 * 2 * 2  # two pairs an accident


def test_rows_per_tick_repeat_with_the_pool():
    """``rows_due`` counts the first cycle and the second and takes every
    later one for the second: the deliveries it is asked about end on a
    tick's last event, so it is the rows per tick that have to repeat."""
    pool = _pool(5)
    first = REF.expected(pool, 0, POOL)
    later = [REF.expected(pool, c * POOL, (c + 1) * POOL)["@idx"] - c * POOL
             for c in (1, 2, 3, 7)]
    # two accidents a period, two vehicles each, three rows a vehicle;
    # the first cycle's windows lack what came before event 0
    assert len(first["@idx"]) <= len(later[0]) == 12
    assert all(np.array_equal(later[0], x) for x in later[1:])
    cfg = _cfg(rows_per_event=None)
    whole = REF.expected(pool, 0, 9 * POOL)["@idx"]
    for g0, g1 in ((0, POOL), (160, 5 * POOL + 320), (POOL + 16, 9 * POOL)):
        assert rows_due(REF, pool, cfg, g0, g1) == int(
            ((whole >= g0) & (whole < g1)).sum())


# -- the reference -------------------------------------------------------------
@pytest.mark.parametrize("seed", [11, 2_147_483_659])
def test_reference_equals_interpreter(seed):
    from flink_siddhi_tpu.baseline import BaselineEngine

    cfg = _cfg()
    n = 25 * ROUND
    pool = _pool(seed)
    cols = pool.columns(0, n)
    eng = BaselineEngine(cfg["cql"], [name for name, _ in cfg["fields"]])
    out_ts, rows = [], []
    eng._emit = lambda _o, t, row: (out_ts.append(t), rows.append(row))
    eng.run_columns({k: v.tolist() for k, v in cols.items()},
                    cols["time"].tolist())
    got = REF.expected(pool, 0, n)
    assert len(rows) == len(got["@ts"]) > 30
    assert got["@ts"].tolist() == out_ts
    names = [c for c in REF.COLUMNS if not c.startswith("@")]
    assert list(zip(*(got[c].tolist() for c in names))) == [
        tuple(r) for r in rows]
    assert set(got["n"].tolist()) == {4}
    assert np.array_equal(got["pos_lo"], got["pos_hi"])
    # two vehicles an accident, at one place
    assert len(set(got["vid"].tolist())) >= 2 * len(
        set(zip(got["xway"].tolist(), got["dir"].tolist(),
                got["pos"].tolist())))
    # a range in the middle reads back as far as its windows reach,
    # whether or not it is cut on a tick
    for a, b in ((9 * ROUND, 14 * ROUND), (9 * ROUND + 7, 14 * ROUND - 3)):
        part = REF.expected(pool, a, b)
        keep = (got["@idx"] >= a) & (got["@idx"] < b)
        assert keep.sum() > 3
        for key in part:
            assert np.array_equal(part[key], got[key][keep]), key


def test_a_report_inside_the_purge_band_is_refused():
    """The reference answers only where ``@purge`` does."""
    class Gap:
        n = 8

        def columns(self, lo, hi, names=None):
            t = np.asarray([0, 30, 60, 90, 190, 220, 250, 280][lo:hi])
            return {"type": np.zeros(len(t), np.int32), "time": t * 1_000,
                    "vid": np.full(len(t), 5), "lane": np.ones(len(t)),
                    "pos": np.full(len(t), 7), "xway": t * 0, "dir": t * 0,
                    "seg": t * 0}

        def ts_of(self, i):
            return np.asarray([0, 30, 60, 90, 190, 220, 250, 280, 310])[i] \
                * 1_000

        def index_of(self, ts):
            return np.searchsorted(
                np.asarray([0, 30, 60, 90, 190, 220, 250, 280]) * 1_000,
                ts, side="right") - 1

    with pytest.raises(ValueError, match="leaves that open"):
        REF.expected(Gap(), 0, 8)


def test_the_lower_precision_control_fails_the_limits():
    """Positions kept in bfloat16 are 2,048 feet apart where the road is
    past its 50th mile: a vehicle under 15 mph stays in one value for
    four reports and reads as stopped."""
    cfg = load_json("configs", "linear_road_rows4")
    pool = make_pool(
        {**cfg, "expressways": 2, "reports_per_s_per_xway": 200}, 12,
        8 * 12_000)
    a, b = 8 * 12_000, 9 * 12_000
    want = REF.expected(pool, a, b)
    assert len(want["@idx"]) > 0
    assert all(
        v == 0 for v in compare_range(want, want, cfg["compare"]).values())
    low = REF.expected(pool, a, b, "bf16")
    assert len(low["@idx"]) > 2 * len(want["@idx"])
    assert compare_range(low, want, cfg["compare"])["rows_lost_or_extra"] > 0


# -- the cell ------------------------------------------------------------------
def _run(trace, **kw):
    lines = []
    out = run_cell(CELL, 2_147_483_659, 3.0, trace, overrides=dict(TINY_LR),
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
    control = json.loads(
        next(x for x in lines if x.startswith("[bench] control"))[21:])
    assert control["correct"] is False
    failing = [k for k, (v, lim) in control["numbers"].items() if v > lim]
    assert failing and len(failing) < len(control["numbers"])


def test_traced_run_reports_the_group_and_perkey_metrics():
    out, _ = _run(True)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"tape_build_ms_per_batch", "group_intern_ms_per_batch",
            "group_slot_reuse_share", "group_regrow_per_kbatch",
            "perkey_rows_per_batch", "perkey_purged_per_batch",
            "dispatches_per_kbatch", "h2d_overlap_share",
            "drain_busy_share", "compact_identity_share",
            "source_pull_ms_per_batch", "trace_stamp_ms_per_batch",
            "starved_share",
            # the run loop's waits and the drain's legs, as the NEXmark
            # cells report them
            "backpressure_wait_share", "drain_backlog_wait_share",
            "runloop_unattributed_share", "drain_request_ms_per_batch",
            "drain_emit_ms_per_mrow", "trace_complete_ms_per_mrow",
            "fetch_ms_per_mrow", "decode_ms_per_mrow"} <= set(m)
    assert m["group_intern_ms_per_batch"]["value"] \
        <= m["tape_build_ms_per_batch"]["value"]
    # 12 rows a pool period of 48 batches; some 80 vids a round end their
    # trips, so about 13 slots a batch are purged and as many reused
    assert 0.15 <= m["perkey_rows_per_batch"]["value"] <= 0.35
    assert 5 <= m["perkey_purged_per_batch"]["value"] <= 30
    assert m["group_slot_reuse_share"]["value"] > 50
    assert m["group_regrow_per_kbatch"]["value"] == 0
    # `having` keeps a row in 300 here: an append finds a prefix only
    # where its batch gave no row at all (most do here, few in the cell)
    assert 50 < m["compact_identity_share"]["value"] < 100
