"""The generators' contract (``generators/__init__.py``), the ``uniform``
stream pinned to the bytes it had before it moved (digests computed at
the parent commit, PR 26), the NEXmark stream's invariants, and the
sink's rule for a clock on which events share a timestamp."""

import hashlib

import numpy as np
import pytest

from bmlib.cell import load_json, load_module, make_pool
from bmlib.sink import DeliverySink, SampleRanges

NEXMARK = load_module("generators", "nexmark")
NX_FIELDS = [[name, "int" if name in ("event_type", "category") else "long"]
             for name in NEXMARK.COLUMNS]


def _nexmark(rate=2_000, fields=NX_FIELDS, **kw):
    return {**NEXMARK.SOURCE_DEFAULTS, "generator": "nexmark",
            "fields": fields, "event_time_rate": rate, **kw}


def _uniform(n_ids=50):
    return {**load_json("configs", "pattern3"), "n_ids": n_ids}


def _digest(*arrays):
    m = hashlib.sha256()
    for a in arrays:
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()


def _intern(_field, _value):
    return 0


STREAMS = [
    pytest.param(_uniform(), 65_536, 4_096, id="uniform"),
    pytest.param(_nexmark(), 20_000, 2_000, id="nexmark"),
    pytest.param(_nexmark(1_000_000, first_event_number=50_000), 100_000,
                 10_000, id="nexmark-1M-a-second"),
]


@pytest.mark.parametrize("cfg, n, batch", STREAMS)
def test_same_seed_same_bytes_and_batches_equal_columns(cfg, n, batch):
    pool, again = make_pool(cfg, 8008, n), make_pool(cfg, 8008, n)
    other = make_pool(cfg, 8009, n)
    whole = pool.columns(0, 3 * n)
    assert _digest(*whole.values()) == _digest(
        *again.columns(0, 3 * n).values())
    assert _digest(*whole.values()) != _digest(
        *other.columns(0, 3 * n).values())
    serve = pool.server(batch, _intern)
    for j in (0, 1, n // batch - 1, n // batch, 2 * (n // batch) + 3):
        cols, ts = serve(j)
        want = pool.columns(j * batch, (j + 1) * batch)
        assert list(cols) == [name for name, _ in cfg["fields"]]
        for name, kind in cfg["fields"]:
            if kind == "string":
                assert not cols[name].any()  # the interned code
                continue
            assert cols[name].dtype == want[name].dtype
            assert np.array_equal(cols[name], want[name]), (j, name)
        assert ts.dtype == np.int64
        assert np.array_equal(ts, pool.ts_of(
            np.arange(j * batch, (j + 1) * batch)))


@pytest.mark.parametrize("cfg, n, _batch", STREAMS)
def test_the_event_clock_both_ways(cfg, n, _batch):
    pool = make_pool(cfg, 1, n)
    i = np.arange(0, 3 * n)
    ts = pool.ts_of(i)
    assert np.all(np.diff(ts) >= 0)
    last = pool.index_of(ts)  # the last event of i's tick
    assert np.all(last >= i) and np.all(pool.ts_of(last) == ts)
    assert np.all(pool.ts_of(last + 1) > ts)
    assert pool.index_of(ts[0] - 1) == -1
    assert pool.index_of(int(ts[77])) == last[77]  # scalars too


# computed at the parent commit (33f142c) from bmlib.data.Pool and
# CyclingSource, before the stream moved: 65,536 events, batches of 4,096;
# sha256 of id, of price, of the first two served batches' timestamps
PARENT = {
    (5, 50): "51b238711f13c50b3b9026e2cbe22ca377bade06769fdfb179eaa6d34a6373bd",
    (5, 1000): "e81bef7b71f77b65d1b99bf245ae494444f1547b6a8fcc693ddd28149c821b09",
    (8008, 50): "00fc7463895eb01c6a4b73b448e46f2aefafe9ba68203a4cd6640c1861580a08",
    (8008, 1000): "41d0e481553cdc3fc02a2425ea92e04fae16a1124253cdb37882699c89b910e9",
}
PARENT_PRICE = {
    5: "7f3765c41d41f9c56d0a3a07b8619c64b0a30df54e57d755c2a9e660b130cd2e",
    8008: "e0b782c2249594dd2371c14971f4e215a8011bda4282c163e02688fd8add2f00",
}
PARENT_TS01 = "96f504e9d85316b14c75d1e9f98c98529241a69c41e2e8753a05f3357431e47f"
# all four columns of the second batch served, and of the second batch of
# the second cycle with its timestamps
PARENT_BATCHES = {
    (5, 50): ("450b4ca78aed957eaab803ec52d3b4f5d863e6f9b3396858ddaefdfabe8a7bc4",
              "5afd9bbaac6f59972be1140abba119d0b0849633a52ccd2397b4d6ada2aac1dc"),
    (8008, 1000): (
        "918e33e1d02334b2b8f17dfba152d1333c98d4994b5af67b218bc4fea5de4b2a",
        "2327646fc8ceeb4dde21348e63b0f71fdad3178cabba522615b73482074db1f2"),
}


@pytest.mark.parametrize("seed, n_ids", list(PARENT))
def test_uniform_is_byte_identical_to_the_parent(seed, n_ids):
    from bmlib.data import make_schema

    cfg = _uniform(n_ids)
    pool = make_pool(cfg, seed, 65_536)
    whole = pool.columns(0, 65_536, ("id", "price"))
    assert _digest(whole["id"]) == PARENT[seed, n_ids]
    assert _digest(whole["price"]) == PARENT_PRICE[seed]
    schema = make_schema(cfg)
    serve = pool.server(
        4_096, lambda f, s: schema.string_tables[f].intern(s))
    (_, ts0), (cols1, ts1) = serve(0), serve(1)
    assert _digest(ts0, ts1) == PARENT_TS01
    if (seed, n_ids) in PARENT_BATCHES:
        cols17, ts17 = serve(17)
        assert (_digest(*cols1.values()),
                _digest(*cols17.values(), ts17)) == PARENT_BATCHES[seed, n_ids]


def test_nexmark_proportions_hot_auction_and_id_growth():
    fields = [["event_type", "int"], ["id", "long"], ["auction", "long"],
              ["dateTime", "long"]]
    pool = make_pool(_nexmark(fields=fields), 5, 1_100_000)
    c = pool.columns(0, 1_100_000)
    kinds = c["event_type"].reshape(-1, 50)
    assert np.all(kinds[:, 0] == 0) and np.all(kinds[:, 1:4] == 1)
    assert np.all(kinds[:, 4:] == 2)
    bids = c["event_type"] == 2
    assert bids.sum() > 1_000_000
    auction = c["auction"][bids]
    newest = np.maximum.accumulate(
        np.where(c["event_type"] == 1, c["id"], 0))[bids]
    assert abs(np.mean(auction == newest // 100 * 100) - 0.5) < 0.01
    assert np.all(auction <= newest + 10) and np.all(auction >= 1_000)
    assert np.all(auction >= newest - 100)
    assert not c["auction"][~bids].any() and not c["id"][bids].any()
    # ids open in order: an auction's id is 1,000 + its rank
    opened = c["id"][c["event_type"] == 1]
    assert np.array_equal(opened, 1_000 + np.arange(len(opened)))


def test_nexmark_a_window_at_a_million_a_second_holds_600k_auctions():
    """... and the pool is a tenth of it: the ids churn across cycles."""
    fields = [["event_type", "int"], ["auction", "long"],
              ["dateTime", "long"]]
    pool = make_pool(_nexmark(1_000_000, fields=fields), 5, 1_000_000)
    c = pool.columns(0, 10_000_000)
    assert c["dateTime"][-1] - c["dateTime"][0] == 9_999
    distinct = len(np.unique(c["auction"][c["event_type"] == 2]))
    assert 590_000 < distinct < 610_000
    # thousands of events share a millisecond: no timestamp is an index
    assert pool.index_of(pool.ts_of(5_000)) == 5_999


def test_nexmark_cycled_equals_generated_directly():
    """Past the young stream, cycle c is the stream generated directly
    from the global event number with cycle 0's draws: a hot draw names
    the hot id of its own time, a cold draw keeps its place among the
    newest ids."""
    n, first = 100_000, 50_000
    pool = make_pool(_nexmark(first_event_number=first), 3, n)
    zero, later = pool.columns(0, n), pool.columns(3 * n, 4 * n)

    def newest(e):  # (newest person, newest auction, offset) at event e
        epoch, off = e // 50, e % 50
        return epoch, epoch * 3 + np.where(
            off == 0, -1, np.minimum(off - 1, 2)), off

    p0, a0, off = newest(first + np.arange(n))
    p3, a3, _ = newest(first + np.arange(3 * n, 4 * n))
    person, auction, bid = off == 0, (off > 0) & (off < 4), off >= 4
    assert np.array_equal(later["id"][person], 1_000 + p3[person])
    assert np.array_equal(later["id"][auction], 1_000 + a3[auction])
    for col, rows, new0, new3, plus in (
            ("auction", bid, a0, a3, 0), ("bidder", bid, p0, p3, 1),
            ("seller", auction, p0, p3, 0)):
        hot = zero[col] == 1_000 + new0 // 100 * 100 + plus
        assert 0.4 < hot[rows].mean() < 0.8
        assert np.array_equal(
            later[col][rows & hot],
            (1_000 + new3 // 100 * 100 + plus)[rows & hot])
        cold = rows & ~hot
        assert np.array_equal((later[col] - new3)[cold],
                              (zero[col] - new0)[cold])
        assert not later[col][~rows].any()
    for col in ("event_type", "category", "price", "reserve"):
        assert np.array_equal(zero[col], later[col])
    assert np.array_equal(later["dateTime"],
                          pool.ts_of(np.arange(3 * n, 4 * n)))
    assert np.all((later["expires"] > later["dateTime"])[auction])


@pytest.mark.parametrize("change, word", [
    ({"fields": NX_FIELDS + [["name", "string"]]}, "'name'"),
    ({"fields": [["dateTime", "int"]]}, "dateTime"),
    ({"hot_auction_ratio": None}, "hot_auction_ratio"),
    ({"event_time_rate": 3_000}, "whole number of ms"),
])
def test_nexmark_refuses_what_it_cannot_make(change, word):
    cfg = _nexmark()
    cfg.update(change)
    if change.get("hot_auction_ratio", 0) is None:
        del cfg["hot_auction_ratio"]
    with pytest.raises(ValueError, match=word):
        make_pool(cfg, 1, 20_000)
    with pytest.raises(ValueError, match="cannot cycle"):
        make_pool(_nexmark(), 1, 16_384)


def test_uniform_refuses_other_fields():
    cfg = _uniform()
    cfg["fields"] = cfg["fields"][:3]
    with pytest.raises(ValueError, match="uniform makes"):
        make_pool(cfg, 1, 4_096)


def test_sink_counts_a_shared_tick_only_when_the_next_has_begun():
    """Four events a millisecond. A delivery whose newest row is stamped
    t is complete through the last event of t - 1; the rows of t count
    once a later delivery has passed it. Never overstated."""
    pool = make_pool(_nexmark(4_000, fields=[["dateTime", "long"]]), 1,
                     20_000)
    sink = DeliverySink(None, SampleRanges(1, 20_000, 2_000, 250), pool)
    sink.recording = True
    t0 = int(pool.ts_of(0))

    def deliver(stamps):
        ts = np.asarray(stamps, np.int64) + t0
        sink.accept_columns(ts, {"x": np.zeros(len(ts))})

    deliver([0, 0, 1, 2, 2])  # events 0..11 exist; tick 2 may go on
    assert (sink.lo[0], sink.hi[0], sink.top[0]) == (3, 7, 11)
    assert (sink.rows[0], sink.tail[0]) == (5, 2)
    deliver([2, 3])  # the rest of tick 2, and one row of tick 3
    deliver([3, 3])  # a delivery inside one tick completes nothing new
    deliver([3, 5, 6])
    assert sink.hi == [7, 11, 11, 23] and sink.top == [11, 15, 15, 27]
    assert sink.tail == [2, 1, 2, 1]
    # rows with an index in (hi[0], hi[n - 1]]
    assert sink.rows_between(2) == 2 + 2 - 1  # ticks 2: three rows
    assert sink.rows_between(3) == 3  # tick 3 is still open
    assert sink.rows_between(4) == 2 + 2 + 2 + 3 - 1  # ticks 2, 3, 5
    # a clock with one event a tick: complete with the row, no tail
    exact = DeliverySink(None, sink.ranges, make_pool(_uniform(), 1, 4_096))
    exact.recording = True
    exact.accept_columns(np.array([1_000, 1_001, 1_007]), {})
    assert (exact.lo, exact.hi, exact.top, exact.tail) == ([0], [7], [7], [0])
