"""The benchmark's streams, guarded in tier-1 (numpy only; the twin of
``benchmark/tests/test_generators.py``): the ``uniform`` stream's bytes
pinned to the digests computed before it moved (PR 26), the generators'
contract, and the NEXmark stream's invariants at 10^5 events."""

import hashlib
import importlib.util
import json
import os

import numpy as np
import pytest

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")


def _module(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"t1_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def make_pool(cfg, seed, n):
    return _module("generators", cfg["generator"]).make_pool(seed, n, cfg)


NEXMARK = _module("generators", "nexmark")
NX_FIELDS = [[name, "int" if name in ("event_type", "category") else "long"]
             for name in NEXMARK.COLUMNS]


def _nexmark(rate=2_000, fields=NX_FIELDS, **kw):
    return {**NEXMARK.SOURCE_DEFAULTS, "generator": "nexmark",
            "fields": fields, "event_time_rate": rate, **kw}


def _uniform(n_ids=50):
    return {**_config("pattern3"), "n_ids": n_ids}


def _digest(*arrays):
    m = hashlib.sha256()
    for a in arrays:
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()


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
    serve = pool.server(batch, lambda _field, _value: 0)
    for j in (0, 1, n // batch - 1, n // batch, 2 * (n // batch) + 3):
        cols, ts = serve(j)
        want = pool.columns(j * batch, (j + 1) * batch)
        for name, kind in cfg["fields"]:
            if kind != "string":
                assert cols[name].dtype == want[name].dtype
                assert np.array_equal(cols[name], want[name]), (j, name)
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


# sha256 of id and of price over 65,536 events, computed at the commit
# before the stream moved into generators/uniform.py (PR 26)
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


@pytest.mark.parametrize("seed, n_ids", list(PARENT))
def test_uniform_is_byte_identical_to_the_parent(seed, n_ids):
    whole = make_pool(_uniform(n_ids), seed, 65_536).columns(
        0, 65_536, ("id", "price"))
    assert _digest(whole["id"]) == PARENT[seed, n_ids]
    assert _digest(whole["price"]) == PARENT_PRICE[seed]


def test_nexmark_proportions_hot_auction_and_id_growth():
    fields = [["event_type", "int"], ["id", "long"], ["auction", "long"],
              ["dateTime", "long"]]
    pool = make_pool(_nexmark(fields=fields), 5, 100_000)
    c = pool.columns(0, 100_000)
    kinds = c["event_type"].reshape(-1, 50)
    assert np.all(kinds[:, 0] == 0) and np.all(kinds[:, 1:4] == 1)
    assert np.all(kinds[:, 4:] == 2)
    bids = c["event_type"] == 2
    auction = c["auction"][bids]
    newest = np.maximum.accumulate(
        np.where(c["event_type"] == 1, c["id"], 0))[bids]
    assert abs(np.mean(auction == newest // 100 * 100) - 0.5) < 0.02
    assert np.all(auction <= newest + 10) and np.all(auction >= 1_000)
    assert np.all(auction >= newest - 100)
    assert not c["auction"][~bids].any() and not c["id"][bids].any()
    opened = c["id"][c["event_type"] == 1]
    assert np.array_equal(opened, 1_000 + np.arange(len(opened)))


def test_nexmark_cycled_equals_generated_directly():
    """Past the young stream, cycle c is the stream generated directly
    from the global event number with cycle 0's draws."""
    n, first = 100_000, 50_000
    pool = make_pool(_nexmark(first_event_number=first), 3, n)
    zero, later = pool.columns(0, n), pool.columns(3 * n, 4 * n)

    def newest(e):  # (newest person, newest auction, offset) at event e
        epoch, off = e // 50, e % 50
        return epoch, epoch * 3 + np.where(
            off == 0, -1, np.minimum(off - 1, 2)), off

    p0, a0, off = newest(first + np.arange(n))
    p3, a3, _ = newest(first + np.arange(3 * n, 4 * n))
    bid = off >= 4
    hot = zero["auction"] == 1_000 + a0 // 100 * 100
    assert 0.4 < hot[bid].mean() < 0.8
    assert np.array_equal(later["auction"][bid & hot],
                          (1_000 + a3 // 100 * 100)[bid & hot])
    cold = bid & ~hot
    assert np.array_equal((later["auction"] - a3)[cold],
                          (zero["auction"] - a0)[cold])
    assert not later["auction"][~bid].any()
    assert np.array_equal(zero["event_type"], later["event_type"])
    assert np.array_equal(later["dateTime"],
                          pool.ts_of(np.arange(3 * n, 4 * n)))


@pytest.mark.parametrize("change, word", [
    ({"fields": NX_FIELDS + [["name", "string"]]}, "'name'"),
    ({"fields": [["dateTime", "int"]]}, "dateTime"),
    ({"event_time_rate": 3_000}, "whole number of ms"),
])
def test_nexmark_refuses_what_it_cannot_make(change, word):
    cfg = _nexmark()
    cfg.update(change)
    with pytest.raises(ValueError, match=word):
        make_pool(cfg, 1, 20_000)
    with pytest.raises(ValueError, match="cannot cycle"):
        make_pool(_nexmark(), 1, 16_384)


def test_the_q5_configuration_carries_the_sources_shapes():
    cfg = _config("nexmark_q5")
    for key, value in NEXMARK.SOURCE_DEFAULTS.items():
        if key != "first_event_number":
            assert cfg[key] == value, key
    assert cfg["base_time_ms"] == 1436918400000
    assert cfg["first_event_number"] == 50 * cfg["active_people"]
    assert cfg["reduced"] == ["event_time_rate", "queries"]
    assert set(cfg["departures"]) and "hop" in cfg["cql"]
    # the pool of the cell cycles: a multiple of 5,000 and of the batch
    make_pool({**cfg, "event_time_rate": 1_000}, 1, 40_000)
