"""``GroupEncoder`` over several integer columns (schema/encoders.py):
the packed path (one int64 key a row, numpy alone) against the per-row
path (a dict, one Python step a row) on the same batches: the same
codes, the same ``value()``, with expiry, ``mark_new`` and a checkpoint;
fields that widen; columns that do not pack."""

import numpy as np
import pytest

from flink_siddhi_tpu.schema.encoders import GroupEncoder


def _batches(seed, n_batches=30, n=400, spans=(64, 2, 100), churn=0):
    """Batches of three int32 columns, a selection mask and a time
    column; with ``churn`` the keys drift so that old ones die."""
    rng = np.random.default_rng(seed)
    for b in range(n_batches):
        cols = [
            (rng.integers(0, s, n) + (b * churn if j == 2 else 0)).astype(
                np.int32)
            for j, s in enumerate(spans)
        ]
        yield cols, rng.random(n) < 0.8, np.full(n, 1_000 * b, np.int64)


def _per_row(**kw):
    enc = GroupEncoder(**kw)
    enc._pack = lambda cols, select: None
    return enc


def _codes(enc, batches, tick_ms=0):
    out = []
    for cols, select, ticks in batches:
        out.append(enc.intern_rows(
            cols, select, ticks if tick_ms else None, tick_ms))
    return out


@pytest.mark.parametrize("kw, churn", [
    ({}, 0),
    ({"mark_new": True}, 3),
    ({"retain_ticks": 3}, 7),
    ({"retain_ticks": 2, "mark_new": True}, 40),
], ids=["plain", "mark_new", "expiry", "expiry_and_mark_new"])
@pytest.mark.parametrize("spans", [(64, 2, 100), (5, 1, 3), (10 ** 6, 2, 10 ** 5)],
                         ids=["dense", "tiny", "sparse"])
def test_the_packed_path_gives_the_per_row_paths_codes(kw, churn, spans):
    tick_ms = 1_000 if "retain_ticks" in kw else 0
    packed, rowwise = GroupEncoder(**kw), _per_row(**kw)
    got = _codes(packed, _batches(1, spans=spans, churn=churn), tick_ms)
    want = _codes(rowwise, _batches(1, spans=spans, churn=churn), tick_ms)
    for g, w in zip(got, want):
        assert g.dtype == np.int32 and np.array_equal(g, w)
    assert packed._skeys is not None and rowwise._skeys is None
    # keys of few bits look their slots up in a table, wide ones search
    bits = sum(b for _, b in packed._packing)
    assert (packed._dense is not None) == (bits <= GroupEncoder.DENSE_BITS)
    assert (bits <= GroupEncoder.DENSE_BITS) == (spans[0] < 10 ** 6)
    assert len(packed) == len(rowwise) and packed.live == rowwise.live
    assert packed.stats == rowwise.stats
    if "retain_ticks" in kw:
        assert packed.stats["slots_reused"] > 50
    if kw.get("mark_new"):
        assert any((g < 0).any() for g in got[1:])
    live = [s for s, v in enumerate(rowwise._values) if v is not None]
    assert len(live) > 3
    for s in live:
        assert packed.value(s) == rowwise.value(s)
        assert all(type(x) is int for x in packed.value(s))
    assert packed.state_dict() == rowwise.state_dict()


def test_a_row_outside_the_selection_is_not_interned():
    enc = GroupEncoder()
    cols = [np.asarray([1, 2, 1, 9], np.int32), np.asarray([5, 5, 5, 9], np.int64)]
    codes = enc.intern_rows(cols, np.asarray([True, True, True, False]))
    assert codes.tolist() == [0, 1, 0, 0] and len(enc) == 2
    assert enc.value(0) == (1, 5) and enc.value(1) == (2, 5)
    none = enc.intern_rows(cols, np.zeros(4, bool))
    assert none.tolist() == [0, 0, 0, 0] and len(enc) == 2


def test_fields_widen_and_the_keys_keep_their_slots():
    enc, ref = GroupEncoder(), _per_row()
    small = [np.asarray([3, 4, 3], np.int32), np.asarray([0, 1, 1], np.int32)]
    wide = [np.asarray([-70_000, 3, 4], np.int32),
            np.asarray([2 ** 31 - 1, 0, 1], np.int32)]
    every = np.ones(3, bool)
    for cols in (small, wide, small):
        assert np.array_equal(
            enc.intern_rows(cols, every), ref.intern_rows(cols, every))
    assert [enc.value(s) for s in range(len(enc))] == [
        (3, 0), (4, 1), (3, 1), (-70_000, 2 ** 31 - 1)]
    assert enc._packing[0][0] == -70_000


@pytest.mark.parametrize("dtypes", [
    (np.int8, np.int8), (np.int64, np.uint8), (np.int16, np.int64),
], ids=lambda d: "-".join(np.dtype(t).name for t in d))
def test_a_narrow_columns_whole_span_packs(dtypes):
    """A field holds a column's value less the lowest seen: 255 for an
    int8 column from -128 to 127, which its own type does not hold."""
    rng = np.random.default_rng(3)
    enc, ref = GroupEncoder(mark_new=True), _per_row(mark_new=True)
    for _ in range(6):
        cols = [rng.integers(-128 if np.dtype(t).kind == "i" else 0,
                             128, 300).astype(t) for t in dtypes]
        select = rng.random(300) < 0.9
        assert np.array_equal(
            enc.intern_rows(cols, select), ref.intern_rows(cols, select))
    assert enc._dense is not None and len(enc) == len(ref) > 1000
    assert all(enc.value(s) == ref.value(s) for s in range(len(ref)))


def test_a_restored_table_goes_on_with_the_same_slots():
    a, b = GroupEncoder(retain_ticks=3), GroupEncoder(retain_ticks=3)
    batches = list(_batches(5, n_batches=24, churn=9))
    first = _codes(a, batches[:12], 1_000)
    b.load_state_dict(a.state_dict())
    rest_a = _codes(a, batches[12:], 1_000)
    rest_b = _codes(b, batches[12:], 1_000)
    assert len(first) == 12
    for x, y in zip(rest_a, rest_b):
        assert np.array_equal(x, y)
    assert a.state_dict() == b.state_dict()


def test_columns_that_do_not_pack_take_the_dict():
    """Three columns that need 32 bits each do not fit 62: the per-row
    path takes them, with the same codes as ever."""
    rng = np.random.default_rng(2)
    cols = [rng.integers(-2 ** 31, 2 ** 31 - 1, 50).astype(np.int32)
            for _ in range(3)]
    enc = GroupEncoder()
    codes = enc.intern_rows(cols, np.ones(50, bool))
    assert enc._skeys is None and codes.tolist() == list(range(50))
    floats = [np.asarray([1.5, 1.5]), np.asarray([2, 2], np.int32)]
    assert GroupEncoder().intern_rows(floats, np.ones(2, bool)).tolist() == [0, 0]
