"""``GroupEncoder`` over several integer columns (schema/encoders.py):
the packed path (one int64 key a row, numpy alone) against the per-row
path (a dict, one Python step a row) on the same batches: the same
codes, the same ``value()``, with expiry, ``mark_new`` and a checkpoint;
fields that widen; columns that do not pack. And over ONE column whose
values lie far apart (the sparse path: one sort of the batch, a main and
a side table) against a plain per-row reference written here."""

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


# -- one column, values far apart (the sparse path) ---------------------------

class _PlainTable:
    """The table's rules a row at a time, in a dict: a key keeps its
    slot; a batch's new keys take slots in sorted order, the last
    freed slots first and fresh ones after them; under ``mark_new``
    every row of such a key carries ``~slot``; a slot is stamped with
    the batch's tick (that of its last selected row, never behind an
    earlier batch's) and freed, in slot order, by the first call after
    the tick moved once ``retain_ticks`` ticks have passed the stamp."""

    def __init__(self, retain_ticks=None, mark_new=False):
        self.retain, self.mark_new = retain_ticks, mark_new
        self.slot_of, self.key_at, self.stamp, self.free = {}, [], [], []
        self.tick = self.swept = None
        self.stats = {"interned": 0, "slots_reused": 0, "expired": 0}

    def __len__(self):
        return len(self.key_at)

    @property
    def live(self):
        return len(self.slot_of)

    def intern(self, col, select, ticks, tick_ms):
        out = [0] * len(select)
        rows = [i for i in range(len(select)) if select[i]]
        tick = None
        if self.retain is not None:
            if self.tick is not None and self.tick != self.swept:
                self.swept = self.tick
                for s, key in enumerate(self.key_at):
                    if key is not None and (
                            self.stamp[s] + self.retain <= self.tick):
                        del self.slot_of[key]
                        self.key_at[s] = None
                        self.free.append(s)
                        self.stats["expired"] += 1
            if rows:
                tick = max(int(ticks[rows[-1]]) // tick_ms, self.tick or 0)
        new = sorted({col[i].item() for i in rows} - set(self.slot_of))
        take = min(len(new), len(self.free))
        slots = self.free[len(self.free) - take:] + list(
            range(len(self.key_at), len(self.key_at) + len(new) - take))
        del self.free[len(self.free) - take:]
        self.stats["interned"] += len(new)
        self.stats["slots_reused"] += take
        for key, s in zip(new, slots):
            self.slot_of[key] = s
            if s == len(self.key_at):
                self.key_at.append(key)
                self.stamp.append(0)
            else:
                self.key_at[s] = key
        fresh = set(new)
        for i in rows:
            s = self.slot_of[col[i].item()]
            out[i] = ~s if self.mark_new and col[i].item() in fresh else s
            if tick is not None:
                self.stamp[s] = tick
        if tick is not None:
            self.tick = tick
        return out


def _sparse_stream(dtype, seed=11, n=600):
    """(column, selection, time column) a batch: 40 batches, four a
    tick of 1,000 ms, over keys drawn far apart in ``dtype``'s range.
    The 400 keys in use slide through a pool, a dozen born a batch (so
    the side table fills over a few batches before it is merged in) and
    as many left to die; rows repeat keys; the selection has holes. By
    turns a batch has
    no new key (7, 19: the batch before, again), new keys only (12),
    no selected row (9, 23) and a last row stamped three ticks late
    (15)."""
    rng = np.random.default_rng(seed)
    # int64 keys span 2^63 (no room for a row number beside them: the
    # merge sort), the 32-bit ones ride with it in one word; floats are
    # quarters of 32-bit keys
    top = np.iinfo(np.int32 if dtype is np.float64 else dtype).max
    pool = np.unique(rng.integers(0, top, 3_000, dtype=np.int64))
    if dtype is np.float64:
        pool = pool / 4
    rng.shuffle(pool)
    out = []
    for b in range(40):
        if b in (7, 19):
            col, select, ticks = out[-1]
            out.append((col.copy(), select.copy(), ticks + 250))
            continue
        at = 12 * b if b != 12 else 2_400
        col = pool[at + rng.integers(0, 400, n)].astype(dtype)
        select = rng.random(n) < (0.0 if b in (9, 23) else 0.8)
        ticks = np.full(n, 250 * b, np.int64)
        if b == 15:
            ticks[np.flatnonzero(select)[-1]] -= 3_000
        out.append((col, select, ticks))
    return out


def _spy_on_the_sort(monkeypatch):
    calls, sort = [], GroupEncoder._sorted_runs

    def spy(vals):
        calls.append(len(vals))
        return sort(vals)

    monkeypatch.setattr(GroupEncoder, "_sorted_runs", staticmethod(spy))
    return calls


@pytest.mark.parametrize("dtype", [np.int32, np.int64, np.uint32, np.float64],
                         ids=lambda t: np.dtype(t).name)
@pytest.mark.parametrize("mark_new", [False, True], ids=["plain", "mark_new"])
@pytest.mark.parametrize("retain", [None, 2], ids=["keep", "expire"])
def test_the_sparse_path_gives_a_plain_tables_codes(
        retain, mark_new, dtype, monkeypatch):
    sorts = _spy_on_the_sort(monkeypatch)
    enc = GroupEncoder(retain_ticks=retain, mark_new=mark_new)
    ref = _PlainTable(retain, mark_new)
    merged = 0
    for b, (col, select, ticks) in enumerate(_sparse_stream(dtype)):
        side = 0 if enc._nkeys is None else len(enc._nkeys)
        got = enc.intern_rows([col], select, ticks, 1_000)
        assert got.dtype == np.int32
        assert got.tolist() == ref.intern(col, select, ticks, 1_000), b
        assert (len(enc), enc.live, enc.stats) == (
            len(ref), ref.live, ref.stats), b
        assert len(enc._skeys) + len(enc._nkeys) == enc.live
        merged += len(enc._nkeys) < side
    # every batch with a row went through the one sort, and the side
    # table was both filled and merged in along the way
    assert len(sorts) == 38 and merged > 3
    assert enc.stats["interned"] > 1_000
    if retain is not None:
        # ten ticks: sweeps, and freed slots handed out again
        assert enc.stats["expired"] > 500
        assert enc.stats["slots_reused"] > 300
        assert len(enc) < 800
    for s, key in enumerate(ref.key_at):
        if key is not None:
            assert enc.value(s) == (key,)
    assert enc.state_dict()["values"] == [
        None if k is None else (k,) for k in ref.key_at]


def test_keys_far_apart_and_close_together_get_the_same_codes(monkeypatch):
    """The same key stream once spread wide (the sort) and once close
    together (mark and look up): the order of the keys is the same, so
    are the codes."""
    sorts = _spy_on_the_sort(monkeypatch)
    wide = GroupEncoder(retain_ticks=2, mark_new=True)
    close = GroupEncoder(retain_ticks=2, mark_new=True)
    rng = np.random.default_rng(4)
    for b in range(36):
        ids = (20 * b + rng.integers(0, 90, 200)).astype(np.int64)
        select = rng.random(200) < 0.7
        ticks = np.full(200, 300 * b, np.int64)
        a = wide.intern_rows([ids * 1_000_003 - 7], select, ticks, 1_000)
        assert len(sorts) == b + 1
        c = close.intern_rows([ids + 5_000], select, ticks, 1_000)
        assert len(sorts) == b + 1
        assert np.array_equal(a, c), b
    assert wide.stats == close.stats and wide.stats["slots_reused"] > 300
    assert (a < 0).any()


@pytest.mark.parametrize("dtype", [np.int64, np.uint32],
                         ids=lambda t: np.dtype(t).name)
def test_a_table_restored_between_two_sweeps_goes_on_alike(dtype):
    """A snapshot taken while the side table holds keys the main one
    lacks: the restored table hands out the same slots and the same
    ``~slot`` marks, and ``value`` knows the unmerged keys."""
    stream = _sparse_stream(dtype)
    a = GroupEncoder(retain_ticks=2, mark_new=True)
    cut = None
    for b, (col, select, ticks) in enumerate(stream):
        a.intern_rows([col], select, ticks, 1_000)
        # sweeps behind it and ahead of it, and keys unmerged
        if b >= 16 and len(a._nkeys) > 5:
            cut = b + 1
            break
    assert cut is not None and a.stats["expired"] > 0
    for key, slot in zip(a._nkeys.tolist(), a._nslots.tolist()):
        assert a.value(slot) == (key,)
        assert key not in a._skeys
    snap = a.state_dict()
    b_ = GroupEncoder(retain_ticks=2, mark_new=True)
    b_.load_state_dict(snap)
    marks = 0
    for col, select, ticks in stream[cut:]:
        x = a.intern_rows([col], select, ticks, 1_000)
        y = b_.intern_rows([col], select, ticks, 1_000)
        assert np.array_equal(x, y)
        marks += int((x < 0).sum())
    assert marks > 100 and a.stats["expired"] > 500
    assert a.state_dict() == b_.state_dict()
    assert b_.stats["slots_reused"] > 100


@pytest.mark.parametrize("dtype, lo, hi", [
    (np.int32, -2 ** 31, 2 ** 31 - 1),
    (np.uint32, 0, 2 ** 32 - 1),
    (np.int64, -7 * 10 ** 9, 9 * 10 ** 10),
    (np.int64, -2 ** 63, 2 ** 63 - 1),
    (np.uint64, 2 ** 63 - 50, 2 ** 63 + 50),
    (np.float64, -1_000, 1_000),
], ids=["int32", "uint32", "int64_in_one_word", "int64_too_wide",
        "uint64_past_int64", "float64"])
def test_the_one_sort_is_the_stable_sort_whatever_the_type(dtype, lo, hi):
    """Values that leave room for the row number are sorted as one word
    with it, the rest by a merge sort: the same order, the values in
    their own type, the runs' heads where a value repeats."""
    rng = np.random.default_rng(8)
    if dtype is np.float64:
        distinct = rng.integers(lo, hi, 300) / 8
    else:
        distinct = rng.integers(lo, hi, 300, dtype=dtype, endpoint=True)
    for vals in (rng.choice(distinct, 1_000), np.unique(distinct),
                 distinct[:1]):
        vals = vals.astype(dtype)
        order, ranked, head = GroupEncoder._sorted_runs(vals)
        want = np.argsort(vals, kind="stable")
        assert np.array_equal(order, want)
        assert ranked.dtype == vals.dtype
        assert np.array_equal(ranked, vals[want])
        uniq, first = np.unique(vals, return_index=True)
        if len(uniq) == len(vals):
            assert head is None
        else:
            assert np.array_equal(ranked[head], uniq)
            assert np.array_equal(order[head], first)
