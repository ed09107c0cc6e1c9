"""Plain reference for ``nexmark_q8``: NEXmark query 8, "monitor new
users".

Window ``k`` is ``[10,000 k, 10,000 (k + 1))`` ms of ``dateTime``,
epoch-aligned. For every window, for every person (``event_type == 0``)
in it whose ``id`` is the ``seller`` of at least one auction
(``event_type == 1``) **of the same window**: one row ``(id,
auctions)``, ``auctions`` the number of those auctions (times the
person's events, should an id register twice: the joined pairs),
whichever of the two came first in the stream; rows of a window in
``id`` order, windows in order. A row is stamped with its window's last
millisecond, so its index (the pool's ``index_of``) is the window's
last event, as the sink reads it.

numpy and the pool alone, nothing of the program: a window's persons
and sellers are each counted in place (their ids lie close together),
and the rows are the ids both counts hold.

The stream's cycles repeat (the generators' contract: the draws of
event ``i`` are those of ``i % pool.n``, ids and times run on). Where a
cycle is a whole number of windows, the rows asked for in cycle 2 or
later are cycle 1's rows moved by whole cycles: counted once a run
(``_direct``), then shifted by what the pool's own columns say a cycle
adds to a person id and to ``dateTime``. Where it is not (a window then
straddles the cycle's end differently each time) every range is counted
directly. ``benchmark/tests/test_nexmark_q8.py`` holds the moved rows to
the direct count.
"""

import numpy as np

SIZE_MS = 10_000
PERSON, AUCTION = 0, 1
FIELDS = ("event_type", "id", "seller")
COLUMNS = ("@idx", "@ts", "id", "auctions")


def _counts(ids):
    """(sorted distinct ids, how often each came)."""
    if not len(ids):
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    first = int(ids.min())
    num = np.bincount(ids - first)
    at = np.flatnonzero(num)
    return at + first, num[at]


def _window(pool, k):
    """(ids, auctions) of window k: the persons registered in it that
    also sold in it, in id order."""
    lo = max(int(pool.index_of(k * SIZE_MS - 1)) + 1, 0)
    hi = int(pool.index_of((k + 1) * SIZE_MS - 1)) + 1
    if hi <= lo:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    cols = pool.columns(lo, hi, FIELDS)
    persons, registered = _counts(
        cols["id"][cols["event_type"] == PERSON].astype(np.int64))
    sellers, sold = _counts(
        cols["seller"][cols["event_type"] == AUCTION].astype(np.int64))
    ids, at_p, at_s = np.intersect1d(
        persons, sellers, assume_unique=True, return_indices=True)
    return ids, registered[at_p] * sold[at_s]


_MEMO = {}


def expected(pool, a, b, precision="f64"):
    """Rows whose index is a stream event a <= i < b."""
    rows = _rows(pool, a, b)
    if precision == "bf16":
        # the control: an id held in bfloat16 keeps eight bits. (A
        # count in bfloat16 is no control here: a seller's auctions stay
        # under 256, which bfloat16 holds exactly.)
        from bmlib.compare import bf16_round

        rows = {**rows, "id": bf16_round(rows["id"]).astype(np.int64)}
    return rows


def _rows(pool, a, b):
    back = (a // pool.n - 1) * pool.n
    span = int(pool.ts_of(pool.n)) - int(pool.ts_of(0))
    if back <= 0 or span % SIZE_MS:
        return _direct(pool, a, b)
    key = (id(pool), a - back, b - back)
    if key not in _MEMO:
        _MEMO[key] = _direct(pool, a - back, b - back)
    rows = _MEMO[key]
    # what `back` events add: to dateTime, and to a person's id
    ms = int(pool.ts_of(back)) - int(pool.ts_of(0))
    head = pool.columns(0, 50, FIELDS)
    person = int(np.flatnonzero(head["event_type"] == PERSON)[0])
    ids = int(
        pool.columns(person + back, person + back + 1, FIELDS)["id"][0]
        - head["id"][person])
    return {
        "@idx": rows["@idx"] + back,
        "@ts": rows["@ts"] + ms,
        "id": rows["id"] + ids,
        "auctions": rows["auctions"],
    }


def _direct(pool, a, b):
    out = {k: [] for k in COLUMNS}
    first = int(pool.ts_of(a)) // SIZE_MS
    last = int(pool.ts_of(b - 1)) // SIZE_MS
    for k in range(first, last + 1):
        end = (k + 1) * SIZE_MS
        idx = int(pool.index_of(end - 1))
        if not a <= idx < b:
            continue
        ids, auctions = _window(pool, k)
        out["@idx"].append(np.full(len(ids), idx, np.int64))
        out["@ts"].append(np.full(len(ids), end - 1, np.int64))
        out["id"].append(ids)
        out["auctions"].append(auctions.astype(np.int64))
    return {
        k: np.concatenate(v) if v else np.zeros(0, np.int64)
        for k, v in out.items()
    }
