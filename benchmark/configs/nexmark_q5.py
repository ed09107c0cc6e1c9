"""Plain reference for ``nexmark_q5``: NEXmark query 5, "hot items".

Over bids (``event_type == 2``), for every window end ``e`` that is a
multiple of 2,000 ms of ``dateTime`` (epoch-aligned, as SQL's ``HOP``
cuts them) and whose window ``[e - 10,000, e)`` holds at least one bid,
the partial windows at the stream's start included: ``num(a)`` is the
number of bids on auction ``a`` in the window, and the window's rows are
``(auction, num)`` for every auction whose ``num`` is the window's
maximum, ties all emitted, in auction order. A row is stamped with its
window's last millisecond, ``e - 1``, so its index (the pool's
``index_of``) is the window's last event, as the sink reads it.

A 10 s window is five 2 s panes. Each pane's bids are counted once
(auctions and their counts); a window merges its five. numpy and the
pool alone: nothing of the program.

The stream's cycles repeat (the generators' contract: the draws of
event ``i`` are those of ``i % pool.n``, ids and times run on), and a
window reaches back less than a cycle, so rows asked for in cycle 2 or
later are cycle 1's rows moved by whole cycles: counted once a run
(``_direct``), then shifted by what the pool's own columns say a cycle
adds to an auction id and to ``dateTime``.
``benchmark/tests/test_nexmark_q5.py`` holds the moved rows to the direct
count.
"""

import numpy as np

SIZE_MS, SLIDE_MS = 10_000, 2_000
BID = 2
FIELDS = ("event_type", "auction")


def _pane(pool, p):
    """(auctions, bids on each) of pane p: SLIDE_MS * p <= dateTime <
    SLIDE_MS * (p + 1)."""
    lo = max(int(pool.index_of(p * SLIDE_MS - 1)) + 1, 0)
    hi = int(pool.index_of((p + 1) * SLIDE_MS - 1)) + 1
    if hi <= lo:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    cols = pool.columns(lo, hi, FIELDS)
    bids = cols["auction"][cols["event_type"] == BID]
    if not len(bids):
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    # a pane's auction ids lie close together: count them in place
    first = int(bids.min())
    num = np.bincount(bids - first)
    ids = np.flatnonzero(num)
    return ids + first, num[ids]


_MEMO = {}


def expected(pool, a, b, precision="f64"):
    """Rows whose index is a stream event a <= i < b."""
    back = (a // pool.n - 1) * pool.n
    if back <= 0:
        return _direct(pool, a, b, precision)
    key = (id(pool), a - back, b - back, precision)
    if key not in _MEMO:
        _MEMO[key] = _direct(pool, a - back, b - back, precision)
    rows = _MEMO[key]
    # what `back` events add: to dateTime, and to a bid's auction id
    ms = int(pool.ts_of(back)) - int(pool.ts_of(0))
    head = pool.columns(0, 50, FIELDS)
    bid = int(np.flatnonzero(head["event_type"] == BID)[0])
    ids = int(pool.columns(bid + back, bid + back + 1, FIELDS)["auction"][0]
              - head["auction"][bid])
    return {
        "@idx": rows["@idx"] + back,
        "@ts": rows["@ts"] + ms,
        "auction": rows["auction"] + ids,
        "num": rows["num"],
    }


def _direct(pool, a, b, precision):
    out = {k: [] for k in ("@idx", "@ts", "auction", "num")}
    first = int(pool.ts_of(a)) // SLIDE_MS
    last = int(pool.ts_of(b - 1)) // SLIDE_MS + 1
    panes = {}
    for q in range(first, last + 1):  # the window that ends where pane q starts
        e = q * SLIDE_MS
        idx = int(pool.index_of(e - 1))
        if not a <= idx < b:
            continue
        for p in range(q - SIZE_MS // SLIDE_MS, q):
            if p not in panes:
                panes[p] = _pane(pool, p)
        ids = np.concatenate([panes[p][0] for p in range(q - 5, q)])
        if not len(ids):
            continue  # a window without a bid emits nothing
        ids, inv = np.unique(ids, return_inverse=True)
        num = np.bincount(
            inv, np.concatenate([panes[p][1] for p in range(q - 5, q)])
        ).astype(np.int64)
        if precision == "bf16":
            # the control: a count kept in bfloat16 stops at 256 (256 + 1
            # rounds back to 256), which thousands of hot auctions pass in
            # every window: they all tie at the top
            num = np.minimum(num, 256)
        top = ids[num == num.max()]
        out["@idx"].append(np.full(len(top), idx, np.int64))
        out["@ts"].append(np.full(len(top), e - 1, np.int64))
        out["auction"].append(top.astype(np.int64))
        out["num"].append(np.full(len(top), num.max(), np.int64))
    return {
        k: np.concatenate(v) if v else np.zeros(0, np.int64)
        for k, v in out.items()
    }
