"""Plain reference for ``nexmark_q11``: NEXmark query 11, "user
sessions".

Over bids (``event_type == 2``), per ``bidder``: bids less than 10,000
ms of ``dateTime`` apart are one session (a bid 10,000 ms or more after
the bidder's last opens the next). One row a session: ``(bidder,
bid_count, starttime)``, ``starttime`` the session's first
``dateTime``. The row is stamped with the session's last millisecond,
``last + 9,999`` (q11.sql's ``SESSION_END`` less one), so its index
(the pool's ``index_of``) is the last event that could have belonged to
it, as the sink reads it. Rows in stamp order, within a stamp in bidder
order.

numpy and the pool alone, nothing of the program: the bids of a span
sorted by (bidder, position), cut where the bidder changes or the gap
is reached. A range's rows need the bids back to where every session
kept is whole: the read starts two and a quarter gaps before the range
(a row of the range closed a gap after its session's last bid, and the
session's first bid has to lie a gap after the first event read for its
start to be certain) and goes further back while a kept session's start
is not certain, to the stream's start if need be.

The stream's cycles repeat (the generators' contract: the draws of
event ``i`` are those of ``i % pool.n``, ids and times run on), and
sessions need no alignment with the epoch: the rows asked for in cycle
2 or later are cycle 1's rows (or cycle 2's) moved by whole cycles,
counted once a run
(``_direct``), then shifted by what the pool's own columns say a cycle
adds to a bidder id and to ``dateTime``. That holds where cycle 1's
rows are whole without the stream's start (every session kept there
begins a gap or more after event 0, so a stream that began earlier
would have held no more of it); where one is not, cycle 2's rows are
taken, and where those are not whole either the range is counted
directly.
``benchmark/tests/test_nexmark_q11.py`` holds the moved rows to the
direct count.
"""

import numpy as np

GAP_MS = 10_000
BID = 2
FIELDS = ("event_type", "bidder", "dateTime")
COLUMNS = ("@idx", "@ts", "bidder", "bid_count", "starttime")

_MEMO = {}


def expected(pool, a, b, precision="f64"):
    """Rows whose index is a stream event a <= i < b."""
    rows = _rows(pool, a, b)
    if precision == "bf16":
        # the control: a count kept in bfloat16 stops at 256 (256 + 1
        # rounds back to 256), which every hot bidder's session passes
        rows = {**rows, "bid_count": np.minimum(rows["bid_count"], 256)}
    return rows


def _rows(pool, a, b):
    cycle = a // pool.n
    for base in (1, 2):  # cycle 1's rows, else (they lean on the
        if cycle <= base:  # stream's start) cycle 2's
            break
        back = (cycle - base) * pool.n
        key = (id(pool), a - back, b - back)
        if key not in _MEMO:
            _MEMO[key] = _direct(pool, a - back, b - back)
        rows, whole = _MEMO[key]
        if whole:
            return _moved(pool, rows, back)
    return _direct(pool, a, b)[0]


def _moved(pool, rows, back):
    """``rows`` a whole number of cycles (``back`` events) later."""
    # what `back` events add: to dateTime, and to a bid's bidder id
    ms = int(pool.ts_of(back)) - int(pool.ts_of(0))
    head = pool.columns(0, 50, FIELDS)
    bid = int(np.flatnonzero(head["event_type"] == BID)[0])
    ids = int(pool.columns(bid + back, bid + back + 1, FIELDS)["bidder"][0]
              - head["bidder"][bid])
    return {
        "@idx": rows["@idx"] + back,
        "@ts": rows["@ts"] + ms,
        "bidder": rows["bidder"] + ids,
        "bid_count": rows["bid_count"],
        "starttime": rows["starttime"] + ms,
    }


def _sessions(pool, lo, hi):
    """(bidder, bid_count, first, last) of every session among the
    bids of stream events lo <= i < hi, as if the stream began at lo."""
    cols = pool.columns(lo, hi, FIELDS)
    bids = cols["event_type"] == BID
    who = cols["bidder"][bids].astype(np.int64)
    when = cols["dateTime"][bids].astype(np.int64)
    order = np.argsort(who, kind="stable")  # a bidder's bids stay in order
    who, when = who[order], when[order]
    head = np.ones(len(who), bool)
    head[1:] = (who[1:] != who[:-1]) | (when[1:] - when[:-1] >= GAP_MS)
    at = np.flatnonzero(head)
    ends = np.append(at[1:], len(who)) - 1
    return who[at], ends - at + 1, when[at], when[ends]


def _direct(pool, a, b):
    """(rows, whether they are whole without the stream's start: each
    row's session begins a gap or more after the first event read, and
    the range lies a gap or more after event 0)."""
    t_a = int(pool.ts_of(a))
    reach = 2 * GAP_MS + GAP_MS // 4
    while True:
        lo = max(int(pool.index_of(t_a - reach - 1)) + 1, 0)
        who, num, first, last = _sessions(pool, lo, b)
        stamp = last + GAP_MS - 1
        idx = pool.index_of(stamp)
        keep = (idx >= a) & (idx < b)
        # whole: no bid of the session can lie before the first read
        whole = first - int(pool.ts_of(lo)) >= GAP_MS
        if lo == 0 or whole[keep].all():
            break
        reach *= 2
    whole = bool(whole[keep].all())
    assert lo == 0 or whole
    # ... and no session that ended before event 0 would close here
    whole = whole and t_a - GAP_MS >= int(pool.ts_of(0))
    order = np.lexsort((who[keep], stamp[keep]))
    return {
        "@idx": idx[keep][order].astype(np.int64),
        "@ts": stamp[keep][order],
        "bidder": who[keep][order],
        "bid_count": num[keep][order].astype(np.int64),
        "starttime": first[keep][order],
    }, whole
