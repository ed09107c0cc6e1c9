"""Plain reference for ``linear_road_rows4``: Linear Road's
stopped-vehicle rule as a partitioned row window.

Over position reports (``type == 0``), per ``vid``: the vehicle's last
four reports (CQL's ``[Partition By vid Rows 4]``). A report that is its
vehicle's fourth or later in a row at one ``(lane, pos)`` gives one row:
``(vid, xway, dir, seg, lane, pos, n, pos_lo, pos_hi, lane_lo,
lane_hi)``, ``n`` the window's size and the four bounds its least and
greatest ``pos`` and ``lane``. Rows in stream order, stamped with their
report's ``time``; a row's index is the last event of its tick
(``pool.index_of``), as the sink reads it.

``@purge``: a vehicle whose last report lies ``IDLE_MS + INTERVAL_MS``
or more behind is forgotten and its window starts anew; under
``IDLE_MS`` it is remembered. In between the answer is not specified
(Siddhi's purge runs every ``interval`` and forgets what has been idle
for ``idle.period``), and a stream that puts a report there is refused.

numpy and the pool alone, nothing of the program: the reports of a span
sorted by (vid, position in the stream), cut where the vehicle changes
or was forgotten, each report looking back over its run. A range's rows
need the reports back to where every window kept is certain: the read
starts ``IDLE_MS + INTERVAL_MS`` before the range and goes further back
while a report that could still qualify has fewer than three reports
before it in the span and a vehicle that could have been remembered
from before it, to the stream's start if need be.
"""

import numpy as np

ROWS = 4
IDLE_MS, INTERVAL_MS = 90_000, 30_000  # the query's @purge
FORGET_MS = IDLE_MS + INTERVAL_MS
REPORT = 0
COLUMNS = ("@idx", "@ts", "vid", "xway", "dir", "seg", "lane", "pos", "n",
           "pos_lo", "pos_hi", "lane_lo", "lane_hi")


def _bf16(x):
    """float values rounded to bfloat16 (nearest even), as float64: the
    control's precision (``bmlib/compare.py:bf16_round``, copied: this
    file imports nothing of the benchmark either)."""
    u = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def _tick_start(pool, i):
    """The first event of the tick that holds event ``i``."""
    return int(pool.index_of(int(pool.ts_of(i)) - 1)) + 1


def expected(pool, a, b, precision="f64"):
    """Rows whose index is a stream event a <= i < b: those of the
    reports in the ticks that end there."""
    ea, eb = _tick_start(pool, a), _tick_start(pool, b)
    reach = FORGET_MS
    while True:
        lo = max(int(pool.index_of(int(pool.ts_of(ea)) - reach - 1)) + 1, 0)
        rows, certain = _stopped(pool, lo, ea, max(eb, ea), precision)
        if lo == 0 or certain:
            return rows
        reach *= 2


def _stopped(pool, lo, ea, eb, precision):
    """(the rows of reports ea <= i < eb, whether each is certain
    without the stream before ``lo``), as if the stream began at lo."""
    cols = pool.columns(lo, eb, ("type", "vid", "lane", "pos"))
    report = cols["type"] == REPORT
    # a vehicle's reports side by side and in order; the requests first
    key = np.where(report, cols["vid"], -1).astype(np.int64)
    order = np.argsort(key, kind="stable")[len(key) - int(report.sum()):]
    vid = key[order]
    when = np.asarray(pool.ts_of(order + lo), dtype=np.int64)
    lane = cols["lane"][order].astype(np.int64)
    pos = cols["pos"][order].astype(np.int64)
    if precision == "bf16":
        # the control: positions kept in bfloat16 (8 bits of mantissa: a
        # mile of road is one or two values) merge a slow vehicle's
        # reports into stops that are none
        pos = _bf16(pos)
    same = np.zeros(len(vid), bool)
    same[1:] = vid[1:] == vid[:-1]
    gap = np.zeros(len(vid), np.int64)
    gap[1:] = when[1:] - when[:-1]
    if np.any(same & (gap >= IDLE_MS) & (gap < FORGET_MS)):
        raise ValueError(
            "a vehicle reports again between idle.period and idle.period "
            "+ interval after its last report: @purge leaves that open")
    head = ~same | (gap >= FORGET_MS)  # a new vehicle, or one forgotten
    i = np.arange(len(vid))
    rank = i - np.maximum.accumulate(np.where(head, i, 0))
    inside = order + lo >= ea
    # where it was at its last report too: only such a report can be at
    # one place with all its window; the windows of those alone are read
    as_before = np.zeros(len(vid), bool)
    as_before[1:] = (
        ~head[1:] & (pos[1:] == pos[:-1]) & (lane[1:] == lane[:-1]))
    # a run that is short and begins where the span does may be the end
    # of a longer one: certain only if nothing before the span could
    # have been remembered when it began
    doubt = np.flatnonzero(
        inside & (rank < ROWS - 1) & ((rank == 0) | as_before))
    run_head = doubt - rank[doubt]
    certain = not np.any(
        ~same[run_head]
        & (when[run_head] - int(pool.ts_of(lo)) < FORGET_MS))
    at = np.flatnonzero(inside & as_before)
    at = at[np.argsort(order[at], kind="stable")]  # stream order
    n = np.minimum(rank[at] + 1, ROWS)
    bounds = {}
    for name, col in (("pos", pos), ("lane", lane)):
        lo_, hi_ = col[at].copy(), col[at].copy()
        for k in range(1, ROWS):  # the k-th report before, where there is
            has = rank[at] >= k
            before = col[at - k]
            lo_ = np.where(has, np.minimum(lo_, before), lo_)
            hi_ = np.where(has, np.maximum(hi_, before), hi_)
        bounds[name] = lo_, hi_
    keep = ((n == ROWS) & (bounds["pos"][0] == bounds["pos"][1])
            & (bounds["lane"][0] == bounds["lane"][1]))
    at = at[keep]
    rest = pool.columns(ea, max(eb, ea + 1), ("xway", "dir", "seg"))
    rows = {
        "@idx": np.asarray(pool.index_of(when[at]), dtype=np.int64),
        "@ts": when[at],
        "vid": vid[at],
        "lane": lane[at],
        "pos": pos[at],
        "n": n[keep].astype(np.int64),
        "pos_lo": bounds["pos"][0][keep], "pos_hi": bounds["pos"][1][keep],
        "lane_lo": bounds["lane"][0][keep],
        "lane_hi": bounds["lane"][1][keep],
    }
    for name in ("xway", "dir", "seg"):
        rows[name] = rest[name][order[at] + lo - ea].astype(np.int64)
    return rows, certain
