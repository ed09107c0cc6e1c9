"""Plain reference for ``linear_road_lav5m``: Linear Road's latest
average velocity as a five-minute sliding window.

Over position reports (``type == 0``), one row a report: ``(vid, xway,
dir, seg, lav, n)``, where ``n`` and ``lav`` are the count and the
average ``spd`` of the reports of its segment ``(xway, dir, seg)``
stamped later than ``time - 300,000`` ms, up to and including itself in
stream order (CQL's ``[Range 5 Minutes]`` with ``Group By``: a report
stamped ``T - 300,000`` has left when one stamped ``T`` arrives; the
reports of one second share a stamp and leave together). Rows in stream
order, stamped with their report's ``time``; a row's index is the last
event of its tick (``pool.index_of``), as the sink reads it.

numpy and the pool alone, nothing of the program. The stream is read
tick by tick from five minutes before the range on, in blocks of whole
ticks: per tick and segment the sum and the count of ``spd``, and their
running sums over the ticks, so that the window of a report is the
difference of two running sums (the ticks that are still inside, less
its own) plus its own tick's prefix up to it: a stable sort of the
tick's reports by segment and a cumulative sum. Sums and counts are
int64, ``lav`` is one float64 division.
"""

import numpy as np

SPAN_MS = 300_000  # the query's #window.time(5 min)
REPORT = 0
DIRS, SEGS = 2, 100  # an expressway's directions and segments
BLOCK = 3_000_000  # events read at a time
COLUMNS = ("@idx", "@ts", "vid", "xway", "dir", "seg", "lav", "n")


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


def _ticks(pool, lo, hi):
    """(stamp, first event, end) of each tick of events lo <= i < hi,
    ``lo`` a tick's first event."""
    stamps, firsts, i = [], [], lo
    while i < hi:
        stamp = int(pool.ts_of(i))
        stamps.append(stamp)
        firsts.append(i)
        i = int(pool.index_of(stamp)) + 1
    return (np.asarray(stamps, np.int64), np.asarray(firsts, np.int64),
            np.asarray(firsts[1:] + [max(hi, lo)], np.int64))


def _wider(table, width):
    if width <= table.shape[1]:
        return table
    return np.pad(table, ((0, 0), (0, width - table.shape[1])))


def expected(pool, a, b, precision="f64"):
    """Rows whose index is a stream event a <= i < b: those of the
    reports in the ticks that end there."""
    ea = _tick_start(pool, a)
    eb = max(_tick_start(pool, b), ea)
    h0 = max(int(pool.index_of(int(pool.ts_of(ea)) - SPAN_MS)) + 1, 0)
    stamps, firsts, ends = _ticks(pool, h0, eb)
    # the first tick still inside the window of each tick's reports
    oldest = np.searchsorted(stamps, stamps - SPAN_MS, side="right")
    # run[k] = per segment, the sum (the count) over the ticks before k
    run_s = np.zeros((len(stamps) + 1, 1), np.int64)
    run_n = np.zeros((len(stamps) + 1, 1), np.int64)
    out = {c: [] for c in COLUMNS}
    k0 = 0
    while k0 < len(stamps):
        k1 = k0 + 1
        while (k1 < len(stamps) and firsts[k1] != ea
               and ends[k1] - firsts[k0] <= BLOCK):
            k1 += 1  # (a block ends where the range begins)
        lo, hi = int(firsts[k0]), int(ends[k1 - 1])
        in_range = lo >= ea
        cols = pool.columns(lo, hi, (
            ("type", "spd", "xway", "dir", "seg", "vid") if in_range
            else ("type", "spd", "xway", "dir", "seg")))
        at = np.flatnonzero(cols["type"] == REPORT)
        tick = np.repeat(np.arange(k0, k1), ends[k0:k1] - firsts[k0:k1])[at]
        spd = cols["spd"][at].astype(np.int64)
        seg = ((cols["xway"][at].astype(np.int64) * DIRS + cols["dir"][at])
               * SEGS + cols["seg"][at])
        G = max(int(seg.max()) + 1 if len(seg) else 1, run_s.shape[1])
        run_s, run_n = _wider(run_s, G), _wider(run_n, G)
        key = (tick - k0) * G + seg
        shape = (k1 - k0, G)
        tick_s = np.bincount(key, spd, (k1 - k0) * G).reshape(shape)
        tick_n = np.bincount(key, None, (k1 - k0) * G).reshape(shape)
        run_s[k0 + 1:k1 + 1] = run_s[k0] + np.cumsum(
            tick_s.astype(np.int64), axis=0)
        run_n[k0 + 1:k1 + 1] = run_n[k0] + np.cumsum(tick_n, axis=0)
        if in_range and len(at):
            # a report's own tick up to it: its segment's reports side
            # by side, in stream order
            order = np.argsort(key, kind="stable")
            ks = key[order]
            head = np.ones(len(ks), bool)
            head[1:] = ks[1:] != ks[:-1]
            i = np.arange(len(ks))
            start = np.maximum.accumulate(np.where(head, i, 0))
            cs = np.cumsum(spd[order])
            own_s = np.empty(len(ks), np.int64)
            own_n = np.empty(len(ks), np.int64)
            own_s[order] = cs - (cs[start] - spd[order][start])
            own_n[order] = i - start + 1
            total_s = run_s[tick, seg] - run_s[oldest[tick], seg] + own_s
            total_n = run_n[tick, seg] - run_n[oldest[tick], seg] + own_n
            if precision == "bf16":
                # the control: a segment's sum of 2,500 speeds kept in
                # bfloat16 (8 bits of mantissa) is off by parts in a
                # thousand
                total_s = _bf16(total_s)
            when = stamps[tick]
            out["@idx"].append(np.asarray(pool.index_of(when), np.int64))
            out["@ts"].append(when)
            out["vid"].append(cols["vid"][at].astype(np.int64))
            for name in ("xway", "dir", "seg"):
                out[name].append(cols[name][at])
            out["lav"].append(total_s / total_n)
            out["n"].append(total_n.astype(np.int32))
        k0 = k1
    kinds = {"lav": np.float64, "n": np.int32, "xway": np.int32,
             "dir": np.int32, "seg": np.int32}
    return {
        c: (np.concatenate(v) if v else np.zeros(0, kinds.get(c, np.int64)))
        for c, v in out.items()
    }
