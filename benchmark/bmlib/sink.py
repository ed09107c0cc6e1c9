"""The consumer: a columnar sink that logs deliveries.

For every delivery it keeps the host time and the newest event index
delivered ("results complete through event i at time t"): every rate and
every latency of the benchmark is read from this log and from nothing
the job counts at dispatch. It also keeps the rows of seeded sample
ranges of the stream, which the comparison checks against the reference
once the window has closed.
"""

from __future__ import annotations

import time

import numpy as np

from .data import TS0


class DeliverySink:
    def __init__(self, index_col, ranges, keep_index=False) -> None:
        """``index_col``: the row column holding the timestamp of the
        last event that contributes to the row (None: the row's own
        ts). ``ranges``: a ``SampleRanges``. ``keep_index``: keep every
        delivery's event indices (the live cell's latency samples)."""
        self.index_col = index_col
        self.ranges = ranges
        self.keep_index = keep_index
        self.recording = False
        self.deliveries = 0  # since the job started, recording or not
        self.t = []  # perf_counter at each recorded delivery
        self.hi = []  # newest event index in it
        self.lo = []  # oldest event index in it
        self.rows = []  # its row count
        self.index = []  # its event indices (keep_index)
        self.pieces = {}  # range start -> [{column: rows in range}]
        self.none_columns = 0  # deliveries carrying an undecodable column

    def accept_columns(self, ts, cols) -> None:
        self.deliveries += 1
        if not self.recording:
            return
        t = time.perf_counter()
        idx = cols[self.index_col] if self.index_col else ts
        if idx.dtype == object:  # the row lane hands over object columns
            try:
                idx = idx.astype(np.int64)
            except TypeError:  # a None in it: an undecodable row
                self.none_columns += 1
                return
        idx = idx - TS0
        lo, hi = int(idx[0]), int(idx[-1])
        self.t.append(t)
        self.lo.append(min(lo, hi))
        self.hi.append(max(lo, hi))
        self.rows.append(len(idx))
        if self.keep_index:
            self.index.append(idx)
        for a, b in self.ranges.overlapping(lo, hi):
            i, j = np.searchsorted(idx, (a, b))
            if i == j:
                continue
            piece = {"@idx": idx[i:j].copy(), "@ts": ts[i:j] - TS0}
            for k, v in cols.items():
                v = v[i:j]
                if v.dtype == object:
                    if any(x is None for x in v):
                        self.none_columns += 1
                        continue
                    v = v.astype(np.float64)
                piece[k] = v.copy()
            self.pieces.setdefault(a, []).append(piece)


class SampleRanges:
    """Seeded ranges of the stream, the same in every cycle of the
    pool: ``[c * period + o, c * period + o + length)`` for each offset
    ``o``. Half the offsets straddle a batch boundary, and as the pool's
    length is no multiple of the fused segment the cycles put them
    across segment boundaries too."""

    def __init__(self, seed, period, batch, length, count=3) -> None:
        rng = np.random.default_rng([seed, 0x5A])
        self.period = period
        self.length = length
        offs = set()
        n_b = period // batch
        while len(offs) < count:
            j = int(rng.integers(1, max(n_b, 2)))
            if len(offs) % 2 == 0:
                o = j * batch - length // 2  # across a batch boundary
            else:
                o = (j - 1) * batch + int(
                    rng.integers(0, max(batch - length, 1))
                )
            offs.add(min(max(o, 0), period - length))
        self.offsets = sorted(offs)

    def overlapping(self, lo, hi):
        """Ranges that intersect event indices lo..hi."""
        out = []
        for c in range(max(lo // self.period - 1, 0), hi // self.period + 1):
            for o in self.offsets:
                a = c * self.period + o
                if a <= hi and a + self.length > lo:
                    out.append((a, a + self.length))
        return out
