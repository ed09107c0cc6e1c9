"""The consumer: a columnar sink that logs deliveries.

For every delivery it keeps the host time and the newest event index
delivered ("results complete through event i at time t"): every rate and
every latency of the benchmark is read from this log and from nothing
the job counts at dispatch. It also keeps the rows of seeded sample
ranges of the stream, which the comparison checks against the reference
once the window has closed.

Rows carry timestamps; the stream's event clock (the generator's pool)
turns them into event indices: ``index_of(ts)``, the last event stamped
``ts`` or earlier. Where several events share a timestamp (a tick), the
rest of a delivery's newest tick may still be to come, so the delivery
counts as complete only through the last event of the tick before; a
tick that holds one event is complete with its row. "Complete through
event i" never overstates.
"""

from __future__ import annotations

import time

import numpy as np


class DeliverySink:
    def __init__(self, index_col, ranges, clock, keep_index=False) -> None:
        """``index_col``: the row column holding the timestamp of the
        last event that contributes to the row (None: the row's own
        ts). ``ranges``: a ``SampleRanges``. ``clock``: the pool, for
        its ``index_of``. ``keep_index``: keep every delivery's event
        indices (the live cell's latency samples)."""
        self.index_col = index_col
        self.ranges = ranges
        self.index_of = clock.index_of
        self.keep_index = keep_index
        self.recording = False
        self.deliveries = 0  # since the job started, recording or not
        self.t = []  # perf_counter at each recorded delivery
        self.hi = []  # the event index it is complete through
        self.lo = []  # oldest event index in it
        self.top = []  # newest event index in it (>= hi: an open tick)
        self.rows = []  # its row count
        self.tail = []  # its rows beyond hi (those of the open tick)
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
        newest = idx[-1]
        idx = self.index_of(idx)
        lo, hi = int(idx[0]), int(idx[-1])
        lo, hi = min(lo, hi), max(lo, hi)
        before = int(self.index_of(newest - 1))  # the tick before ends here
        shared = hi - before > 1  # the newest tick holds several events
        self.t.append(t)
        self.lo.append(lo)
        self.hi.append(before if shared else hi)
        self.top.append(hi)
        self.rows.append(len(idx))
        self.tail.append(
            len(idx) - int(np.searchsorted(idx, before, side="right"))
            if shared else 0
        )
        if self.keep_index:
            self.index.append(idx)
        for a, b in self.ranges.overlapping(lo, hi):
            i, j = np.searchsorted(idx, (a, b))
            if i == j:
                continue
            piece = {"@idx": idx[i:j].copy(), "@ts": ts[i:j].copy()}
            for k, v in cols.items():
                v = v[i:j]
                if v.dtype == object:
                    if any(x is None for x in v):
                        self.none_columns += 1
                        continue
                    v = v.astype(np.float64)
                piece[k] = v.copy()
            self.pieces.setdefault(a, []).append(piece)

    def rows_between(self, n) -> int:
        """Rows with an index in ``(hi[0], hi[n - 1]]`` among the first
        ``n`` deliveries, which came in order: all after the first but
        for the first's open tick, less every row of the last's."""
        top = self.top[n - 1]
        beyond = sum(self.tail[k] for k in range(n) if self.top[k] == top)
        return self.tail[0] + sum(self.rows[1:n]) - beyond


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
