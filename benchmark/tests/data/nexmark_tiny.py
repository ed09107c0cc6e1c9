"""Plain reference for the test-only ``nexmark_tiny``: bids per auction
over tumbling panes of 2 s of ``dateTime`` (NEXmark Q5's first half).

Panes count from the stream's first event: pane ``w`` holds the bids
with ``w * 2000 <= dateTime - dateTime[0] < (w + 1) * 2000``. A pane
that has closed emits one row per auction bid on in it: the auction and
its number of bids, stamped with the ``dateTime`` of the auction's last
bid in the pane, in the order of those stamps. The query fixes no order
among rows of one millisecond; the program's is by auction id (read
from its output, PR 26), and that is what stands here.

A row's index is the last event of its stamp's tick (the pool's
``index_of``), as the sink reads it.
"""

import numpy as np

PANE_MS = 2_000
FIELDS = ("event_type", "auction", "dateTime")


def expected(pool, a, b, precision="f64"):
    """Rows whose index is a stream event a <= i < b."""
    t0 = int(pool.ts_of(0))
    # whole panes: from the start of a's to the end of (b - 1)'s
    w0 = (int(pool.ts_of(a)) - t0) // PANE_MS
    w1 = (int(pool.ts_of(b - 1)) - t0) // PANE_MS + 1
    lo = int(pool.index_of(t0 + w0 * PANE_MS - 1)) + 1
    hi = int(pool.index_of(t0 + w1 * PANE_MS - 1)) + 1
    cols = pool.columns(lo, hi, FIELDS)
    bids = np.flatnonzero(cols["event_type"] == 2)
    auction, ts = cols["auction"][bids], cols["dateTime"][bids]
    pane = (ts - t0) // PANE_MS
    # one row per (pane, auction): its count and its last bid's stamp
    order = np.lexsort((ts, auction, pane))
    auction, ts, pane = auction[order], ts[order], pane[order]
    last = np.flatnonzero(np.r_[
        (auction[1:] != auction[:-1]) | (pane[1:] != pane[:-1]), True])
    num = np.diff(np.r_[-1, last])
    auction, ts, pane = auction[last], ts[last], pane[last]
    order = np.lexsort((auction, ts, pane))
    auction, ts, num = auction[order], ts[order], num[order]
    if precision == "bf16":
        # the control: a count kept in bfloat16 stops at 256 (256 + 1
        # rounds back to 256), which the hot auction passes in every pane
        num = np.minimum(num, 256)
    idx = pool.index_of(ts)
    keep = (idx >= a) & (idx < b)
    return {
        "@idx": idx[keep],
        "@ts": ts[keep],
        "auction": auction[keep],
        "num": num[keep],
    }
