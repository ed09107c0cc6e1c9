"""Plain reference for ``window1k``: ``#window.length(1000)`` with
``sum(price)``, ``count()`` grouped by id.

Every event emits one row: its id, and the sum and count of the events
with that id among the last 1,000 events, itself included. Sums in
float64.
"""

import numpy as np

LENGTH = 1_000


def expected(pool, a, b, precision="f64"):
    from bmlib.compare import bf16_round

    lo = max(a - (LENGTH - 1), 0)
    cols = pool.columns(lo, b, ("id", "price", "timestamp"))
    ids, price = cols["id"], cols["price"]
    if precision == "bf16":
        price = bf16_round(price)
    n = len(ids)
    order = np.argsort(ids, kind="stable")  # by id, then by position
    key = ids[order].astype(np.int64) * (n + LENGTH) + order
    first = ids[order].astype(np.int64) * (n + LENGTH) + np.maximum(
        order - (LENGTH - 1), 0
    )
    m = np.searchsorted(key, first, side="left")
    cs = np.concatenate([[0.0], np.cumsum(price[order])])
    r = np.arange(n)
    total = np.empty(n)
    cnt = np.empty(n, np.int64)
    total[order] = cs[r + 1] - cs[m]
    cnt[order] = r - m + 1
    if precision == "bf16":
        total = bf16_round(total)
    s = a - lo
    idx = np.arange(a, b, dtype=np.int64)
    return {
        "@idx": idx,
        "@ts": cols["timestamp"][s:],
        "id": ids[s:],
        "total": total[s:],
        "cnt": cnt[s:],
    }
