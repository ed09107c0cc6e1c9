"""Plain reference for ``pattern3``: every s1[id == 1] -> s2[id == 2] ->
s3[id == 3] within 5 sec.

Each id == 1 event opens a partial match; it takes the next id == 2
event after it, then the next id == 3 event after that, and emits
(t1, t3, s3.price) at the third event unless more than 5,000 ms have
passed since the first. Rows come out in the order of their third
event, oldest partial first. Written from the query's semantics
(Siddhi's ``every`` chain), over numpy arrays, with nothing of the
program in it.
"""

import numpy as np

WITHIN_MS = 5_000


def expected(pool, a, b, precision="f64"):
    """Rows whose third event is stream event a <= i < b."""
    from bmlib.compare import bf16_round

    lo = max(a - WITHIN_MS - 1, 0)  # an older first event has expired
    cols = pool.columns(lo, b, ("id", "price", "timestamp"))
    ids, price, ts = cols["id"], cols["price"], cols["timestamp"]
    n = len(ids)
    pos = np.arange(n)

    def next_at_or_after(code):
        p = np.where(ids == code, pos, n)
        return np.append(np.minimum.accumulate(p[::-1])[::-1], n)

    p1 = pos[ids == 1]
    p2 = next_at_or_after(2)[p1]
    p3 = next_at_or_after(3)[p2]
    keep = p3 < n
    p1, p3 = p1[keep], p3[keep]
    keep = (ts[p3] - ts[p1] <= WITHIN_MS) & (p3 + lo >= a)
    p1, p3 = p1[keep], p3[keep]
    out_price = price[p3]
    if precision == "bf16":
        out_price = bf16_round(out_price)
    return {
        "@idx": p3 + lo,
        "@ts": ts[p3],
        "t1": ts[p1],
        "t3": ts[p3],
        "price": out_price,
    }
