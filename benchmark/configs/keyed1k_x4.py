"""Plain reference for ``keyed1k_x4``: the running ``sum(price)``,
``count()`` grouped by id, since the first event of the stream.

Every event emits its id with the sum and count of all events of that id
so far, itself included. The stream is the pool repeated, so the sum at
event i is (whole cycles before i) x (the key's sum over one cycle) + the
key's running sum inside the cycle. Float64.
"""

import numpy as np


def _prefix(pool, precision):
    """Per-event running sum and count of its key inside one cycle."""
    key = ("keyed_prefix", precision)
    cache = pool.__dict__.setdefault("_cache", {})
    if key not in cache:
        cols = pool.columns(0, pool.n, ("id", "price"))
        ids, price = cols["id"], cols["price"]
        order = np.argsort(ids, kind="stable")
        sid = ids[order]
        cs = np.cumsum(price[order])
        starts = np.flatnonzero(np.r_[True, sid[1:] != sid[:-1]])
        base = np.repeat(
            np.r_[0.0, cs[starts[1:] - 1]], np.diff(np.r_[starts, len(sid)])
        )
        run = np.empty(pool.n)
        cnt = np.empty(pool.n, np.int64)
        run[order] = cs - base
        cnt[order] = np.arange(pool.n) - np.repeat(
            starts, np.diff(np.r_[starts, len(sid)])
        ) + 1
        cache[key] = (ids, run, cnt, np.bincount(ids, weights=price),
                      np.bincount(ids))
    return cache[key]


def _bf16_running(pool, a, b):
    """The control: every key's sum carried in bfloat16 from event 0.
    The carry is kept on the pool between calls, which come in
    ascending order; events between two calls are folded in too."""
    from bmlib.compare import bf16_round

    n_ids = len(_prefix(pool, "f64")[3])
    st = pool.__dict__.setdefault(
        "_bf16_carry", {"upto": 0, "acc": np.zeros(n_ids)}
    )
    if st["upto"] > a:
        st.update(upto=0, acc=np.zeros(n_ids))
    lo = st["upto"]
    cols = pool.columns(lo, b, ("id", "price"))
    ids, price = cols["id"], cols["price"]
    order = np.argsort(ids, kind="stable")
    sid = ids[order]
    starts = np.flatnonzero(np.r_[True, sid[1:] != sid[:-1]])
    lens = np.diff(np.r_[starts, len(sid)])
    rank = np.arange(len(sid)) - np.repeat(starts, lens)
    x = bf16_round(price[order])
    by_rank = np.argsort(rank, kind="stable")
    cut = np.searchsorted(rank[by_rank], np.arange(int(lens.max()) + 1))
    out = np.empty(len(sid))
    acc = st["acc"]
    for r in range(len(cut) - 1):
        sel = by_rank[cut[r]:cut[r + 1]]
        g = sid[sel]
        acc[g] = bf16_round(acc[g] + x[sel])
        out[sel] = acc[g]
    st["upto"] = b
    total = np.empty(len(sid))
    total[order] = out
    return total[a - lo:]


def expected(pool, a, b, precision="f64"):
    pool_ids, run, cnt, full_sum, full_cnt = _prefix(pool, "f64")
    idx = np.arange(a, b, dtype=np.int64)
    row, cyc = idx % pool.n, idx // pool.n
    ids = pool_ids[row]
    total = cyc * full_sum[ids] + run[row]
    if precision == "bf16":
        total = _bf16_running(pool, a, b)
    return {
        "@idx": idx,
        "@ts": pool.ts_of(idx),
        "id": ids,
        "total": total,
        "cnt": cyc * full_cnt[ids] + cnt[row],
    }
