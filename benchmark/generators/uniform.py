"""The repo's own stream (``bench.py``, ``chip_smoke.py``): (id int, name
string, price double, timestamp long), ids uniform over ``n_ids``, one
constant name, price uniform in [0, 100), event time 1 ms apart and in
order. Event ``i`` is row ``i % n`` of the pool with timestamp
``TS0 + i``: a timestamp is an index, and nothing but the timestamp
changes from one cycle to the next.

The pool is long enough (32 replay batches) that what a seed changes in
a batch's mix of ids averages out over one cycle (PERF.md, finding 1).
"""

from __future__ import annotations

import numpy as np

TS0 = 1_000
FIELDS = [["id", "int"], ["name", "string"], ["price", "double"],
          ["timestamp", "long"]]
NAME = "test_event"


class Pool:
    def __init__(self, seed: int, n: int, n_ids: int) -> None:
        rng = np.random.default_rng(seed)
        self.n = n
        self.id = rng.integers(0, n_ids, size=n).astype(np.int32)
        self.price = rng.random(n, dtype=np.float64) * 100.0

    def columns(self, lo, hi, names=None):
        idx = np.arange(lo, hi, dtype=np.int64)
        rows = idx % self.n
        make = {
            "id": lambda: self.id[rows],
            "name": lambda: np.full(len(idx), NAME),
            "price": lambda: self.price[rows],
            "timestamp": lambda: idx + TS0,
        }
        return {k: make[k]() for k in names or make}

    def server(self, batch, intern):
        n_pool = self.n // batch
        ids = self.id.reshape(n_pool, batch)
        price = self.price.reshape(n_pool, batch)
        name = np.full(batch, intern("name", NAME), dtype=np.int32)
        ts0 = TS0 + np.arange(batch, dtype=np.int64)

        def serve(j):
            k = j % n_pool
            ts = ts0 + j * batch  # the one vectorised shift
            return {"id": ids[k], "name": name, "price": price[k],
                    "timestamp": ts}, ts

        return serve

    def ts_of(self, i):
        return i + TS0

    def index_of(self, ts):
        return ts - TS0


def make_pool(seed, n, cfg):
    if cfg["fields"] != FIELDS:
        raise ValueError(
            f"generator uniform makes {FIELDS}, not {cfg['fields']}")
    return Pool(seed, n, cfg["n_ids"])
