"""The reference workloads: ``BASELINE.json``'s five queries and the
synthetic stream they replay.

``config_cql(name)`` is the query text of one ``BASELINE.json`` config;
``make_batches`` is the stream every one of them runs over (ids uniform
over ``n_ids``, one interned name, prices in [0, 100), timestamps
``step_ms`` apart, seed 7). Tests replay the same batches through the
engine and through ``BaselineEngine`` (``interp.py``) and compare rows;
``chip_smoke.py`` takes its queries from here.
"""

from __future__ import annotations

import numpy as np

CONFIGS = ("headline", "filter", "pattern2", "window_groupby", "multiquery64")


def make_batches(n_events, batch, schema, stream_id, n_ids=50, step_ms=1):
    """Prebuilt columnar EventBatches — zero per-record Python work."""
    from ..schema.batch import EventBatch

    rng = np.random.default_rng(7)
    out = []
    ts0 = 1_000
    name_code = schema.string_tables["name"].intern("test_event")
    for start in range(0, n_events, batch):
        m = min(batch, n_events - start)
        ids = rng.integers(0, n_ids, size=m).astype(np.int32)
        cols = {
            "id": ids,
            "name": np.full(m, name_code, dtype=np.int32),
            "price": rng.random(m, dtype=np.float64) * 100.0,
            "timestamp": (
                ts0 + step_ms * (start + np.arange(m, dtype=np.int64))
            ),
        }
        ts = cols["timestamp"]
        out.append(EventBatch(stream_id, schema, cols, ts))
    return out


def config_cql(config):
    if config == "headline":
        return (
            "from every s1 = inputStream[id == 1] -> "
            "s2 = inputStream[id == 2] -> s3 = inputStream[id == 3] "
            "within 5 sec "
            "select s1.timestamp as t1, s3.timestamp as t3, "
            "s3.price as price insert into matches"
        )
    if config == "filter":
        return (
            "from inputStream[id == 2] select id, name, price "
            "insert into matches"
        )
    if config == "pattern2":
        return (
            "from every s1 = inputStream[id == 1] -> "
            "s2 = inputStream[id == 2] "
            "select s1.timestamp as t1, s2.timestamp as t2 "
            "insert into matches"
        )
    if config == "window_groupby":
        return (
            "from inputStream#window.length(1000) "
            "select id, sum(price) as total, count() as cnt "
            "group by id insert into matches"
        )
    if config == "multiquery64":
        parts = []
        for q in range(64):
            a, b = q % 50, (q * 7 + 1) % 50
            parts.append(
                f"from every s1 = inputStream[id == {a}] -> "
                f"s2 = inputStream[id == {b}] "
                f"select s1.timestamp as t1, s2.timestamp as t2 "
                f"insert into m{q}"
            )
        return "; ".join(parts)
    raise ValueError(f"unknown BASELINE.json config {config!r}")
