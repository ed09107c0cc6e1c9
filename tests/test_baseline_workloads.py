"""``BASELINE.json``'s five queries (``baseline/workloads.py``) through
the paths a deployment takes, held to the per-event interpreter.

``test_baseline_crosscheck.py`` holds the per-batch path and
``test_fused_stream.py`` filter and headline at K = 3 on whole batches.
Here: fused segments of 3 and 8 and the resident replay, on a stream of
whole batches and on one whose last batch is a single event; the fused
runs also count what they dispatched; and a second run of a staged job
lowers nothing. Counts only: nothing here is a rate or a time.
"""

import numpy as np
import pytest

from flink_siddhi_tpu.baseline import BaselineEngine
from flink_siddhi_tpu.baseline.workloads import (
    CONFIGS, config_cql, make_batches,
)
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType
from flink_siddhi_tpu.telemetry import compile_events

BATCH = 2048
# whole: ten full batches. ragged: nine and a batch of one event
SHAPES = {"whole": 10 * BATCH, "ragged": 9 * BATCH + 1}
PATHS = {"fused3": 3, "fused8": 8, "resident": None}
# what tests/test_fused_stream.py already holds
HELD_ELSEWHERE = {
    ("filter", "fused3", "whole"), ("headline", "fused3", "whole")}
CASES = [
    (c, p, s) for c in CONFIGS for p in PATHS for s in SHAPES
    if (c, p, s) not in HELD_ELSEWHERE
]


def _schema():
    return StreamSchema([
        ("id", AttributeType.INT),
        ("name", AttributeType.STRING),
        ("price", AttributeType.DOUBLE),
        ("timestamp", AttributeType.LONG),
    ])


def _n_ids(config):
    return 1000 if config == "window_groupby" else 50


def _job(config, n, **kw):
    schema = _schema()
    plan = compile_plan(
        config_cql(config), {"inputStream": schema},
        config=EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    batches = make_batches(n, BATCH, schema, "inputStream", _n_ids(config))
    return Job(
        [plan], [BatchSource("inputStream", schema, iter(batches))],
        batch_size=BATCH, time_mode="processing", **kw,
    )


def _table(rows):
    """{stream: rows sorted by (ts, row)}; rows are (ts, tuple)."""
    out = {}
    for sid, ts, row in rows:
        out.setdefault(sid, []).append((int(ts), tuple(row)))
    return {sid: sorted(v) for sid, v in out.items()}


def _interpret(config, n):
    batches = make_batches(n, BATCH, _schema(), "inputStream", _n_ids(config))
    ts = np.concatenate([b.timestamps for b in batches]).tolist()
    cols = {
        "id": np.concatenate([b.columns["id"] for b in batches]).tolist(),
        "name": ["test_event"] * n,
        "price": np.concatenate(
            [b.columns["price"] for b in batches]).tolist(),
        "timestamp": ts,
    }
    eng = BaselineEngine(
        config_cql(config), ["id", "name", "price", "timestamp"])
    rows = []
    eng._emit = lambda out, t, row: rows.append((out, t, row))
    eng.run_columns(cols, ts)
    return _table(rows)


@pytest.fixture(scope="module")
def interpreter():
    """``interpreter(config, n)``: the interpreter's rows, computed once
    for the three paths that are held to them."""
    done = {}

    def get(config, n):
        if (config, n) not in done:
            done[config, n] = _interpret(config, n)
        return done[config, n]

    return get


def _assert_rows_equal(got, want):
    """Same streams, same rows: ints and strings equal, floats as the
    engine computes them (float32: 1e-4 relative + 1e-3 absolute, the
    window sum's limit in ``benchmark/configs/window1k.json``)."""
    assert got.keys() == want.keys()
    for sid in want:
        assert len(got[sid]) == len(want[sid]), sid
        for (gt, grow), (wt, wrow) in zip(got[sid], want[sid]):
            assert gt == wt, sid
            for g, w in zip(grow, wrow):
                if isinstance(w, float):
                    assert abs(g - w) <= 1e-3 + 1e-4 * abs(w), (sid, gt)
                else:
                    assert g == w, (sid, gt)


@pytest.mark.parametrize("config, path, shape", CASES)
def test_rows_equal_the_interpreters(config, path, shape, interpreter):
    n, k = SHAPES[shape], PATHS[path]
    job = _job(config, n)
    if k is None:
        ResidentReplay(job).execute()
    else:
        job.fused_segment_len = k
        job.run()
    got = _table(
        (sid, ts, row) for sid in job.collected
        for ts, row in job.results_with_ts(sid)
    )
    want = interpreter(config, n)
    assert sum(len(v) for v in want.values()) > 0
    _assert_rows_equal(got, want)
    if k is not None:
        # every batch was staged toward a segment, the segments hold K
        # batches or fewer, and the dispatches were really collapsed
        counters = job.telemetry.snapshot()["counters"]
        n_batches = -(-n // BATCH)
        assert counters["fusion.batches"] == n_batches
        assert counters["fusion.dispatches"] * k >= n_batches
        assert counters["fusion.dispatches"] < n_batches


@pytest.mark.parametrize("config", CONFIGS)
def test_a_second_run_of_a_staged_job_lowers_nothing(config):
    job = _job(config, SHAPES["ragged"], retain_results=False)
    rep = ResidentReplay(job)
    rep.stage()
    rep.run()
    job.flush()
    first = dict(job.emitted_counts)
    assert sum(first.values()) > 0
    with compile_events.watch() as w:
        rep.rerun()
    assert w.count == 0
    assert dict(job.emitted_counts) == {k: 2 * v for k, v in first.items()}
