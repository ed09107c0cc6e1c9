"""Engine vs measured-baseline interpreter: the two implementations of
the benchmark semantics (the vectorized device engine and the per-event
Python reference) must agree on the SAME stream — this is what makes
``vs_baseline`` an apples-to-apples ratio.

Coverage: all FIVE bench configs. filter and headline additionally
compare ROW CONTENTS + timestamps as sorted multisets (float fields at
f32 tolerance — the device computes in f32, the interpreter in f64), so
compensating row-level bugs cannot hide behind equal counts (ADVICE
round 4). multiquery64 compares per-output-stream counts, pinning each
of the 64 stacked queries individually.
"""

import numpy as np
import pytest

from flink_siddhi_tpu.baseline.workloads import config_cql, make_batches
from flink_siddhi_tpu.baseline import BaselineEngine
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType


def _schema():
    return StreamSchema(
        [
            ("id", AttributeType.INT),
            ("name", AttributeType.STRING),
            ("price", AttributeType.DOUBLE),
            ("timestamp", AttributeType.LONG),
        ]
    )


def _norm_row(ts, row):
    """f32-tolerant canonical form: the engine's DOUBLE columns compute
    and ship as f32; compare at that precision."""
    return (
        int(ts),
        tuple(
            np.float32(v).item() if isinstance(v, float) else v
            for v in row
        ),
    )


@pytest.mark.parametrize(
    "config",
    ["headline", "filter", "pattern2", "window_groupby", "multiquery64"],
)
def test_engine_matches_baseline_interpreter(config):
    n, batch = 100_000, 16_384
    if config == "multiquery64":
        n = 50_000  # the interpreter fans every event through 64 NFAs
    compare_rows = config in ("headline", "filter")
    schema = _schema()
    n_ids = 1000 if config == "window_groupby" else 50
    batches = make_batches(n, batch, schema, "inputStream", n_ids)
    cql = config_cql(config)
    plan = compile_plan(
        cql, {"inputStream": schema},
        config=EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    eng_rows = []
    eng_counts = {}
    job = Job(
        [plan],
        [BatchSource("inputStream", schema,
                     iter(make_batches(n, batch, schema,
                                       "inputStream", n_ids)))],
        batch_size=batch, time_mode="processing", retain_results=False,
    )
    for rt in job._plans.values():
        for out_stream in rt.plan.output_streams():
            def sink(ts, row, _sid=out_stream):
                eng_counts[_sid] = eng_counts.get(_sid, 0) + 1
                if compare_rows:
                    eng_rows.append(_norm_row(ts, row))

            job.add_sink(out_stream, sink)
    job.run()

    eng = BaselineEngine(cql, ["id", "name", "price", "timestamp"])
    base_rows = []
    base_counts = {}

    def base_emit(out, ts, row):
        eng.emitted += 1
        base_counts[out] = base_counts.get(out, 0) + 1
        if compare_rows:
            base_rows.append(_norm_row(ts, row))

    eng._emit = base_emit
    cols = {
        "id": np.concatenate(
            [b.columns["id"] for b in batches]
        ).tolist(),
        "name": ["test_event"] * n,
        "price": np.concatenate(
            [b.columns["price"] for b in batches]
        ).tolist(),
        "timestamp": np.concatenate(
            [b.timestamps for b in batches]
        ).tolist(),
    }
    eng.run_columns(cols, cols["timestamp"])

    assert sum(eng_counts.values()) == eng.emitted
    assert eng_counts == base_counts  # per-output-stream agreement
    if compare_rows:
        assert eng.emitted > 0
        assert sorted(eng_rows) == sorted(base_rows)
