"""Resident (bounded-replay) mode vs streaming mode: bit-identical rows.

The resident replay (runtime/replay.py) changes only the DISPATCH
granularity — its scan body is the streaming step — so the two modes
must agree on every emitted row and timestamp across plan shapes:
stateless filters, pattern chains, windowed group-by (incl. the
end-of-stream timeBatch flush), multi-stream patterns, and wide
multi-query stacks that exercise the tape-capacity chunking.
"""

import numpy as np
import pytest

from flink_siddhi_tpu.baseline.workloads import make_batches
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType


def _schema(shared=None):
    return StreamSchema(
        [
            ("id", AttributeType.INT),
            ("name", AttributeType.STRING),
            ("price", AttributeType.DOUBLE),
            ("timestamp", AttributeType.LONG),
        ],
        shared_strings=shared,
    )


def _run(cql, batches_fn, mode, batch, config=None, time_mode="processing"):
    schema = _schema()
    plan = compile_plan(
        cql, {"inputStream": schema},
        config=config or EngineConfig(),
    )
    job = Job(
        [plan],
        [BatchSource("inputStream", schema, iter(batches_fn(schema)))],
        batch_size=batch, time_mode=time_mode,
    )
    if mode == "resident":
        ResidentReplay(job).execute()
    else:
        job.run()
    out = {}
    for sid in job.collected:
        out[sid] = sorted(job.results_with_ts(sid))
    return out


CASES = {
    "filter": (
        "from inputStream[id == 2] select id, name, price "
        "insert into out",
        50,
    ),
    "pattern3": (
        "from every s1 = inputStream[id == 1] -> "
        "s2 = inputStream[id == 2] -> s3 = inputStream[id == 3] "
        "within 5 sec "
        "select s1.timestamp as t1, s3.timestamp as t3, "
        "s3.price as price insert into out",
        50,
    ),
    "window_groupby": (
        "from inputStream#window.length(100) "
        "select id, sum(price) as total, count() as cnt "
        "group by id insert into out",
        40,
    ),
    "timebatch": (
        "from inputStream#window.timeBatch(3 sec) "
        "select sum(price) as total insert into out",
        50,
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_resident_matches_streaming(case):
    cql, n_ids = CASES[case]
    n, batch = 40_000, 4096

    def batches(schema):
        return make_batches(n, batch, schema, "inputStream", n_ids)

    cfg = EngineConfig(lazy_projection=True, pred_pushdown=True)
    a = _run(cql, batches, "streaming", batch, config=cfg)
    b = _run(cql, batches, "resident", batch, config=cfg)
    assert a.keys() == b.keys() and a, (case, a.keys(), b.keys())
    for sid in a:
        assert a[sid] == b[sid], (case, sid, len(a[sid]), len(b[sid]))


def test_resident_matches_streaming_multiquery():
    # 8 stacked chain queries over one stream: exercises the stacked
    # group artifact and (with a small tape cap) the chunked windows
    parts = []
    for q in range(8):
        a, b = q % 5, (q * 3 + 1) % 5
        parts.append(
            f"from every s1 = inputStream[id == {a}] -> "
            f"s2 = inputStream[id == {b}] "
            f"select s1.timestamp as t1, s2.timestamp as t2 "
            f"insert into m{q}"
        )
    cql = "; ".join(parts)
    n, batch = 20_000, 4096

    def batches(schema):
        return make_batches(n, batch, schema, "inputStream", 5)

    a = _run(cql, batches, "streaming", batch)
    b = _run(cql, batches, "resident", batch)
    assert a.keys() == b.keys() and len(a) == 8
    for sid in a:
        assert a[sid] == b[sid], (sid, len(a[sid]), len(b[sid]))


def test_resident_multi_stream_event_time():
    # two physical sources, event-time watermark gating: the replay
    # stager must reproduce the streaming reorder-release exactly
    s1 = _schema()
    s2 = _schema()
    rng = np.random.default_rng(3)

    def mk(schema, sid, n, seed_off):
        r = np.random.default_rng(10 + seed_off)
        out = []
        for start in range(0, n, 512):
            m = min(512, n - start)
            ts = 1000 + 7 * (start + np.arange(m, dtype=np.int64))
            cols = {
                "id": r.integers(0, 4, size=m).astype(np.int32),
                "name": np.zeros(m, dtype=np.int32),
                "price": r.random(m) * 10.0,
                "timestamp": ts,
            }
            out.append(EventBatch(sid, schema, cols, ts))
        return out

    cql = (
        "from every a = in1[id == 1] -> b = in2[id == 2] "
        "select a.timestamp as t1, b.timestamp as t2 insert into out"
    )

    def build(mode):
        plan = compile_plan(cql, {"in1": s1, "in2": s2})
        job = Job(
            [plan],
            [
                BatchSource("in1", s1, iter(mk(s1, "in1", 4000, 0))),
                BatchSource("in2", s2, iter(mk(s2, "in2", 4000, 1))),
            ],
            batch_size=1024, time_mode="event",
        )
        if mode == "resident":
            ResidentReplay(job).execute()
        else:
            job.run()
        return sorted(job.results_with_ts("out"))

    a, b = build("streaming"), build("resident")
    assert a and a == b


def test_resident_control_streams_contract():
    """ResidentReplay ACCEPTS control sources (epoch-boundary apply —
    the control/ plane; behavior pinned in tests/test_control_plane.py)
    while the sharded variant still refuses, naming the contract and
    the working alternatives — no stale pointers."""
    from flink_siddhi_tpu.runtime.replay import ShardedResidentReplay
    from flink_siddhi_tpu.runtime.sources import ControlListSource

    schema = _schema()
    plan = compile_plan(
        "from inputStream[id == 1] select id insert into out",
        {"inputStream": schema},
    )
    job = Job(
        [plan],
        [BatchSource("inputStream", schema, iter([]))],
        control_sources=[ControlListSource([])],
    )
    rep = ResidentReplay(job)  # accepted: epoch-boundary control
    rep.execute()
    assert job.finished
    job2 = Job(
        [plan],
        [BatchSource("inputStream", schema, iter([]))],
        control_sources=[ControlListSource([])],
    )
    with pytest.raises(ValueError, match="epoch") as ei:
        ShardedResidentReplay(job2)
    msg = str(ei.value)
    assert "streaming" in msg and "control_plane" in msg


def test_rerun_is_deterministic_counts_only():
    """rerun() resets state and replays the staged tapes: emitted
    counts double exactly (same matches found twice), and it refuses
    jobs with consumers."""
    schema = _schema()
    n, batch = 20_000, 4096
    cql = CASES["pattern3"][0]

    def batches():
        return make_batches(n, batch, schema, "inputStream", 50)

    plan = compile_plan(
        cql, {"inputStream": schema},
        config=EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    job = Job(
        [plan],
        [BatchSource("inputStream", schema, iter(batches()))],
        batch_size=batch, time_mode="processing", retain_results=False,
    )
    rep = ResidentReplay(job)
    rep.stage()
    rep.run()
    job.flush()
    first = dict(job.emitted_counts)
    assert sum(first.values()) > 0
    dt = rep.rerun()
    assert dt > 0
    assert {k: 2 * v for k, v in first.items()} == dict(
        job.emitted_counts
    )

    # with a consumer attached, rerun refuses
    job2 = Job(
        [compile_plan(cql, {"inputStream": schema})],
        [BatchSource("inputStream", schema, iter(batches()))],
        batch_size=batch, time_mode="processing",
    )
    rep2 = ResidentReplay(job2)
    rep2.stage()
    rep2.run()
    job2.flush()
    with pytest.raises(ValueError, match="counts-only"):
        rep2.rerun()


@pytest.mark.slow  # full-mesh-8 shard_map: minutes of XLA CPU compile on the 2-core tier-1 lane (mesh-4 sharded coverage stays tier-1)
def test_sharded_resident_matches_sharded_streaming():
    """Bounded replay over a ShardedJob mesh: the [cycles, shards, ...]
    scan whose body is the shard_map'd step must reproduce the sharded
    streaming run row-for-row (8-device virtual CPU mesh)."""
    from flink_siddhi_tpu.parallel import ShardedJob
    from flink_siddhi_tpu.runtime.replay import ShardedResidentReplay

    schema = StreamSchema(
        [("k", AttributeType.INT), ("v", AttributeType.DOUBLE),
         ("timestamp", AttributeType.LONG)]
    )
    rng = np.random.default_rng(17)
    n, batch = 6000, 512
    ks = rng.integers(0, 11, n).astype(np.int32)
    vs = np.round(rng.random(n) * 10, 2)
    ts = (1000 + np.arange(n)).astype(np.int64)

    def batches():
        return iter([
            EventBatch(
                "S", schema,
                {"k": ks[s:s + batch], "v": vs[s:s + batch],
                 "timestamp": ts[s:s + batch]},
                ts[s:s + batch],
            )
            for s in range(0, n, batch)
        ])

    cql = (
        "from S select k, sum(v) as s group by k insert into o; "
        "partition with (k of S) begin "
        "from every a = S[v > 5] -> b = S[v <= 5] "
        "select a.timestamp as t1, b.timestamp as t2, a.k as kk "
        "insert into p end"
    )

    def build():
        return ShardedJob(
            [compile_plan(cql, {"S": schema})],
            [BatchSource("S", schema, iter(batches()))],
            n_shards=8, batch_size=batch, time_mode="processing",
        )

    sj1 = build()
    sj1.run()
    sj2 = build()
    rep = ShardedResidentReplay(sj2)
    rep.stage()
    rep.run()
    sj2.flush()
    for sid in ("o", "p"):
        a = sorted(sj1.results_with_ts(sid))
        b = sorted(sj2.results_with_ts(sid))
        assert a and len(a) == len(b), (sid, len(a), len(b))
        for (t1, r1), (t2, r2) in zip(a, b):
            assert t1 == t2
            for x, y in zip(r1, r2):
                if isinstance(x, float):
                    assert x == pytest.approx(y, rel=1e-5)
                else:
                    assert x == y
