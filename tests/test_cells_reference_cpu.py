"""Every benchmark configuration's answer, on the CPU, before the chip.

Each configuration's query (``benchmark/configs/<name>.json``) runs
through the program on a seeded tiny stream from the configuration's own
generator, down every execution path that configuration can take and
both sink lanes, and is held to its plain reference
(``benchmark/configs/<name>.py``) by ``benchmark/bmlib/compare.py``, to
the limits the configuration's file gives: what the driver decides as
``correct`` on the chip (an ``outputs_incorrect`` there costs a PR).
No file under ``benchmark/`` is edited; nothing here is a rate or a time.
"""

import json
import os
import sys

import numpy as np
import pytest

from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.replay import ResidentReplay
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch

BENCH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:  # bmlib is the benchmark's package
    sys.path.insert(0, BENCH)

from bmlib import cell as bmcell, compare, data as bmdata  # noqa: E402

BATCH, POOL_BATCHES = 4_096, 8  # benchmark/tests/conftest.py's TINY
# (seed, events in the last batch): every stream runs ten batches past
# its start, so past the pool's end (the draws repeat, time runs on), and
# ends on a ragged batch; the third ends on a batch of one event
STREAMS = ((11, 1_000), (2_147_483_659, 1_000), (12, 1))
# NEXmark at two events a millisecond: a batch is 1 s of event time, a
# pool cycle 20 s, a slide two batches; slots cut to fit (the cell's 2^20
# hold 0.63M live auctions, these a few hundred). A window closes when a
# later event comes, so the stream's last slide (4,000 events) is left
# out of the comparison (``open_tail``)
TINY = {
    "nexmark_q5": {
        "event_time_rate": 2_000, "batch": 2_000, "pool": 40_000,
        "whole_batches": 30, "fused_segment_len": 2, "open_tail": 4_000,
        "engine_config": {"hop_group_slots": 8_192},
    },
    # the same stream: a tumbling window is ten batches, a pool cycle two
    # windows. The stream's 30 whole batches end on a window's end, and
    # the first event after it (a person: every fiftieth event is) closes
    # the third window, so nothing is left open that the reference counts
    "nexmark_q8": {
        "event_time_rate": 2_000, "batch": 2_000, "pool": 40_000,
        "whole_batches": 30, "fused_segment_len": 2,
        "engine_config": {"hop_group_slots": 4_096},
    },
    # the same stream: the 10 s gap is ten batches. A person is among
    # the newest 1,000 for 25 s here (50 ms at the cell's rate), so a
    # bidder comes back, inside the gap and after it, and has several
    # sessions. The flush closes what the stream's end leaves open; those
    # rows are stamped past the stream and are not settled
    "nexmark_q11": {
        "event_time_rate": 2_000, "batch": 2_000, "pool": 40_000,
        "whole_batches": 30, "fused_segment_len": 2,
        "engine_config": {"hop_group_slots": 4_096},
    },
    # Linear Road on one expressway at 16 reports a second: a tick of 1 s
    # holds 16 events, a round of 30 s 480, a batch 5 s as in the cell.
    # Trips of 3-9 reports, so vids die, their slots are purged (idle 90 s
    # + 30 s) and reused inside the 25 rounds run; accidents of 6 reports
    # every two minutes, so each gives three rows a vehicle
    "linear_road_rows4": {
        "expressways": 1, "reports_per_s_per_xway": 16,
        "trip_reports_min": 3, "trip_reports_max": 9,
        "accident_every_s": 120, "accident_reports": 6,
        "batch": 80, "pool": 3_840, "whole_batches": 150,
        "fused_segment_len": 4,
        "engine_config": {"hop_group_slots": 1_024},
    },
    # the same stream through the five-minute window: 300 ticks x 16
    # reports are 4,800 members at most, in a ring of 8,192; the 150
    # batches are 750 s, so the window fills and slides for 450 s
    "linear_road_lav5m": {
        "expressways": 1, "reports_per_s_per_xway": 16,
        "trip_reports_min": 3, "trip_reports_max": 9,
        "accident_every_s": 120, "accident_reports": 6,
        "batch": 80, "pool": 3_840, "whole_batches": 150,
        "fused_segment_len": 4,
        "engine_config": {"time_ring_capacity": 8_192,
                          "acc_budget_bytes": 1 << 20},
    },
}


def _config(name):
    cfg = bmcell.load_json("configs", name)
    cfg.update({"batch": BATCH, "whole_batches": 10, "open_tail": 0,
                **TINY.get(name, {})})
    cfg.setdefault("pool", POOL_BATCHES * cfg["batch"])
    return cfg


with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as _f:
    CONFIGS = [c["name"] for c in json.load(_f)["configs"]]


def _paths(cfg):
    """The execution paths a configuration can take. A lazy plan on a
    mesh is refused by design, so a ShardedJob configuration has its one
    path; a Job configuration runs per batch, fused at its cell's
    segment length, and as a resident replay."""
    if cfg["job"] == "ShardedJob":
        return ["sharded"]
    return ["per_batch", "fused", "resident"]


CASES = [
    pytest.param(name, path, lane, seed, tail,
                 id=f"{name}-{path}-{lane}-{seed}+{tail}")
    for name in CONFIGS
    for path in _paths(_config(name))
    for lane in ("rows", "columns")
    for seed, tail in STREAMS
]


class _RowSink:
    """The row lane: a callable sink, one (ts, row) at a time."""

    def __init__(self):
        self.ts, self.rows = [], []

    def __call__(self, ts, row):
        self.ts.append(ts)
        self.rows.append(row)

    def table(self, fields):
        cols = {"@ts": np.asarray(self.ts, np.int64)}
        for name, col in zip(fields, zip(*self.rows)):
            cols[name] = np.asarray(col)
        return cols


class _ColumnSink:
    """The columnar lane: ``accept_columns(ts, cols)`` per delivery."""

    def __init__(self):
        self.pieces = []

    def accept_columns(self, ts, cols):
        # the row lane hands a columnar sink object columns: typed
        # columns say the columnar lane was the one taken
        assert all(v.dtype != object for v in cols.values())
        self.pieces.append({"@ts": np.array(ts, np.int64), **{
            k: np.array(v) for k, v in cols.items()}})

    def table(self, _fields):
        return compare.join_pieces(self.pieces)


@pytest.fixture(scope="module")
def stream():
    """``stream(cfg, seed, n)``: (pool, reference rows) of a
    configuration's tiny stream of ``n`` events, the rows due for events
    ``0 <= i < n - open_tail``; made once for the cases that share it."""
    made = {}

    def get(cfg, seed, n):
        key = (cfg["name"], seed, n)
        if key not in made:
            pool = bmcell.make_pool(cfg, seed, cfg["pool"])
            want = bmcell.load_module("configs", cfg["name"]).expected(
                pool, 0, n - cfg["open_tail"])
            made[key] = (pool, want)
        return made[key]

    return get


def _batches(cfg, pool, schema, stream, n):
    serve = pool.server(
        cfg["batch"], lambda field, s: schema.string_tables[field].intern(s))
    batch = cfg["batch"]
    for j in range(-(-n // batch)):
        cols, ts = serve(j)
        m = min(batch, n - j * batch)
        yield EventBatch(
            stream, schema, {k: v[:m] for k, v in cols.items()}, ts[:m])


def _run(cfg, path, sink, pool, n):
    schema, stream = bmdata.make_schema(cfg), bmdata.stream_name(cfg)
    plan = compile_plan(
        cfg["cql"], {stream: schema}, plan_id=cfg["name"],
        config=EngineConfig(**cfg["engine_config"]),
    )
    source = BatchSource(
        stream, schema, _batches(cfg, pool, schema, stream, n))
    kw = dict(batch_size=cfg["batch"], time_mode=cfg["time_mode"],
              retain_results=False)
    if path == "sharded":
        from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh

        job = ShardedJob([plan], [source], mesh=make_cep_mesh(cfg["chips"]),
                         **kw)
    else:
        job = Job([plan], [source], **kw)
    if path == "fused":
        job.fused_segment_len = cfg["fused_segment_len"]
    job.add_sink(cfg["output_stream"], sink)
    if path == "resident":
        ResidentReplay(job).execute()
    else:
        job.run()
    assert job.processed_events == n
    return job


@pytest.mark.parametrize("name, path, lane, seed, tail", CASES)
def test_cell_query_equals_its_plain_reference(
        name, path, lane, seed, tail, stream):
    cfg = _config(name)
    n = cfg["whole_batches"] * cfg["batch"] + tail
    pool, want = stream(cfg, seed, n)
    assert len(want["@idx"]) > 0
    sink = _RowSink() if lane == "rows" else _ColumnSink()
    job = _run(cfg, path, sink, pool, n)
    got = sink.table(job.output_fields[cfg["output_stream"]])
    index = got[cfg["index_col"]] if cfg["index_col"] else got["@ts"]
    got["@idx"] = pool.index_of(index.astype(np.int64))
    settled = got["@idx"] < n - cfg["open_tail"]
    got = {k: v[settled] for k, v in got.items()}
    numbers = compare.compare_range(got, want, cfg["compare"])
    limits = compare.limits(cfg["compare"])
    assert set(numbers) == set(limits), numbers
    assert all(numbers[k] <= limits[k] for k in limits), numbers
