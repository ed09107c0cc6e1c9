"""The names the benchmark reads out of the program, guarded on the CPU.

Every per-layer metric whose ``source`` is ``program_span`` or
``program_counter`` (``benchmark/metrics/<metric>.json``) names spans,
histograms, counters or gauges of the job's telemetry in its ``args``.
A program PR that renames one is otherwise found on the chip, as a
``null`` under ``per_layer`` in the ledger. Here each metric is a case:
after a tiny run of every cell that ``BENCHMARK.json`` reads it in
(built by the benchmark's own ``build_job`` over its closed-loop source,
so whatever those files call of the program is exercised too), every name
in its ``args`` is in the job's telemetry, and its reader gives a number.
Metrics are collected from the directory: one added later gets its case.
Nothing here is a rate or a time: the readers' values are not looked at.
"""

import glob
import json
import math
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:  # bmlib is the benchmark's package
    sys.path.insert(0, BENCH)

from bmlib import cell as bmcell, data as bmdata, layers  # noqa: E402
from bmlib.sink import DeliverySink, SampleRanges  # noqa: E402

# benchmark/tests/conftest.py's sizes; NEXmark as its test_nexmark_q5.py
# cuts it (a batch is 1 s of event time), the slots cut to fit
TINY = {"batch": 4_096}
TINY_Q5 = {"event_time_rate": 2_000, "batch": 2_000, "fused_segment_len": 2,
           "engine_config": {"hop_group_slots": 8_192}}
# (nexmark_q8.replay: a window is ten batches, the pool two windows, and
# the run's 40 batches close three)
# (nexmark_q11.replay: the gap is ten batches; sessions close from the
# eleventh batch on, through drains and at the flush)
# (linear_road_rows4.replay: tests/test_cells_reference_cpu.py's cut: one
# expressway at 16 reports a second, a batch 5 s, a pool eight rounds;
# trips of 3-9 reports, so slots are purged and reused inside the run's
# 20 rounds, and accidents of 6 reports give rows)
TINY_LR = {"expressways": 1, "reports_per_s_per_xway": 16,
           "trip_reports_min": 3, "trip_reports_max": 9,
           "accident_every_s": 120, "accident_reports": 6, "batch": 80,
           "engine_config": {"hop_group_slots": 1_024}}
# (linear_road_lav5m.replay: the same stream; 300 ticks x 16 reports are
# 4,800 members, in a ring of 8,192; the window is full from batch 60 on)
TINY_LAV = {**TINY_LR, "engine_config": {"time_ring_capacity": 8_192,
                                         "acc_budget_bytes": 1 << 20}}
POOL_BATCHES = {"nexmark_q5.replay": 20, "nexmark_q8.replay": 20,
                "nexmark_q11.replay": 20, "linear_road_rows4.replay": 48,
                "linear_road_lav5m.replay": 48}
WARM_BATCHES, RUN_BATCHES = 8, 40
LONGER_RUNS = {"linear_road_rows4.replay": 120,
               "linear_road_lav5m.replay": 120}

# Names a sound tiny run does not book, or books only when the timing
# falls so, and why. Their cases stay: they assert that the package still
# books that literal name.
NOT_ON_EVERY_SOUND_TINY_RUN = {
    "backpressure_wait": "the run loop never meets a full ticket window",
    "nested.drain.backlog_wait": "never more than six drains pending",
    "groups.regrow": "no group table outgrows its slots",
    "fusion.h2d_overlapped": "only when an upload finds the device busy",
}
# Readers that give nothing here, and why.
NO_READING_ON_THE_CPU = {
    "peak_hbm_bytes": "a CPU device reports no memory_stats",
}

with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as _f:
    PER_LAYER = {m["name"]: m for m in json.load(_f)["per_layer"]}


def _program_metrics():
    out = []
    for path in sorted(glob.glob(os.path.join(BENCH, "metrics", "*.json"))):
        with open(path, encoding="utf-8") as f:
            spec = json.load(f)
        if spec["source"] in ("program_span", "program_counter"):
            out.append(spec)
    return out


METRICS = _program_metrics()


class _TinyRun:
    """One cell's job after a tiny run, with the two snapshots a traced
    run takes (``bmlib/cell.py``): after warm-up, and at the end."""

    def __init__(self, workload):
        over = dict(TINY_Q5 if workload.startswith("nexmark")
                    else TINY_LAV if workload.startswith("linear_road_lav")
                    else TINY_LR if workload.startswith("linear_road")
                    else TINY)
        self.cell, self.cfg, params = bmcell.load_cell(workload, over)
        batch = self.cfg["batch"]
        pool = bmcell.make_pool(
            self.cfg, 11, POOL_BATCHES.get(workload, 8) * batch)
        self.source = bmdata.CyclingSource(
            pool, bmdata.make_schema(self.cfg),
            bmdata.stream_name(self.cfg), batch)
        self.sink = DeliverySink(
            self.cfg["index_col"], SampleRanges(11, pool.n, batch, 8), pool)
        self.sink.recording = True
        self.job = job = bmcell.build_job(
            self.cfg, params, self.source, self.sink)
        while self.source.served < WARM_BATCHES:
            job.run_cycle()
        self.snap0, self.served0 = bmcell._snapshot(job), self.source.served
        while self.source.served < LONGER_RUNS.get(workload, RUN_BATCHES):
            job.run_cycle()
        self.source.stop()
        while not job.finished:
            job.run_cycle()
        job.flush()
        self.snap1 = bmcell._snapshot(job)

    def booked(self):
        snap = self.snap1
        return (set(snap["stages"]) | set(snap["histograms"])
                | set(snap["counters"]) | set(snap["gauges"]))

    def context(self):
        return layers.Context(
            cell=self.cell, cfg=self.cfg, job=self.job, snap0=self.snap0,
            snap1=self.snap1, batches=self.source.served - self.served0,
            batch=self.cfg["batch"], trace=None, source=self.source,
            sink=self.sink, device={"memory_peak_bytes": 0},
        )


@pytest.fixture(scope="module")
def tiny_run():
    """One tiny run per cell, shared by every metric that reads it."""
    runs = {}

    def get(workload):
        if workload not in runs:
            runs[workload] = _TinyRun(workload)
        return runs[workload]

    yield get
    runs.clear()


def _names(args):
    """Every string in a metric's ``args`` is a name the program books
    (the other arguments are numbers and switches)."""
    for value in args.values():
        for v in value if isinstance(value, list) else [value]:
            if isinstance(v, str):
                yield v


def _package_books(name):
    """The literal the package passes to ``span`` / ``inc`` (the
    registry adds ``nested.`` to a span opened inside another)."""
    literal = '"%s"' % name.removeprefix("nested.")
    for root, _dirs, files in os.walk(os.path.join(REPO, "flink_siddhi_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(root, fn), encoding="utf-8") as f:
                    if literal in f.read():
                        return True
    return False


def test_the_directory_and_the_contract_list_the_same_metrics():
    assert METRICS and {m["name"] for m in METRICS} == {
        name for name, m in PER_LAYER.items()
        if m["source"] in ("program_span", "program_counter")}


@pytest.mark.parametrize("spec", METRICS, ids=[m["name"] for m in METRICS])
def test_every_name_a_metric_reads_is_booked_by_the_program(spec, tiny_run):
    workloads = PER_LAYER[spec["name"]]["workloads"]
    runs = [tiny_run(w) for w in workloads]
    booked = set().union(*(r.booked() for r in runs))
    for name in _names(spec.get("args", {})):
        if name in NOT_ON_EVERY_SOUND_TINY_RUN:
            assert _package_books(name), name
        elif name.endswith("."):  # a gauge family
            assert any(b.startswith(name) for b in booked), name
        else:
            assert name in booked, (name, workloads)
    # and the reader reads it: a number in every cell that lists it
    reader = bmcell.load_module("metrics", spec["reader"])
    for run, workload in zip(runs, workloads):
        value = reader.read(run.context(), **spec.get("args", {}))
        if spec["name"] in NO_READING_ON_THE_CPU:
            assert value is None, (workload, value)
        else:
            assert value is not None and math.isfinite(value), workload
