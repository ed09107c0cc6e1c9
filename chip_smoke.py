"""Chip smoke: the served path, once, on the accelerator, at deployment size.

    python chip_smoke.py [--seed N] [--chips 1|4]

Not a benchmark. It answers one question — does the program a user would
deploy still start, run and give right answers on the chip — and it is
the quickest proof of that. One process; nothing it starts touches JAX
(the only child is ``make``, building the C++ decoder). Every phase
checks its rows against an independent reference and raises on a
mismatch: there is no ``try/except`` between a failed phase and a
non-zero exit.

Phases (sizes are the deployment's: ``BASELINE.json`` configs 3 and 4,
their queries from ``flink_siddhi_tpu/baseline/workloads.py``):

* **kernels**   the Pallas kernel left in the tree compiles (no
  interpreter) and equals its XLA form at its probe shape and at
  E = 524,288, alone and under shard_map.
* **headline, streaming**   the 3-step ``within 5 sec`` pattern,
  8 x 524,288 = 4,194,304 events through ``Job.run()`` with fused
  segments of 8 and ROWS delivered to a sink; rows equal
  ``baseline.BaselineEngine`` on the same events.
* **headline, resident**   the same events through
  ``ResidentReplay(job).execute()``; rows identical to streaming; the
  compiled segment program contains the kernel's custom call.
* **window**   ``#window.length(1000)`` group-by over 1,000 keys,
  4,194,304 events, every row against ``BaselineEngine``; a checkpoint
  taken mid-stream and restored into a fresh ``Job`` gives the same rows.
* **pipeline**   ``app.pipeline.CEPPipeline`` from a JSON config over a
  generated file of 1,048,576 JSON lines -> filter -> file sink, on the
  C++ decoder built on this machine from ``fast_decode.cpp``.
* **four chips**   (when four devices are visible; required by
  ``--chips 4``) the four-plan mix — segment-parallel pattern, keyed
  pattern, group-by, shuffle filter — on a 4-device ``ShardedJob`` at a
  65,536-event batch; rows equal the one-chip ``Job``'s; state and
  accumulators live on four devices.

Data is made from ``--seed`` in the shapes of ``baseline/workloads.py``: ``id``
uniform over 50 ids (1,000 for the window phase), one interned ``name``,
``price`` in [0, 100), timestamps 1 ms apart.

It exits non-zero, printing nothing on standard output, when the platform
is not ``tpu`` or when the package is not beside it. On success the line
before last is ``[chip_smoke] summary: {...}`` — per-phase events / rows /
seconds (information about a cold start, not a measurement), the compile
cache's hits and which kernels ran compiled — and the last line of standard
output is exactly ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``, the device as JAX reports it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# the one compile cache (tests/conftest.py uses the same
# idiom): where the environment names a directory, there; else a fixed
# path in the repo — the path is part of the cache key, so it never moves
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

import jax  # noqa: E402
import numpy as np  # noqa: E402

FIELDS = ("id", "name", "price", "timestamp")
STREAM = "inputStream"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The deployment's sizes. tests/test_chip_smoke.py passes tiny ones
    to drive the same phase functions on the CPU lane."""

    events: int = 8 * 524_288
    batch: int = 524_288
    pipeline_lines: int = 1_048_576
    shard_batch: int = 65_536


SEGMENT = 8  # Job.fused_segment_len, as the one-chip cells set it
SHARD_BATCHES = 4


# -- XLA compile accounting (information only) -------------------------------
_COMPILE = {"backend_compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def _on_duration(name: str, secs: float, **_kw) -> None:
    if name == "/jax/core/compile/backend_compile_duration":
        _COMPILE["backend_compile_s"] += secs


def _on_event(name: str, **_kw) -> None:
    if name == "/jax/compilation_cache/cache_hits":
        _COMPILE["cache_hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        _COMPILE["cache_misses"] += 1


# -- data --------------------------------------------------------------------
def make_schema():
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    return StreamSchema(
        [
            ("id", AttributeType.INT),
            ("name", AttributeType.STRING),
            ("price", AttributeType.DOUBLE),
            ("timestamp", AttributeType.LONG),
        ]
    )


def make_columns(seed: int, n: int, n_ids: int):
    rng = np.random.default_rng(seed)
    return {
        "id": rng.integers(0, n_ids, size=n).astype(np.int32),
        "price": rng.random(n, dtype=np.float64) * 100.0,
        "timestamp": 1_000 + np.arange(n, dtype=np.int64),
    }


def make_batches(schema, cols, batch: int):
    """Prebuilt columnar EventBatches — zero per-record Python work."""
    from flink_siddhi_tpu.schema.batch import EventBatch

    name_code = schema.string_tables["name"].intern("test_event")
    n = len(cols["id"])
    out = []
    for s in range(0, n, batch):
        ts = cols["timestamp"][s:s + batch]
        out.append(
            EventBatch(
                STREAM,
                schema,
                {
                    "id": cols["id"][s:s + batch],
                    "name": np.full(len(ts), name_code, dtype=np.int32),
                    "price": cols["price"][s:s + batch],
                    "timestamp": ts,
                },
                ts,
            )
        )
    return out


# -- rows: collecting and comparing ------------------------------------------
class RowSink:
    """Columnar sink keeping every delivered batch (the consumer)."""

    def __init__(self) -> None:
        self._ts = []
        self._cols = {}

    def accept_columns(self, ts, cols) -> None:
        self._ts.append(np.array(ts))
        for k, v in cols.items():
            self._cols.setdefault(k, []).append(np.array(v))

    def table(self):
        if not self._ts:
            return {"@ts": np.zeros(0, np.int64)}
        return {
            "@ts": np.concatenate(self._ts),
            **{k: np.concatenate(v) for k, v in self._cols.items()},
        }


def _table(ts, rows, names):
    """Row tuples + their timestamps as a column table."""
    table = {"@ts": np.asarray(ts, dtype=np.int64)}
    for name, col in zip(names, zip(*rows)):
        table[name] = np.asarray(col)
    return table


def baseline_table(cql: str, cols, names):
    """The plain reference: ``BaselineEngine`` replaying the same events
    one at a time; its rows as a column table."""
    from flink_siddhi_tpu.baseline import BaselineEngine

    eng = BaselineEngine(cql, list(FIELDS))
    ts_out, rows = [], []

    def emit(_out, ts, row):
        ts_out.append(ts)
        rows.append(row)

    eng._emit = emit
    ts = cols["timestamp"].tolist()
    eng.run_columns(
        {
            "id": cols["id"].tolist(),
            "name": ["test_event"] * len(ts),
            "price": cols["price"].tolist(),
            "timestamp": ts,
        },
        ts,
    )
    return _table(ts_out, rows, names)


def results_table(job, stream: str, names):
    """A job's retained rows for one output stream as a column table."""
    pairs = job.results_with_ts(stream)
    return _table([t for t, _ in pairs], [r for _, r in pairs], names)


def assert_same_rows(
    what: str, got, want, rtol: float = 0.0, atol: float = 0.0
) -> int:
    """Both tables as sorted multisets: float columns within
    ``atol + rtol * |want|`` (both 0 = bit-equal), everything else
    exact. Returns the row count."""
    assert set(got) == set(want), (what, sorted(got), sorted(want))
    n = len(want["@ts"])
    assert len(got["@ts"]) == n, (
        f"{what}: {len(got['@ts'])} rows, reference has {n}"
    )
    ints = [k for k in want if np.issubdtype(want[k].dtype, np.integer)]
    for k in got:
        # an object column means a row field decoded as None
        assert got[k].dtype != object, f"{what}: column {k} holds None"

    def order(t):
        return np.lexsort([t[k].astype(np.int64) for k in reversed(ints)])

    og, ow = order(got), order(want)
    for k in want:
        a, b = got[k][og], want[k][ow]
        if k in ints:
            assert np.array_equal(a.astype(np.int64), b.astype(np.int64)), (
                f"{what}: column {k} differs"
            )
        elif rtol and np.issubdtype(b.dtype, np.floating):
            assert np.allclose(a, b, rtol=rtol, atol=atol), (
                f"{what}: column {k} differs beyond rtol={rtol} "
                f"atol={atol}: max |diff| {np.abs(a - b).max()}"
            )
        else:
            assert np.array_equal(a, b), f"{what}: column {k} differs"
    return n


class _Clock:
    """One phase's seconds: set-up until ``run()``, run until
    ``done()``, and the XLA backend-compile share of both."""

    def __init__(self) -> None:
        self.t0 = self.t1 = time.perf_counter()
        self.c0 = _COMPILE["backend_compile_s"]

    def run(self) -> None:
        self.t1 = time.perf_counter()

    def done(self, name: str, **kw):
        out = {
            **kw,
            "setup_s": round(self.t1 - self.t0, 3),
            "run_s": round(time.perf_counter() - self.t1, 3),
            "xla_compile_s": round(
                _COMPILE["backend_compile_s"] - self.c0, 3
            ),
        }
        print(f"[chip_smoke] {name}: {json.dumps(out)}", flush=True)
        return out


# -- phase: kernels ----------------------------------------------------------
def phase_kernels(sizes: Sizes, seed: int, expect_mode: str = "compiled"):
    import jax.numpy as jnp

    from flink_siddhi_tpu.compiler import pallas_ops

    clock = _Clock()
    mode = pallas_ops.mode()
    assert mode == expect_mode, (
        f"reverse cummin would run as {mode!r}, expected {expect_mode!r}"
    )
    # the probes raise on a build/compile failure or an oracle mismatch
    assert pallas_ops.warmup() and pallas_ops.warmup_shard()
    rng = np.random.default_rng(seed)
    kernel = jax.jit(lambda *r: pallas_ops.multi_reverse_cummin(list(r)))
    xla = jax.jit(
        lambda *r: [jax.lax.cummin(x, axis=0, reverse=True) for x in r]
    )
    shapes = {}
    for E in sorted({4 * 1024, sizes.batch}):
        # the chain matcher's own input: tape positions, E = "no match"
        rows = [
            jnp.asarray(
                np.where(
                    rng.random(E) < 0.02, np.arange(E), E
                ).astype(np.int32)
            )
            for _ in range(3)
        ]
        if mode == "compiled":
            text = kernel.lower(*rows).as_text()
            assert "tpu_custom_call" in text, "kernel not in lowering"
        # first call of each form = its compile, as information: what
        # the engine pays for the kernel, and would pay without it
        t = time.perf_counter()
        got = jax.block_until_ready(kernel(*rows))
        t_kernel = time.perf_counter() - t
        t = time.perf_counter()
        ref = jax.block_until_ready(xla(*rows))
        t_xla = time.perf_counter() - t
        for g, r in zip(got, ref):
            assert np.array_equal(np.asarray(g), np.asarray(r)), (
                f"reverse cummin != XLA form at E={E}"
            )
        shapes[E] = {
            "kernel_first_call_s": round(t_kernel, 3),
            "xla_form_first_call_s": round(t_xla, 3),
        }
    return clock.done(
        "kernels",
        reverse_cummin={"mode": mode, "shard_map": True, "shapes": shapes},
    )


# -- phases: headline pattern, streaming and resident ------------------------
def _headline_job(schema, batches, sizes: Sizes, sink):
    from flink_siddhi_tpu.baseline.workloads import config_cql
    from flink_siddhi_tpu.compiler.config import EngineConfig
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.executor import Job
    from flink_siddhi_tpu.runtime.sources import BatchSource

    # as benchmark/configs/pattern3.json builds it: late
    # materialization + predicate pushdown
    plan = compile_plan(
        config_cql("headline"), {STREAM: schema}, plan_id="headline",
        config=EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    job = Job(
        [plan], [BatchSource(STREAM, schema, iter(batches))],
        batch_size=sizes.batch, time_mode="processing",
        retain_results=False,
    )
    job.fused_segment_len = SEGMENT
    job.add_sink("matches", sink)
    return job


def phase_headline(sizes: Sizes, seed: int, expect_mode: str = "compiled"):
    """Streaming then resident over the same events; one baseline run."""
    from flink_siddhi_tpu.baseline.workloads import config_cql
    from flink_siddhi_tpu.runtime.replay import ResidentReplay

    out = {}
    clock = _Clock()
    schema = make_schema()
    cols = make_columns(seed, sizes.events, n_ids=50)
    batches = make_batches(schema, cols, sizes.batch)
    want = baseline_table(
        config_cql("headline"), cols, ("t1", "t3", "price")
    )
    sink = RowSink()
    job = _headline_job(schema, batches, sizes, sink)
    clock.run()
    job.run()
    streamed = sink.table()
    # timestamps exact; price at f32 tolerance (the device computes in
    # f32, the interpreter in f64)
    n = assert_same_rows("headline streaming", streamed, want, rtol=1e-6)
    assert n > 0 and job.processed_events == sizes.events
    counters = job.telemetry.snapshot()["counters"]
    out["headline_streaming"] = clock.done(
        "headline_streaming", events=sizes.events, rows=n,
        # counts from the job's own registry: segments dispatched, and
        # uploads issued while the previous segment was still computing
        **{
            name: int(counters.get(key, 0))
            for name, key in (
                ("batches", "fusion.batches"),
                ("dispatches", "fusion.dispatches"),
                ("h2d_uploads", "fusion.h2d_uploads"),
                ("h2d_overlapped", "fusion.h2d_overlapped"),
                ("drains", "drains.completed"),
            )
        },
    )

    clock = _Clock()
    sink = RowSink()
    job = _headline_job(schema, batches, sizes, sink)
    rep = ResidentReplay(job)
    rep.stage()  # tape building + H2D + compile: set-up
    if expect_mode == "compiled":
        text = rep._staged["headline"]["scan"].as_text()
        assert "tpu_custom_call" in text, (
            "the compiled headline step holds no Pallas custom call"
        )
    clock.run()
    rep.run()
    job.flush()
    n = assert_same_rows("headline resident", sink.table(), streamed)
    out["headline_resident"] = clock.done(
        "headline_resident", events=rep.total_events, rows=n,
        kernel_in_step=expect_mode == "compiled",
    )
    return out


# -- phase: window state at deployment size + checkpoint ---------------------
def phase_window(sizes: Sizes, seed: int):
    from flink_siddhi_tpu.baseline.workloads import config_cql
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.executor import Job
    from flink_siddhi_tpu.runtime.sources import ReplayBatchSource

    cql = config_cql("window_groupby")
    clock = _Clock()
    schema = make_schema()
    cols = make_columns(seed + 1, sizes.events, n_ids=1000)
    batches = make_batches(schema, cols, sizes.batch)
    want = baseline_table(cql, cols, ("id", "total", "cnt"))

    def build(sink):
        job = Job(
            [compile_plan(cql, {STREAM: schema}, plan_id="window")],
            [ReplayBatchSource(STREAM, schema, batches)],
            batch_size=sizes.batch, time_mode="processing",
            retain_results=False,
        )
        job.fused_segment_len = SEGMENT
        job.add_sink("matches", sink)
        return job

    sink = RowSink()
    job = build(sink)
    clock.run()
    job.run()
    full = sink.table()
    # counts exact; sums within 1e-4 of the f64 interpreter. The engine
    # sums in f32 over the whole micro-batch, so a sum's error is set by
    # the batch, not by the sum: 1e-3 absolute (prices are < 100) covers
    # the few-cent sums a relative bound alone cannot
    n = assert_same_rows("window", full, want, rtol=1e-4, atol=1e-3)
    assert n == sizes.events  # one row per event

    # checkpoint mid-stream (inside a fused segment), restore into a
    # fresh Job, finish: head + tail are the uninterrupted run's rows
    head, tail = RowSink(), RowSink()
    first = build(head)
    first.run(max_cycles=max(1, len(batches) // 2 - 1))
    assert not first.finished
    with tempfile.TemporaryDirectory() as d:
        ckpt = os.path.join(d, "ckpt")
        first.save_checkpoint(ckpt)
        second = build(tail)
        second.restore(ckpt)
        second.run()
    h, t = head.table(), tail.table()
    assert len(h["@ts"]) and len(t["@ts"])
    joined = {k: np.concatenate([h[k], t[k]]) for k in h}
    assert_same_rows("window checkpoint/restore", joined, full)
    return clock.done(
        "window", events=sizes.events, rows=n,
        restored_at_event=int(len(h["@ts"])),
    )


# -- phase: the deployable entry ---------------------------------------------
def phase_pipeline(sizes: Sizes, seed: int):
    from flink_siddhi_tpu.baseline.workloads import config_cql
    from flink_siddhi_tpu import native
    from flink_siddhi_tpu.app.pipeline import CEPPipeline, PipelineConfig

    clock = _Clock()
    # built by `make` from fast_decode.cpp on this machine, this run
    assert native.available(), "the C++ decoder did not build"
    n = sizes.pipeline_lines
    cols = make_columns(seed + 2, n, n_ids=50)
    cql = config_cql("filter")
    want = baseline_table(cql, cols, ("id", "name", "price"))
    with tempfile.TemporaryDirectory() as d:
        src, dst = os.path.join(d, "in.jsonl"), os.path.join(d, "out.jsonl")
        with open(src, "w", encoding="utf-8") as f:
            f.write(
                "".join(
                    '{"id": %d, "name": "test_event", "price": %r, '
                    '"timestamp": %d}\n' % row
                    for row in zip(
                        cols["id"].tolist(), cols["price"].tolist(),
                        cols["timestamp"].tolist(),
                    )
                )
            )
        config = PipelineConfig.from_json(json.dumps({
            "stream_id": STREAM,
            "fields": [["id", "int"], ["name", "string"],
                       ["price", "double"], ["timestamp", "long"]],
            "cql": cql,
            "input_path": src,
            "output_path": dst,
            "ts_field": "timestamp",
        }))
        pipe = CEPPipeline(config)
        clock.run()
        job = pipe.run()
        pipe.close()
        assert job._sources[0].native, "pipeline ran the Python decoder"
        with open(dst, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
    got = {
        "@ts": np.asarray([r["ts"] for r in rows], np.int64),
        "id": np.asarray([r["id"] for r in rows]),
        "name": np.asarray([r["name"] for r in rows]),
        "price": np.asarray([r["price"] for r in rows]),
    }
    assert job.processed_events == n
    n_rows = assert_same_rows("pipeline", got, want, rtol=1e-6)
    return clock.done(
        "pipeline", events=n, rows=n_rows, native_decoder=True
    )


# -- phase: four chips -------------------------------------------------------
_MIX = {
    # unkeyed every-chain: time-SEGMENT parallel, partials hop shard to
    # shard through lax.ppermute
    "pattern": ("matches", ("t1", "t3", "price")),
    # `partition with` pattern: key-hash routing, per-key NFA state
    "keyed": ("keyed_matches", ("t1", "t2", "kid")),
    # keyed aggregation: dp over the key axis
    "groupby": ("totals", ("id", "total", "cnt")),
    # stateless: shuffle routing
    "filter": ("big", ("id", "price")),
}


def _mix_plans(schema):
    from flink_siddhi_tpu.baseline.workloads import config_cql
    from flink_siddhi_tpu.compiler.plan import compile_plan

    texts = {
        "pattern": config_cql("headline"),
        "keyed": (
            "partition with (id of inputStream) begin "
            "from every k1 = inputStream[price > 0.0] -> "
            "k2 = inputStream[price > 1.0] "
            "select k1.timestamp as t1, k2.timestamp as t2, "
            "k1.id as kid insert into keyed_matches; end"
        ),
        "groupby": (
            "from inputStream select id, sum(price) as total, "
            "count() as cnt group by id insert into totals"
        ),
        "filter": (
            "from inputStream[price > 10.0] select id, price "
            "insert into big"
        ),
    }
    return [
        compile_plan(text, {STREAM: schema}, plan_id=pid)
        for pid, text in texts.items()
    ]


def phase_four_chips(sizes: Sizes, seed: int, n_shards: int = 4):
    from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh
    from flink_siddhi_tpu.runtime.executor import Job
    from flink_siddhi_tpu.runtime.sources import BatchSource

    clock = _Clock()
    n = sizes.shard_batch * SHARD_BATCHES
    schema = make_schema()
    cols = make_columns(seed + 3, n, n_ids=50)
    batches = make_batches(schema, cols, sizes.shard_batch)
    kw = dict(batch_size=sizes.shard_batch, time_mode="processing")
    sharded = ShardedJob(
        _mix_plans(schema), [BatchSource(STREAM, schema, iter(batches))],
        mesh=make_cep_mesh(n_shards), **kw,
    )
    parts = {
        pid: rt.plan.partitions[STREAM].kind
        for pid, rt in sharded._plans.items()
    }
    assert parts["pattern"] == "segment" and parts["keyed"] == "groupby"
    single = Job(
        _mix_plans(schema), [BatchSource(STREAM, schema, iter(batches))],
        **kw,
    )
    clock.run()
    sharded.run()
    # state and accumulators really live on every device of the mesh
    for pid, rt in sharded._plans.items():
        for leaf in jax.tree.leaves((rt.states, rt.acc)):
            assert len(leaf.sharding.device_set) == n_shards, (
                pid, leaf.shape, leaf.sharding
            )
    single.run()
    rows = {}
    for pid, (stream, names) in _MIX.items():
        # float sums associate differently per shard: f32 tolerance
        rows[pid] = assert_same_rows(
            f"sharded {pid}",
            results_table(sharded, stream, names),
            results_table(single, stream, names),
            rtol=1e-4,
        )
        assert rows[pid] > 0, f"{pid}: no rows"
    return clock.done(
        "four_chips", events=n, rows=rows, shards=n_shards,
        partitions=parts,
    )


# -- main --------------------------------------------------------------------
def report(device: dict, summary: str) -> None:
    """The details, then the last line: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) — the driver reads nothing else."""
    print(f"[chip_smoke] summary: {summary}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=None,
        help="4: the four-chip phase is required, not optional",
    )
    args = ap.parse_args(argv)

    # alone in a directory (no package beside it) this raises before
    # anything is written to standard output
    import flink_siddhi_tpu  # noqa: F401

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    header = (
        f"[chip_smoke] jax {jax.__version__} on {json.dumps(device)} "
        f"({'; '.join(dev.client.platform_version.splitlines())}); "
        f"compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}"
    )
    # no accelerator (or not the one asked for): nothing on standard
    # output, so nothing can be read as a result
    if dev.platform != "tpu":
        print(
            f"{header}\nchip_smoke: platform is {dev.platform!r}, not "
            "'tpu' — this check runs on the accelerator or not at all",
            file=sys.stderr,
        )
        return 2
    if args.chips == 4 and device["count"] < 4:
        print(
            f"{header}\nchip_smoke: --chips 4 but {device['count']} "
            "device(s)", file=sys.stderr,
        )
        return 2
    print(header, flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    sizes = Sizes()
    t0 = time.perf_counter()
    phases = {"kernels": phase_kernels(sizes, args.seed)}
    phases.update(phase_headline(sizes, args.seed))
    phases["window"] = phase_window(sizes, args.seed)
    phases["pipeline"] = phase_pipeline(sizes, args.seed)
    if device["count"] >= 4:  # required by --chips 4, checked above
        phases["four_chips"] = phase_four_chips(sizes, args.seed)
    # every phase raised on a mismatch; reaching here is the result
    summary = json.dumps({
        "jax": jax.__version__,
        "seed": args.seed,
        "total_s": round(time.perf_counter() - t0, 1),
        "compile_cache": {
            "dir": os.environ["JAX_COMPILATION_CACHE_DIR"],
            "hits": _COMPILE["cache_hits"],
            "misses": _COMPILE["cache_misses"],
        },
        "device_peak_bytes": (dev.memory_stats() or {}).get(
            "peak_bytes_in_use"
        ),
        "phases": phases,
    })
    report(device, summary)
    return 0


if __name__ == "__main__":
    sys.exit(main())
