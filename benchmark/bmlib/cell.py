"""One run of one cell: load, warm, measure, compare, reduce.

``run_cell`` is everything ``run.py`` does after it has found the chip.
The tests call it with tiny sizes on the CPU.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import logging
import os
import time

import numpy as np

from . import compare, estimate
from .data import CyclingSource, PacedSource, make_schema, stream_name
from .sink import DeliverySink, SampleRanges

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(kind: str, name: str, root: str = HERE):
    with open(os.path.join(root, kind, name + ".json"), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str, root: str = HERE):
    path = os.path.join(root, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bm_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, overrides=None, root: str = HERE):
    """(cell, config, traffic parameters) of a workload, found by name."""
    cell = load_json("cells", workload, root)
    cfg = load_json("configs", cell["config"], root)
    params = load_json("traffic", cell["traffic"], root)
    params.update(cell.get("params") or {})
    for k, v in (overrides or {}).items():
        (cfg if k in cfg and k not in params else params)[k] = v
    return cell, cfg, params


def make_pool(cfg, seed, n, root: str = HERE):
    """The configuration's stream: ``n`` events' worth of draws from
    ``seed``, by the generator its file names."""
    return load_module("generators", cfg["generator"], root).make_pool(
        seed, n, cfg)


class _DropCounter(logging.Handler):
    """The program reports dropped emissions only as a WARNING; count
    them (``%d emissions dropped``: the number is the record's)."""

    def __init__(self) -> None:
        super().__init__(logging.WARNING)
        self.dropped = 0

    def emit(self, record) -> None:
        if "emissions dropped" in str(record.msg):
            nums = [a for a in (record.args or ()) if isinstance(a, int)]
            self.dropped += nums[-1] if nums else 1


def build_job(cfg, params, source, sink):
    from flink_siddhi_tpu.compiler.config import EngineConfig
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.executor import Job

    plan = compile_plan(
        cfg["cql"], {source.stream_id: source.schema}, plan_id=cfg["name"],
        config=EngineConfig(**cfg["engine_config"]),
    )
    kw = dict(
        batch_size=source.batch, time_mode=cfg["time_mode"],
        retain_results=False,
    )
    if cfg["job"] == "ShardedJob":
        from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh

        job = ShardedJob([plan], [source], mesh=make_cep_mesh(cfg["chips"]),
                         **kw)
    else:
        job = Job([plan], [source], **kw)
    job.fused_segment_len = cfg["fused_segment_len"]
    if params.get("drain_interval_ms") is not None:
        job.drain_interval_ms = float(params["drain_interval_ms"])
    job.add_sink(cfg["output_stream"], sink)
    job.prewarm_drains()
    return job


def _overflow(job) -> int:
    """Dropped partial matches: every ``overflow`` leaf of the plans'
    device state, read once the window has closed."""
    import jax

    total = 0
    for rt in job._plans.values():
        for path, leaf in jax.tree_util.tree_leaves_with_path(rt.states):
            if any(getattr(p, "key", None) == "overflow" for p in path):
                total += int(np.sum(np.asarray(leaf)))
    return total


def _snapshot(job):
    snap = job.telemetry.snapshot()
    snap["t"] = time.perf_counter()
    return snap


def _decide_correct(cfg, pool, sink, n_window, root, control, say):
    """What the window delivered against the plain reference: (correct,
    each number compared beside its limit), which it also prints."""
    t = time.perf_counter()
    reference = load_module("configs", cfg["name"], root)
    lo, hi = sink.hi[0], sink.hi[n_window - 1]
    numbers, n_ranges, n_rows = compare.check_samples(
        sink, reference, pool, cfg["compare"], lo, hi
    )
    numbers["deliveries_out_of_order"] = sum(
        1 for i in range(1, n_window) if sink.lo[i] < sink.hi[i - 1]
    )
    # every row due between the window's first and last delivery arrived
    numbers["window_rows_lost_or_extra"] = abs(
        sink.rows_between(n_window)
        - compare.rows_due(reference, pool, cfg, lo + 1, hi + 1)
    )
    numbers["undecodable_columns"] = sink.none_columns
    lim = compare.limits(cfg["compare"])
    lim.update(deliveries_out_of_order=0, window_rows_lost_or_extra=0,
               undecodable_columns=0)
    compared = {
        "ranges": n_ranges, "rows": n_rows,
        "numbers": {k: [numbers[k], lim[k]] for k in lim},
    }
    say("[bench] compared " + json.dumps(
        {**compared, "reference_s": time.perf_counter() - t}))
    if control:
        low, _, _ = compare.check_samples(
            sink, reference, pool, cfg["compare"], lo, hi,
            substitute=lambda a, b: reference.expected(pool, a, b, "bf16"),
        )
        say("[bench] control(bf16) " + json.dumps({
            "numbers": {k: [low[k], lim[k]] for k in low},
            "correct": all(low[k] <= lim[k] for k in low),
        }))
    correct = n_ranges > 0 and all(numbers[k] <= lim[k] for k in lim)
    return correct, compared


def _end_to_end(names, params, source, sink, n_window, t_open, batch,
                handed_over):
    """(metrics, attempted, what is printed beside them), from the
    sink's log alone. ``names`` is the cell's ``reports``: the name in
    BENCHMARK.json under which it reports its rate, or its latency."""
    tt, ee = sink.t[:n_window], sink.hi[:n_window]
    summary = estimate.rate_summary(tt, ee, params["slice_seconds"])
    info = {k: v for k, v in summary.items() if k != "slice_rates"}
    info["slice_rates"] = [round(r, 1) for r in summary["slice_rates"]]
    info["first_deliveries_s"] = [round(x - t_open, 3) for x in tt[:16]]
    if params["loop"] != "open":
        # all the events completed over all the time of the window
        metrics = {names["rate"]: {"value": summary["plain_rate"],
                                   "unit": "events/s"}}
        return metrics, handed_over, info
    due = np.concatenate([source.due_s(ix) for ix in sink.index[:n_window]])
    emit = np.repeat(tt, sink.rows[:n_window])
    lat = np.sort((emit - due) * 1e3)
    metrics = {
        names["p50"]: {"value": estimate.percentile(lat, 50), "unit": "ms"},
    }
    # the tail is printed and not reported: it reads what the host's
    # hiccups did to the run, 437-514 ms on one code (PERF.md, finding 5)
    info["latency_p95_ms"] = estimate.percentile(lat, 95)
    info["latency_samples"] = len(lat)
    info["latency_samples_beyond_p95"] = int(len(lat) * 0.05)
    info["latency_max_ms"] = float(lat[-1])
    # backlog: events due and not yet completed, at open and close
    for name, i in (("open", 0), ("close", n_window - 1)):
        info[f"backlog_{name}_events"] = int(
            (tt[i] - source.t0) * source.rate
            - (ee[i] - source.first * batch)
        )
    # offered in the window: what was due
    return metrics, int((tt[-1] - t_open) * source.rate), info


def _annotated(fn, name):
    import jax

    def wrapped(*args, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kw)

    return wrapped


def run_cell(workload, seed, seconds, trace, *, t_start=None, overrides=None,
             control=False, root=HERE, say=print, keep_trace=None):
    """Returns the result line as a dict. ``say`` prints the earlier
    lines. ``control`` adds the lower-precision control's numbers."""
    import jax

    from . import tracered

    t_enter = time.perf_counter()
    t_start = t_enter if t_start is None else t_start
    cell, cfg, params = load_cell(workload, overrides, root)
    live = params["loop"] == "open"
    batch = int(params["release_batch"] if live else cfg["batch"])
    pool_events = int(
        params.get("pool_events") or params["pool_batches"] * batch
    )
    t = time.perf_counter()
    pool = make_pool(cfg, seed, pool_events, root)
    schema, stream = make_schema(cfg), stream_name(cfg)
    if live:
        source = PacedSource(pool, schema, stream, batch,
                             params["rate_events_per_s"],
                             params["max_release"])
    else:
        source = CyclingSource(pool, schema, stream, batch)
    length = max(int(params["sample_length_per_batch"] * batch), 8)
    ranges = SampleRanges(seed, pool.n, batch, length,
                          params["sample_ranges"])
    sink = DeliverySink(cfg["index_col"], ranges, pool, keep_index=live)
    if trace:
        # the harness's own spans, around its calls into the job
        source.poll = _annotated(source.poll, "bench.poll")
        sink.accept_columns = _annotated(sink.accept_columns, "bench.sink")
    # process start to here: interpreter, imports, reaching the chip
    setup = {"start_s": t_enter - t_start, "data_s": time.perf_counter() - t}
    drops = _DropCounter()
    logging.getLogger("flink_siddhi_tpu").addHandler(drops)

    t = time.perf_counter()
    job = build_job(cfg, params, source, sink)
    setup["build_s"] = time.perf_counter() - t

    # -- warm-up: until more segments have gone out than the in-flight
    # queue holds, and results have come back: the queue is then full
    t = time.perf_counter()
    k = job.fused_segment_len or 1
    warm_batches = max(
        k * (job.max_inflight_cycles
             + params["warm_dispatches_beyond_inflight"]),
        -(-cfg.get("warm_events_min", 0) // batch),
    )
    while (
        source.served < warm_batches
        or sink.deliveries < params["warm_deliveries"]
    ):
        job.run_cycle()
    if live:
        job.drain_outputs(wait=True)  # the schedule starts on an empty queue
    gc.collect()
    gc.freeze()
    if live:
        source.begin_schedule(time.perf_counter())
        t_settle = time.perf_counter() + params["settle_seconds"]
        while time.perf_counter() < t_settle:
            if not job.run_cycle():
                time.sleep(0.0005)
    setup["warm_s"] = time.perf_counter() - t

    # -- the window: opens at the next delivery, closes at the last one
    # before ``seconds`` are up
    annotate = (
        jax.profiler.TraceAnnotation if trace else
        (lambda name: contextlib.nullcontext())
    )
    seen = sink.deliveries
    sink.recording = True
    while sink.deliveries == seen or not sink.t:
        job.run_cycle()
    t_open = sink.t[0]
    setup_s = t_open - t_start
    snap0 = _snapshot(job)
    served0 = source.served
    t_close = t_open + seconds
    traced = None
    if trace:
        lead = min(params["trace_lead_seconds"], seconds / 4)
        t_trace0 = t_open + lead
        t_close = t_trace0 + min(params["trace_seconds"], seconds - lead)
    while time.perf_counter() < t_close and not source.exhausted:
        if trace and traced is None and time.perf_counter() >= t_trace0:
            traced = tracered.start(
                os.path.join(root, "..", ".bench_trace"))
            snap0 = _snapshot(job)
            served0 = source.served
        with annotate("bench.run_cycle"):
            n = job.run_cycle()
        if live and not n:
            time.sleep(0.0005)
    snap1 = _snapshot(job)
    served1 = source.served
    if traced is not None:
        traced = tracered.stop(traced, keep_trace)
    sink.recording = False
    n_window = sum(1 for x in sink.t if x <= t_open + seconds)

    # -- outside the window: close the stream, then compare
    source.stop()
    while not job.finished:
        job.run_cycle()
    job.flush()
    logging.getLogger("flink_siddhi_tpu").removeHandler(drops)
    correct, compared = _decide_correct(
        cfg, pool, sink, n_window, root, control, say)
    failed = (
        int(getattr(job, "shed_events", 0)) + drops.dropped + _overflow(job)
    )
    metrics, attempted, info = _end_to_end(
        cell["reports"], params, source, sink, n_window, t_open, batch,
        (served1 - served0) * batch,
    )
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    info["window_cut_at_event_horizon"] = source.exhausted
    say("[bench] window " + json.dumps(info))
    say("[bench] setup " + json.dumps({**setup, "setup_s": setup_s}))

    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        ),
    }
    result = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        from . import layers

        ctx = layers.Context(
            cell=cell, cfg=cfg, job=job, snap0=snap0, snap1=snap1,
            batches=served1 - served0, batch=batch, trace=traced,
            source=source, sink=sink, device=device,
        )
        result["metrics"] = layers.read_all(ctx, root, say)
        if traced is not None:
            device["busy_s"] = traced["busy_s"]
            device["window_s"] = traced["window_s"]
            result["breakdown"] = traced["breakdown"]
    result["compared"] = compared  # last in the line
    return result
