"""Does the program know when its device starves? A few seconds of one
closed-loop cell's job under a profiler session, then the device's idle
gaps laid beside the program's own belief on the same clock.

The starvation clock (flink_siddhi_tpu/telemetry/starve.py) flags every
``fst.<span>`` profiler annotation it opens while nothing is queued on
the device (``starved=1``) and writes a zero-length ``fst.starved_onset``
marker (``since_us``: its bracket) at the poll that finds the queue
empty. This script reads both from the trace's host plane and the
``XLA Ops`` line of device 0, and prints

* each idle gap of device 0 over 1 ms (the longest forty; all of them go
  to ``chiprun_out/profile_starve_<cell>.txt``) with the run loop's
  spans that cover it, each marked ``S`` (entered starved), ``O`` (the
  queue ran empty in it) or ``-`` (the program believed work queued);
* the share of device-0 idle time that lies under spans the program
  flagged (``S`` or ``O``, and the onset brackets), the largest gaps it
  did not flag, and the job's own ``starved.*`` ledger over the traced
  window beside the trace's idle time.

No operation name is read, so an older compile cache cannot mislabel
anything here. Built on the benchmark's own files (``benchmark/bmlib``),
so the job is the cell's job.

Usage (the chip tool): python scripts/profile_starve.py <cell> [seconds]
(``tiny`` as a third argument cuts the cell to a CPU rehearsal's size:
there is no device plane then, and the script says so and exits 2).
``python scripts/profile_starve.py is_ready`` times the clock's probe
alone: one ``is_ready()`` on a ticket that is ready, and on one that
waits behind a long program.
One JSON line last, naming the device. A number from a CPU run is not a
device number.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "benchmark"))

from bmlib import tracered  # noqa: E402  (its interval arithmetic)

GAP_NS = 1_000_000  # gaps printed one by one
TINY = {"batch": 4_096}
TINY_NEXMARK = {"event_time_rate": 2_000, "batch": 2_000,
                "fused_segment_len": 2,
                "engine_config": {"hop_group_slots": 8_192}}


def union(intervals):
    """Merged, ascending (starts, ends) of any [(start, end)]."""
    iv = np.asarray(intervals, float).reshape(-1, 2)
    return tracered._union(iv[:, 0], iv[:, 1])


def covered(merged, x):
    """Length of the merged intervals that lies below each x."""
    return tracered._covered(*merged, x)


def reduce(ops, spans, onsets):
    """``ops``: device-0 operation intervals [(start, end)] in ns;
    ``spans``: the run loop's ``fst.*`` spans [(name, start, end,
    starved)]; ``onsets``: the markers [(time, since_ns)]. Returns the
    idle time, how much of it the program flagged, and the gaps over
    ``GAP_NS``, each with the spans that cover it."""
    bs, be = union(ops)
    gs, ge = be[:-1], bs[1:]
    marked = []
    for name, s, e, starved in spans:
        onset = any(s <= t <= e for t, _since in onsets)
        marked.append((name, s, e, "S" if starved else "O" if onset else "-"))
    flagged = union(
        [(s, e) for _n, s, e, mark in marked if mark != "-"]
        + [(t - since, t) for t, since in onsets]
    )
    in_span = union([(s, e) for _n, s, e, _m in marked])
    g_flag = covered(flagged, ge) - covered(flagged, gs)
    g_span = covered(in_span, ge) - covered(in_span, gs)
    gaps = []
    for i in np.nonzero(ge - gs >= GAP_NS)[0]:
        s, e = gs[i], ge[i]
        cover = sorted(
            ((name, mark, min(e, se) - max(s, ss))
             for name, ss, se, mark in marked if ss < e and se > s),
            key=lambda c: -c[2],
        )
        gaps.append({
            "start": s, "ns": e - s, "flagged": g_flag[i],
            "in_spans": g_span[i], "cover": cover,
        })
    return {
        "idle_ns": float(np.sum(ge - gs)),
        "window_ns": float(be[-1] - bs[0]) if len(bs) else 0.0,
        "flagged_ns": float(np.sum(g_flag)),
        "in_spans_ns": float(np.sum(g_span)),
        "gaps": gaps,
    }


def load(path):
    """(device-0 op intervals, run-loop spans, onset markers) of one
    ``.xplane.pb``; the run loop's line is the one that holds
    ``fst.ingest``."""
    from jax.profiler import ProfileData

    ops, lines = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [e for e in line.events
                          if e.name.startswith("fst.")]
                if any(e.name == "fst.ingest" for e in events):
                    lines.append(events)
    spans, onsets = [], []
    for e in max(lines, key=len, default=[]):
        stats = dict(e.stats)
        if e.name == "fst.starved_onset":
            onsets.append((e.start_ns, int(stats.get("since_us", 0)) * 1000))
        else:
            spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                          bool(stats.get("starved"))))
    return ops, spans, onsets


def build(workload, tiny):
    from bmlib import cell as bmcell, data as bmdata
    from bmlib.sink import DeliverySink, SampleRanges

    over = None
    if tiny:
        over = dict(TINY_NEXMARK if workload.startswith("nexmark") else TINY)
    cell, cfg, params = bmcell.load_cell(workload, over)
    if params["loop"] == "open":
        sys.exit(f"{workload}: an open loop; this script drives closed ones")
    batch = int(cfg["batch"])
    n = int(params.get("pool_events") or params["pool_batches"] * batch)
    pool = bmcell.make_pool(cfg, 20260929, n)
    source = bmdata.CyclingSource(
        pool, bmdata.make_schema(cfg), bmdata.stream_name(cfg), batch)
    sink = DeliverySink(
        cfg["index_col"], SampleRanges(20260929, pool.n, batch, 8), pool)
    job = bmcell.build_job(cfg, params, source, sink)
    k = job.fused_segment_len  # None on a mesh
    k = 1 if k is None else max(1, int(k))
    warm = max(
        k * (job.max_inflight_cycles
             + params["warm_dispatches_beyond_inflight"]),
        -(-cfg.get("warm_events_min", 0) // batch),
    )
    if tiny:
        warm = min(warm, 16)
    return job, source, sink, warm, params["warm_deliveries"]


def _starved_ledger(snap0, snap1):
    out = {}
    for name, d in snap1["stages"].items():
        if name.startswith("starved."):
            before = snap0["stages"].get(name, {}).get("seconds", 0.0)
            out[name[len("starved."):]] = d["seconds"] - before
    return out


def time_is_ready():
    """Nanoseconds of one ``is_ready()`` on the executor's ticket: ready,
    and not ready (polled until a chain of matrix products ends)."""
    import jax
    import jax.numpy as jnp

    from flink_siddhi_tpu.runtime.executor import Job

    x = jnp.full((2048, 2048), 1e-3, jnp.float32)
    ticket = Job._make_ticket({"x": x})
    ticket.block_until_ready()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        ticket.is_ready()
    ready_ns = (time.perf_counter() - t0) / n * 1e9

    chain = jax.jit(lambda a: jax.lax.fori_loop(
        0, 400, lambda _i, y: (y @ a) * 0.5, a))
    chain(x).block_until_ready()  # compiled
    ticket = Job._make_ticket({"y": chain(x)})
    polls, t0 = 0, time.perf_counter()
    while not ticket.is_ready():
        polls += 1
    waiting_ns = (time.perf_counter() - t0) / max(polls, 1) * 1e9
    dev = jax.devices()[0]
    print(json.dumps({
        "is_ready_ns_ready": ready_ns, "is_ready_ns_waiting": waiting_ns,
        "polls_while_waiting": polls,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }))
    return 0


def main(argv):
    if not argv or argv[0].startswith("-"):
        sys.exit(__doc__)
    if argv[0] == "is_ready":
        return time_is_ready()
    workload = argv[0]
    seconds = float(argv[1]) if len(argv) > 1 else 3.0
    tiny = len(argv) > 2 and argv[2] == "tiny"

    import jax

    job, source, sink, warm, warm_deliveries = build(workload, tiny)
    while source.served < warm or sink.deliveries < warm_deliveries:
        job.run_cycle()
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="profile_starve_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        snap0 = job.telemetry.snapshot()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds and not source.exhausted:
            job.run_cycle()
        wall = time.perf_counter() - t0
        snap1 = job.telemetry.snapshot()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    ops, spans, onsets = load(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    source.stop()
    while not job.finished:
        job.run_cycle()
    job.flush()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    ledger = _starved_ledger(snap0, snap1)
    print(f"[starve] {workload}: {wall:.3f} s traced, "
          f"{len(spans)} run-loop spans, {len(onsets)} onsets")
    print("[starve] ledger (s) " + json.dumps(
        {k: round(v, 6) for k, v in sorted(ledger.items())}))
    if not ops:
        print("[starve] no device plane in the trace: not a TPU run")
        print(json.dumps({"device": device}))
        return 2
    red = reduce(ops, spans, onsets)
    t_first = min(s for s, _e in ops)
    lines = []
    for g in sorted(red["gaps"], key=lambda g: -g["ns"]):
        cover = " ".join(
            f"{name}[{mark}]{ns / 1e6:.2f}" for name, mark, ns in g["cover"])
        lines.append(
            f"gap at {(g['start'] - t_first) / 1e6:9.2f} ms  "
            f"{g['ns'] / 1e6:7.2f} ms  flagged {g['flagged'] / 1e6:7.2f}  "
            f"in no span {(g['ns'] - g['in_spans']) / 1e6:6.2f}  | {cover}")
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"profile_starve_{workload}.txt"), "w",
              encoding="utf-8") as f:
        f.write("\n".join(lines) + "\n")
    for line in lines[:40]:
        print(line)
    unflagged = sorted(
        red["gaps"], key=lambda g: g["flagged"] - g["ns"])[:8]
    print("[starve] largest unflagged stretches (ms): " + ", ".join(
        f"{(g['ns'] - g['flagged']) / 1e6:.2f} under "
        + (g["cover"][0][0] + "[" + g["cover"][0][1] + "]"
           if g["cover"] else "no span")
        for g in unflagged))
    idle = red["idle_ns"] or 1
    summary = {
        "workload": workload,
        "window_s": red["window_ns"] / 1e9,
        "idle_s": red["idle_ns"] / 1e9,
        "idle_share": 100.0 * red["idle_ns"] / (red["window_ns"] or 1),
        "idle_flagged_share": 100.0 * red["flagged_ns"] / idle,
        "idle_in_any_span_share": 100.0 * red["in_spans_ns"] / idle,
        "gaps_over_1ms": len(lines),
        "ledger_starved_s": sum(
            v for k, v in ledger.items() if k != "onset"),
        "ledger_onset_s": ledger.get("onset", 0.0),
        "traced_wall_s": wall,
        "device": device,
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
