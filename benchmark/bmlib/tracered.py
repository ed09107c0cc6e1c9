"""Profiler trace -> device busy time, operation totals, idle gaps.

``reduce`` works on plain lists, so a small recorded trace
(``tests/data/trace_small.json``) checks it without a chip. Device
planes are the planes named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation (a ``while`` spans the operations of its
body, so busy time is the union of the intervals, never their sum) and
their ``XLA Modules`` line one event per program execution. Host spans
are the ``bench.*`` annotations this benchmark writes around its calls
into the job.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

import numpy as np

HOST_SPANS = ("bench.sink", "bench.poll", "bench.run_cycle")
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast")


def start(trace_dir: str):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the bench.* annotations are enough
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return {"dir": trace_dir}


def stop(handle, keep=None):
    """Ends the trace and reduces it. ``keep``: also write the first
    events of every line there as JSON (a fixture for the tests)."""
    import jax

    jax.profiler.stop_trace()
    paths = glob.glob(
        os.path.join(handle["dir"], "plugins", "profile", "*", "*.xplane.pb")
    )
    events = load(paths[0])
    if keep:
        import json

        small = {"devices": {}, "host": events["host"][:300]}
        for name, dev in events["devices"].items():
            small["devices"][name] = {k: v[:600] for k, v in dev.items()}
        with open(keep, "w", encoding="utf-8") as f:
            json.dump(small, f)
    out = reduce(events)
    shutil.rmtree(handle["dir"], ignore_errors=True)
    return out


def short_name(name: str) -> str:
    """``%while.4 = (...) while(...)`` -> ``while.4``;
    ``jit_seg_scan(1438...)`` -> ``jit_seg_scan``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def load(path: str):
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]}; every event is (name, start_ns, duration_ns)."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = out["devices"].setdefault(
                plane.name, {"ops": [], "modules": []}
            )
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name
                )
                if key:
                    dev[key] = [
                        (short_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [
                    (e.name, e.start_ns, e.duration_ns)
                    for e in line.events
                    if e.name in HOST_SPANS
                ]
    return out


def _union(starts, ends):
    """Merged intervals of (starts, ends), as two ascending arrays."""
    if not len(starts):
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts)
    s, e = np.asarray(starts, float)[order], np.asarray(ends, float)[order]
    reach = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > reach[:-1]]
    return s[new], np.r_[reach[:-1][new[1:]], reach[-1]]


def _covered(s, e, x):
    """Length of the merged intervals (s, e) that lies below each x."""
    if not len(s):
        return np.zeros(len(x))
    cum = np.r_[0.0, np.cumsum(e - s)]
    i = np.searchsorted(s, x, side="right")
    over = np.where(i > 0, np.clip(e[np.maximum(i - 1, 0)] - x, 0, None), 0)
    return cum[i] - over


def reduce(events):
    """Busy seconds (mean over devices), the traced window, per-device
    operation and module totals, and the idle gaps of device 0 by what
    the host was doing."""
    per_dev = {}
    for name, dev in sorted(events["devices"].items()):
        ops = dev["ops"]
        if not ops:
            continue
        st = np.array([o[1] for o in ops], float)
        en = st + np.array([o[2] for o in ops], float)
        us, ue = _union(st, en)
        totals = {}
        for (n, _s, d) in ops:
            totals[n] = totals.get(n, 0.0) + d / 1e9
        mods = {}
        for (n, _s, d) in dev["modules"]:
            m = mods.setdefault(n, [0.0, 0])
            m[0] += d / 1e9
            m[1] += 1
        per_dev[name] = {
            "busy_s": float(np.sum(ue - us)) / 1e9,
            "span": (float(us[0]), float(ue[-1])),
            "union": (us, ue),
            "op_totals": totals,
            "modules": mods,
        }
    if not per_dev:
        return None
    first = per_dev[sorted(per_dev)[0]]
    lo = min(d["span"][0] for d in per_dev.values())
    hi = max(d["span"][1] for d in per_dev.values())
    # idle gaps of the first device by what the host was doing. The
    # spans nest (sink and poll lie inside run_cycle), so run_cycle's
    # own share is what its children do not cover
    us, ue = first["union"]
    gap_s, gap_e = ue[:-1], us[1:]
    inside = {}
    for span in HOST_SPANS:
        hs = np.array([h[1] for h in events["host"] if h[0] == span], float)
        hd = np.array([h[2] for h in events["host"] if h[0] == span], float)
        ss, se = _union(hs, hs + hd)
        inside[span] = float(
            np.sum(_covered(ss, se, gap_e) - _covered(ss, se, gap_s))
        ) / 1e9
    idle = float(np.sum(gap_e - gap_s)) / 1e9
    gaps = {
        "bench.sink": inside["bench.sink"],
        "bench.poll": inside["bench.poll"],
        "bench.run_cycle": inside["bench.run_cycle"]
        - inside["bench.sink"] - inside["bench.poll"],
        "outside_bench.run_cycle": idle - inside["bench.run_cycle"],
    }
    top = sorted(first["op_totals"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": float(np.mean([d["busy_s"] for d in per_dev.values()])),
        "window_s": (hi - lo) / 1e9,
        "devices": len(per_dev),
        "modules": first["modules"],
        "collective_s": sum(
            v for k, v in first["op_totals"].items()
            if any(c in k for c in COLLECTIVES)
        ),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [
                [k, v] for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])
            ],
        },
    }
