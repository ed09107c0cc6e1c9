"""Per-layer metrics: one small reader each, found by name.

``BENCHMARK.json`` lists the per-layer metrics and the cells each is
read in. ``metrics/<metric>.json`` names the reader that computes it
(``metrics/<reader>.py``, a function ``read(ctx, **args)``) and the
reader's arguments. A reader that finds nothing to read returns None
and the metric is left out of the line. A later PR adds a metric over
an existing span or counter with a JSON file and an entry; one that
needs new reading code brings one new reader file.
"""

from __future__ import annotations

import dataclasses
import json
import os

from .cell import load_json, load_module


@dataclasses.dataclass
class Context:
    cell: dict
    cfg: dict
    job: object
    snap0: dict  # the job's telemetry when the traced window opened
    snap1: dict  # ... and when it closed
    batches: int  # batches the source handed over in between
    batch: int
    trace: dict  # tracered.reduce's result, or None
    source: object
    sink: object
    device: dict

    def counter(self, name):
        return (self.snap1["counters"].get(name, 0)
                - self.snap0["counters"].get(name, 0))

    def span_seconds(self, name):
        a = self.snap0["stages"].get(name, {}).get("seconds", 0.0)
        b = self.snap1["stages"].get(name, {}).get("seconds", 0.0)
        return b - a

    def hist_mass_ms(self, name):
        """(summed milliseconds, samples) recorded inside the window."""

        def mass(snap):
            h = snap["histograms"].get(name) or {}
            n = h.get("count", 0)
            return (h.get("mean_ms", 0.0) * n, n)

        (m0, n0), (m1, n1) = mass(self.snap0), mass(self.snap1)
        return m1 - m0, n1 - n0

    def rows(self):
        """Rows delivered between the two snapshots."""
        t0, t1 = self.snap0["t"], self.snap1["t"]
        return sum(
            n for t, n in zip(self.sink.t, self.sink.rows) if t0 <= t <= t1
        )


def read_all(ctx: Context, root: str, say=print):
    with open(os.path.join(root, "..", "BENCHMARK.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    name = ctx.cell["name"]
    reported = {
        m["name"] for m in bench["end_to_end"]
        if name in m.get("workloads", [name])
    }
    out = {}
    for m in bench["per_layer"]:
        if name not in m.get("workloads", [name]) or (
            "workloads" not in m and m["moves"] not in reported
        ):
            continue
        spec = load_json("metrics", m["name"], root)
        reader = load_module("metrics", spec["reader"], root)
        value = reader.read(ctx, **spec.get("args", {}))
        if value is None:
            say(f"[bench] {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
