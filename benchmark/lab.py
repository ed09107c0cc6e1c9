"""Trials on the chip: ``run.py`` with three more options, for the
builder of a cell and never for the driver.

    python3 benchmark/lab.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
        [--set key=value ...] [--control 1] [--keep-trace FILE]

``--set`` overrides a parameter of the cell's traffic or configuration
for one run (the live cell's rate sweep); ``--control 1`` also reads the
lower-precision control on the same samples; ``--keep-trace`` keeps the
profiler's trace (the fixture of ``tests/test_trace_reduction.py``).
"""

from __future__ import annotations

import json
import sys

import run


def _overrides(args):
    return {k: json.loads(v) for k, v in (kv.split("=", 1) for kv in args.set)}


if __name__ == "__main__":
    ap = run.parser()
    ap.add_argument("--set", action="append", default=[], metavar="K=V")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="FILE")
    sys.exit(run.main(
        ap=ap, overrides=_overrides,
        control=lambda a: bool(a.control),
        keep_trace=lambda a: a.keep_trace,
    ))
