"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It exits 2 with nothing on standard output when
JAX finds no TPU or fewer chips than the cell asks for, and fails (also
with nothing on standard output) where the program is not beside it.
Otherwise it loads, warms, measures and prints, as the last line of
standard output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, traced ``breakdown``, and last ``compared``: each
number compared beside its limit, which is also the last line of
standard error. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. Earlier lines (``[bench] ...``) carry what a reader
wants beside them: each number compared with its limit, the slices'
median and quartiles, every slice, the split of set-up.

It takes these four flags and no other; ``lab.py`` beside it is for
trials (a rate sweep, the lower-precision control, a kept trace).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, HERE)

# the one compile cache: where the environment names a directory, there;
# else a fixed path in the checkout (the path is part of the cache's key)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")


def parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main(argv=None, ap=None, **trial) -> int:
    """``trial`` is ``lab.py``'s: overrides, control, keep_trace."""
    args = (ap or parser()).parse_args(argv)

    # alone in a directory (no program beside it) this raises before
    # anything is written to standard output
    import flink_siddhi_tpu  # noqa: F401
    import jax

    from bmlib.cell import load_cell, run_cell

    trial = {k: f(args) for k, f in trial.items()}
    cell, _cfg, _params = load_cell(args.workload, trial.get("overrides"))
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"benchmark: {args.workload} needs {cell['chips']} TPU chip(s); "
            f"JAX reports {len(devices)} x {devices[0].platform!r}. This "
            "runs on the accelerator or not at all.",
            file=sys.stderr,
        )
        return 2
    result = run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, **trial,
    )
    sys.stdout.flush()
    print("[bench] compared " + json.dumps(result["compared"]),
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
