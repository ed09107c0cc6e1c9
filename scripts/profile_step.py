"""Isolate the device step cost: compute vs host<->device round trip.

Times the jitted step_acc at several tape capacities, both per-call-synced
(compute + RTT) and pipelined-chain (N async calls, one final sync).
"""

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")
)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

import jax
import numpy as np

from bench import build_job


def bench_capacity(batch):
    job = build_job("headline", batch, batch)
    rt = list(job._plans.values())[0]
    job._pull_sources()
    ready = job._release_ready()
    from flink_siddhi_tpu.runtime.tape import build_wire_tape

    wire, _ = build_wire_tape(
        rt.plan.spec, ready, int(ready[0].timestamps.min()), rt.wire_kinds
    )
    states, acc = rt.states, rt.acc
    # warm compile
    t0 = time.perf_counter()
    states, acc = rt.jitted_acc(states, acc, wire)
    jax.block_until_ready(states)
    compile_or_warm = time.perf_counter() - t0

    # synced: each call waits
    N = 10
    t0 = time.perf_counter()
    for _ in range(N):
        states, acc = rt.jitted_acc(states, acc, wire)
        jax.block_until_ready(states)
    synced = (time.perf_counter() - t0) / N

    # pipelined: N dispatches, one sync
    t0 = time.perf_counter()
    for _ in range(N):
        states, acc = rt.jitted_acc(states, acc, wire)
    jax.block_until_ready(states)
    piped = (time.perf_counter() - t0) / N

    print(
        f"E={batch:>7}: warm {compile_or_warm*1e3:7.1f}ms  "
        f"synced {synced*1e3:7.1f}ms/step ({batch/synced/1e6:5.2f}M ev/s)  "
        f"piped {piped*1e3:7.1f}ms/step ({batch/piped/1e6:5.2f}M ev/s)"
    )


def main():
    for batch in (16384, 65536, 131072, 262144, 524288):
        bench_capacity(batch)


if __name__ == "__main__":
    main()
