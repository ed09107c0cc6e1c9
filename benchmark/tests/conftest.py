"""Self-tests of the benchmark, run on the CPU in rehearsal:

    python3 -m pytest benchmark/tests -q

They drive every cell's functions at a tiny size on a 4-device CPU mesh.
Nothing here is a measurement.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")
)
for p in (REPO, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny sizes for every cell: the same code paths, seconds instead of minutes
TINY = {
    "batch": 4096,
    "pool_batches": 8,
    "release_batch": 1024,
    "pool_events": 32768,
    "rate_events_per_s": 100000,
    "settle_seconds": 0.2,
    "warm_events_min": 0,
    "slice_seconds": 0.25,
    "trace_lead_seconds": 0.2,
    "trace_seconds": 1.6,
}
CELLS = ("window1k.replay", "pattern3.live", "keyed1k_x4.replay")
