"""Pallas reverse-cummin kernel: equivalence with lax.cummin.

The kernel logic (blocked right-to-left grid, in-block shift-min sweep,
revisited-output carry) is exercised on CPU via the pallas interpreter,
in a clean subprocess: FST_PALLAS_INTERPRET changes what every plan in
the process traces, and the rest of the suite runs the XLA form.
"""

import os
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_INTERPRET_SNIPPET = """
import numpy as np, jax.numpy as jnp
from flink_siddhi_tpu.compiler import pallas_ops
assert pallas_ops.available()
assert pallas_ops.warmup(), "kernel failed to build/probe"
E = 4096
rng = np.random.default_rng(7)
rows = [jnp.asarray(rng.integers(0, 2 ** 29, E).astype(np.int32))
        for _ in range(3)]
out = pallas_ops.multi_reverse_cummin(rows)
assert pallas_ops.mode() == "interpret", pallas_ops.mode()
for o, r in zip(out, rows):
    ref = np.minimum.accumulate(np.asarray(r)[::-1])[::-1]
    assert np.array_equal(np.asarray(o), ref)
print("OK")
"""


def test_multi_reverse_cummin_interpret():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        FST_PALLAS_INTERPRET="1",
        PYTHONPATH=_REPO + os.pathsep + env.get("PYTHONPATH", ""),
    )
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _INTERPRET_SNIPPET],
        env=env,
        capture_output=True,
        text=True,
        timeout=240,
    )
    assert r.returncode == 0 and "OK" in r.stdout, (
        r.stdout + "\n" + r.stderr
    )


def test_fallback_matches():
    # available() reads the env dynamically; no module reload needed
    os.environ["FST_NO_PALLAS"] = "1"
    try:
        import jax.numpy as jnp

        from flink_siddhi_tpu.compiler import pallas_ops

        rows = [jnp.asarray(np.array([5, 3, 7, 1], np.int32))]
        out = pallas_ops.multi_reverse_cummin(rows)
        assert np.asarray(out[0]).tolist() == [1, 1, 1, 1]
    finally:
        os.environ.pop("FST_NO_PALLAS", None)


# -- the kernel inside real queries ----------------------------------------
# The chain matcher's next-match tables are the kernel's one call site
# (nfa._chain_core). The snippet runs real pattern queries twice in ONE
# process, kernel on (interpreter) vs the XLA form (FST_NO_PALLAS reread
# dynamically), and pins row-identical output.

_KERNELS_SNIPPET = """
import os
import numpy as np
from flink_siddhi_tpu.compiler import pallas_ops
assert pallas_ops.available()
assert pallas_ops.warmup() and pallas_ops.mode() == "interpret"

from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

schema = StreamSchema([
    ("id", AttributeType.INT), ("price", AttributeType.DOUBLE),
    ("timestamp", AttributeType.LONG),
])
rng = np.random.default_rng(11)
n, batch = 6000, 512
ids = rng.integers(0, 5, n).astype(np.int32)
prices = np.round(rng.random(n) * 50, 2)
ts = (1000 + 3 * np.arange(n)).astype(np.int64)

def batches():
    return iter([
        EventBatch("S", schema,
                   {"id": ids[s:s + batch], "price": prices[s:s + batch],
                    "timestamp": ts[s:s + batch]}, ts[s:s + batch])
        for s in range(0, n, batch)
    ])

CQLS = {
    "chain": "from every s1 = S[id == 1] -> s2 = S[id == 2] -> "
             "s3 = S[id == 3] within 5 sec select s1.timestamp as t1, "
             "s3.timestamp as t3, s3.price as p insert into o",
    "guard": "from every s1 = S[id == 1] -> not S[id == 4] -> "
             "s2 = S[id == 2] select s1.timestamp as t1, "
             "s2.timestamp as t2 insert into o",
}

def run_all():
    out = {}
    for name, cql in CQLS.items():
        plan = compile_plan(cql, {"S": schema})
        job = Job([plan], [BatchSource("S", schema, batches())],
                  batch_size=batch, time_mode="processing")
        job.run()
        out[name] = job.results_with_ts("o")
    return out

with_kernel = run_all()
os.environ["FST_NO_PALLAS"] = "1"  # read dynamically: forces fallback
without = run_all()
for name in CQLS:
    a, b = with_kernel[name], without[name]
    assert len(a) == len(b) and a, (name, len(a), len(b))
    assert a == b, f"{name}: kernel rows != fallback rows"
print("OK", {k: len(v) for k, v in with_kernel.items()})
"""


def test_chain_queries_kernel_vs_xla_interpret_equivalence():
    """The kernel-vs-XLA contract end to end: the warmup oracle probe
    must PASS under the interpreter (it raises otherwise), and full
    chain queries — plain, and with a mid-chain absence guard — produce
    row-identical output with the kernel on vs forced off."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        FST_PALLAS_INTERPRET="1",
        PYTHONPATH=_REPO + os.pathsep + env.get("PYTHONPATH", ""),
    )
    env.pop("XLA_FLAGS", None)
    env.pop("FST_NO_PALLAS", None)
    r = subprocess.run(
        [sys.executable, "-c", _KERNELS_SNIPPET],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert r.returncode == 0 and "OK" in r.stdout, (
        r.stdout + "\n" + r.stderr
    )
