"""The pieces of the per-key length window's step
(compiler/window.py ``PerKeyWindowArtifact``), each timed alone on
whatever device JAX picked, at ``linear_road_rows4``'s sizes: a
544,000-event tape in its 2^20 bucket, 99% of it position reports of
538,560 distinct vehicles, a table of 2^22 slots. PERF.md's reading of
``linear_road_rows4.replay`` (PR 42) rests on these numbers.

* ``sort_8``: the stable sort of the batch by slot code with seven
  operands riding along; ``sort_back_8``: the sort back by position;
* ``seg_scan``: one segmented scan over the tape;
* ``read_records``: the step's one read of its state
  (``_perkey_read``): a row of 128 lanes per tape row from the record
  table ``[G W / 128, 128]`` by sorted slot codes, a block of 65,536 at
  a time, the rows turned and of the row's ``128 / W`` records the
  slot's own taken, for the 544,000 rows that hold an event; ``[whole
  tape]``: for all 2^20; ``gather_rows``: the gather alone, a block's;
* ``scatter_word``: one of the step's writes, a value per tape row into
  the table's flat view at ``slot * W + word``;
* ``step``: the artifact's whole step on that tape, from
  ``compile_plan`` of the configuration's query;
* ``step_acc``: the step and the accumulator's append (the
  front-compaction of twelve rows of the tape's width);
* ``append[nine rows]``: that front-compaction alone
  (compiler/compact.py ``front_compact`` on a ``[12, 2^20]`` block of
  which nine scattered rows pass: the sort branch, a sort of the
  positions and a gather of rows); ``append[no row]``:
  the identity branch; ``append_x4_in_scan``: four of the first as the
  body of one ``lax.scan``, as the fused segment runs them.

Usage (the chip tool): python scripts/profile_perkey.py [dense]
(``dense``: a batch's slots lie side by side, as a young table hands
them out; without it anywhere among 3.6M, as after a few dozen rounds).
``compile`` as its argument compiles the step for a described v5e and
prints its memory and its gathers, scatters, sorts and copies, without a
chip (the programs' text goes to ``chiprun_out/``). ``drift`` runs
``step_acc`` 160 times on one tape, the state carried along, and prints
its time twenty steps at a time: the same slots every step, so what changes over those 40 s is
the chip and not the table. One line per piece, ``<name> <ms>``,
then one JSON line naming the device. A number from a CPU run is not a
device number.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

E, N, G, C = 1 << 20, 544_000, 1 << 22, 4
REPEATS = 10
DENSE = "dense" in sys.argv[1:]  # a batch's slots side by side


def timed(name, fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name} {(time.perf_counter() - t0) / REPEATS * 1e3:.3f} ms",
          flush=True)


def plan_and_tape(rng):
    from flink_siddhi_tpu.compiler.config import EngineConfig
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.tape import Tape
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    with open(os.path.join(REPO, "benchmark", "configs",
                           "linear_road_rows4.json")) as f:
        cfg = json.load(f)
    s = cfg["stream"]
    schema = StreamSchema(
        [(n, AttributeType(k)) for n, k in cfg["fields"]])
    plan = compile_plan(cfg["cql"], {s: schema},
                        config=EngineConfig(**cfg["engine_config"]))
    art = plan.artifacts[0]
    valid = np.arange(E) < N
    # every report a vehicle of its own; one in forty is new to its slot.
    # A young table hands its slots out in the order the vehicles came,
    # so a batch's slots lie side by side (``dense``); once trips have
    # ended and slots been reused they lie anywhere among the live ones
    slot = (np.arange(E) + 1_000_000 if DENSE
            else rng.permutation(3_600_000)[:E]).astype(np.int32)
    code = np.where(rng.random(E) < 1 / 40, ~slot, slot)
    cols = {
        f"{s}.type": (rng.random(E) < 0.01).astype(np.int32) * 2,
        f"{s}.vid": rng.integers(0, 1 << 26, E).astype(np.int32),
        f"{s}.pos": rng.integers(0, 528_000, E).astype(np.int32),
        art.code_key: code.astype(np.int32),
    }
    for name in ("xway", "dir", "seg", "lane"):
        cols[f"{s}.{name}"] = rng.integers(0, 4, E).astype(np.int32)
    tape = Tape(
        ts=(np.arange(E) // 108_800 * 1_000).astype(np.int32),
        stream=np.where(valid, 0, -1).astype(np.int32), valid=valid,
        cols=cols,
    )
    return plan, art, tape


def compile_only():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    plan, art, tape = plan_and_tape(np.random.default_rng(42))
    shaped = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        (jax.eval_shape(plan.init_state), jax.eval_shape(plan.init_acc),
         tape))
    for name, fn in (("step", lambda st, _acc, tp: plan.step(st, tp)),
                     ("step_acc", plan.step_acc)):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*shaped).compile()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s")
        print(compiled.memory_analysis())
        text = compiled.as_text()
        print({op: text.count(f" {op}(")
               for op in ("gather", "scatter", "sort", "copy")})
        os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
        with open(os.path.join(REPO, "chiprun_out",
                               f"profile_perkey.{name}.hlo.txt"), "w") as f:
            f.write(text)


def main():
    rng = np.random.default_rng(42)
    plan, art, tape = plan_and_tape(rng)
    tape = jax.device_put(tape)
    if "drift" in sys.argv[1:]:
        states, acc = jax.jit(plan.init_state)(), jax.jit(plan.init_acc)()
        step_acc = jax.jit(plan.step_acc)
        jax.block_until_ready(step_acc(states, acc, tape))  # compiled
        t_first = time.perf_counter()
        for block in range(8):
            t0 = time.perf_counter()
            for _ in range(20):
                states, _acc = step_acc(states, acc, tape)
            jax.block_until_ready(states)
            t1 = time.perf_counter()
            print(f"step_acc steps {block * 20}-{block * 20 + 19} from "
                  f"{t0 - t_first:.1f} s: {(t1 - t0) / 20 * 1e3:.3f} ms",
                  flush=True)
        return
    code = tape.cols[art.code_key]
    g = jnp.where(code < 0, ~code, code)
    cols = [tape.cols[k] for k in sorted(tape.cols)][:6]
    iota = jnp.arange(E, dtype=jnp.int32)
    W, R = art._record()
    table = jnp.zeros((G // R, R * W), jnp.int32)
    g_sorted = jnp.sort(g)
    flags = jnp.asarray(rng.random(E) < 0.9)

    from flink_siddhi_tpu.compiler.window import _perkey_read, _seg_scan

    timed("sort_8", jax.jit(lambda g, *cols: lax.sort(
        [g, iota, *cols], num_keys=1, is_stable=True)), g, *cols)
    timed("sort_back_8", jax.jit(lambda g, *cols: lax.sort(
        [g, iota, *cols], num_keys=1)), g, *cols)
    timed("seg_scan", jax.jit(lambda f: _seg_scan(
        f, jnp.ones(E, jnp.int32), jnp.add)), flags)

    read = functools.partial(_perkey_read, W=W, need=1 + C * 2)
    timed("read_records", jax.jit(read), table, g_sorted, jnp.int32(N))
    timed("read_records[whole tape]", jax.jit(read), table, g_sorted,
          jnp.int32(E))
    timed("gather_rows", jax.jit(lambda t, g: t.at[g[:1 << 16] // R].get(
        indices_are_sorted=True, mode="promise_in_bounds")), table, g_sorted)
    timed("scatter_word", jax.jit(
        lambda t, g: t.reshape(-1).at[g * W + 1 + iota % C].set(
            iota, mode="drop").reshape(t.shape)), table, g_sorted)

    append_pieces(rng)
    states, acc = jax.jit(plan.init_state)(), jax.jit(plan.init_acc)()
    timed("step", jax.jit(plan.step), states, tape)
    timed("step_acc", jax.jit(plan.step_acc), states, acc, tape)
    device_ops(plan, art, tape, states, acc)
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "memory_peak_bytes": (dev.memory_stats() or {}).get(
                          "peak_bytes_in_use", 0)}))


NINE_ROWS = np.arange(9) * 50_021 + 17  # the lanes that pass ``having``


def append_pieces(rng):
    from flink_siddhi_tpu.compiler.compact import front_compact

    block = jnp.asarray(
        rng.integers(-(1 << 31), 1 << 31, (12, E)).astype(np.int32))
    nine = jnp.zeros(E, bool).at[NINE_ROWS].set(True)
    timed("append[nine rows]", jax.jit(front_compact), nine, block)
    timed("append[no row]", jax.jit(front_compact), jnp.zeros(E, bool),
          block)

    def in_scan(block, masks):
        def body(blk, mask):  # each append's block hangs on the one before
            return blk ^ front_compact(mask, blk)[1], None

        return lax.scan(body, block, masks)[0]

    timed("append_x4_in_scan", jax.jit(in_scan), block,
          jnp.stack([nine] * 4))


def device_ops(plan, art, tape, states, acc, steps=6, top=28):
    """Device time of ``step_acc`` by XLA operation, from a profiler
    trace, each beside the ``op_name`` (scopes included) the compiled
    program gives it, on tapes as the cell's: every vehicle moves on
    from step to step but nine, which stand still, so from the fourth
    step on nine rows pass ``having`` and the accumulator's append takes
    ``compact.py``'s sort branch (a tape of which no row passes is a
    prefix of length 0 and sorts nothing: ``step_acc`` above)."""
    import collections
    import dataclasses
    import glob
    import re
    import shutil
    import tempfile

    from jax.profiler import ProfileData

    pos_key = next(k for k in tape.cols if k.endswith(".pos"))
    still = jnp.zeros(E, bool).at[NINE_ROWS].set(True)

    def moved(t):
        return dataclasses.replace(tape, cols={
            **tape.cols,
            pos_key: jnp.where(still, tape.cols[pos_key],
                               (tape.cols[pos_key] + 97 * t) % 528_000),
            art.code_key: jnp.where(  # a key is new to its slot once
                tape.cols[art.code_key] < 0,
                ~tape.cols[art.code_key], tape.cols[art.code_key])
            if t else tape.cols[art.code_key],
        })

    fn = jax.jit(plan.step_acc, donate_argnums=(0, 1))
    hlo = fn.lower(states, acc, moved(0)).compile().as_text()
    scope_of = dict(re.findall(
        r'%([\w.\-]+) = [^\n]*?op_name="([^"]*)"', hlo))
    for t in range(5):
        states, acc = fn(states, acc, moved(t))
    jax.block_until_ready(acc)
    print("  meta after five appends:", np.asarray(acc["meta"]).tolist())
    tapes = [moved(5 + t) for t in range(steps)]
    t0 = time.perf_counter()
    for tp in tapes:
        states, acc = fn(states, acc, tp)
    jax.block_until_ready(acc)
    print(f"step_acc[nine rows pass] "
          f"{(time.perf_counter() - t0) / steps * 1e3:.3f} ms", flush=True)
    trace_dir = tempfile.mkdtemp(prefix="profile_perkey_")
    try:
        jax.profiler.start_trace(trace_dir)
        for tp in tapes:
            states, acc = fn(states, acc, tp)
        jax.block_until_ready(acc)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
        total, calls = collections.Counter(), collections.Counter()
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/device:TPU:"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    op = e.name.split(" = ", 1)[0].lstrip("%")
                    total[op] += e.duration_ns
                    calls[op] += 1
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if not total:
        print("  no device plane in the trace (not a TPU)")
    for op, ns in total.most_common(top):
        print(f"  {ns / steps / 1e6:8.3f} ms x{calls[op] / steps:<5.0f} "
              f"{op:36s} {scope_of.get(op, '')[-90:]}")


if __name__ == "__main__":
    compile_only() if "compile" in sys.argv[1:] else main()
