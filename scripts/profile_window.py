"""The pieces of the length window's step (compiler/window.py
``_step_blocked`` under compiler/plan.py ``step_acc``), each timed alone
on whatever device JAX picked, at ``window1k``'s sizes: a 524,288-event
tape, a ring of 1,000 rows, 1,024 group slots, four accumulator rows.
The twin of ``scripts/profile_hop.py``; PERF.md's split of
``window1k.replay``'s step by site (PR 32) rests on these numbers.

Both sites front-compact the tape's selected rows (compiler/compact.py).
Each is timed in the form the step had before PR 32 (a scatter by
``cumsum(mask) - 1``, a gather back) and in the form it takes when the
mask is a prefix (a select, a slice); since PR 43 a mask that is no
prefix takes a sort of the positions and a gather of rows in place of
that scatter, which the helper's ``[hole]`` lines and
``step_acc[hole]`` time:

* ``prefix_check``: what deciding costs: ``sum``, compare with
  ``iota < n``, ``all``;
* ``cumsum_rank``: the rank a scatter needs and the identity does not;
* ``append_scatter`` / ``append_identity``: site one, the accumulator
  append's block of four int32 rows (``fst.acc_append``);
* ``append_front_compact[prefix|hole]``: the helper itself on a prefix
  mask and on one with a hole (``lax.cond`` and the check included);
* ``fold_compact_scatter`` / ``fold_compact_identity``: site two, the
  fold's four columns (sum argument, ring copy, group codes, timestamps);
* ``fold_unsort_gather`` / ``fold_unsort_slice``: site two, two
  aggregates back from concat order to tape order;
* ``fold_batch_rows[prefix|hole]``: the helper for that way back
  (``batch_rows``: one gather of rows where the mask is no prefix);
* the fold's static merge of arrivals and expiries
  (compiler/window_merge.py; PR 34), on the cell's concat sequence
  (ring 1,000 + tape 524,288 rows, two value planes, 1,024 group slots):
  ``merge_read_halves`` (the tiles: slices and concatenations) against
  ``merge_read_interleave`` (the layout not taken: the same rows
  interleaved in memory with ``stack(...).reshape``),
  ``merge_back_slice`` (``R[:N]``; for the interleave the odd rows of
  ``R[C : C + 2E]``), and ``tile_fold[static]``, the tiled sums (the
  merge by rank that processing-time windows took, and its lines here,
  went in PR 50);
* ``step_acc[prefix|hole]``: the whole step of ``window1k``'s query on a
  tape whose mask is a prefix (the cell's) and on the same tape with one
  row invalid, which takes the sorts and the row gathers; under
  each, from a profiler
  trace of five more calls, the device time of its costliest XLA
  operations, each beside the scope the compiled program names for it
  (``fst.window_fold``, ``fst.acc_append``, ``cond/branch_...``).

* ``time [log2 of the ring ...]`` (PR 50): the processing-time window's
  step (compiler/time_window.py) at ``linear_road_lav5m``'s shapes: a
  tape of 2^20 lanes with 544,000 events (five ticks of 108,800, 99% of
  them reports of 12,800 segments), for each ring size (default 20 and
  25) a span that fills 96% of it (a ring of 2^25 holds the cell's five
  minutes), stepped until the window is full and expiring, then
  ``step_acc[ring 2^k]`` over ten more batches and the device time of
  its XLA operations by scope; ``time compile`` compiles that step for
  a described v5e at 2^25, no chip.

Usage (the chip tool): python scripts/profile_window.py
(a number cuts the tape for a rehearsal on the CPU; ``--step-only``
leaves the pieces out; ``compile`` runs nothing: it compiles the step
for a described v5e, no chip, and lists the gathers, scatters and sorts
the compiled program holds, each beside its scope).
Run from another checkout's root, it times that checkout's step (one
that lacks compiler/compact.py gives the pieces it has).
One line per piece, ``<name> <ms>``, then one JSON line naming the
device. A number from a CPU run is not a device number.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = (os.getcwd() if os.path.isdir(os.path.join(os.getcwd(),
                                                   "flink_siddhi_tpu"))
        else HERE)
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

E, C, ROWS = 524_288, 1_000, 4
REPEATS = 20
CQL = ("from inputStream#window.length(1000) select id, sum(price) as "
       "total, count() as cnt group by id insert into matches")


def timed(name, fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name} {(time.perf_counter() - t0) / REPEATS * 1e3:.3f} ms",
          flush=True)


def timed_carry(name, fn, states, acc, tape):
    """A step that takes its own outputs back (donated, as the job's)."""
    states, acc = fn(states, acc, tape)
    jax.block_until_ready(acc)
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        states, acc = fn(states, acc, tape)
    jax.block_until_ready(acc)
    print(f"{name} {(time.perf_counter() - t0) / REPEATS * 1e3:.3f} ms",
          flush=True)
    return states, acc


def pieces(rng):
    n_valid = E - 37
    prefix = jnp.asarray(np.arange(E) < n_valid)
    hole = prefix.at[11].set(False)
    src = jnp.asarray(rng.integers(0, 1 << 30, (ROWS, E)).astype(np.int32))
    cols = {
        "s0": jnp.asarray(rng.random(E).astype(np.float32)),
        "a0": jnp.asarray(rng.random(E).astype(np.float32) + 1.0),
        "gc": jnp.asarray(rng.integers(0, 1_000, E).astype(np.int32)),
        "ts": jnp.asarray(np.arange(E, dtype=np.int32)),
    }
    seqs = {
        "total": jnp.asarray(rng.random(C + E).astype(np.float32)),
        "cnt": jnp.asarray(rng.random(C + E).astype(np.float32)),
    }
    iota = jnp.arange(E, dtype=jnp.int32)

    @jax.jit
    def prefix_check(mask):
        n = mask.sum().astype(jnp.int32)
        return n, jnp.all(mask == (iota < n))

    @jax.jit
    def cumsum_rank(mask):
        return jnp.cumsum(mask.astype(jnp.int32)) - 1

    def dest_of(mask):
        return jnp.where(mask, jnp.cumsum(mask.astype(jnp.int32)) - 1, E)

    @jax.jit
    def append_scatter(mask, src):
        return jnp.zeros_like(src).at[:, dest_of(mask)].set(src, mode="drop")

    @jax.jit
    def append_identity(mask, src):
        return jnp.where(mask, src, 0)

    @jax.jit
    def fold_compact_scatter(mask, cols):
        dest = dest_of(mask)
        return {k: jnp.zeros(E, v.dtype).at[dest].set(v, mode="drop")
                for k, v in cols.items()}

    @jax.jit
    def fold_compact_identity(mask, cols):
        return {k: jnp.where(mask, v, jnp.zeros((), v.dtype))
                for k, v in cols.items()}

    @jax.jit
    def fold_unsort_gather(mask, seqs):
        at = C + jnp.clip(jnp.cumsum(mask.astype(jnp.int32)) - 1, 0)
        return {k: jnp.where(mask, v[at], 0) for k, v in seqs.items()}

    @jax.jit
    def fold_unsort_slice(mask, seqs):
        return {k: jnp.where(mask, lax.slice_in_dim(v, C, C + E), 0)
                for k, v in seqs.items()}

    timed("prefix_check", prefix_check, prefix)
    timed("cumsum_rank", cumsum_rank, prefix)
    timed("append_scatter", append_scatter, prefix, src)
    timed("append_identity", append_identity, prefix, src)
    timed("fold_compact_scatter", fold_compact_scatter, prefix, cols)
    timed("fold_compact_identity", fold_compact_identity, prefix, cols)
    timed("fold_unsort_gather", fold_unsort_gather, prefix, seqs)
    timed("fold_unsort_slice", fold_unsort_slice, prefix, seqs)
    try:
        from flink_siddhi_tpu.compiler.compact import (
            batch_rows, front_compact)
    except ImportError:
        print("helpers: this checkout has no compiler/compact.py")
        return
    helper = jax.jit(front_compact)
    timed("append_front_compact[prefix]", helper, prefix, src)
    timed("append_front_compact[hole]", helper, hole, src)

    @jax.jit
    def way_back(mask, seqs):
        flag = prefix_check(mask)[1]
        return batch_rows(mask, flag, seqs, C)

    timed("fold_batch_rows[prefix]", way_back, prefix, seqs)
    timed("fold_batch_rows[hole]", way_back, hole, seqs)


def merge_pieces(rng):
    try:
        from flink_siddhi_tpu.compiler.window_merge import (
            blocked_tiling, static_merge, tile_fold)
    except ImportError:
        print("merge: this checkout has no compiler/window_merge.py")
        return
    N, K, G = C + E, 2, 1_024
    tile, chunk = blocked_tiling()
    codes = jnp.asarray(rng.integers(0, 1_000, N).astype(np.int32))
    live = jnp.asarray(np.arange(N) < N - 37)
    V_n = jnp.asarray(
        np.stack([rng.random(N) * 100.0, np.ones(N)], 1).astype(np.float32))

    def weave(x, neg):
        # head ++ interleave(expiry of p, arrival of p + C) ++ tail
        pairs = jnp.stack([neg(x[:E]), x[C:]], axis=1)
        return jnp.concatenate(
            [x[:C], pairs.reshape((2 * E,) + x.shape[1:]), neg(x[E:])])

    @jax.jit
    def read_interleave(codes, live, V_n):
        V = jnp.where(live[:, None], V_n, 0.0)
        return weave(codes, lambda c: c), weave(V, lambda v: -v)

    @jax.jit
    def read_halves(codes, live, V_n):
        return static_merge(codes, live, V_n, C, tile, chunk)[:2]

    @jax.jit
    def back_weave(R):
        return jnp.concatenate([R[:C], R[C + 1:C + 2 * E:2]])

    @jax.jit
    def back_slice(R):
        return R[:N]

    @jax.jit
    def fold_static(codes, live, V_n):
        return tile_fold(static_merge(codes, live, V_n, C, tile, chunk),
                         G, (), chunk)

    print("merge_order_static 0.000 ms (no device work: the order is the "
          "tiles' constant precedence matrix)")
    timed("merge_read_halves", read_halves, codes, live, V_n)
    timed("merge_read_interleave", read_interleave, codes, live, V_n)
    R2 = jnp.asarray(rng.random((2 * N, K)).astype(np.float32))
    timed("merge_back_slice[interleave]", back_weave, R2)
    timed("merge_back_slice[halves]", back_slice, R2)
    timed("tile_fold[static]", fold_static, codes, live, V_n)


def plan_and_tape(rng):
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.tape import build_tape
    from flink_siddhi_tpu.schema.batch import EventBatch
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    schema = StreamSchema([
        ("id", AttributeType.INT), ("name", AttributeType.STRING),
        ("price", AttributeType.DOUBLE), ("timestamp", AttributeType.LONG),
    ])
    plan = compile_plan(CQL, {"inputStream": schema}, plan_id="window1k")
    ts = 1_000 + np.arange(E, dtype=np.int64)
    cols = {
        "id": rng.integers(0, 1_000, E).astype(np.int32),
        "name": np.zeros(E, dtype=np.int32),
        "price": rng.random(E) * 100.0,
        "timestamp": ts,
    }
    batch = EventBatch("inputStream", schema, cols, ts)
    tape, _ = build_tape(plan.spec, [batch], 0, capacity=E, want_prov=False)
    return plan, tape


def list_moves(compiled):
    """The gathers, scatters and sorts a compiled step holds, by scope."""
    seen = collections.Counter(
        (kind, shape.split("{")[0], scope.split("step_acc)/")[-1])
        for shape, kind, scope in re.findall(
            r'= (\S+) (gather|scatter|sort)\([^\n]*?op_name="([^"]*)"',
            compiled.as_text()))
    for (kind, shape, scope), n in sorted(seen.items()):
        print(f"  {n:3d} x {kind:8s}{shape:24s} {scope}")


def compile_only(rng):
    """The step compiled for a described v5e (no chip, nothing runs):
    which gathers, scatters and sorts the program holds, by scope."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    plan, tape = plan_and_tape(rng)
    args = (plan.init_state(), jax.eval_shape(plan.init_acc), tape)
    shaped = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one),
        args)
    t0 = time.perf_counter()
    compiled = jax.jit(plan.step_acc, donate_argnums=(0, 1)).lower(
        *shaped).compile()
    print(f"compiled in {time.perf_counter() - t0:.1f} s")
    print(compiled.memory_analysis())
    list_moves(compiled)


def whole_step(rng):
    plan, tape = plan_and_tape(rng)
    valid = np.asarray(tape.valid).copy()
    valid[11] = False
    tapes = {
        "prefix": jax.device_put(tape),
        "hole": jax.device_put(dataclasses.replace(tape, valid=valid)),
    }
    step_acc = jax.jit(plan.step_acc, donate_argnums=(0, 1))
    states, acc = plan.init_state(), jax.jit(plan.init_acc)()
    hlo = step_acc.lower(states, acc, tapes["prefix"]).compile().as_text()
    scope_of = dict(re.findall(
        r'%([\w.\-]+) = [^\n]*?op_name="([^"]*)"', hlo))
    for name, t in tapes.items():
        states, acc = plan.init_state(), jax.jit(plan.init_acc)()
        states, acc = timed_carry(f"step_acc[{name}]", step_acc, states, acc, t)
        meta = np.asarray(acc["meta"])
        print(f"  meta rows after {REPEATS + 1} appends: {meta.tolist()}")
        device_ops(f"step_acc[{name}]", step_acc, states, acc, t, scope_of)


TIME_CQL = ("from PositionReport[type == 0]#window.time({span}) "
            "select vid, xway, dir, seg, avg(spd) as lav, count() as n "
            "group by xway, dir, seg insert into SegSpeed")
TIME_BATCH, TIME_TICK, TIME_TAPE = 544_000, 108_800, 1 << 20


def time_plan(ring, span_ms, tape=TIME_TAPE):
    from flink_siddhi_tpu.compiler.config import EngineConfig
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    schema = StreamSchema([
        ("type", AttributeType.INT), ("vid", AttributeType.LONG),
        ("spd", AttributeType.INT), ("xway", AttributeType.INT),
        ("dir", AttributeType.INT), ("seg", AttributeType.INT),
    ])
    plan = compile_plan(
        TIME_CQL.format(span=span_ms), {"PositionReport": schema},
        plan_id="lav", config=EngineConfig(
            time_ring_capacity=ring, acc_budget_bytes=128 << 20))
    return plan, schema


def time_tape(plan, schema, rng, j, batch=TIME_BATCH, tape=TIME_TAPE):
    """Batch ``j`` of the stream: five ticks of a second."""
    from flink_siddhi_tpu.runtime.tape import build_tape
    from flink_siddhi_tpu.schema.batch import EventBatch

    ts = 1_000 * ((j * batch + np.arange(batch, dtype=np.int64))
                  // (batch // 5))
    cols = {
        "type": (rng.random(batch) < 0.01).astype(np.int32) * 2,
        "vid": rng.integers(0, 1 << 40, batch),
        "spd": rng.integers(10, 76, batch).astype(np.int32),
        "xway": rng.integers(0, 64, batch).astype(np.int32),
        "dir": rng.integers(0, 2, batch).astype(np.int32),
        "seg": rng.integers(0, 100, batch).astype(np.int32),
    }
    ev = EventBatch("PositionReport", schema, cols, ts)
    out, _ = build_tape(plan.spec, [ev], 0, capacity=tape, want_prov=False)
    return out


def time_compile_only():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    plan, schema = time_plan(1 << 25, 300_000)
    tape = time_tape(plan, schema, np.random.default_rng(50), 0)
    states = plan.grow_state(jax.eval_shape(plan.init_state))
    args = (states, jax.eval_shape(plan.init_acc), tape)
    shaped = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=one),
        args)
    t0 = time.perf_counter()
    compiled = jax.jit(plan.step_acc, donate_argnums=(0, 1)).lower(
        *shaped).compile()
    print(f"compiled in {time.perf_counter() - t0:.1f} s")
    print(compiled.memory_analysis())
    list_moves(compiled)


def time_steps(logs, batch=TIME_BATCH, tape=TIME_TAPE):
    rng = np.random.default_rng(50)
    for k in logs:
        ring = 1 << k
        # ticks the ring holds at 96%: a ring of 2^25 holds 300
        ticks = max(1, int(ring * 0.963 / (batch // 5 * 0.99)))
        plan, schema = time_plan(ring, ticks * 1_000, tape)
        step_acc = jax.jit(plan.step_acc, donate_argnums=(0, 1))
        init_acc = jax.jit(plan.init_acc)
        tapes = [time_tape(plan, schema, rng, 0, batch, tape)]
        states = plan.grow_state(plan.init_state())
        t0 = time.perf_counter()
        hlo = step_acc.lower(states, init_acc(), tapes[0]).compile().as_text()
        print(f"ring 2^{k}: span {ticks} s, compiled in "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        scope_of = dict(re.findall(
            r'%([\w.\-]+) = [^\n]*?op_name="([^"]*)"', hlo))
        acc, j = init_acc(), 0
        fill = -(-ticks // 5) + 2
        for j in range(fill):
            if j:
                tapes = [time_tape(plan, schema, rng, j, batch, tape)]
            states, acc = step_acc(plan.grow_state(states), init_acc(),
                                   jax.device_put(tapes[0]))
        win = states[plan.artifacts[0].name]
        print(f"  filled after {fill} batches: live {int(win['count'])}, "
              f"overflow {int(win['overflow'])}, stepped "
              f"{np.asarray(win['stepped']).tolist()}", flush=True)
        todo = [jax.device_put(time_tape(plan, schema, rng, fill + i, batch,
                                         tape)) for i in range(10)]
        jax.block_until_ready(todo)
        t0 = time.perf_counter()
        for t in todo:
            states, acc = step_acc(states, init_acc(), t)
        jax.block_until_ready(acc)
        print(f"step_acc[ring 2^{k}] "
              f"{(time.perf_counter() - t0) / len(todo) * 1e3:.3f} ms "
              "(with an accumulator zeroed a step)", flush=True)
        win = states[plan.artifacts[0].name]
        print(f"  after: live {int(win['count'])}, overflow "
              f"{int(win['overflow'])}, stepped "
              f"{np.asarray(win['stepped']).tolist()}", flush=True)
        # the same tape again and again: its stamps lie behind the clock,
        # so its reports join at the clock and nothing more expires
        device_ops(f"step_acc[ring 2^{k}]", step_acc, states, init_acc(),
                   jax.device_put(time_tape(plan, schema, rng, fill + 10,
                                            batch, tape)), scope_of, steps=2)
        del states, acc, todo


def device_ops(name, fn, states, acc, tape, scope_of, steps=5, top=24):
    """Device time of ``steps`` calls by XLA operation, from a profiler
    trace, each beside the ``op_name`` (scopes included) that the
    compiled program gives it. A conditional's time holds its branch's
    operations, a loop's its body's."""
    from jax.profiler import ProfileData

    trace_dir = tempfile.mkdtemp(prefix="profile_window_")
    try:
        jax.profiler.start_trace(trace_dir)
        for _ in range(steps):
            states, acc = fn(states, acc, tape)
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
        print(f"  {name}: no device plane in the trace (not a TPU)")
    for op, ns in total.most_common(top):
        print(f"  {ns / steps / 1e6:8.3f} ms x{calls[op] / steps:<5.0f} "
              f"{op:40s} {scope_of.get(op, '')[-80:]}")


def main():
    global E
    if sys.argv[1:] == ["compile"]:
        return compile_only(np.random.default_rng(32))
    if sys.argv[1:2] == ["time"]:
        if sys.argv[2:] == ["compile"]:
            return time_compile_only()
        if sys.argv[2:3] == ["tiny"]:  # the CPU's rehearsal
            time_steps([10, 13], batch=500, tape=1 << 10)
        else:
            time_steps([int(a) for a in sys.argv[2:]] or [20, 25])
        print(json.dumps({"device": str(jax.devices()[0]), "root": ROOT}))
        return None
    args = [a for a in sys.argv[1:] if a != "--step-only"]
    if args:
        E = int(args[0])
    rng = np.random.default_rng(32)
    if "--step-only" not in sys.argv:
        pieces(rng)
        merge_pieces(rng)
    whole_step(rng)
    print(json.dumps({"device": str(jax.devices()[0]), "root": ROOT}))


if __name__ == "__main__":
    main()
