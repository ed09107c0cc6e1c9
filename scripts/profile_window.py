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
* the fold's merge of arrivals and expiries in its two forms
  (compiler/window_merge.py; PR 34), on the cell's concat sequence
  (ring 1,000 + tape 524,288 rows, two value planes, 1,024 group slots):
  ``merge_order_scatter`` (the ranked form's order: a histogram
  scatter-add, a cumsum, a gather, two scatters over 2N; the static form
  builds no order, ``merge_order_static`` is a line that says so),
  ``merge_read_gather`` (codes, live flags and value rows gathered
  through that order) against ``merge_read_halves`` (the static form's
  tiles: slices and concatenations) and ``merge_read_interleave`` (the
  layout not taken: the same rows interleaved in memory with
  ``stack(...).reshape``), ``merge_back_gather`` (``R[m_arr]``) against
  ``merge_back_slice`` (``R[:N]``; for the interleave the odd rows of
  ``R[C : C + 2E]``), and ``tile_fold[ranked|static]``, the tiled sums
  both share, on each form's tiles;
* ``step_acc[prefix|hole]``: the whole step of ``window1k``'s query on a
  tape whose mask is a prefix (the cell's) and on the same tape with one
  row invalid, which takes the sorts and the row gathers; under
  each, from a profiler
  trace of five more calls, the device time of its costliest XLA
  operations, each beside the scope the compiled program names for it
  (``fst.window_fold``, ``fst.acc_append``, ``cond/branch_...``).

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
            blocked_tiling, merge_order, ranked_merge, static_merge,
            tile_fold)
    except ImportError:
        print("merge: this checkout has no compiler/window_merge.py")
        return
    N, K, G = C + E, 2, 1_024
    tile, chunk = blocked_tiling()
    codes = jnp.asarray(rng.integers(0, 1_000, N).astype(np.int32))
    live = jnp.asarray(np.arange(N) < N - 37)
    V_n = jnp.asarray(
        np.stack([rng.random(N) * 100.0, np.ones(N)], 1).astype(np.float32))
    exp_rank = jnp.arange(N, dtype=jnp.int32) + C  # a length window's
    m_arr, src = jax.jit(merge_order)(exp_rank)

    @jax.jit
    def read_gather(codes, live, V_n, src):
        is_arr = src < N
        idx = jnp.where(is_arr, src, src - N)
        sign = jnp.where(is_arr, 1.0, -1.0).astype(jnp.float32)
        return codes[idx], jnp.where(
            live[idx][:, None], V_n[idx] * sign[:, None], 0.0)

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
    def back_gather(R, m_arr):
        return R[m_arr]

    @jax.jit
    def back_weave(R):
        return jnp.concatenate([R[:C], R[C + 1:C + 2 * E:2]])

    @jax.jit
    def back_slice(R):
        return R[:N]

    @jax.jit
    def fold_ranked(codes, live, V_n, exp_rank):
        return tile_fold(ranked_merge(codes, live, V_n, exp_rank, tile,
                                      chunk), G, (), chunk)

    @jax.jit
    def fold_static(codes, live, V_n):
        return tile_fold(static_merge(codes, live, V_n, C, tile, chunk),
                         G, (), chunk)

    timed("merge_order_scatter", jax.jit(merge_order), exp_rank)
    print("merge_order_static 0.000 ms (no device work: the order is the "
          "tiles' constant precedence matrix)")
    timed("merge_read_gather", read_gather, codes, live, V_n, src)
    timed("merge_read_halves", read_halves, codes, live, V_n)
    timed("merge_read_interleave", read_interleave, codes, live, V_n)
    got, want = read_interleave(codes, live, V_n), read_gather(
        codes, live, V_n, src)
    dead = np.asarray(want[1] == 0).all(axis=1)  # a dead row's code is free
    print("  interleave == gather:", bool(
        (np.asarray(got[0]) == np.asarray(want[0]))[~dead].all()
        and (np.asarray(got[1]) == np.asarray(want[1])).all()))
    R2 = jnp.asarray(rng.random((2 * N, K)).astype(np.float32))
    timed("merge_back_gather", back_gather, R2, m_arr)
    timed("merge_back_slice[interleave]", back_weave, R2)
    timed("merge_back_slice[halves]", back_slice, R2)
    timed("tile_fold[ranked]", fold_ranked, codes, live, V_n, exp_rank)
    timed("tile_fold[static]", fold_static, codes, live, V_n)
    a, b = fold_ranked(codes, live, V_n, exp_rank), fold_static(
        codes, live, V_n)
    keep = np.asarray(live)
    print("  static == ranked: counts", bool(
        (np.asarray(a[1])[keep] == np.asarray(b[1])[keep]).all()),
        "sums within", float(np.abs(
            np.asarray(a[0])[keep] - np.asarray(b[0])[keep]).max()))


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
    seen = collections.Counter(
        (kind, shape.split("{")[0], scope.split("step_acc)/")[-1])
        for shape, kind, scope in re.findall(
            r'= (\S+) (gather|scatter|sort)\([^\n]*?op_name="([^"]*)"',
            compiled.as_text()))
    for (kind, shape, scope), n in sorted(seen.items()):
        print(f"  {n:3d} x {kind:8s}{shape:24s} {scope}")


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
