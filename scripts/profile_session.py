"""The pieces of ``#window.session``'s step
(compiler/session_window.py), each timed alone on whatever device JAX
picked, at ``nexmark_q11``'s sizes: a 500,000-event tape in its 524,288
bucket whose 460,000 bids go to 10,800 bidders (three in four of a
stretch of 5,000 events to its one hot bidder), a table of 2^20 slots
with 200,000 sessions open of which 10,000 close. PERF.md's reading of
``nexmark_q11.replay`` (PR 33) rests on these numbers.

* ``scatter_add`` / ``scatter_min`` / ``scatter_max`` / ``key_set``: the
  fold's four scatters over the tape (``fst.session_fold``);
* ``scatter_max_rows3`` / ``scatter_max_cols3``: three of them as one
  scatter of rows of three words into a ``[G, 3]`` / ``[3, G]`` table;
* ``cummax``: the running maximum of the times;
* ``close``: the table's mask compacted and five columns gathered for
  the rows that close (``fst.session_close``);
* ``sort_2``: a sort of the tape's (code, time), what a segmented fold
  would pay before it adds;
* ``step``: the artifact's whole step on that tape, from
  ``compile_plan`` of the configuration's query.

Usage (the chip tool): python scripts/profile_session.py
``compile`` as its argument compiles the step for a described v5e and
prints its memory, without a chip. One line per piece, ``<name> <ms>``,
then one JSON line naming the device. A number from a CPU run is not a
device number.
"""

from __future__ import annotations

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

E, N, G = 524_288, 500_000, 1 << 20
REPEATS = 20


def timed(name, fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name} {(time.perf_counter() - t0) / REPEATS * 1e3:.3f} ms",
          flush=True)


def tape_columns(rng):
    """(event_type, bidder's slot, time) of one batch: 1 : 3 : 46, a hot
    bidder per 5,000 events, the others over 1,000 slots that move on."""
    i = np.arange(E)
    kind = np.where(i % 50 == 0, 0, np.where(i % 50 < 4, 1, 2))
    newest = 300_000 + i // 50
    slot = np.where(rng.random(E) < 0.75, newest // 100 * 100,
                    newest - rng.integers(0, 1_000, E))
    t = 40_000 + i // 1_000
    return (kind.astype(np.int32), (slot % G).astype(np.int32),
            t.astype(np.int32))


def plan_and_state():
    from flink_siddhi_tpu.compiler.config import EngineConfig
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    with open(os.path.join(REPO, "benchmark", "configs",
                           "nexmark_q11.json")) as f:
        cfg = json.load(f)
    schema = StreamSchema(
        [(n, AttributeType(k)) for n, k in cfg["fields"]])
    plan = compile_plan(cfg["cql"], {cfg["stream"]: schema},
                        config=EngineConfig(**cfg["engine_config"]))
    return plan, plan.artifacts[0]


def step_tape(art, kind, slot, t):
    from flink_siddhi_tpu.runtime.tape import Tape

    valid = np.arange(E) < N
    return Tape(
        ts=t, stream=np.where(valid, 0, -1).astype(np.int32), valid=valid,
        cols={"nexmark.event_type": kind, "nexmark.bidder": slot + 1_000,
              "@time:nexmark.dateTime": t, art.code_key: slot},
    )


def open_state(art, rng):
    """200,000 sessions open, 10,000 of them due at the batch's clock."""
    st = art.init_state()
    at = rng.choice(G, 200_000, replace=False)
    last = np.zeros(G, np.int32)
    last[at] = rng.integers(30_600, 40_000, len(at))
    last[at[:10_000]] = rng.integers(30_000, 30_500, 10_000)
    opened = np.zeros(G, bool)
    opened[at] = True
    st.update(open=jnp.asarray(opened), last=jnp.asarray(last),
              first=jnp.asarray(last - 40), cnt=jnp.asarray(opened * 46),
              clock=jnp.int32(39_999), started=jnp.asarray(True))
    return st


def compile_only():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    _plan, art = plan_and_state()
    rng = np.random.default_rng(33)
    st = jax.eval_shape(art.init_state)
    tape = step_tape(art, *tape_columns(rng))
    shaped = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
        (st, tape))
    t0 = time.perf_counter()
    compiled = jax.jit(art.step).lower(*shaped).compile()
    print(f"compiled in {time.perf_counter() - t0:.1f} s")
    print(compiled.memory_analysis())


def main():
    from flink_siddhi_tpu.compiler.session_window import _pick, _tile_prefix

    rng = np.random.default_rng(33)
    kind, slot, t = tape_columns(rng)
    g, tt = jnp.asarray(slot), jnp.asarray(t)
    key = g + 1_000

    timed("scatter_add", jax.jit(
        lambda g: jnp.zeros(G, jnp.int32).at[g].add(1, mode="drop")), g)
    timed("scatter_min", jax.jit(
        lambda g, v: jnp.full(G, 2 ** 31 - 1, jnp.int32).at[g].min(
            v, mode="drop")), g, tt)
    timed("scatter_max", jax.jit(
        lambda g, v: jnp.full(G, -2 ** 31, jnp.int32).at[g].max(
            v, mode="drop")), g, tt)
    timed("key_set", jax.jit(
        lambda g, v: jnp.zeros(G, jnp.int32).at[g].set(v, mode="drop")),
        g, key)
    rows3 = jnp.stack([tt, ~tt, key], axis=1)
    timed("scatter_max_rows3", jax.jit(
        lambda g, v: jnp.full((G, 3), -2 ** 31, jnp.int32).at[g].max(
            v, mode="drop")), g, rows3)
    timed("scatter_max_cols3", jax.jit(
        lambda g, v: jnp.full((3, G), -2 ** 31, jnp.int32).at[:, g].max(
            v, mode="drop")), g, rows3.T)
    timed("cummax", jax.jit(lax.cummax), tt)
    timed("sort_2", jax.jit(lambda a, b: lax.sort((a, b), num_keys=1)),
          g, tt)

    _plan, art = plan_and_state()
    st = open_state(art, rng)
    K = 16_384

    @jax.jit
    def close(opened, last, cols):
        ends = opened & (40_499 - last >= 10_000)
        within, count, start = _tile_prefix(ends)
        idx = _pick(within, count, start, 0, K)
        return count.sum(), [c[idx] for c in cols]

    timed("close", close, st["open"], st["last"],
          [st["last"], st["first"], st["cnt"], st["key"], st["last"] + 1])

    tape = jax.device_put(step_tape(art, kind, slot, t))
    step = jax.jit(art.step)
    _new, (n, _block) = step(st, tape)
    print(f"step closes {int(n)} rows", flush=True)
    timed("step", step, st, tape)
    print(json.dumps({"device": str(jax.devices()[0])}))


if __name__ == "__main__":
    compile_only() if sys.argv[1:] == ["compile"] else main()
