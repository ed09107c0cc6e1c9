"""The pieces of ``#window.hop``'s step (compiler/hop_window.py), each
timed alone on whatever device JAX picked, at ``nexmark_q5``'s sizes:
a 500,000-event tape in its 524,288 bucket, a ring of 6 pane rows by
2^20 group slots, 4,096 rows out. PERF.md's prediction for
``nexmark_q5.replay`` (PR 27) rests on these numbers.

* ``scatter_add_skewed`` / ``scatter_add_uniform``: the pane fold
  (``fst.hop_fold``), half of the events on one slot or all spread;
* ``key_scatter_set``: the slot's key, written by the events;
* ``close_window_nonzero``: closing one window (``fst.hop_max``): the
  sum of five ring rows, the maximum, ``nonzero(size=4096)`` over the
  slots, the rows packed in key order;
* ``close_window_max_only``: the same without the packing;
* ``sort_only``: a sort of the tape's codes, what a segmented fold
  would pay before it adds.

Usage (the chip tool): python scripts/profile_hop.py
One line per piece, ``<name> <ms>``, then one JSON line naming the
device. A number from a CPU run is not a device number.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

E, P, G, V = 524_288, 6, 1 << 20, 4_096
REPEATS = 20


def timed(name, fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        out = fn(*args)
    jax.block_until_ready(out)
    print(f"{name} {(time.perf_counter() - t0) / REPEATS * 1e3:.3f} ms")


def main():
    rng = np.random.default_rng(27)
    uniform = rng.integers(0, 660_000, E).astype(np.int32)
    skewed = np.where(rng.random(E) < 0.5, 7, uniform).astype(np.int32)
    row = np.full(E, 3, np.int32)
    cnt = jnp.zeros((P, G), jnp.int32)
    keys = jnp.zeros(G, jnp.int32)

    @jax.jit
    def fold(cnt, g, row):
        flat = row * G + g
        return cnt.reshape(-1).at[flat].add(1, mode="drop").reshape(P, G)

    @jax.jit
    def key_set(keys, g):
        return keys.at[g].set(g + 1_000, mode="drop")

    def window(cnt):
        num = cnt[:5].sum(0)
        live = num > 0
        top = jnp.max(jnp.where(live, num, -1))
        return num, live & (num >= top)

    @jax.jit
    def close_max(cnt):
        return window(cnt)[1].sum()

    @jax.jit
    def close(cnt, keys):
        num, mask = window(cnt)
        idx = jnp.nonzero(mask, size=V, fill_value=G)[0]
        ok = idx < G
        idx = jnp.minimum(idx, G - 1)
        order = jnp.lexsort((keys[idx], (~ok).astype(jnp.int32)))
        idx = idx[order]
        return keys[idx], num[idx], mask.sum()

    full = fold(cnt, jnp.asarray(skewed), jnp.asarray(row))
    timed("scatter_add_skewed", fold, cnt, jnp.asarray(skewed),
          jnp.asarray(row))
    timed("scatter_add_uniform", fold, cnt, jnp.asarray(uniform),
          jnp.asarray(row))
    timed("key_scatter_set", key_set, keys, jnp.asarray(uniform))
    timed("close_window_nonzero", close, full, keys)
    timed("close_window_max_only", close_max, full)
    timed("sort_only", jax.jit(jnp.sort), jnp.asarray(skewed))
    print(json.dumps({"device": str(jax.devices()[0])}))


if __name__ == "__main__":
    main()
