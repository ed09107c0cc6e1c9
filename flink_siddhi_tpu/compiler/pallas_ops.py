"""The engine's one Pallas TPU kernel: fused reverse cummin.

The chain matcher's "next match at/after position p" indexes are
reverse cumulative minimums over the event axis, one per pattern
element (nfa.py:_chain_core). XLA compiles each as its own pass over
HBM; up to 8 channels are fused into ONE blocked Pallas pass: the grid
walks the event axis right-to-left, each step does a log-width
shift-min sweep over its (8, 1024) tile in VMEM and threads the running
minimum through a VMEM carry.

The XLA form (``lax.cummin`` per row) is reached only where Pallas
cannot apply BY CONSTRUCTION, and ``mode()`` says which:

* a non-TPU backend (``FST_PALLAS_INTERPRET=1`` runs the kernel under
  the Pallas interpreter on any backend — the CPU-lane equivalence
  tests' mode);
* ``FST_NO_PALLAS=1`` (the operator switch);
* shapes the kernel never claims (more than 8 channels, an event axis
  that is not a multiple of 1024).

Everywhere else the kernel is used, and a kernel that fails to build,
compile or match its oracle RAISES — from ``warmup()`` /
``warmup_shard()`` for the probed executable, or from the caller's jit
compile. It is never logged and skipped: an engine that quietly runs
its XLA form on the chip is a different program from the one measured.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

_BLOCK = 1024  # lanes per grid step (bounded VMEM sweep)
_SUB = 8  # sublane tile for int32
_INF = 2 ** 30


@functools.cache
def _build():
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref, carry_ref):
        # carry_ref: a (SUB, 128) output block revisited by every grid
        # step (index_map pins it to (0, 0)) — the running minimum of all
        # blocks to the right. Using a revisited output instead of VMEM
        # scratch keeps the kernel importable without the TPU-specific
        # pallas module (so it also runs under the interpreter on CPU).
        blk = pl.program_id(0)

        @pl.when(blk == 0)
        def _init():  # rightmost block: nothing to the right yet
            carry_ref[...] = jnp.full_like(carry_ref[...], _INF)

        x = x_ref[...]  # (SUB, BLOCK) int32
        # in-block suffix min via masked shift-mins: offsets B/2..1 cover
        # every distance by binary decomposition
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        acc = x
        step = _BLOCK // 2
        while step >= 1:
            shifted = jnp.roll(acc, -step, axis=1)
            take = lane < (_BLOCK - step)
            acc = jnp.where(take, jnp.minimum(acc, shifted), acc)
            step //= 2
        carry = carry_ref[..., :1]  # (SUB, 1): min of all blocks right
        out = jnp.minimum(acc, carry)
        o_ref[...] = out
        carry_ref[..., :1] = out[..., :1]

    def run(x2d):
        n_blocks = x2d.shape[1] // _BLOCK
        out, _carry = pl.pallas_call(
            kernel,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec(
                    (_SUB, _BLOCK),
                    # right-to-left: grid step i handles block n-1-i
                    lambda i, n=n_blocks: (0, n - 1 - i),
                )
            ],
            out_specs=[
                pl.BlockSpec(
                    (_SUB, _BLOCK), lambda i, n=n_blocks: (0, n - 1 - i)
                ),
                pl.BlockSpec((_SUB, 128), lambda i: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(x2d.shape, jnp.int32),
                jax.ShapeDtypeStruct((_SUB, 128), jnp.int32),
            ],
            interpret=bool(os.environ.get("FST_PALLAS_INTERPRET")),
        )(x2d)
        return out

    return run


# (mode(), under shard_map) pairs whose probe matched its oracle
_PROBED: set = set()


def mode() -> str:
    """How reverse cummins run in this process: ``compiled`` (Mosaic, on
    the TPU), ``interpret`` (the Pallas interpreter), or ``xla (...)``
    naming why Pallas cannot apply. Environmental, so NOT latched (the
    environment may change between plans)."""
    if os.environ.get("FST_NO_PALLAS"):
        return "xla (FST_NO_PALLAS)"
    if os.environ.get("FST_PALLAS_INTERPRET"):
        return "interpret"  # any backend (tests)
    backend = jax.default_backend()
    return "compiled" if backend == "tpu" else f"xla ({backend} backend)"


def available() -> bool:
    return not mode().startswith("xla")


def _probe_once(sharded: bool, make_fn) -> None:
    """Run ``make_fn()`` on random data once per (mode, lowering) and
    raise unless it equals the numpy oracle. The probe spans FOUR grid
    blocks so both the in-block sweep and the cross-block carry are
    validated."""
    key = (mode(), sharded)
    if key in _PROBED:
        return
    probe = np.random.default_rng(int(sharded)).integers(
        0, 2 ** 29, (_SUB, 4 * _BLOCK)
    ).astype(np.int32)
    out = np.asarray(make_fn()(jnp.asarray(probe)))
    if not np.array_equal(
        out, np.minimum.accumulate(probe[:, ::-1], axis=1)[:, ::-1]
    ):
        raise RuntimeError(
            "pallas reverse-cummin probe does not match its oracle"
            + (" under shard_map" if sharded else "")
        )
    _PROBED.add(key)


def warmup() -> bool:
    """Probe the kernel eagerly. MUST be called from host code (never
    inside a jit trace: pallas has no op-by-op eval rule). Returns
    False where Pallas cannot apply by construction (``available()``);
    otherwise the kernel builds, compiles and equals its numpy oracle,
    or this raises."""
    if not available():
        return False
    _probe_once(False, lambda: jax.jit(_build()))
    return True


def warmup_shard() -> bool:
    """``warmup()`` plus a probe of the kernel under a shard_map
    lowering (a configuration the plain probe never exercises). MUST be
    called from host code. Same contract: False where Pallas cannot
    apply, True once the probe matched, raises otherwise."""
    if not warmup():
        return False

    def sharded():
        from jax.sharding import AxisType, PartitionSpec as P

        mesh = jax.make_mesh(
            (1,), ("@pallas_probe",), axis_types=(AxisType.Auto,)
        )
        run = _build()
        # check_vma=False matches the engine's sharded step: the
        # kernel's out_shape carries no vma annotation, and the
        # per-shard body uses no collectives the checker would guard
        f = jax.jit(
            jax.shard_map(
                lambda x: run(x[0])[None],
                mesh=mesh,
                in_specs=P("@pallas_probe"),
                out_specs=P("@pallas_probe"),
                check_vma=False,
            )
        )
        return lambda probe: f(probe[None])[0]

    _probe_once(True, sharded)
    return True


def multi_reverse_cummin(rows):
    """Reverse cummin along the last axis for up to 8 int32 channels of
    equal length E (E a multiple of 1024), fused in one Pallas pass.
    ``rows``: list of (E,) int32 arrays with values < 2**30 (the kernel's
    carry/padding sentinel — larger values would clamp to it; the chain
    matcher's inputs are tape positions <= E, far below). Returns the
    same. Per-row ``lax.cummin`` where Pallas cannot apply by
    construction (module docstring); a kernel that does not lower
    fails the caller's jit compile."""
    E = rows[0].shape[0]
    if available() and 0 < len(rows) <= _SUB and E % _BLOCK == 0:
        pad = [jnp.full(E, _INF, jnp.int32)] * (_SUB - len(rows))
        x = jnp.stack([r.astype(jnp.int32) for r in rows] + pad)
        out = _build()(x)  # ONE fused pass for all channels
        return [out[i] for i in range(len(rows))]
    return [
        jax.lax.cummin(r.astype(jnp.int32), axis=0, reverse=True)
        for r in rows
    ]
