"""Front-compaction of a tape's selected rows, tape order kept.

An ``aligned`` artifact hands the step one row per tape position and a
mask; the accumulator append (plan.py ``_append_outputs``) and the
blocked window fold (window.py ``_step_blocked``) both move the selected
rows to the front. In general that is a scatter by ``cumsum(mask) - 1``,
which on a TPU pays per tape row whatever it carries. But a tape's valid
rows are a prefix by contract (runtime/tape.py: ``iota < n_valid``), so an
unfiltered query over one stream selects a prefix, every selected row
already lies where the scatter would put it, and the compacted block is
the source with its tail zeroed. ``front_compact`` looks at the mask and
takes that branch when it holds; both branches give the same bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# fst:hotpath device=mask,rows
def front_compact(mask, rows):
    """``(n, compacted, is_prefix)`` for a boolean ``mask`` of length E
    and ``rows``, a pytree of arrays whose last axis is E: ``n`` is the
    number of selected rows (int32), ``compacted`` holds them at
    positions ``0 .. n-1`` of each leaf in tape order with zeros after,
    and ``is_prefix`` says that the mask was ``iota < n`` already, so
    nothing was scattered."""
    vlen = int(mask.shape[0])
    n = mask.sum().astype(jnp.int32)
    is_prefix = jnp.all(mask == (jnp.arange(vlen, dtype=jnp.int32) < n))
    # O(V) front-compaction, tape order kept (no sort). The rank stays
    # outside the branch: a cumsum inside a conditional takes the TPU's
    # compiler 30 s longer (PERF.md, PR 32)
    dest = jnp.where(mask, _rank(mask), vlen)  # vlen -> dropped

    def identity(_dest, rows):
        return jax.tree.map(
            lambda r: jnp.where(mask, r, jnp.zeros((), r.dtype)), rows
        )

    def scatter(dest, rows):
        return jax.tree.map(
            lambda r: jnp.zeros_like(r).at[..., dest].set(r, mode="drop"),
            rows,
        )

    compacted = lax.cond(is_prefix, identity, scatter, dest, rows)
    return n, compacted, is_prefix


def _rank(mask):
    """Each selected row's position among the selected rows."""
    return jnp.cumsum(mask.astype(jnp.int32)) - 1


# fst:hotpath device=mask,is_prefix,seqs
def batch_rows(mask, is_prefix, seqs, offset: int):
    """The inverse for values computed per compacted position: ``seqs``
    is a pytree of sequences in which the batch's ``k``-th selected row
    sits at ``offset + k``; the result holds, at each tape position the
    mask selects, that row's value (the other positions are
    unspecified: callers mask them). Under ``is_prefix`` the ``k``-th
    selected row is tape position ``k`` and the gather is a slice.

    The sequences travel as the columns of one matrix of 32-bit words,
    so that the gather fetches a row per position: a gather pays per
    index, and one per sequence inside a conditional pays twice over
    (PERF.md, PR 32: 2.3 ms for two sequences where two gathers took
    7.5 ms before the conditional and 16.2 ms in it)."""
    vlen = int(mask.shape[0])
    leaves, tree = jax.tree.flatten(seqs)
    words = jnp.stack([to_word(v) for v in leaves], axis=1)
    at = offset + jnp.clip(_rank(mask), 0)

    def window(_at, words):
        return lax.slice_in_dim(words, offset, offset + vlen, axis=0)

    def gather(at, words):
        return words[at]

    rows = lax.cond(is_prefix, window, gather, at, words)
    return jax.tree.unflatten(
        tree, [_from_word(rows[:, k], v.dtype) for k, v in enumerate(leaves)]
    )


def to_word(v):
    """A value as an int32 word: a float32's bits, anything else's
    value (the accumulator's rows and ``batch_rows``' columns)."""
    if v.dtype == jnp.float32:
        return lax.bitcast_convert_type(v, jnp.int32)
    return v.astype(jnp.int32)


def _from_word(w, dtype):
    if dtype == jnp.float32:
        return lax.bitcast_convert_type(w, dtype)
    return w.astype(dtype)
