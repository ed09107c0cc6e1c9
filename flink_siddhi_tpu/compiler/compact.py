"""Front-compaction of a tape's selected rows, tape order kept.

An ``aligned`` artifact hands the step one row per tape position and a
mask; the accumulator append (plan.py ``_append_outputs``) and the
blocked window fold (window.py ``_step_blocked``) both move the selected
rows to the front. In general that is one sort of the tape's positions
keyed on the mask and a gather of the rows in that order, which on a TPU
costs an eighth of the scatter by ``cumsum(mask) - 1`` that it replaced
(PERF.md, PR 43). But a
tape's valid rows are a prefix by contract (runtime/tape.py:
``iota < n_valid``), so an unfiltered query over one stream selects a
prefix, every selected row already lies where the sort would put it, and
the compacted block is the source with its tail zeroed. ``front_compact``
looks at the mask and takes that branch when it holds; both branches
give the same bits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


# A gathered row's width is rounded up to a multiple of this. Set from
# two shapes, the accumulator's twelve-word block (12 -> 16: 39.8 ms ->
# 7.76 in the conditional) and the filtered ``window1k`` fold; it is no
# rule: wider rows (20 -> 32 words, 40 -> 40) fall off the gather's
# cliffs all the same, still under the scatter's 5.2 ms a word
# (PERF.md, PR 43, the table of widths)
WORDS = 8


# fst:hotpath device=mask,rows
def front_compact(mask, rows):
    """``(n, compacted, is_prefix)`` for a boolean ``mask`` of length E
    and ``rows``, a pytree of arrays of the device's dtypes (int32,
    float32, bool) whose last axis is E: ``n`` is the number of
    selected rows (int32), ``compacted`` holds them at
    positions ``0 .. n-1`` of each leaf in tape order with zeros after,
    and ``is_prefix`` says that the mask was ``iota < n`` already, so
    nothing was sorted."""
    vlen = int(mask.shape[0])
    n = mask.sum().astype(jnp.int32)
    iota = jnp.arange(vlen, dtype=jnp.int32)
    kept = iota < n
    is_prefix = jnp.all(mask == kept)

    def identity(rows):
        return jax.tree.map(
            lambda r: jnp.where(mask, r, jnp.zeros((), r.dtype)), rows
        )

    def sort(rows):
        # the selected rows' keys are their positions, so they come out
        # first and in tape order whatever becomes of the ties behind
        # them. Only the positions ride along: every operand more adds
        # to the sort's time and far more to its compile time (134 s for
        # a key and twelve lanes), where one gather of rows then moves
        # all the lanes at once. A row is a multiple of WORDS wide:
        # inside a conditional a gather of twelve-word rows takes five
        # times as long as one of sixteen (PERF.md, PR 43)
        leaves, tree = jax.tree.flatten(rows)
        words = jnp.concatenate([to_word(r).reshape(-1, vlen) for r in leaves])
        words = jnp.pad(words, ((0, -len(words) % WORDS), (0, 0)))
        _key, order = lax.sort(
            [jnp.where(mask, iota, vlen), iota], num_keys=1, is_stable=False
        )
        words = words.T.at[order].get(
            mode="promise_in_bounds", unique_indices=True
        ).T
        words = jnp.where(kept, words, 0)
        out, at = [], 0
        for r in leaves:
            k = r.size // vlen
            out.append(_from_word(words[at:at + k].reshape(r.shape), r.dtype))
            at += k
        return jax.tree.unflatten(tree, out)

    compacted = lax.cond(is_prefix, identity, sort, rows)
    return n, compacted, is_prefix


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
    # each selected row's position among the selected rows
    at = offset + jnp.clip(jnp.cumsum(mask.astype(jnp.int32)) - 1, 0)

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
    value (the accumulator's rows, ``front_compact``'s lanes and
    ``batch_rows``' columns)."""
    if v.dtype == jnp.float32:
        return lax.bitcast_convert_type(v, jnp.int32)
    return v.astype(jnp.int32)


def _from_word(w, dtype):
    if dtype == jnp.float32:
        return lax.bitcast_convert_type(w, dtype)
    return w.astype(dtype)
