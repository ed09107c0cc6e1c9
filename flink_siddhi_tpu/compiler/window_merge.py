"""The merge of arrivals and expiries in the blocked sliding-window fold
(window.py ``SlidingWindowArtifact._step_blocked``), and the tiled
per-group running sums over it.

The fold sees the concat sequence: the ring's C rows, then the batch's E
arrivals, N = C + E rows with a group code, a live flag and K value
planes each. Row ``p`` arrives (+v) and, once it leaves the window,
expires (-v); the windowed sums of an arrival are the running per-group
sums of the merged sequence of arrivals and expiries up to it.

``static_merge`` (``#window.length``): the expiry of row ``p`` comes
right before the arrival of row ``p + C``, whatever the data hold, so
the order is known when the step is traced. Nothing is ranked,
scattered or gathered: tile ``j`` holds pairs ``j*h .. (j+1)*h - 1`` of
(expiry of ``i - C``, arrival of ``i``) as two halves of h rows, cut
from the concat sequence with slices, and a constant precedence
matrix stands where the interleave would be (an arrival comes after
the expiries and the arrivals of its own and of earlier pairs). The
ring's C rows have no expiry to pair with and get a dead one; the
expiries after the last arrival reach no sum and are left out. (A
processing-time window's order depends on the data: it does not merge
ring ++ arrivals at all but reads the ring's oldest stretch,
time_window.py. The merge by rank that it used until PR 50 lives on as
the oracle of tests/test_static_merge.py.)

``tile_fold``: per tile a one-hot matmul gives the
groups' totals and a same-group matmul under the precedence matrix the
running sums inside the tile; a ``cumsum`` across tiles carries the
totals forward, one gather by (tile, group code) reads the carry for
each wanted row, and the merge's ``back`` puts the rows in concat order.
"""

from __future__ import annotations

import os
from typing import Callable, NamedTuple, Sequence, Tuple

import jax.numpy as jnp
from jax import lax


def blocked_tiling() -> Tuple[int, int]:
    """``(tile, chunk)``: rows a tile, tiles a batched matmul."""
    return (
        int(os.environ.get("FST_BLOCKED_TILE", 512)),
        int(os.environ.get("FST_BLOCKED_CHUNK", 16)),
    )


class Merged(NamedTuple):
    """The merged sequence cut into T tiles of t rows."""

    codes_t: jnp.ndarray  # [T, t] int32 group codes
    V_t: jnp.ndarray  # [T, t, K] float32: +v arrival, -v expiry, 0 dead
    rows: slice  # the tile rows whose running sums are wanted (r of t)
    prec: jnp.ndarray  # [r, t] 1.0 where tile row j is not after wanted row i
    back: Callable  # [T * r, K'] sums of the wanted rows -> [N, K'] by concat row


# fst:hotpath device=codes,live,V_n
def static_merge(codes, live, V_n, C: int, tile: int, chunk: int) -> Merged:
    """The length window's merge: slices and concatenations alone."""
    N = V_n.shape[0]
    E = N - C
    h = tile // 2
    pad = (-N) % (h * chunk)
    T = (N + pad) // h
    V = jnp.where(live[:, None], V_n, 0.0)

    def halves(expiring, arriving):
        # pair i = (expiry of row i - C, arrival of row i): the expiries
        # are the arrivals C rows late, and the first C pairs have none
        def dead(n):
            return jnp.zeros((n,) + arriving.shape[1:], arriving.dtype)

        exp = jnp.concatenate([dead(C), expiring, dead(pad)])
        arr = jnp.concatenate([arriving, dead(pad)])
        shape = (T, h) + arriving.shape[1:]
        return jnp.concatenate(
            [exp.reshape(shape), arr.reshape(shape)], axis=1
        )

    tril = jnp.tril(jnp.ones((h, h), jnp.float32))
    return Merged(
        codes_t=halves(codes[:E], codes),
        V_t=halves(-V[:E], V),
        rows=slice(h, 2 * h),
        prec=jnp.concatenate([tril, tril], axis=1),
        back=lambda R: R[:N],
    )


# fst:hotpath device=merged
def tile_fold(merged: Merged, G: int, int_planes: Sequence[int], chunk: int):
    """Per value plane, in plane order, its windowed per-own-group sums
    by concat row (``[N]``): float32, or modular int32 for the planes
    in ``int_planes``.

    All tiles are independent matmul work (MXU): a [t,G] one-hot
    contraction gives per-tile group totals, a same-group [r,t]
    contraction under the precedence matrix gives within-tile prefixes;
    the only sequential piece is a [T,G,K] cumsum across tiles. Tiles
    run in CHUNKS of batched matmuls — a per-tile lax.scan would pay
    ~2000 iterations of dispatch overhead for microscopic matmuls."""
    codes_t, V_t, rows, prec, back = merged
    T, t, K = V_t.shape
    r = prec.shape[0]
    giota = jnp.arange(G, dtype=jnp.int32)

    def chunk_body(inp):
        c, v = inp  # [chunk, t] codes, [chunk, t, K] signed values
        onehot = (
            c[:, :, None] == giota[None, None, :]
        ).astype(jnp.float32)
        # HIGHEST precision: the TPU's default matmul precision
        # truncates f32 operands to bf16 passes — a window SUM must
        # not lose mantissa (caught by the real-device smoke lane)
        tile_sums = jnp.einsum(
            "cig,cik->cgk", onehot, v,
            precision=lax.Precision.HIGHEST,
        )
        eq = (
            c[:, rows, None] == c[:, None, :]
        ).astype(jnp.float32) * prec[None]
        partial = jnp.einsum(
            "cij,cjk->cik", eq, v,
            precision=lax.Precision.HIGHEST,
        )
        return tile_sums, partial

    S, partial = lax.map(
        chunk_body,
        (
            codes_t.reshape(T // chunk, chunk, t),
            V_t.reshape(T // chunk, chunk, t, K),
        ),
    )
    S = S.reshape(T, G, K)
    partial = partial.reshape(T * r, K)
    at = (
        jnp.arange(T * r, dtype=jnp.int32) // r * G
        + codes_t[:, rows].reshape(T * r)
    )

    def carried(S_, partial_):
        # exclusive across-tile scan; laid out scan-axis-last
        # (cumsum along a large-stride leading axis is ~30x slower
        # on TPU); per concat-arrival windowed totals
        Kx = S_.shape[-1]
        cum = jnp.cumsum(S_.reshape(T, G * Kx).T, axis=1)
        carry = cum.T.reshape(T, G, Kx) - S_
        return back(carry.reshape(T * G, Kx)[at] + partial_)

    int_planes = list(int_planes)
    f_order = [k for k in range(K) if k not in int_planes]
    planes = [None] * K
    win_f = carried(S[..., f_order], partial[:, f_order])
    for n, k in enumerate(f_order):
        planes[k] = win_f[:, n]
    if int_planes:
        # digit planes accumulate in MODULAR int32 (f32 tile sums
        # are exact below 2^24; the running totals are not)
        win_i = carried(
            jnp.round(S[..., int_planes]).astype(jnp.int32),
            jnp.round(partial[:, int_planes]).astype(jnp.int32),
        )
        for n, k in enumerate(int_planes):
            planes[k] = win_i[:, n]
    return planes
