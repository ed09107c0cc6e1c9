"""The comparison that decides ``correct``.

What the window delivered for the seeded sample ranges is held against
the configuration's plain reference (``configs/<config>.py``), which
sees only the seeded pool. Each number compared has a limit of its own
in the configuration's file; every run prints each beside its limit.
"""

from __future__ import annotations

import numpy as np


def bf16_round(x):
    """Round float values to bfloat16 (nearest even), back as float64:
    the precision below float32, for the control."""
    u = np.asarray(x, dtype=np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def join_pieces(pieces):
    cols = set(pieces[0])
    for p in pieces[1:]:
        cols &= set(p)
    return {k: np.concatenate([p[k] for p in pieces]) for k in cols}


def compare_range(got, want, spec):
    """Numbers for one sample range. ``spec`` is the configuration's
    ``compare`` block: ``exact`` columns must be equal, ``float``
    columns are read as the largest |got - want| / (atol + rtol *
    |want|), which has to stay under 1."""
    n_got, n_want = len(got["@idx"]), len(want["@idx"])
    out = {"rows_lost_or_extra": abs(n_got - n_want), "rows_out_of_order": 0}
    if n_got != n_want:
        return out
    if n_got and np.any(np.diff(got["@idx"]) < 0):
        out["rows_out_of_order"] = int(np.sum(np.diff(got["@idx"]) < 0))
    for col in spec["exact"]:
        if col not in got:
            out[f"{col}_mismatches"] = n_want
            continue
        out[f"{col}_mismatches"] = int(
            np.sum(got[col].astype(np.int64) != want[col].astype(np.int64))
        )
    for col, tol in spec["float"].items():
        if col not in got:
            out[f"{col}_err_over_tol"] = float("inf")
            continue
        g = got[col].astype(np.float64)
        w = want[col].astype(np.float64)
        lim = tol["atol"] + tol["rtol"] * np.abs(w)
        out[f"{col}_err_over_tol"] = (
            float(np.max(np.abs(g - w) / lim)) if n_want else 0.0
        )
    return out


def limits(spec):
    lim = {"rows_lost_or_extra": 0, "rows_out_of_order": 0}
    for col in spec["exact"]:
        lim[f"{col}_mismatches"] = 0
    for col in spec["float"]:
        lim[f"{col}_err_over_tol"] = 1.0
    return lim


def check_samples(sink, reference, pool, spec, lo, hi, precision="f64",
                  substitute=None):
    """Compare every sample range that lies wholly inside event indices
    ``lo..hi`` (what the window delivered). Returns (numbers, ranges
    compared, rows compared); each number is the worst over the ranges.
    ``substitute(want)`` puts other rows in the program's place (the
    control: the reference in a lower precision)."""
    worst = {k: 0 for k in limits(spec)}
    n_ranges = n_rows = 0
    for a, b in sink.ranges.overlapping(lo, hi):
        if a <= lo or b > hi + 1:
            continue  # cut by the window's edge: not all of it was due
        want = reference.expected(pool, a, b)
        pieces = sink.pieces.get(a)
        if substitute is not None:
            got = substitute(a, b)
        elif pieces:
            got = join_pieces(pieces)
        else:
            got = {"@idx": np.zeros(0, np.int64)}
        for k, v in compare_range(got, want, spec).items():
            worst[k] = max(worst[k], v)
        n_ranges += 1
        n_rows += len(want["@idx"])
    return worst, n_ranges, n_rows


def rows_due(reference, pool, cfg, g0, g1):
    """How many rows the reference emits for stream events g0 <= i < g1.
    A configuration that emits ``rows_per_event`` rows for every event
    needs no reference run; otherwise the count uses the stream's period
    (the pool repeats, and a row depends on bounded history): one pass
    over the first cycle, which lacks history, and one over the second."""
    if cfg.get("rows_per_event") is not None:
        return (g1 - g0) * cfg["rows_per_event"]
    p = pool.n
    first = reference.expected(pool, 0, p)["@idx"]
    later = reference.expected(pool, p, 2 * p)["@idx"] - p

    def below(g):
        if g <= p:
            return int(np.searchsorted(first, g))
        return (len(first) + (g // p - 1) * len(later)
                + int(np.searchsorted(later, g % p)))

    return below(g1) - below(g0)
