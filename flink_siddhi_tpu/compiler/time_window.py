"""``#window.time`` at a deployment's size: the members in a ring, in
arrival order, and a step that costs by the tape, whatever the ring
holds (``docs/time_window.md`` has the semantics and the layout).

A processing-time window's clock is the stream's own: the tape's
timestamps, taken as a running maximum so that it never goes back. At an
arrival stamped ``T`` every member stamped ``<= T - time_ms`` leaves
first, then the arrival joins, then its row carries its group's
aggregates, itself included. Stamps never fall along the ring, so what
leaves is always the ring's oldest stretch, and the groups' sums over
the live members are state: a step reads that stretch (a tape's width
of slots, further rounds where more are due), merges its expiries with
the arrivals by one sort keyed on (group code, rank in time), takes the
running sums in that order, and writes the arrivals behind the live
stretch. Nothing in it is as long as the ring.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .compact import WORDS, _from_word, batch_rows, front_compact, to_word
from .expr import ColumnEnv
from .window import AlignedBlocks, SlidingWindowArtifact

_I32_MAX = np.int32(2 ** 31 - 1)
_I32_MIN = np.int32(-(2 ** 31))


def _two_sum(a, b):
    """``a + b`` in float32 and what the addition lost."""
    t = a + b
    err = jnp.where(jnp.abs(a) >= jnp.abs(b), (a - t) + b, (b - t) + a)
    return t, err


def _pair_add(s1, c1, s2, c2):
    """Two compensated sums (value, what it lost) added."""
    t, err = _two_sum(s1, s2)
    return t, c1 + c2 + err


def _rows_by(order, planes):
    """``planes`` (a list of ``[K, N]`` arrays of 32-bit values) read
    at ``order`` along their last axis: one gather of rows a multiple
    of ``WORDS`` wide (compact.py: a gather pays by the lookup)."""
    words = jnp.concatenate([to_word(p) for p in planes])
    words = jnp.pad(words, ((0, -len(words) % WORDS), (0, 0)))
    words = words.T.at[order].get(mode="promise_in_bounds").T
    out, at = [], 0
    for p in planes:
        out.append(_from_word(words[at:at + len(p)], p.dtype))
        at += len(p)
    return out


# fst:hotpath device=codes,keys,ints,floats
def group_fold(codes, keys, ints, floats, G: int, want_run: bool = True):
    """Running sums per group over ``N`` signed entries, in the order
    ``keys`` give inside a group.

    ``codes`` ``[N]`` (``G`` for an entry that takes no part), ``keys``
    a list of ``[N]`` int32 arrays (empty: any order), ``ints`` a list
    of ``Ki`` ``[N]`` int32 rows, ``floats`` of ``Kf`` float32 rows (a
    plane is a row of its own throughout: a ``[K, N]`` array pads its
    few rows to a tile's eight). ONE sort by
    (code, keys) with the value planes riding along as operands (a
    gather of 2^21 rows in the sorted order cost 10.4 ms on a v5e where
    the operands cost the sort 3: PERF.md, PR 50). An int plane is then
    one cumulative sum over all groups (it wraps as int32 does): an
    entry's running sum inside its group is that, less what the sum
    held where its group began, which is read per GROUP (a search of
    the ``G + 1`` codes in the sorted codes: 2.4 ms on a v5e at
    ``G`` 2^14 and ``N`` 2^21, where a gather per entry cost 7) and not
    per entry. A float plane is a Neumaier pair (value, what it
    lost) from a scan that restarts at each group, so that a group's sum
    never holds another's magnitude. A second sort, keyed on the first
    one's positions, takes the running sums back to the entries' order.
    Returns ``(run, began, totals)``: ``run`` the list ``[ints, fsum,
    fcomp]`` by entry (the ints a list of rows, not yet less their
    group's start; None without ``want_run``); ``began`` ``[Ki, G]``,
    the cumulative sum before each group's first entry; ``totals`` the
    three per group, ``[K, G]``."""
    N = codes.shape[0]
    Ki = len(ints)
    iota = jnp.arange(N, dtype=jnp.int32)
    rows = [*ints, *(to_word(f) for f in floats)]
    sorted_ = lax.sort(
        [codes, *keys, iota, *rows], num_keys=1 + len(keys),
        is_stable=False)
    code_s, order = sorted_[0], sorted_[1 + len(keys)]
    rows_s = sorted_[2 + len(keys):]
    # where each group's entries begin in the sorted order (and, at
    # [G], where the entries that take part end)
    start = jnp.searchsorted(
        code_s, jnp.arange(G + 1, dtype=jnp.int32)).astype(jnp.int32)
    cs = [jnp.cumsum(row) for row in rows_s[:Ki]]
    before = jnp.stack([
        jnp.concatenate([jnp.zeros(1, jnp.int32), row])[start] for row in cs
    ]) if Ki else jnp.zeros((0, G + 1), jnp.int32)
    run = [cs]  # (a list of rows; the float planes below are arrays)
    if len(floats):
        floats_s = _from_word(jnp.stack(rows_s[Ki:]), jnp.float32)
        first = jnp.concatenate(
            [jnp.ones(1, bool), code_s[1:] != code_s[:-1]])

        def comb(a, b):
            fa, sa, ca = a
            fb, sb, cb = b
            t, err = _two_sum(sa, sb)
            return (fa | fb, jnp.where(fb, sb, t),
                    jnp.where(fb, cb, ca + cb + err))

        _, run_s, run_c = lax.associative_scan(
            comb, (first[None, :], floats_s, jnp.zeros_like(floats_s)),
            axis=1)
        run += [run_s, run_c]
    else:
        run += [jnp.zeros((0, N), jnp.float32)] * 2
    # a group's total: an int plane's from the cumulative sum at its two
    # ends, a float plane's what its last entry holds
    some = start[1:] > start[:-1]
    last = jnp.clip(start[1:] - 1, 0, N - 1)
    totals = [before[:, 1:] - before[:, :-1]] + [
        jnp.where(some[None, :], r[:, last], 0) for r in run[1:]
    ]
    if not want_run:
        return None, before[:, :-1], totals
    back = lax.sort(
        [order, *cs, *(to_word(row) for r in run[1:] for row in r)],
        num_keys=1, is_stable=False)[1:]
    by_entry, at = [list(back[:Ki])], Ki
    for r in run[1:]:
        k = len(r)
        by_entry.append(
            _from_word(jnp.stack(back[at:at + k]), r.dtype) if k else r)
        at += k
    return by_entry, before[:, :-1], totals


def _ring_read(ring, start, S: int):
    """``S`` slots of ``ring`` (``[R, C]``) from slot ``start`` on,
    around the end: two slices, no gather. (A ring shorter than ``S``
    is read as so many copies of itself end to end.)"""
    ring = jnp.tile(ring, (1, -(-S // ring.shape[1])))
    R, C = ring.shape
    lo = jnp.minimum(start, C - S)
    both = jnp.concatenate(
        [lax.dynamic_slice(ring, (0, lo), (R, S)), ring[:, :S]], axis=1)
    return lax.dynamic_slice(both, (0, start - lo), (R, S))


def _ring_write(ring, tail, arr, M):
    """``ring`` with the first ``M`` columns of ``arr`` written from
    slot ``tail`` on, around the end: two read-modify-writes of ``E``
    slots (behind ``tail``, and from slot 0 for what went around). Of
    more arrivals than the ring has slots only the last ``C`` are
    written: the others would be written over."""
    R, C = ring.shape
    E = min(arr.shape[1], C)
    skip = jnp.maximum(M - E, 0)
    arr = lax.dynamic_slice(arr, (0, skip), (R, E))
    tail, M = (tail + skip) % C, M - skip
    k = jnp.arange(E, dtype=jnp.int32)
    zeros = jnp.zeros_like(arr)
    lo = jnp.minimum(tail, C - E)
    d = tail - lo
    shifted = lax.dynamic_slice(
        jnp.concatenate([zeros, arr], axis=1), (0, E - d), (R, E))
    cur = lax.dynamic_slice(ring, (0, lo), (R, E))
    ring = lax.dynamic_update_slice(
        ring,
        jnp.where(((k >= d) & (k - d < M))[None, :], shifted, cur),
        (0, lo),
    )
    around = jnp.minimum(C - tail, E)
    shifted = lax.dynamic_slice(
        jnp.concatenate([arr, zeros], axis=1), (0, around), (R, E))
    cur = ring[:, :E]
    return lax.dynamic_update_slice(
        ring,
        jnp.where((k + (C - tail) < M)[None, :], shifted, cur),
        (0, 0),
    )


@dataclass
class TimeWindowArtifact(AlignedBlocks, SlidingWindowArtifact):
    """``from S[f]#window.time(t) select ... group by ...`` with
    ``count`` / ``sum`` / ``avg`` / ``stddev``: see the module's text.

    State: ``ring`` ``[2 + A, C]`` int32 words (a member's stamp, its
    group code, its ``A`` arguments: an int's value, a float's bits),
    live from slot ``head`` for ``count`` slots; ``clock``, the last
    stamp; ``sums``, per group and value plane the sum over the live
    members (``cnt`` and an int argument in int32, exact; a float
    argument and ``stddev``'s squares as a float32 pair ``<name>`` +
    ``<name>c``, zeroed when the group's count reaches 0 and rebuilt
    from the ring each step where the ring is no longer than a tape);
    ``overflow``, the members ever lost to a full ring (a wrong
    answer, counted); ``stepped``, what the last step counted:
    members that left on the clock, members lost to capacity."""

    # the names of ``stepped``'s counts, booked at the drain from the
    # count prefix (plan.py ``_append_outputs``, ``Job._book_prefix``)
    step_counters = ("window.time_expired", "window.ring_evicted")

    def _blocked(self) -> bool:
        return True

    @property
    def merge_form(self) -> str:
        return "ring"

    def _planes(self) -> Tuple[List[Tuple[str, int]], List[Tuple[str, int]]]:
        """``(ints, floats)``: each value plane's name and argument
        (-1 for the count)."""
        need_sum = sorted(
            {a.arg_idx for a in self.aggs
             if a.kind in ("sum", "avg", "stddev")})
        need_sq = sorted(
            {a.arg_idx for a in self.aggs if a.kind == "stddev"})
        ints, floats = [("cnt", -1)], []
        for j in need_sum:
            is_float = jnp.issubdtype(
                np.dtype(self.arg_types[j].device_dtype), jnp.floating)
            (floats if is_float else ints).append((f"s{j}", j))
        floats += [(f"q{j}", j) for j in need_sq]
        return ints, floats

    def init_state(self) -> Dict:
        G = self._gcap()
        ints, floats = self._planes()
        sums = {n: jnp.zeros(G, jnp.int32) for n, _ in ints}
        for n, _ in floats:
            sums[n] = jnp.zeros(G, jnp.float32)
            sums[n + "c"] = jnp.zeros(G, jnp.float32)
        return {
            "enabled": jnp.asarray(True),
            "ring": jnp.zeros(
                (2 + len(self.arg_types), self.capacity), jnp.int32),
            "head": jnp.int32(0),
            "count": jnp.int32(0),
            "clock": jnp.int32(_I32_MIN),
            "sums": sums,
            "overflow": jnp.int32(0),
            "stepped": jnp.zeros(2, jnp.int32),
        }

    def grow_state(self, state: Dict) -> Dict:
        G, need = state["sums"]["cnt"].shape[0], self._gcap()
        if G >= need:
            return state
        out = dict(state)
        out["sums"] = {
            k: jnp.concatenate([v, jnp.zeros(need - G, v.dtype)])
            for k, v in state["sums"].items()
        }
        return out

    def _values(self, words, sign, live):
        """A member's value planes from its ring words ``[2 + A, n]``:
        ``(ints, floats)``, a list of ``[n]`` rows each, ``sign``
        applied, zero where ``live`` is not."""

        def plane(name, j, dtype):
            if j < 0:
                v = jnp.ones(words.shape[1], dtype)
            else:
                v = _from_word(
                    words[2 + j], np.dtype(self.arg_types[j].device_dtype)
                ).astype(dtype)
                if name.startswith("q"):
                    v = v * v
            return jnp.where(live, v * sign, 0).astype(dtype)

        ints, floats = self._planes()
        return ([plane(nm, j, jnp.int32) for nm, j in ints],
                [plane(nm, j, jnp.float32) for nm, j in floats])

    @jax.named_scope("fst.window_fold")
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        C = self.capacity
        span = jnp.int32(self.time_ms)
        ring, head, n0 = state["ring"], state["head"], state["count"]
        sums = state["sums"]
        G = sums["cnt"].shape[0]
        int_names = [n for n, _ in self._planes()[0]]
        float_names = [n for n, _ in self._planes()[1]]
        Ki, Kf = len(int_names), len(float_names)

        def tape_col(col):
            return to_word(jnp.broadcast_to(jnp.asarray(col), (E,)))

        # the arrivals as ring words, in tape order at the front
        rows = [tape_col(tape.ts)]
        rows.append(
            tape_col(env[self.code_key]) if self.code_key is not None
            else jnp.zeros(E, jnp.int32))
        for j, fn in enumerate(self.arg_fns):
            rows.append(tape_col(jnp.asarray(fn(env)).astype(
                self.arg_types[j].device_dtype)))
        M, arr, is_prefix = front_compact(mask, jnp.stack(rows))
        k = jnp.arange(E, dtype=jnp.int32)
        live_b = k < M
        # the stream's clock never goes back: an arrival's stamp is the
        # latest timestamp up to it
        stamp = jnp.maximum(
            lax.cummax(jnp.where(live_b, arr[0], _I32_MIN)), state["clock"])
        clock = jnp.where(M > 0, jnp.max(stamp), state["clock"])
        arr = arr.at[0].set(jnp.where(live_b, stamp, 0))
        reach = jnp.where(live_b, stamp, _I32_MAX)  # no member outlasts it
        arr_i, arr_f = self._values(arr, 1, live_b)
        arr_code = jnp.where(live_b, arr[1], G)
        pad = jnp.zeros_like(arr)
        arr3 = jnp.concatenate([pad, arr, pad], axis=1)

        base_i = jnp.stack([sums[n] for n in int_names])
        base_f = [jnp.stack([sums[n + c] for n in float_names])
                  if Kf else jnp.zeros((0, G), jnp.float32)
                  for c in ("", "c")]
        at_code = jnp.clip(arr[1], 0, G - 1)
        reach2 = jnp.concatenate([reach, jnp.full(E, _I32_MAX)])

        def one_round(carry):
            r, _, acc_i, acc_s, acc_c, tot_i, tot_s, tot_c, left, lost = carry
            # the virtual sequence: the live ring, then the arrivals;
            # this round's stretch is its members r * E onward
            q = r * E + k
            ring_part = _ring_read(ring, (head + r * E) % C, E)
            arr_part = lax.dynamic_slice(
                arr3, (0, jnp.clip(E - (n0 - r * E), 0, 2 * E)),
                (arr.shape[0], E))
            memb = jnp.where((q < n0)[None, :], ring_part, arr_part)
            # a member leaves ahead of the first arrival stamped `span`
            # later (on a tie the expiry sorts first: key -1), or ahead
            # of the arrival that takes its slot (capacity: arrival
            # ``f_c``, key 2 f_c against that arrival's 2 f_c + 1),
            # whichever comes first
            due = memb[0] + span
            due = jnp.where(due < memb[0], _I32_MAX, due)
            f_c = q + (C - n0)
            slot_at = lax.dynamic_slice(
                reach2, (jnp.clip(r * E + (C - n0), 0, E),), (E,))
            on_clock = due <= slot_at
            leaves = (q < n0 + M) & (M > 0) & (
                (due <= clock) | (f_c < M))
            exp_i, exp_f = self._values(memb, -1, leaves)
            first = (r == 0)
            run, began, totals = group_fold(
                jnp.concatenate([jnp.where(leaves, memb[1], G), arr_code]),
                [jnp.concatenate([jnp.where(on_clock, due, slot_at), reach]),
                 jnp.concatenate(
                     [jnp.where(on_clock, -1, 2 * f_c), 2 * k + 1])],
                [jnp.concatenate([e, jnp.where(first, a, 0)])
                 for e, a in zip(exp_i, arr_i)],
                [jnp.concatenate([e, jnp.where(first, a, 0)])
                 for e, a in zip(exp_f, arr_f)],
                G,
            )
            # an arrival's windowed sums so far: the running sum at its
            # entry, and per group (one gather of rows, by code) the
            # sums at the step's start less the cumulative sum's value
            # where the group began
            got_i = [row[E:] for row in run[0]]
            got_s, got_c = run[1][:, E:], run[2][:, E:]
            tab_i, tab_s, tab_c = _rows_by(at_code, [
                jnp.where(first, base_i, 0) - began,
                jnp.where(first, base_f[0], 0),
                jnp.where(first, base_f[1], 0),
            ])
            acc_s, acc_c = _pair_add(acc_s, acc_c, got_s, got_c)
            acc_s, acc_c = _pair_add(acc_s, acc_c, tab_s, tab_c)
            tot_s, tot_c = _pair_add(tot_s, tot_c, totals[1], totals[2])
            n_left = leaves.sum().astype(jnp.int32)
            more = (n_left == E) & ((r + 1) * E < n0 + M)
            return (
                r + 1, more,
                tuple(a + g + t for a, g, t in zip(acc_i, got_i, tab_i)),
                acc_s, acc_c,
                tot_i + totals[0], tot_s, tot_c, left + n_left,
                lost + (leaves & ~on_clock).sum().astype(jnp.int32),
            )

        with jax.named_scope("fst.time_expire"):
            zi = tuple(jnp.zeros(E, jnp.int32) for _ in range(Ki))
            zf = jnp.zeros((Kf, E), jnp.float32)
            gi = jnp.zeros((Ki, G), jnp.int32)
            gf = jnp.zeros((Kf, G), jnp.float32)
            (_, _, acc_i, acc_s, acc_c, tot_i, tot_s, tot_c, left,
             lost) = lax.while_loop(
                lambda c: c[1], one_round,
                (jnp.int32(0), jnp.asarray(True), zi, zf, zf, gi, gf, gf,
                 jnp.int32(0), jnp.int32(0)),
            )

        win = {n: acc_i[i] for i, n in enumerate(int_names)}
        for i, n in enumerate(float_names):
            win[n] = acc_s[i] + acc_c[i]

        cnt = win["cnt"]
        cnt_f = jnp.maximum(cnt, 1).astype(jnp.float32)
        concat_rows = {}
        for agg in self.aggs:
            if agg.kind == "count":
                rows_ = cnt
            elif agg.kind == "sum":
                rows_ = win[f"s{agg.arg_idx}"]
            elif agg.kind == "avg":
                rows_ = win[f"s{agg.arg_idx}"].astype(jnp.float32) / cnt_f
            else:  # stddev
                mean = win[f"s{agg.arg_idx}"].astype(jnp.float32) / cnt_f
                rows_ = jnp.sqrt(jnp.maximum(
                    win[f"q{agg.arg_idx}"] / cnt_f - mean * mean, 0.0))
            concat_rows[agg.slot] = rows_
        by_slot = batch_rows(mask, is_prefix, concat_rows, 0)
        for agg in self.aggs:
            env[agg.slot] = jnp.where(mask, by_slot[agg.slot], 0).astype(
                agg.out_type.device_dtype)

        out_mask, cols = self._project(env, mask, E)

        new_ring = _ring_write(ring, (head + n0) % C, arr, M)
        new_head = (head + left) % C
        new_count = n0 + M - left
        new_sums = {
            n: sums[n] + tot_i[i] for i, n in enumerate(int_names)}
        if Kf:
            empty = new_sums["cnt"] == 0
            if C <= E:
                # the ring is no longer than a tape: the float sums are
                # what the live members add up to, afresh
                slot = jnp.arange(C, dtype=jnp.int32)
                live_r = (slot - new_head) % C < new_count
                _, ring_f = self._values(new_ring, 1, live_r)
                _, _, fresh = group_fold(
                    jnp.where(live_r, new_ring[1], G), [], [], ring_f, G,
                    want_run=False)
                f_s, f_c = fresh[1], fresh[2]
            else:
                f_s, f_c = _pair_add(base_f[0], base_f[1], tot_s, tot_c)
            for i, n in enumerate(float_names):
                new_sums[n] = jnp.where(empty, 0.0, f_s[i])
                new_sums[n + "c"] = jnp.where(empty, 0.0, f_c[i])
        new_state = {
            "enabled": state["enabled"],
            "ring": new_ring,
            "head": new_head,
            "count": new_count,
            "clock": clock,
            "sums": new_sums,
            "overflow": state["overflow"] + lost,
            "stepped": jnp.stack([left - lost, lost]),
        }
        return new_state, (out_mask, tape.ts, cols)
