"""Dense group-key encoding for group-by state tables.

Aggregation state on device is a dense table indexed by group code; arbitrary
group-by key values (ints, floats, multi-column tuples) are interned on the
host into stable dense codes, the same trick dictionary-coded strings use
(schema/strings.py). The reference keeps per-group aggregation state in JVM
hash maps inside siddhi-core; a dense code + fixed table is the TPU shape of
that state (SURVEY.md §7 hard part 1: data-dependent structures -> fixed
buffers).

A code is a **slot** of the device table. By default the table is
append-only: a key keeps its slot for the life of the job, which is what
every artifact that decodes codes from a cached look-up table relies on. An
encoder built with ``retain_ticks`` also **expires** keys (a time window
whose keys are born and die: auctions, sessions, orders): the window's
artifact gives every interning call the rebased time column, a slot is
stamped with the tick of the last batch that touched it, and once
``retain_ticks`` ticks have passed that stamp the slot is freed and handed
to the next new key. The table then stays at the size of the keys a window
holds, however many keys the stream has seen.

A table whose device state outlives a slot's key (the per-key length
window's count and ring: nothing on the device closes them) is built with
``mark_new``: every row of a key that the call gave a slot (a fresh or a
reused one) carries ``~slot``, a negative code, and the step starts that
slot's state anew before the key's first event.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def _dense_span(vals: np.ndarray):
    """(lowest value, span) of integer ``vals`` whose range is at most
    four times their number, else (None, None)."""
    if vals.dtype.kind not in "iu":
        return None, None
    lo, hi = int(vals.min()), int(vals.max())
    if hi - lo >= 4 * len(vals) + 64:
        return None, None
    return lo, hi - lo + 1


class GroupEncoder:
    """Intern table over tuples of column values -> dense slots.

    A single numeric column (the common group-by, and the hot path: one
    call per micro-batch) is interned with numpy alone. The live keys
    are kept sorted beside their slots in TWO tables: the main one
    (``_skeys`` / ``_sslots``, as large as the key set: 3.6M vehicles)
    and a short side table (``_nkeys`` / ``_nslots``) of the keys
    interned since the main one was last written. A batch's distinct
    values are searched in the main table, a block of them at a time
    in the stretch of keys between the block's ends (``_search``), and
    the misses alone in the side table (``_find``); a new key costs an
    insert into the side table, never a copy of the main one. The side
    table is merged in once it holds a thirty-second as many keys as
    the main one (``_add``: every eighth batch at 3.6M keys and 13,000
    new ones a batch), and the sweep takes the dead keys out of both.
    A batch's distinct values come from a mark in a table where they
    lie close together (``_dense_span``) and else from ONE stable sort
    of the batch (``_sorted_runs``), which also gives each row's key
    and the way back to stream order (``_intern_sparse``).
    Several integer columns go the same way as ONE packed key (a
    field of bits a column, as wide as the values seen need: ``_pack``),
    with the slots handed out in the order the keys first appear, as
    the per-row path does, so that both give the same codes; a packed
    key of at most ``DENSE_BITS`` bits finds its slot in a table kept
    from batch to batch (``_intern_dense``), one look-up a row. Anything
    else (object values, integers that 62 bits do not hold side by
    side) goes through a dict, one Python step per selected row."""

    # packed keys up to this many bits take their slots from a dense
    # table (``_intern_dense``: 4 MB at the most)
    DENSE_BITS = 20
    # sorted values are looked up this many at a time (``_search``)
    SEARCH_BLOCK = 4096

    def __init__(self, retain_ticks: Optional[int] = None,
                 mark_new: bool = False) -> None:
        self.retain_ticks = retain_ticks
        self.mark_new = mark_new
        self._n = 0  # slots ever handed out (the table's high-water mark)
        # array mode: slot -> key, and the live keys sorted: the main
        # table, and the keys interned since it was last written
        self._slot_key: Optional[np.ndarray] = None
        self._skeys: Optional[np.ndarray] = None
        self._sslots: Optional[np.ndarray] = None
        self._nkeys: Optional[np.ndarray] = None
        self._nslots: Optional[np.ndarray] = None
        # several columns in array mode: (lowest value, bits) a column
        self._packing: Optional[List[Tuple[int, int]]] = None
        # packed keys of at most DENSE_BITS bits: key -> slot (-1: none),
        # and the sorted keys it was laid out from
        self._dense: Optional[np.ndarray] = None
        self._dense_of: Optional[np.ndarray] = None
        # dict mode: key tuple -> slot, slot -> key tuple (None: free)
        self._codes: Dict[Tuple, int] = {}
        self._values: List[Optional[Tuple]] = []
        # expiry: slot -> tick of the last batch that touched it; free
        # slots, reused last-in first-out; the newest tick seen
        self._last_tick = np.zeros(0, dtype=np.int64)
        self._free = np.zeros(0, dtype=np.int32)
        self._tick: Optional[int] = None
        self._swept: Optional[int] = None
        # what the executor's counters read (groups.*)
        self.stats = {"interned": 0, "slots_reused": 0, "expired": 0}

    def __len__(self) -> int:
        """Slots the device table needs (free slots included)."""
        return self._n

    @property
    def live(self) -> int:
        return self._n - len(self._free)

    # -- interning ------------------------------------------------------------
    def intern_rows(
        self,
        cols: Sequence[np.ndarray],
        select: np.ndarray,
        tick_col: Optional[np.ndarray] = None,
        tick_ms: int = 0,
    ) -> np.ndarray:
        """Codes for each row of ``zip(*cols)``; rows where ``select`` is
        False get code 0 and are NOT interned (they belong to other streams
        and must not grow the table). ``tick_col`` (the owning window's
        rebased time column) and ``tick_ms`` drive expiry: the batch's tick
        is that of its last selected row. Under ``mark_new`` the rows of
        a key interned by this call get ``~slot``."""
        n = len(select)
        out = np.zeros(n, dtype=np.int32)
        if not n:
            return out
        tick = None
        if self.retain_ticks is not None and tick_col is not None:
            self._sweep()
            pos = np.flatnonzero(select)
            if len(pos):
                # never behind an earlier batch's (a late last row)
                tick = max(int(tick_col[pos[-1]]) // tick_ms,
                           self._tick or 0)
        vals, rows = None, None
        if len(cols) == 1 and cols[0].dtype != object:
            # vectorized single-column path: the distinct values once,
            # nothing per row or per key in Python
            vals = cols[0][select]
        elif len(cols) > 1:
            # several integer columns: the same path over one packed key
            # a row, the slots in the per-row path's order
            vals = rows = self._pack(cols, select)
        if vals is not None:
            if not len(vals):
                return out
            # few bits a key (``_pack``): one table look-up a row
            narrow = rows is not None and rows.dtype == np.int32
            lo, span = (None, None) if narrow else _dense_span(vals)
            if narrow:
                slots, codes = self._intern_dense(rows)
                out[select] = codes
            elif span is not None:
                # ids that lie close together (a stream's newest keys, a
                # small key set): mark and look up, no sort and no search
                rel = vals - lo
                seen = np.zeros(span, dtype=np.bool_)
                seen[rel] = True
                present = np.flatnonzero(seen)
                slots, codes = self._intern_unique(
                    (present + lo).astype(vals.dtype), rows
                )
                lut = np.zeros(span, dtype=np.int32)
                lut[present] = codes
                out[select] = lut[rel]
            else:
                slots, codes = self._intern_sparse(vals, rows is not None)
                out[select] = codes
        else:
            idx = np.nonzero(select)[0]
            slots = np.empty(len(idx), dtype=np.int32)
            fresh = []
            for j, i in enumerate(idx):
                born = self.stats["interned"]
                slots[j] = self._intern_key(tuple(c[i].item() for c in cols))
                if self.stats["interned"] != born:
                    fresh.append(slots[j])
            out[idx] = slots
            if self.mark_new and fresh:
                out[idx] = np.where(np.isin(slots, fresh), ~slots, slots)
        if tick is not None:
            self._last_tick[slots] = tick
            self._tick = tick if self._tick is None else max(tick, self._tick)
        return out

    def intern_sources(self, sources, tick_ms: int = 0) -> np.ndarray:
        """One table fed by several columns: ``sources`` is a list of
        ``(column, select, tick_col, counter)``; a row takes its key
        from the source that selects it (the sources are disjoint: the
        two sides of a window join), and a key has one slot whichever
        source brought it. The selected values are interned together as
        one short column; ``stats[counter]`` counts each source's
        rows."""
        at, vals, ticks = [], [], []
        for col, select, tick_col, counter in sources:
            pos = np.flatnonzero(select)
            at.append(pos)
            vals.append(col[pos].astype(np.int64, copy=False))
            if counter is not None:
                self.stats[counter] = self.stats.get(counter, 0) + len(pos)
            if tick_col is not None and len(pos):
                # the side's last selected row (the stream is in order)
                ticks.append(int(tick_col[pos[-1]]))
        merged = np.concatenate(vals)
        codes = self.intern_rows(
            [merged], np.ones(len(merged), dtype=np.bool_),
            np.broadcast_to(np.int64(max(ticks)), merged.shape)
            if ticks else None,
            tick_ms,
        )
        out = np.zeros(len(sources[0][1]), dtype=np.int32)
        out[np.concatenate(at)] = codes
        return out

    def _pack(self, cols, select) -> Optional[np.ndarray]:
        """The selected rows of several integer columns as one key a
        row, or None where they are not integers or do not fit: a
        column's field is its value less the lowest seen, in as many
        bits as the span seen needs. A batch outside the fields widens
        them, and the live keys are packed anew (through the dict).
        Keys of at most ``DENSE_BITS`` bits come as int32 (and go
        through ``_intern_dense``), wider ones as int64."""
        if any(c.dtype.kind not in "iu" for c in cols):
            return None
        sel = [c[select] for c in cols]
        if not len(sel[0]):
            return sel[0].astype(np.int64)
        pk = self._packing
        seen = [(int(c.min()), int(c.max())) for c in sel]
        if pk is None or any(
            lo < plo or hi >= plo + (1 << bits)
            for (lo, hi), (plo, bits) in zip(seen, pk)
        ):
            values = [v for v in self._value_list() if v is not None]
            for j, (lo, hi) in enumerate(seen):
                known = [v[j] for v in values]
                lo, hi = min([lo] + known), max([hi] + known)
                seen[j] = (lo, max(1, (hi - lo).bit_length()))
            if sum(bits for _, bits in seen) > 62:
                return None
            if self._skeys is not None:
                self._to_dict()
            # fst:ephemeral derived from the keys: a restored table (dict mode, its keys whole) packs anew at its first call
            pk = self._packing = seen
        # a field's values less its lowest fit the key's type, whatever
        # the column's own (an int8 column may span 255)
        kt = (np.int32 if sum(bits for _, bits in pk) <= self.DENSE_BITS
              else np.int64)
        key, shift = None, 0
        for c, (lo, bits) in zip(sel, pk):
            if c.dtype.itemsize < 4 or kt is np.int64:
                c = c.astype(kt, copy=False)
            field = (c - lo).astype(kt, copy=False) << shift
            key = field if key is None else key | field
            shift += bits
        return key

    def _unpack(self, key: int) -> Tuple:
        if self._packing is None:
            return (key,)
        out = []
        for lo, bits in self._packing:
            out.append(lo + (key & ((1 << bits) - 1)))
            key >>= bits
        return tuple(out)

    def _intern_dense(self, keys: np.ndarray):
        """(slot a row, code a row) of packed int32 ``keys``: a look-up
        in a table of ``1 << bits`` slots, -1 where the key has none.
        The keys it lacks (few, once a job's first batches have run) go
        through ``_intern_unique`` in the order they first appear; under
        ``mark_new`` all their rows carry ``~slot``."""
        if self._skeys is None:
            self._to_arrays(np.int64)
        if self._dense_of is not self._skeys:
            # the sorted keys are replaced, never written in place
            bits = sum(b for _, b in self._packing)
            # fst:ephemeral laid out from the sorted keys, which a restored table makes anew
            self._dense = np.full(1 << bits, -1, dtype=np.int32)
            self._dense[self._skeys] = self._sslots
            self._dense[self._nkeys] = self._nslots
            # fst:ephemeral the sorted keys the table above was laid out from
            self._dense_of = self._skeys
        slots = codes = self._dense[keys]
        if slots.min() < 0:
            miss = np.flatnonzero(slots < 0)
            new = keys[miss].astype(np.int64)
            uniq = np.unique(new)
            got, _ = self._intern_unique(uniq, new)
            self._dense[uniq] = got
            slots[miss] = got[np.searchsorted(uniq, new)]
            if self.mark_new:
                codes = slots.copy()
                codes[miss] = ~slots[miss]
        return slots, codes

    def _take_slots(self, n_new: int, rowwise: bool = False) -> np.ndarray:
        """``n_new`` slots: freed ones first, then fresh ones;
        ``rowwise`` in the order that many calls for one slot give."""
        take = min(n_new, len(self._free))
        fresh = np.arange(self._n, self._n + n_new - take, dtype=np.int32)
        freed = self._free[len(self._free) - take:]
        slots = np.concatenate([freed[::-1] if rowwise else freed, fresh])
        self._free = self._free[: len(self._free) - take]
        self._n += n_new - take
        self.stats["interned"] += n_new
        self.stats["slots_reused"] += take
        if self._n > len(self._last_tick):
            grow = max(self._n, 2 * len(self._last_tick), 64)
            self._last_tick = np.concatenate([
                self._last_tick,
                np.zeros(grow - len(self._last_tick), dtype=np.int64),
            ])
        return slots

    def _intern_sparse(self, vals: np.ndarray, rowwise: bool):
        """(slot a distinct key, code a row) of ``vals`` that lie far
        apart: one sort of the batch gives its distinct keys, each
        row's key and the way back to stream order. ``rowwise`` (a
        packed key): new keys take their slots in the order they first
        appear, which a stable sort puts at the head of a key's run."""
        order, ranked, head = self._sorted_runs(vals)
        first = None
        if rowwise:
            first = order if head is None else order[head]
        slots, codes = self._intern_unique(
            ranked if head is None else ranked[head], first=first)
        if head is not None:
            codes = np.repeat(codes, np.diff(head, append=len(ranked)))
        rows = np.empty(len(vals), dtype=np.int32)
        rows[order] = codes
        return slots, rows

    @staticmethod
    def _sorted_runs(vals: np.ndarray):
        """(order, ``vals`` in that order, head): the stable sort of
        ``vals`` and where each run of equal values starts in it, None
        where no value repeats (each row is its own run). Integers
        whose span leaves room for the row number ride with it in one
        word: a plain sort of the words is the stable sort of the
        values, at one price whatever order the rows came in (a merge
        sort of 538,560 values costs from one to three times that as
        the stream's keys interleave)."""
        n, bits = len(vals), (len(vals) - 1).bit_length()
        lo, hi = vals.min().item(), vals.max().item()
        if (vals.dtype.kind in "iu" and hi < 1 << 63
                and (hi - lo).bit_length() + bits < 63):
            word = vals.astype(np.int64)
            word -= lo
            word <<= bits
            word |= np.arange(n)
            word.sort()
            order = word & ((1 << bits) - 1)
            word >>= bits
            word += lo
            ranked = word.astype(vals.dtype, copy=False)
        else:
            order = np.argsort(vals, kind="stable")
            ranked = vals[order]
        step = ranked[1:] != ranked[:-1]
        if step.all():
            return order, ranked, None
        return order, ranked, np.concatenate(
            [[0], np.flatnonzero(step) + 1])

    def _intern_unique(self, uniq: np.ndarray,
                       rows: Optional[np.ndarray] = None,
                       first: Optional[np.ndarray] = None):
        """(slots, codes) of the sorted distinct values ``uniq`` (array
        mode): the codes are the slots, under ``mark_new`` ``~slot`` for
        the values interned here. New values take their slots in sorted
        order, or in the order they first appear (the per-row path's):
        ``first`` has the row where each of ``uniq`` first appears, or
        ``rows`` the batch's values in row order."""
        if self._skeys is None:
            self._to_arrays(uniq.dtype)
        slots = self._find(uniq)
        new = np.flatnonzero(slots < 0)
        if not len(new):
            return slots, slots
        keys = uniq[new]
        got = self._take_slots(
            len(new), rowwise=rows is not None or first is not None)
        if len(new) > 1:
            if rows is not None:
                at = np.flatnonzero(np.isin(rows, keys))
                _, i = np.unique(rows[at], return_index=True)
                got = got[np.argsort(np.argsort(at[i]))]
            elif first is not None:
                got = got[np.argsort(np.argsort(first[new]))]
        self._add(keys, got)
        slots[new] = got
        if not self.mark_new:
            return slots, slots
        codes = slots.copy()
        codes[new] = ~got
        return slots, codes

    def _find(self, uniq: np.ndarray) -> np.ndarray:
        """Slot of each of the sorted values ``uniq``, -1 where it has
        none: the main table, and for its misses the side table."""
        slots = self._search(self._skeys, self._sslots, uniq)
        if len(self._nkeys):
            miss = np.flatnonzero(slots < 0)
            slots[miss] = self._search(
                self._nkeys, self._nslots, uniq[miss])
        return slots

    @staticmethod
    def _search(keys: np.ndarray, slots: np.ndarray, uniq: np.ndarray):
        """``slots`` where the sorted ``keys`` hold each of the sorted
        ``uniq``, -1 where they do not."""
        if not len(keys):
            return np.full(len(uniq), -1, dtype=np.int32)
        # a block of values at a time, in the stretch of keys between
        # the block's first value and the next block's: a search of a
        # few thousand keys that stay in the cache, not of millions
        block = GroupEncoder.SEARCH_BLOCK
        ends = np.searchsorted(keys, uniq[::block]).tolist() + [len(keys)]
        pos = np.empty(len(uniq), dtype=np.intp)
        for i, (lo, hi) in enumerate(zip(ends, ends[1:])):
            part = pos[i * block: (i + 1) * block]
            part[:] = np.searchsorted(
                keys[lo:hi], uniq[i * block: (i + 1) * block])
            part += lo
        np.minimum(pos, len(keys) - 1, out=pos)
        return np.where(keys[pos] == uniq, slots[pos], np.int32(-1))

    def _add(self, keys: np.ndarray, slots: np.ndarray) -> None:
        """New sorted ``keys`` at ``slots``, into the side table; once
        that holds a thirty-second as many keys as the main one it is
        merged in (both sorted arrays written anew, once), so a key is
        copied a bounded number of times whatever the table's size."""
        if self._n > len(self._slot_key):
            grown = np.zeros(
                max(self._n, 2 * len(self._slot_key), 64),
                dtype=self._slot_key.dtype,
            )
            grown[: len(self._slot_key)] = self._slot_key
            self._slot_key = grown
        self._slot_key[slots] = keys
        nk, ns = self._merged(self._nkeys, self._nslots, keys, slots)
        if len(nk) > len(self._skeys) >> 5:
            self._skeys, self._sslots = self._merged(
                self._skeys, self._sslots, nk, ns)
            nk, ns = nk[:0], ns[:0]
        self._nkeys, self._nslots = nk, ns

    @staticmethod
    def _merged(keys: np.ndarray, slots: np.ndarray,
                more_keys: np.ndarray, more_slots: np.ndarray):
        """The sorted table ``keys`` / ``slots`` with the sorted
        ``more_keys`` / ``more_slots`` in their places."""
        if not len(keys):
            return more_keys, more_slots
        at = np.searchsorted(keys, more_keys)
        return np.insert(keys, at, more_keys), np.insert(slots, at, more_slots)

    def _intern_key(self, key: Tuple) -> int:
        if self._skeys is not None:
            self._to_dict()
        code = self._codes.get(key)
        if code is None:
            code = int(self._take_slots(1)[0])
            self._codes[key] = code
            if code == len(self._values):
                self._values.append(key)
            else:
                self._values[code] = key
        return code

    def _sweep(self) -> None:
        """Free every slot that ``retain_ticks`` ticks have passed, as of
        the newest tick an EARLIER call saw: by then the device has closed
        every window the slot's key could be part of."""
        if self._tick is None or self._tick == self._swept:
            return
        self._swept = self._tick
        dead = self._last_tick[: self._n] <= self._tick - self.retain_ticks
        dead[self._free] = False  # a free slot keeps its last stamp
        freed = np.flatnonzero(dead).astype(np.int32)  # in slot order
        if not len(freed):
            return
        if self._skeys is not None:
            keep = ~dead[self._sslots]
            self._skeys, self._sslots = self._skeys[keep], self._sslots[keep]
            keep = ~dead[self._nslots]
            self._nkeys, self._nslots = self._nkeys[keep], self._nslots[keep]
        else:
            for s in freed:
                del self._codes[self._values[s]]
                self._values[s] = None
        self._free = np.concatenate([self._free, freed])
        self.stats["expired"] += len(freed)

    def value(self, code: int) -> Tuple:
        if self._skeys is not None:
            return self._unpack(self._slot_key[code].item())
        return self._values[code]

    # -- the two representations ----------------------------------------------
    def _to_arrays(self, dtype) -> None:
        """Dict mode (or a fresh table) -> array mode."""
        live = [(self._key_of(v), s) for s, v in enumerate(self._values)
                if v is not None]
        self._slot_key = np.zeros(max(self._n, 64), dtype=dtype)
        keys = np.asarray([k for k, _ in live], dtype=dtype)
        slots = np.asarray([s for _, s in live], dtype=np.int32)
        self._slot_key[slots] = keys
        order = np.argsort(keys, kind="stable")
        self._skeys, self._sslots = keys[order], slots[order]
        self._nkeys, self._nslots = keys[:0], slots[:0]
        self._codes, self._values = {}, []

    def _key_of(self, value: Tuple) -> int:
        """A key tuple as array mode holds it: its one value, or its
        columns packed (``_pack``'s fields)."""
        if self._packing is None:
            return value[0]
        key, shift = 0, 0
        for v, (lo, bits) in zip(value, self._packing):
            key |= (v - lo) << shift
            shift += bits
        return key

    def _to_dict(self) -> None:
        self._values = self._value_list()
        self._codes = {
            v: s for s, v in enumerate(self._values) if v is not None
        }
        self._slot_key = self._skeys = self._sslots = None
        self._nkeys = self._nslots = None

    def _value_list(self) -> List[Optional[Tuple]]:
        """slot -> key tuple, None for a free slot."""
        if self._skeys is None:
            return list(self._values)
        values: List[Optional[Tuple]] = [
            self._unpack(k) for k in self._slot_key[: self._n].tolist()
        ]
        for s in self._free.tolist():
            values[s] = None
        return values

    # -- checkpoint support -------------------------------------------------
    def state_dict(self) -> dict:
        """Keys by slot (None: a free slot) and, with expiry, each slot's
        stamp, the free slots in reuse order and the newest tick: a
        restored table hands out the same slots as the one it was taken
        from."""
        d = {"values": self._value_list()}
        if self.retain_ticks is not None:
            d["expiry"] = {
                "last_tick": self._last_tick[: self._n].tolist(),
                "free": self._free.tolist(),
                "tick": self._tick,
            }
        return d

    def load_state_dict(self, d: dict) -> None:
        self._slot_key = self._skeys = self._sslots = None
        self._nkeys = self._nslots = None
        self._values = [
            None if v is None else tuple(v) for v in d["values"]
        ]
        self._codes = {
            v: i for i, v in enumerate(self._values) if v is not None
        }
        self._n = len(self._values)
        exp = d.get("expiry") or {}
        self._last_tick = np.asarray(
            exp.get("last_tick", [0] * self._n), dtype=np.int64
        )
        self._free = np.asarray(exp.get("free", []), dtype=np.int32)
        self._tick = exp.get("tick")
        self._swept = None
