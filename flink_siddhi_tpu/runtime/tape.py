"""The device tape: one timestamp-merged columnar micro-batch.

The physical event representation the jitted step consumes. Where the
reference funnels each event through ``Tuple2<StreamRoute, Object>`` and a
per-event serializer (SiddhiStreamOperator.java:51-54, StreamSerializer.java:
38-66), the tape packs a whole micro-batch: all involved streams merged in
timestamp order, one device array per referenced (stream, field), plus stream
codes, rebased int32 timestamps, and a validity mask. Padded to bucketed
lengths so XLA compiles a handful of shapes, not one per batch.
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..schema.batch import EventBatch
from ..schema.types import AttributeType

MIN_BUCKET = 128
DAY_MS = 86_400_000


def time_origin(epoch_ms: int) -> int:
    """Where a time attribute's device clock starts: midnight (UTC) of
    the job epoch's day. A ``long`` that a window reads as time rides
    the job's clock as ``@ts`` does, int32 ms from here, so an epoch-ms
    value neither wraps nor loses its alignment: every span that
    divides a day cuts the rebased value where it cuts the epoch."""
    return epoch_ms - epoch_ms % DAY_MS


def time_key(key: str) -> str:
    """The tape column that holds ``key`` rebased to the job's clock
    (``TapeSpec.time_columns``): what a window reads as time. The raw
    column ``key`` keeps its value for every other reader."""
    return f"@time:{key}"


def bucket_size(n: int, minimum: int = MIN_BUCKET) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@dataclass(frozen=True)
class KeySource:
    """One feed of a slot table that several columns share (the two
    sides of a window join): an event of ``stream_code`` that passes
    ``select_fn`` takes ``in_key``'s value as its key. ``tick_key`` is
    the rebased time column of that side's window, ``counter`` the job
    counter that counts the events selected."""

    in_key: str
    stream_code: int
    select_fn: object = None
    tick_key: Optional[str] = None
    counter: Optional[str] = None


@dataclass(frozen=True)
class EncodedColumn:
    """A host-computed dense-code column: rows of ``in_keys`` (for events of
    ``stream_code``) interned through ``encoder`` into ``out_key``. Used for
    group-by state tables (schema/encoders.py).

    ``select_fn`` (cols -> bool mask), when set, restricts interning to rows
    the owning query's filters accept — otherwise a heavily filtered query
    over a high-cardinality stream would grow its group table (and retrace)
    for groups that can never emit."""

    out_key: str
    in_keys: Tuple[str, ...]
    stream_code: int
    encoder: object  # GroupEncoder
    select_fn: object = None
    # False = intern only (discover codes host-side) without building /
    # shipping the code column — chained-group consumers map values to
    # codes ON DEVICE from the synced sorted table instead
    materialize: bool = True
    # slot expiry (an encoder built with ``retain_ticks``): the rebased
    # time column the owning window reads and the span of one tick (the
    # hop window's slide). The encoder stamps every slot a batch touches
    # with the tick of the batch's last selected event and frees a slot
    # once ``retain_ticks`` ticks have passed it
    tick_key: Optional[str] = None
    tick_ms: int = 0
    # one table fed by several columns, each under its own filter: a
    # row takes the key of the source that selects it (the sources are
    # disjoint). Set, it stands in for ``in_keys``, ``stream_code``,
    # ``select_fn`` and ``tick_key``
    sources: Tuple[KeySource, ...] = ()


@dataclass(frozen=True)
class HostPred:
    """A host-computed pseudo-column shipped instead of raw columns.

    The original use is wire predicate pushdown: ``fn`` maps a dict of
    merged-order host columns (raw host dtypes — f64 for DOUBLE) to a
    bool mask that ships as ONE BIT per event. With ``dtype`` set to an
    integer type it generalizes to host-computed VALUE columns (e.g.
    #window.cron's per-event window index, calendar math the device
    can't do) — the wire narrowing then applies as for any int column.
    A ref of ``"@ts"`` reads the merged-order absolute event timestamps
    (int64 ms)."""

    out_key: str  # "@p:<n>" pseudo-column the device reads
    fn: object  # Dict[str, np.ndarray] -> np.ndarray
    refs: Tuple[str, ...]
    dtype: object = np.bool_


@dataclass(frozen=True)
class TapeSpec:
    """What the step needs materialized."""

    stream_codes: Dict[str, int]  # stream_id -> dense code
    columns: Tuple[str, ...]  # "stream.field" keys
    column_types: Dict[str, AttributeType]
    encoded: Tuple[EncodedColumn, ...] = ()
    # late materialization: when set, only these columns ship to the
    # device (projection-only columns stay host-side; the engine emits
    # event ordinals that decode against the host's retained batches)
    device_columns: Optional[Tuple[str, ...]] = None
    # wire predicate pushdown: host-evaluated masks added to the tape
    host_preds: Tuple[HostPred, ...] = ()
    # long columns that a window reads as time: each also gets a column
    # ``time_key(k)`` of int32 ms since ``time_origin(epoch)``, which
    # only the window reads. The raw column (where ``columns`` still
    # lists it: something reads it as a value) is built like any long
    time_columns: Tuple[str, ...] = ()

    def built_columns(self) -> Tuple[str, ...]:
        if self.device_columns is None:
            return self.columns
        return tuple(
            k for k in self.columns if k in set(self.device_columns)
        )

    def code_of(self, stream_id: str) -> int:
        return self.stream_codes[stream_id]


@jax.tree_util.register_pytree_node_class
@dataclass
class Tape:
    ts: object  # int32[E] ms since job epoch
    stream: object  # int32[E]
    valid: object  # bool[E]
    cols: Dict[str, object]  # "stream.field" -> array[E]
    # static: job epoch minus ``time_origin`` (0 where the plan has no
    # time column). A time column's value v is job-relative v - time_off
    time_off: int = 0

    @property
    def capacity(self) -> int:
        return self.ts.shape[-1]

    def tree_flatten(self):
        keys = tuple(sorted(self.cols))
        children = (self.ts, self.stream, self.valid) + tuple(
            self.cols[k] for k in keys
        )
        return children, (keys, self.time_off)

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, time_off = aux
        ts, stream, valid = children[:3]
        cols = dict(zip(keys, children[3:]))
        return cls(ts, stream, valid, cols, time_off)


@dataclass(frozen=True)
class TapeRows:
    """The rows of a cycle's batches that each of several tapes takes:
    tape ``r`` holds rows ``order[offsets[r]:offsets[r + 1]]`` of the
    batches laid end to end, in that order (the router's: by timestamp,
    ties by arrival). A row may appear in several tapes. ``build_tape``
    fills them all in one call and returns ``[tapes, capacity]``
    leaves, row ``r`` being the tape of run ``r``'s rows alone."""

    order: np.ndarray  # intp[R]
    offsets: np.ndarray  # intp[tapes + 1], offsets[0] == 0

    @property
    def counts(self) -> np.ndarray:
        return np.diff(self.offsets)


# --------------------------------------------------------------------------
# Wire tape: the narrow host->device format
# --------------------------------------------------------------------------
# Every event crosses the host->device link once, so bytes over that link
# bound ingest. The wire format strips everything the device can
# reconstruct:
#   * validity mask  -> one scalar (post-sort validity is always a prefix)
#   * stream codes   -> omitted entirely for single-input plans
#   * int columns    -> narrowest safe width (int8/int16/int32), sticky per
#     column so a width upgrade retraces at most twice per column
#   * a column whose values equal the event timestamp (a very common schema
#     shape: an explicit `timestamp` attribute) -> "alias", 0 bytes
# ``WireTape.expand()`` runs as the first (fused, free) ops of the jitted
# step and rebuilds the full logical ``Tape``.

_INT_KINDS = ("i8", "i16", "i32")
_KIND_DTYPE = {
    "i8": np.int8,
    "i16": np.int16,
    "i32": np.int32,
    "f32": np.float32,
    "b": np.bool_,  # legacy unpacked bools (still expandable)
    "b1": np.uint8,  # bit-packed bools: 1 bit/event on the wire
}
_TS_KINDS = ("d0", "d8", "d16", "i32")  # widening order


def _int_kind(lo: int, hi: int) -> str:
    if -128 <= lo and hi <= 127:
        return "i8"
    if -32768 <= lo and hi <= 32767:
        return "i16"
    return "i32"


@jax.tree_util.register_pytree_node_class
@dataclass
class WireTape:
    """Narrow on-the-wire micro-batch; ``expand()`` under jit -> ``Tape``."""

    ts: object  # int32[E], rebased, padding = last ts
    n_valid: object  # int32[1]
    stream: object  # int8[E] or None (single-stream plans)
    cols: Dict[str, object]  # key -> narrow array (absent for aliases)
    kinds: Tuple[Tuple[str, str], ...] = ()  # (key, kind), kind may be alias
    stream_const: int = -1  # valid when stream is None
    epoch_i32: int = 0  # int32-wrapped epoch for alias reconstruction

    # 'i32' absolute | 'd8'/'d16' per-event deltas (+ base) | 'd0'
    # constant delta: ZERO wire bytes — ts reconstructs from (base, step)
    ts_kind: str = "i32"
    ts_base: object = None  # int32[1] first ts, or int32[2] (first, step)
    cap: int = 0  # static tape capacity ('d0' ships no ts array)
    time_off: int = 0  # Tape.time_off; rebuilds an 'alias_tt' column

    @property
    def capacity(self) -> int:
        return self.cap if self.cap else self.ts.shape[-1]

    def tree_flatten(self):
        keys = tuple(sorted(self.cols))
        children = (self.ts, self.n_valid, self.stream, self.ts_base) + tuple(
            self.cols[k] for k in keys
        )
        aux = (keys, self.kinds, self.stream_const, self.epoch_i32,
               self.ts_kind, self.cap, self.time_off)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, kinds, stream_const, epoch_i32, ts_kind, cap, time_off = aux
        ts, n_valid, stream, ts_base = children[:4]
        cols = dict(zip(keys, children[4:]))
        return cls(ts, n_valid, stream, cols, kinds, stream_const,
                   epoch_i32, ts_kind, ts_base, cap, time_off)

    def expand(self) -> Tape:
        import jax.numpy as jnp

        cap = self.capacity
        iota = jnp.arange(cap, dtype=jnp.int32)
        valid = iota < self.n_valid[0]
        if self.ts_kind == "i32":
            ts = self.ts
        elif self.ts_kind == "d0":
            # regular cadence: ts = base + step*i, clamped so padding
            # repeats the last valid timestamp (build_tape contract:
            # padding must never look like the newest event)
            last = jnp.maximum(self.n_valid[0] - 1, 0)
            ts = self.ts_base[0] + self.ts_base[1] * jnp.minimum(
                iota, last
            )
        else:
            # sorted timestamps travel as per-event deltas; the padding
            # deltas are 0, which reproduces build_tape's "padding repeats
            # the last timestamp"
            ts = self.ts_base[0] + jnp.cumsum(
                self.ts.astype(jnp.int32), dtype=jnp.int32
            )
        if self.stream is None:
            stream = jnp.where(
                valid, jnp.int32(self.stream_const), jnp.int32(-1)
            )
        else:
            stream = self.stream.astype(jnp.int32)
        cols = {}
        for key, kind in self.kinds:
            if kind == "alias_ts":
                cols[key] = ts + jnp.int32(self.epoch_i32)
            elif kind == "alias_tt":  # a time column equal to the ts
                cols[key] = ts + jnp.int32(self.time_off)
            elif kind == "b1":
                packed = self.cols[key]
                bits = (
                    packed[:, None] >> jnp.arange(8, dtype=packed.dtype)
                ) & 1
                cols[key] = jnp.reshape(bits, (-1,)).astype(jnp.bool_)
            elif kind == "f32" or kind == "b":
                cols[key] = self.cols[key]
            else:
                cols[key] = self.cols[key].astype(jnp.int32)
        return Tape(ts, stream, valid, cols, self.time_off)


def build_wire_tape(
    spec: TapeSpec,
    batches: Sequence[EventBatch],
    epoch_ms: int,
    sticky_kinds: Dict[str, str],
    capacity: int | None = None,
    want_prov: bool = True,
    intern_span=None,
    codes=None,
) -> Tuple[WireTape, np.ndarray]:
    """build_tape + narrowing. ``sticky_kinds`` (mutated) remembers each
    column's widest kind seen so widths only ever widen (bounded
    retraces). ``want_prov=False`` skips building the merged-order
    provenance map (callers that never consult it — e.g. single-batch
    staging — save two full-width array fills per batch).
    """
    tape, prov = build_tape(
        spec, batches, epoch_ms, capacity, want_prov=want_prov,
        intern_span=intern_span, codes=codes,
    )
    total = sum(len(b) for b in batches)
    time_cols = {time_key(k) for k in spec.time_columns}
    epoch_i32 = int(np.int64(epoch_ms) & 0xFFFFFFFF)
    if epoch_i32 >= 1 << 31:
        epoch_i32 -= 1 << 32

    kinds: List[Tuple[str, str]] = []
    cols: Dict[str, np.ndarray] = {}
    with np.errstate(over="ignore"):
        recon = {}  # offset -> ts + offset, what an alias column holds
        for key in sorted(tape.cols):
            col = tape.cols[key]
            sticky = sticky_kinds.get(key)
            if col.dtype == np.float32:
                kind = "f32"
            elif col.dtype == np.bool_:
                kind = "b1"  # bit-packed: 1 bit/event on the wire
            else:
                # alias check first (0 wire bytes); a sticky alias may
                # degrade to a real int kind the first time it mismatches
                kind = None
                is_time = key in time_cols
                alias = "alias_tt" if is_time else "alias_ts"
                if sticky in (None, alias):
                    off = tape.time_off if is_time else epoch_i32
                    if off not in recon:
                        recon[off] = tape.ts[:total] + np.int32(off)
                    if np.array_equal(col[:total], recon[off]):
                        kind = alias
                if kind is None:
                    lo, hi = (
                        (int(col[:total].min()), int(col[:total].max()))
                        if total
                        else (0, 0)
                    )
                    kind = _int_kind(lo, hi)
                # widths only widen; alias degrades to measured width
                if sticky is not None and sticky != kind:
                    order = (alias,) + _INT_KINDS
                    if kind in order and sticky in order:
                        kind = order[max(order.index(kind),
                                         order.index(sticky))]
            sticky_kinds[key] = kind
            kinds.append((key, kind))
            if kind == "b1":
                cols[key] = np.packbits(col, bitorder="little")
            elif kind not in ("alias_ts", "alias_tt"):
                cols[key] = (
                    col
                    if kind in ("f32", "b", "i32")
                    else col.astype(_KIND_DTYPE[kind])
                )

    # timestamps: sorted, so deltas are small -> 1-2 wire bytes instead
    # of 4; a perfectly regular cadence ('d0', the common replay/sensor
    # shape) ships ZERO ts bytes — just (first, step)
    ts_kind = sticky_kinds.get("__ts__")
    ts_arr = tape.ts
    ts_base = None
    if ts_kind == "d0" and total >= 2:
        # sticky fast path: the cadence was already proven regular on
        # a >=4096-event batch; re-verifying "still constant" is one
        # int32 subtract + compare — no int64 diff allocation. Any
        # size keeps d0 here (widening a small-but-constant batch
        # would only force a needless retrace); an irregular batch
        # falls through to the generic widening below
        step = int(tape.ts[1]) - int(tape.ts[0])
        if 0 <= step <= (1 << 30) and bool(
            np.all(
                tape.ts[1:total] - tape.ts[: total - 1] == step
            )
        ):
            ts_base = np.asarray([tape.ts[0], step], dtype=np.int32)
            ts_arr = np.zeros(0, dtype=np.int8)
            sticky_kinds["__ts__"] = "d0"
            return _finish_wire(
                spec, tape, total, cols, kinds, epoch_i32,
                "d0", ts_base, ts_arr,
            ), prov
    if ts_kind != "i32" and total:
        # valid-region deltas (the padding repeats the last stamp: 0)
        vd = np.diff(tape.ts[:total].astype(np.int64))
        dmax = int(vd.max()) if len(vd) else 0
        dmin = int(vd.min()) if len(vd) else 0
        # d0 needs EVIDENCE of a regular cadence: a small batch is
        # trivially "constant" and would degrade (retrace) on the next
        # irregular one — below the threshold the saving is noise anyway
        if dmin == dmax and 0 <= dmin <= (1 << 30) and total >= 4096:
            want = "d0"
        elif 0 <= dmin and dmax <= 127:
            want = "d8"
        elif 0 <= dmin and dmax <= 32767:
            want = "d16"
        else:
            want = "i32"
        if ts_kind in _TS_KINDS and want in _TS_KINDS:
            want = _TS_KINDS[
                max(_TS_KINDS.index(want), _TS_KINDS.index(ts_kind))
            ]
        ts_kind = want
        if ts_kind == "d0":
            step = int(vd[0]) if len(vd) else 0
            ts_base = np.asarray([tape.ts[0], step], dtype=np.int32)
            ts_arr = np.zeros(0, dtype=np.int8)
        elif ts_kind != "i32":
            ts_base = np.asarray([tape.ts[0]], dtype=np.int32)
            ts_arr = np.zeros(
                len(tape.ts), np.int8 if ts_kind == "d8" else np.int16
            )
            ts_arr[1:total] = vd
    else:
        ts_kind = "i32"
    sticky_kinds["__ts__"] = ts_kind
    return _finish_wire(
        spec, tape, total, cols, kinds, epoch_i32, ts_kind, ts_base,
        ts_arr,
    ), prov


def _finish_wire(
    spec, tape, total, cols, kinds, epoch_i32, ts_kind, ts_base, ts_arr
) -> WireTape:
    single = len(spec.stream_codes) == 1
    stream_const = next(iter(spec.stream_codes.values())) if single else -1
    narrow_stream_ok = max(spec.stream_codes.values(), default=0) <= 127
    return WireTape(
        ts=ts_arr,
        n_valid=np.asarray([total], dtype=np.int32),
        stream=(
            None
            if single
            else tape.stream.astype(np.int8)
            if narrow_stream_ok
            else tape.stream
        ),
        cols=cols,
        kinds=tuple(kinds),
        stream_const=stream_const,
        epoch_i32=epoch_i32,
        ts_kind=ts_kind,
        ts_base=ts_base,
        cap=tape.capacity,
        time_off=tape.time_off,
    )


def _merged_stream_values(
    batches: Sequence[EventBatch],
    stream_id: str,
    field: str,
    total: int,
    order,
    identity: bool,
    dtype=None,
):
    """One (stream, field)'s values in merged tape order, or None when no
    batch carries the stream. THE single implementation of the
    batches->merged-order scatter (device columns and host-predicate
    inputs both go through it). Native host dtype unless ``dtype`` is
    given. ``total`` counts the batches' rows; ``order`` may take some
    of them, or one twice (a run of ``TapeRows.order``). Single-batch
    results may alias the batch's column — callers must copy before
    retaining."""
    if len(batches) == 1:
        b = batches[0]
        if b.stream_id != stream_id:
            return None
        col = b.columns[field]
        if not identity:
            col = col[order]
        return col if dtype is None else col.astype(dtype, copy=False)
    merged = None
    offset = 0
    for b in batches:
        n = len(b)
        if b.stream_id == stream_id and n:
            if merged is None:
                dt = dtype if dtype is not None else b.columns[field].dtype
                merged = np.zeros(total, dtype=dt)
            merged[offset : offset + n] = b.columns[field]
        offset += n
    if merged is None:
        return None
    return merged if identity else merged[order]


def _check_i32_span(key: str, vals: np.ndarray, origin: int, why: str):
    """A time attribute's first and last value (the stream is in order)
    have to fit int32 counted from ``origin``."""
    lo, hi = sorted((int(vals[0]), int(vals[-1])))
    if lo < -(1 << 31) or hi >= 1 << 31:
        raise ValueError(
            f"time attribute {key!r} (value "
            f"{origin + (lo if lo < -(1 << 31) else hi)}, clock origin "
            f"{origin}) {why}"
        )


def _intern_groups(spec, merged, new, cols, stream, total, known=None,
                   ts=None):
    """Group keys -> dense codes (``spec.encoded``), added to ``cols``.
    ``merged(stream_id, field, dtype)`` reads a host column in the
    tape's order (``_merged_stream_values``), ``new`` makes a leaf.
    ``known`` (out_key -> the codes an earlier build of these batches
    interned) are taken as they are: a table whose slots expire hands
    out other slots the second time. ``ts``: the tape's own timestamps,
    a tick column under the name ``@ts`` (a session window that reads
    the event's timestamp; a purged partition's per-key window)."""
    if not spec.encoded:
        return
    view = {k: v[:total] for k, v in cols.items()}
    if ts is not None:
        view["@ts"] = ts[:total]

    def selected(stream_code, select_fn):
        select = stream[:total] == stream_code
        if select_fn is not None:
            select = select & np.asarray(select_fn(view))
        return select

    def key_column(k):
        col = view.get(k)
        if col is None:
            # the raw column was pruned off the wire (group values
            # travel as codes); intern from the host batches
            sid_k, fld_k = k.split(".", 1)
            col = merged(
                sid_k, fld_k,
                spec.column_types[k].device_dtype
                if k in spec.column_types
                else None,
            )
            if col is None:
                col = np.zeros(total, dtype=np.int64)
        return col

    for enc in spec.encoded:
        if known and enc.out_key in known:
            codes = np.asarray(known[enc.out_key][:total], dtype=np.int32)
        elif enc.sources:
            codes = enc.encoder.intern_sources(
                [
                    (
                        key_column(src.in_key),
                        selected(src.stream_code, src.select_fn),
                        view[src.tick_key] if src.tick_key else None,
                        src.counter,
                    )
                    for src in enc.sources
                ],
                enc.tick_ms,
            )
        else:
            tick_col = view[enc.tick_key] if enc.tick_key else None
            codes = enc.encoder.intern_rows(
                [key_column(k) for k in enc.in_keys],
                selected(enc.stream_code, enc.select_fn),
                tick_col, enc.tick_ms,
            )
        if not enc.materialize:
            continue  # interning side effect only
        col = new(enc.out_key, np.int32)
        col[:total] = codes
        cols[enc.out_key] = col


def _new_leaf(shape, dtype, fill=0) -> np.ndarray:
    if fill:
        return np.full(shape, fill, dtype=dtype)
    return np.zeros(shape, dtype=dtype)


class _LeafRows:
    """The leaves of several tapes, ``[tapes, cap]`` each, handed out a
    row at a time (``leaves`` by name: ``ts``, ``stream``, ``valid`` and
    the columns' keys, which all hold a ``.`` or an ``@``): tape ``row``
    is filled in place, and what no tape writes of a zeroed leaf stays
    the zero page it was mapped as."""

    def __init__(self, tapes: int, cap: int) -> None:
        self.shape = (tapes, cap)
        self.leaves: Dict[str, np.ndarray] = {}
        self.row = 0

    def __call__(self, name, dtype, fill=0) -> np.ndarray:
        leaf = self.leaves.get(name)
        if leaf is None:
            leaf = self.leaves[name] = _new_leaf(self.shape, dtype, fill)
        return leaf[self.row]


def build_tape(
    spec: TapeSpec,
    batches: Sequence[EventBatch],
    epoch_ms: int,
    capacity: int | None = None,
    want_prov: bool = True,
    intern_span=None,
    codes=None,
    rows: Optional[TapeRows] = None,
) -> Tuple[Tape, np.ndarray]:
    """Merge per-stream batches into one padded, ts-sorted host tape.

    Returns (tape, order) where order[i] = (batch_idx, row_idx) provenance of
    merged position i (sinks use it to reach host-only payloads).
    ``want_prov=False`` returns None in its place (two full-width array
    fills skipped — for callers that never consult it).
    ``intern_span`` (a context-manager factory) is entered around the
    group interning: the executor's nested ``group_intern`` span.
    ``codes``: group codes that an earlier build of the same batches
    interned (``_intern_groups``), for a rebuild.
    ``rows`` (the mesh's): several tapes of ``capacity`` each, every one
    of its own run of the batches' rows (``TapeRows``). Each leaf comes
    back ``[tapes, capacity]``, row ``r`` filled in place by the rules
    that fill one tape (so run after run meets the encoders as tape
    after tape would), and the provenance in the runs' order, end to
    end. No batch is copied and no tape stacked on the way.
    Arrays are numpy; the jitted step's donate/commit moves them to device.
    """
    total = sum(len(b) for b in batches)
    widest = total if rows is None else int(rows.counts.max(initial=0))
    cap = capacity if capacity is not None else bucket_size(widest)
    if widest > cap:
        raise ValueError(f"{widest} events exceed tape capacity {cap}")

    ts_all = np.empty(total, dtype=np.int64)
    stream_all = np.empty(total, dtype=np.int32)
    prov = (
        np.empty((total, 2), dtype=np.int64) if want_prov else None
    )
    offset = 0
    for bi, b in enumerate(batches):
        n = len(b)
        if b.stream_id not in spec.stream_codes:
            raise KeyError(f"stream {b.stream_id!r} not in tape spec")
        ts_all[offset : offset + n] = b.timestamps
        stream_all[offset : offset + n] = spec.stream_codes[b.stream_id]
        if prov is not None:
            prov[offset : offset + n, 0] = bi
            prov[offset : offset + n, 1] = np.arange(n)
        offset += n

    def fill(order, identity, new):
        return _fill_tape(
            spec, batches, epoch_ms, ts_all, stream_all, order, identity,
            cap, new, intern_span, codes,
        )

    if rows is not None:
        cuts = rows.offsets.tolist()
        new = _LeafRows(len(cuts) - 1, cap)
        for r in range(len(cuts) - 1):
            new.row = r
            one = fill(rows.order[cuts[r] : cuts[r + 1]], False, new)
        leaves = new.leaves
        tape = Tape(
            leaves["ts"], leaves["stream"], leaves["valid"],
            {k: leaves[k] for k in one.cols}, one.time_off,
        )
        return tape, prov if prov is None else prov[rows.order]

    # per-stream batches arrive time-sorted (the reorder buffer sorts on
    # release), so a single-batch cycle — and any multi-batch cycle whose
    # concatenation happens to interleave in order — needs no argsort at
    # all; the O(n) sortedness check replaces the O(n log n) stable sort
    # and, more importantly, all the gather copies behind it
    identity = total == 0 or bool(np.all(ts_all[1:] >= ts_all[:-1]))
    order = None
    if not identity:
        order = np.argsort(ts_all, kind="stable")
        if prov is not None:
            prov = prov[order]
    return fill(
        order, identity,
        lambda name, dtype, fill=0: _new_leaf(cap, dtype, fill),
    ), prov


def _fill_tape(spec, batches, epoch_ms, ts_all, stream_all, order, identity,
               cap, new, intern_span, codes) -> Tape:
    """One tape's leaves (``new(name, dtype, fill)`` makes each, ``cap``
    long) from the rows ``order`` takes of the batches laid end to end
    (``identity``: all of them, as they lie; ``ts_all`` and
    ``stream_all`` are their stamps and stream codes)."""
    if identity:
        ts_sorted = ts_all
        stream_sorted = stream_all
    else:
        ts_sorted = ts_all[order]
        stream_sorted = stream_all[order]
    total = len(ts_sorted)

    def merged(stream_id, field, dtype=None):
        return _merged_stream_values(
            batches, stream_id, field, len(ts_all), order, identity, dtype
        )

    ts = new("ts", np.int32)
    ts[:total] = (ts_sorted - epoch_ms).astype(np.int32)
    # padding gets the max timestamp so time-window logic never treats
    # padding as "newest event"
    if total and total < cap:
        ts[total:] = ts[total - 1]
    stream = new("stream", np.int32, -1)
    stream[:total] = stream_sorted
    valid = new("valid", np.bool_)
    valid[:total] = True

    cols: Dict[str, np.ndarray] = {}
    for key in spec.built_columns():
        stream_id, field = key.split(".", 1)
        dtype = spec.column_types[key].device_dtype
        col = new(key, dtype)
        if key in spec.time_columns:
            # read as a value too: the raw long, which has to fit the
            # device's int32 (the window's read does not use it)
            vals = merged(stream_id, field, np.int64)
            if vals is not None and total:
                _check_i32_span(
                    key, vals, 0,
                    "is read as a value (a projection or a filter) and "
                    "does not fit the device's int32; only a window's "
                    "read of it as time rides the job's clock",
                )
        else:
            vals = merged(stream_id, field, dtype)
        if vals is not None:
            col[:total] = vals
        cols[key] = col
    time_off = 0
    if spec.time_columns:
        origin = time_origin(epoch_ms)
        time_off = epoch_ms - origin
    for key in spec.time_columns:
        # the host's int64 value, rebased: never cut to 32 bits
        stream_id, field = key.split(".", 1)
        col = new(time_key(key), np.int32)
        vals = merged(stream_id, field, np.int64)
        if vals is not None and total:
            vals = vals - origin
            if len(spec.stream_codes) > 1:
                # the rows of the other streams hold no value
                mine = stream[:total] == spec.stream_codes[stream_id]
                vals = np.where(
                    mine, vals, vals[mine][0] if mine.any() else 0
                )
            _check_i32_span(
                key, vals, origin,
                "is more than 2**31 ms from the job's clock: a window's "
                "time attribute has to be on the clock of the events' "
                "timestamps",
            )
            col[:total] = vals
        cols[time_key(key)] = col

    with (intern_span or contextlib.nullcontext)():
        _intern_groups(spec, merged, new, cols, stream, total, codes, ts)
    # wire predicate pushdown: evaluate each host predicate over the
    # merged-order RAW host columns (f64 where the schema says DOUBLE)
    # and add the result as a bool pseudo-column — it ships bit-packed,
    # replacing the raw predicate columns on the wire entirely
    if spec.host_preds:
        henv: Dict[str, np.ndarray] = {}
        ref_keys = {k for hp in spec.host_preds for k in hp.refs}
        for key in ref_keys:
            if key == "@ts":  # merged-order absolute timestamps
                henv[key] = ts_sorted[:total]
                continue
            stream_id, fname = key.split(".", 1)
            vals = merged(stream_id, fname)
            henv[key] = (
                vals
                if vals is not None
                else np.zeros(total, dtype=np.int64)
            )
        for hp in spec.host_preds:
            res = np.broadcast_to(
                np.asarray(hp.fn(henv), dtype=hp.dtype), (total,)
            )
            col = new(hp.out_key, hp.dtype)
            col[:total] = res
            cols[hp.out_key] = col

    return Tape(ts, stream, valid, cols, time_off)
