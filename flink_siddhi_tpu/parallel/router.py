"""Host-side event routing into shards.

Re-expresses the reference's routing data plane (AddRouteOperator.java:53-98 +
DynamicPartitioner.java:43-60 + HashPartitioner.java:22-27) as vectorized
columnar routing:

* ``groupby`` streams: a 64-bit mix of the group-key columns, modulo shard
  count (the reference sums Java hashCodes of the group-by fields,
  AddRouteOperator.java:79-92 — same contract, better mixing);
* ``shuffle`` streams: round-robin (reference: random channel for
  partitionKey −1, DynamicPartitioner.java:53-55 — round-robin keeps replay
  deterministic);
* ``broadcast`` streams (pattern inputs, non-equi join sides): pinned to one
  owner shard so the single NFA/join instance sees every event exactly once
  — stronger than the reference, whose random channels make pattern matches
  subtask-local. True fan-out broadcast (DynamicPartitioner.java:46-52) is
  reserved for control events, which the host control plane applies to every
  shard's state identically.

Routing preserves intra-shard timestamp order: inputs arrive time-sorted and
selection indices are ascending.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from ..query.planner import StreamPartition
from ..runtime.tape import TapeRows
from ..schema.batch import EventBatch

_FNV_OFFSET = np.uint64(1469598103934665603)
_FNV_PRIME = np.uint64(1099511628211)


def hash_columns(cols: Sequence[np.ndarray], n: int) -> np.ndarray:
    """Vectorized FNV-1a-style mix over the key columns -> uint64[n].
    Mixed in place: a batch's worth of words is past what the allocator
    keeps mapped, so every temporary of that size is paged in anew."""
    h = np.full(n, _FNV_OFFSET, dtype=np.uint64)
    shifted = np.empty(n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for c in cols:
            if c.dtype.kind == "f":
                # normalize -0.0 -> +0.0: group interning uses value
                # equality (0.0 == -0.0), so both must land on one shard
                cf = np.ascontiguousarray(c, dtype=np.float64)
                cf = cf + 0.0
                v = cf.view(np.uint64)
            elif c.dtype.kind == "b":
                v = c.astype(np.uint64)
            else:
                v = np.ascontiguousarray(c, dtype=np.int64).view(np.uint64)
            np.bitwise_xor(h, v, out=h)
            np.multiply(h, _FNV_PRIME, out=h)
            np.right_shift(h, np.uint64(33), out=shifted)
            np.bitwise_xor(h, shifted, out=h)
    return h


class Router:
    """Routes per-stream EventBatches into ``n_shards`` shard-local lists."""

    def __init__(
        self,
        n_shards: int,
        partitions: Dict[str, StreamPartition],
        default: str = "shuffle",
    ) -> None:
        self.n_shards = n_shards
        self.partitions = dict(partitions)
        self.default = StreamPartition(kind=default)
        self._rr: Dict[str, int] = {}  # per-stream round-robin cursor
        # observability: cumulative events routed to each shard (read
        # into the job's telemetry gauges — skew shows up here first)
        self.routed = np.zeros(n_shards, dtype=np.int64)

    def partition_of(self, stream_id: str) -> StreamPartition:
        return self.partitions.get(stream_id, self.default)

    def route(self, batch: EventBatch) -> List[Optional[EventBatch]]:
        """Split one time-sorted batch into per-shard batches (None = no
        events for that shard)."""
        n = len(batch)
        S = self.n_shards
        if S == 1:
            return [batch]
        part = self.partition_of(batch.stream_id)
        if part.kind == "broadcast":
            # single-owner pinning: the whole stream to shard 0
            return [batch] + [None] * (S - 1)
        if part.kind == "replicate":
            # true fan-out (DynamicPartitioner.java:46-52): every shard
            # sees every event — the replicated side of a non-equi
            # time-window join keeps a full window copy per shard
            return [batch] * S
        if part.kind == "segment":
            # standalone split (route_all coordinates boundaries across
            # streams; a single stream splits on its own quantiles)
            if not n:
                return [None] * S
            bounds = self._segment_bounds([batch.timestamps])
            return self._split_segments(batch, bounds)
        assign = self._keyed_shards(batch, part)
        out: List[Optional[EventBatch]] = []
        for s in range(S):
            idx = np.nonzero(assign == s)[0]
            out.append(batch.take(idx) if len(idx) else None)
        return out

    def _keyed_shards(
        self, batch: EventBatch, part: StreamPartition
    ) -> np.ndarray:
        """The shard of each row of a ``groupby`` stream (its key's
        hash) or a ``shuffle`` stream (round-robin from the stream's
        cursor, which moves on)."""
        n = len(batch)
        S = self.n_shards
        if part.kind == "groupby" and part.keys:
            h = hash_columns([batch.columns[k] for k in part.keys], n)
            if S & (S - 1) == 0:
                # a power of two: the remainder is the low bits, at a
                # tenth of what the division of 64-bit words costs
                return np.bitwise_and(h, np.uint64(S - 1), out=h)
            return np.remainder(h, np.uint64(S), out=h)
        start = self._rr.get(batch.stream_id, 0)
        self._rr[batch.stream_id] = int((start + n) % S)
        return (start + np.arange(n, dtype=np.int64)) % S

    def route_all(
        self, batches: Sequence[EventBatch]
    ) -> List[List[EventBatch]]:
        """Route a set of per-stream batches -> per-shard batch lists.

        ``segment`` streams split on SHARED time boundaries (equal-count
        quantiles of the union of their timestamps) so segment s of every
        involved stream covers the same time slice — the contract the
        segment-parallel chain matcher's shard-to-shard handoff needs."""
        shards: List[List[EventBatch]] = [[] for _ in range(self.n_shards)]
        bounds = self._shared_bounds(batches)
        for b in batches:
            if (
                bounds is not None
                and self.partition_of(b.stream_id).kind == "segment"
            ):
                for s, piece in enumerate(
                    self._split_segments(b, bounds)
                ):
                    if piece is not None:
                        shards[s].append(piece)
                continue
            for s, piece in enumerate(self.route(b)):
                if piece is not None and len(piece):
                    shards[s].append(piece)
        for s, pieces in enumerate(shards):
            self.routed[s] += sum(len(p) for p in pieces)
        return shards

    def select(self, batches: Sequence[EventBatch]) -> TapeRows:
        """``route_all`` without the copies: which rows of ``batches``
        (numbered end to end) each shard receives, in the order
        ``build_tape`` puts the shard's pieces — by timestamp, ties by
        arrival. One stable sort of the per-row shard number; no column
        is gathered. Counts, cursors and ``segment`` boundaries are
        ``route_all``'s."""
        S = self.n_shards
        small = np.min_scalar_type(S - 1)  # a byte a row: a radix sort
        bounds = self._shared_bounds(batches)
        shard, fanned = [], []
        for b in batches:
            n = len(b)
            part = self.partition_of(b.stream_id)
            fanned.append(S > 1 and part.kind == "replicate")
            if S == 1 or part.kind == "broadcast":
                to = np.zeros(n, dtype=small)
            elif part.kind == "replicate":
                to = np.repeat(np.arange(S, dtype=small), n)
            elif part.kind == "segment":
                # left-closed: an event equal to a boundary goes right
                to = np.searchsorted(bounds, b.timestamps, side="right")
            else:
                to = self._keyed_shards(b, part)
            shard.append(to.astype(small, copy=False))

        def end_to_end(arrays):  # one batch: as it lies, no copy
            return arrays[0] if len(arrays) == 1 else np.concatenate(arrays)

        shard = end_to_end(shard)
        ts = end_to_end([b.timestamps for b in batches])
        # the rows as they lie, unless some go to every shard or the
        # batches' stamps interleave
        rows = None
        if any(fanned):
            starts = np.cumsum([0] + [len(b) for b in batches])
            rows = np.concatenate([
                np.tile(np.arange(at, at + len(b)), S if fan else 1)
                for b, at, fan in zip(batches, starts, fanned)
            ])
            ts = ts[rows]
        if not np.all(ts[1:] >= ts[:-1]):
            by_ts = np.argsort(ts, kind="stable")
            shard = shard[by_ts]
            rows = by_ts if rows is None else rows[by_ts]
        order = np.argsort(shard, kind="stable")
        offsets = np.searchsorted(shard[order], np.arange(S + 1))
        self.routed += np.diff(offsets)
        return TapeRows(order if rows is None else rows[order], offsets)

    def _shared_bounds(self, batches) -> Optional[np.ndarray]:
        """The boundary timestamps a cycle's ``segment`` streams share
        (None: no such stream, or one shard)."""
        seg = [
            b.timestamps
            for b in batches
            if self.partition_of(b.stream_id).kind == "segment"
        ]
        if not seg or self.n_shards == 1:
            return None
        return self._segment_bounds(seg)

    def _segment_bounds(self, ts_arrays: List[np.ndarray]) -> np.ndarray:
        """Equal-count quantile boundary timestamps over the union of the
        given (sorted within themselves) timestamp arrays."""
        all_ts = np.concatenate(ts_arrays)
        all_ts.sort(kind="stable")
        S = self.n_shards
        return all_ts[
            [min(len(all_ts) - 1, (len(all_ts) * k) // S)
             for k in range(1, S)]
        ]

    def _split_segments(
        self, batch: EventBatch, bounds: np.ndarray
    ) -> List[Optional[EventBatch]]:
        """Cut one time-sorted batch at the boundary timestamps
        (left-closed: an event equal to a boundary goes right)."""
        cuts = np.searchsorted(batch.timestamps, bounds, side="left")
        out: List[Optional[EventBatch]] = []
        prev = 0
        for cut in list(cuts) + [len(batch)]:
            out.append(batch.slice(prev, cut) if cut > prev else None)
            prev = cut
        return out

    # -- checkpoint support -------------------------------------------------
    def state_dict(self) -> dict:
        return {"rr": dict(self._rr)}

    def load_state_dict(self, d: dict) -> None:
        self._rr = dict(d.get("rr", {}))
