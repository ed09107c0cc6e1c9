"""Output schemas: device buffers -> typed host records.

The role of StreamOutputHandler + SiddhiTypeFactory in the reference
(operator/StreamOutputHandler.java:62-92, utils/SiddhiTypeFactory.java:114-139)
— except output types are inferred statically from the compiled expressions,
not by spinning up a throwaway engine (SiddhiTypeFactory.java:64-112).

Two device emission layouts exist:

* ``aligned``: one potential emission per tape position, gated by a mask
  (stateless select/filter queries, per-event window outputs);
* ``buffered``: a fixed-capacity match buffer + count (pattern matches,
  batch-window flushes).

Two host decode products exist for each layout:

* per-row ``decode_*`` -> ``[(ts, row_tuple), ...]`` — the historical
  path, still the default and the compatibility oracle;
* columnar ``decode_*_columns`` -> :class:`ColumnBatch` — the sink fast
  lane: typed numpy column arrays in emission order, zero per-row Python
  tuples (string decode is one ``np.take`` over the table's values
  array). ``tests/test_output_columnar.py`` pins the two paths to
  identical data.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..schema.strings import StringTable
from ..schema.types import AttributeType


@dataclass
class ColumnBatch:
    """One columnar emission batch: relative timestamps (int64, already
    in emission order) plus one typed numpy array per output field.
    The unit the columnar sink fast lane delivers — sinks receive
    ``(abs_ts_array, cols)`` without any row tuples materializing."""

    ts: np.ndarray  # int64 rel-ms timestamps, emission order
    cols: Dict[str, np.ndarray]  # field name -> decoded column array

    def __len__(self) -> int:
        return int(self.ts.shape[0])

    def take(self, idx) -> "ColumnBatch":
        idx = np.asarray(idx)
        return ColumnBatch(
            self.ts[idx], {k: v[idx] for k, v in self.cols.items()}
        )

    @staticmethod
    def concat(parts: Sequence["ColumnBatch"]) -> "ColumnBatch":
        if len(parts) == 1:
            return parts[0]
        return ColumnBatch(
            np.concatenate([p.ts for p in parts]),
            {
                k: np.concatenate([p.cols[k] for p in parts])
                for k in parts[0].cols
            },
        )

    @staticmethod
    def merge_by_ts(parts: Sequence["ColumnBatch"]) -> "ColumnBatch":
        """Merge batches that are each in timestamp order into one that
        is (the sharded drain's cross-shard merge): one concat, one
        stable argsort, one take. Equal timestamps keep ``parts``'
        order — exactly ``heapq.merge(*rows, key=ts)``'s, the row
        lane's merge, without a Python comparison per row."""
        merged = ColumnBatch.concat(parts)
        if len(parts) > 1:
            merged = merged.take(np.argsort(merged.ts, kind="stable"))
        return merged

    def rows(self) -> List[Tuple[int, Tuple[Any, ...]]]:
        """Materialize ``(rel_ts, row_tuple)`` pairs — the per-row
        compatibility view (fallback delivery to row sinks attached
        alongside columnar ones, and the equivalence oracle)."""
        ts_list = self.ts.tolist()
        col_lists = [v.tolist() for v in self.cols.values()]
        rows = zip(*col_lists) if col_lists else ((),) * len(ts_list)
        return list(zip(ts_list, map(tuple, rows)))


@dataclass(frozen=True)
class OutputField:
    name: str
    atype: AttributeType
    table: Optional[StringTable] = None  # decode dictionary when encoded
    # a time on the job's clock (ms since the job's epoch, as a row's
    # stamp): the emission tail adds the epoch, consumers see epoch ms
    on_clock: bool = False

    def decode(self, v) -> Any:
        if self.table is not None:
            return self.table.value(int(v))
        if self.atype == AttributeType.BOOL:
            return bool(v)
        if self.atype in (AttributeType.INT, AttributeType.LONG):
            return int(v)
        if self.atype in (AttributeType.FLOAT, AttributeType.DOUBLE):
            return float(v)
        return v

    def decode_column(self, arr: np.ndarray) -> List[Any]:
        """Whole-column decode: one host array -> python values.

        ``ndarray.tolist()`` yields native python scalars in C; only the
        dictionary lookup for encoded strings stays a per-value loop.
        """
        if self.table is not None:
            return [self.table.value(v) for v in arr.tolist()]
        if self.atype == AttributeType.BOOL:
            return arr.astype(bool).tolist()
        if self.atype in (AttributeType.INT, AttributeType.LONG):
            return arr.astype(np.int64).tolist()
        if self.atype in (AttributeType.FLOAT, AttributeType.DOUBLE):
            return arr.astype(np.float64).tolist()
        return arr.tolist()

    def decode_column_np(self, arr: np.ndarray) -> np.ndarray:
        """Whole-column decode that STOPS at a typed numpy array (the
        columnar sink fast lane): no python lists, no per-value loop.
        Encoded strings decode via ONE ``np.take`` over the table's
        materialized values array; out-of-range codes decode None,
        matching ``StringTable.value``."""
        if self.table is not None:
            vals = self.table.values_array()
            codes = np.asarray(arr).astype(np.int64, copy=False)
            if vals.size == 0:
                return np.full(codes.shape, None, dtype=object)
            ok = (codes >= 0) & (codes < vals.size)
            out = vals[np.where(ok, codes, 0)]  # fancy index: a copy
            if not bool(ok.all()):
                out[~ok] = None
            return out
        if self.atype == AttributeType.BOOL:
            return np.asarray(arr).astype(bool)
        if self.atype in (AttributeType.INT, AttributeType.LONG):
            return np.asarray(arr).astype(np.int64)
        if self.atype in (AttributeType.FLOAT, AttributeType.DOUBLE):
            return np.asarray(arr).astype(np.float64)
        return np.asarray(arr)


@dataclass
class OutputSchema:
    stream_id: str
    fields: Tuple[OutputField, ...]

    @property
    def field_names(self) -> List[str]:
        return [f.name for f in self.fields]

    def decode_aligned(
        self, mask: np.ndarray, ts: np.ndarray, cols: Sequence[np.ndarray]
    ) -> List[Tuple[int, Tuple[Any, ...]]]:
        """(ts_ms, row) per emitted position, in tape order.

        One device->host transfer per column (the naive per-row
        ``np.asarray(c)[i]`` costs a full dispatch round trip per value,
        catastrophic for the match-heavy benchmarks).
        """
        idx = np.nonzero(np.asarray(mask))[0]
        if idx.size == 0:
            return []
        ts_list = np.asarray(ts)[idx].astype(np.int64).tolist()
        col_lists = [
            f.decode_column(np.asarray(c)[idx])
            for f, c in zip(self.fields, cols)
        ]
        rows = zip(*col_lists) if col_lists else ((),) * idx.size
        return list(zip(ts_list, map(tuple, rows)))

    def decode_aligned_columns(
        self, mask: np.ndarray, ts: np.ndarray, cols: Sequence[np.ndarray]
    ) -> ColumnBatch:
        """Columnar twin of :meth:`decode_aligned` (tape order kept)."""
        idx = np.nonzero(np.asarray(mask))[0]
        ts_out = np.asarray(ts)[idx].astype(np.int64)
        return ColumnBatch(
            ts_out,
            {
                f.name: f.decode_column_np(np.asarray(c)[idx])
                for f, c in zip(self.fields, cols)
            },
        )

    def decode_packed_block(
        self, n: int, block: np.ndarray, data_row: int = 1
    ) -> List[Tuple[int, Tuple[Any, ...]]]:
        """Decode the accumulator's packed int32 layout: row 0 is the
        timestamp, rows ``data_row..`` are one bitcast row per field."""
        cols = []
        for j, f in enumerate(self.fields):
            raw = block[data_row + j, :n]
            if np.dtype(f.atype.device_dtype) == np.dtype(np.float32):
                raw = raw.view(np.float32)
            cols.append(raw)
        return self.decode_buffered(n, block[0, :n], cols)

    def decode_buffered(
        self, count: int, ts: np.ndarray, cols: Sequence[np.ndarray]
    ) -> List[Tuple[int, Tuple[Any, ...]]]:
        n = int(count)
        if n == 0:
            return []
        ts_arr = np.asarray(ts)[:n]
        # buffers are compacted on device in slot order, not time order;
        # restore by-timestamp emission order here (n is small)
        order = emission_order(ts_arr, n)
        ts_list = ts_arr[order].astype(np.int64).tolist()
        col_lists = [
            f.decode_column(np.asarray(c)[:n][order])
            for f, c in zip(self.fields, cols)
        ]
        rows = zip(*col_lists) if col_lists else ((),) * n
        return list(zip(ts_list, map(tuple, rows)))

    def decode_packed_columns(
        self, n: int, block: np.ndarray, data_row: int = 1
    ) -> ColumnBatch:
        """Columnar twin of :meth:`decode_packed_block`."""
        cols = []
        for j, f in enumerate(self.fields):
            raw = block[data_row + j, :n]
            if np.dtype(f.atype.device_dtype) == np.dtype(np.float32):
                raw = raw.view(np.float32)
            cols.append(raw)
        return self.decode_columns(n, block[0, :n], cols)

    def decode_columns(
        self, count: int, ts: np.ndarray, cols: Sequence[np.ndarray]
    ) -> ColumnBatch:
        """Columnar twin of :meth:`decode_buffered`: the same
        ``emission_order`` permutation, but the product is typed numpy
        column arrays — zero per-row tuples. String-table lookups are
        one vectorized ``np.take`` per encoded field."""
        n = int(count)
        if n == 0:
            return ColumnBatch(
                np.empty(0, np.int64),
                {f.name: np.empty(0, object) for f in self.fields},
            )
        ts_arr = np.asarray(ts)[:n]
        order = emission_order(ts_arr, n)
        return ColumnBatch(
            ts_arr[order].astype(np.int64),
            {
                f.name: f.decode_column_np(np.asarray(c)[:n][order])
                for f, c in zip(self.fields, cols)
            },
        )


def emission_order(ts, n: int):
    """THE permutation buffered/packed decode applies to emitted rows
    (stable by-timestamp sort). Artifacts that ship side-channel rows
    alongside the packed block (slot-NFA mbits, join missing-side
    markers) MUST reorder them with this same helper, or the side rows
    desync from their data rows."""
    return np.argsort(np.asarray(ts)[:n], kind="stable")
