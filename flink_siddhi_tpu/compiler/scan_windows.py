"""Per-event scan windows: ``#window.sort(N, attr)``,
``#window.unique(attr)`` and the two heavy-hitter sketches
(``#window.frequent``, ``#window.lossyFrequent``).

``#window.session`` is routed from here and is no scan: its fold is
vectorised over the micro-batch and its sessions close on the stream's
clock (``compiler/session_window.py``, ``docs/session_window.md``).

Sort and unique retain a DATA-DEPENDENT set (top-N by a key; the
latest event per key) whose per-event evolution is inherently
sequential, unlike the positional/time windows the vectorized paths
handle. They compile to one ``lax.scan`` over the micro-batch with a
fixed-size device buffer as carry — the TPU shape of siddhi-core's
SortWindowProcessor / UniqueWindowProcessor per-event loops. Aggregates
are recomputed from the buffer each step (N and the group-table bucket
are small); arriving events emit aligned rows like every other window.

Scan windows are correctness surface, not a benchmark path: per-event
scans pay per-step dispatch, so expect ~1M events/sec, not tens of
millions. Reference parity: siddhi-core 4.2.40 window surface
(reference pom.xml pins the engine; SiddhiExecutionPlanner.java:194-210
treats any window generically).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..query import ast
from ..query.lexer import SiddhiQLError
from ..schema.encoders import GroupEncoder
from ..schema.types import AttributeType
from .expr import ColumnEnv, ExprResolver, compile_expr
from .output import OutputField, OutputSchema
from .window import _Agg, _identity

_MIN_UNIQUE_CAPACITY = 128


def _bucket(n: int, minimum: int) -> int:
    b = minimum
    while b < max(n, 1):
        b *= 2
    return b


@dataclass
class ScanWindowArtifact:
    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    kind: str  # 'sort' | 'unique'
    # sort: buffer length + key fn + direction; unique: key code column
    sort_n: Optional[int]
    sort_key_fn: Optional[Callable]
    sort_desc: bool
    code_key: Optional[str]
    encoder: Optional[GroupEncoder]
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    proj_fns: List
    output_mode: str = "aligned"
    # 'partition with' (per-key window instances): sort buffers gain a
    # leading partition axis [P, C]; unique composite-encodes
    # (partition, attr) and masks aggregation to the arriving event's
    # partition — each key sees only its own window, siddhi-core's
    # per-partition processor instances (reference README.md:77-96
    # partition usage; SiddhiExecutionPlanner.java partition inference)
    part_key: Optional[str] = None
    part_encoder: Optional[GroupEncoder] = None

    def _cap(self) -> int:
        if self.kind == "sort":
            return self.sort_n
        return _bucket(
            len(self.encoder) if self.encoder else 1,
            _MIN_UNIQUE_CAPACITY,
        )

    def _pcap(self) -> int:
        return _bucket(
            len(self.part_encoder) if self.part_encoder else 1, 16
        )

    def _buf_shape(self):
        C = self._cap()
        return (self._pcap(), C) if self._partitioned_sort() else (C,)

    def _partitioned_sort(self) -> bool:
        return self.kind == "sort" and self.part_key is not None

    def cost_info(self) -> Dict:
        """Admission-cost descriptor (analysis/admit.py): sort keeps a
        fixed top-N buffer; unique keeps the last event per key in a
        bucketed table that grows with key cardinality."""
        info = {
            "name": self.name,
            "kind": "scan_window",
            "amplification": 1,
            "residency_ms": None,
        }
        if self.kind == "unique":
            info["grows_with"] = "keys"
        return info

    def init_state(self) -> Dict:
        shape = self._buf_shape()
        st = {
            "enabled": jnp.asarray(True),
            "valid": jnp.zeros(shape, bool),
        }
        if self.kind == "sort":
            st["key"] = jnp.zeros(shape, jnp.float32)
        elif self.part_key is not None:
            # partition code stored per unique-table slot (aggregation
            # masks to the arriving event's partition)
            st["pc"] = jnp.full(shape, -1, jnp.int32)
        for j, t in enumerate(self.arg_types):
            st[f"a{j}"] = jnp.zeros(shape, t.device_dtype)
        return st

    def grow_state(self, state: Dict) -> Dict:
        shape = self._buf_shape()
        if state["valid"].shape == shape:
            return state
        out = {"enabled": state["enabled"]}
        for k, v in state.items():
            if k == "enabled":
                continue
            fill = -1 if k == "pc" else 0
            pad = jnp.full(shape, fill, v.dtype)
            out[k] = pad.at[tuple(slice(0, s) for s in v.shape)].set(v)
        return out

    def _agg_rows(self, buf: Dict, valid, sel) -> Dict[str, jnp.ndarray]:
        """Aggregate slot values from the current buffer (one scalar per
        slot; reductions over the small carry buffer). ``valid`` is the
        membership mask to aggregate over (the arriving event's
        partition under 'partition with'); ``sel`` indexes value
        columns (a partition row index, or slice(None))."""
        cnt = valid.sum().astype(jnp.float32)
        out = {}
        for agg in self.aggs:
            if agg.kind == "count":
                out[agg.slot] = cnt.astype(agg.out_type.device_dtype)
                continue
            vals = buf[f"a{agg.arg_idx}"][sel]
            if agg.kind in ("sum", "avg"):
                s = jnp.where(valid, vals, 0).astype(jnp.float32).sum()
                r = s if agg.kind == "sum" else s / jnp.maximum(cnt, 1.0)
            elif agg.kind in ("min", "max"):
                ident = _identity(agg.kind, vals.dtype)
                masked = jnp.where(valid, vals, ident)
                r = masked.min() if agg.kind == "min" else masked.max()
            else:
                raise SiddhiQLError(
                    f"{agg.kind}() is not supported over "
                    f"#window.{self.kind}"
                )
            out[agg.slot] = jnp.asarray(r).astype(
                agg.out_type.device_dtype
            )
        return out

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        C = self._cap()
        arg_cols = [
            jnp.broadcast_to(jnp.asarray(fn(env)), (E,)).astype(
                t.device_dtype
            )
            for fn, t in zip(self.arg_fns, self.arg_types)
        ]
        part = (
            jnp.clip(
                env[self.part_key].astype(jnp.int32), 0, self._pcap() - 1
            )
            if self.part_key is not None
            else jnp.zeros(E, jnp.int32)
        )
        if self.kind == "sort":
            keys = jnp.broadcast_to(
                jnp.asarray(self.sort_key_fn(env)), (E,)
            ).astype(jnp.float32)
            if self.sort_desc:
                keys = -keys
            xs = (mask, part, keys, *arg_cols)
        else:
            codes = env[self.code_key].astype(jnp.int32)
            xs = (mask, part, codes, *arg_cols)

        buf0 = {k: v for k, v in state.items() if k != "enabled"}
        iota = jnp.arange(C, dtype=jnp.int32)
        psort = self._partitioned_sort()

        def body_sort(buf, x):
            active, p, key, *vals = x
            bvalid = buf["valid"][p] if psort else buf["valid"]
            bkeys = buf["key"][p] if psort else buf["key"]
            bkey = jnp.where(bvalid, bkeys, jnp.inf)
            pos = (bkey < key).sum().astype(jnp.int32)
            do = active & (pos < C)

            def ins(col, v):
                row = col[p] if psort else col
                shifted = jnp.where(
                    iota > pos, row[jnp.clip(iota - 1, 0)], row
                )
                new = jnp.where(
                    do, jnp.where(iota == pos, v, shifted), row
                )
                return col.at[p].set(new) if psort else new

            nb = {
                "valid": ins(buf["valid"], True),
                "key": ins(buf["key"], key),
            }
            for j, v in enumerate(vals):
                nb[f"a{j}"] = ins(buf[f"a{j}"], v)
            sel = p if psort else slice(None)
            return nb, self._agg_rows(nb, nb["valid"][sel], sel)

        def body_unique(buf, x):
            active, p, code, *vals = x
            c = jnp.clip(code, 0, C - 1)
            nb = {
                "valid": jnp.where(
                    active, buf["valid"].at[c].set(True), buf["valid"]
                )
            }
            if "pc" in buf:
                nb["pc"] = jnp.where(
                    active, buf["pc"].at[c].set(p), buf["pc"]
                )
            for j, v in enumerate(vals):
                col = buf[f"a{j}"]
                nb[f"a{j}"] = jnp.where(active, col.at[c].set(v), col)
            valid = nb["valid"]
            if "pc" in nb:  # partition-local membership
                valid = valid & (nb["pc"] == p)
            return nb, self._agg_rows(nb, valid, slice(None))

        body = body_sort if self.kind == "sort" else body_unique
        new_buf, slot_rows = lax.scan(body, buf0, xs)
        for slot, rows in slot_rows.items():
            env[slot] = rows
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(env)), (E,))
            for p in self.proj_fns
        )
        new_state = dict(new_buf)
        new_state["enabled"] = state["enabled"]
        return new_state, (mask, tape.ts, cols)


def compile_scan_window(
    q: ast.Query,
    name: str,
    window,
    resolver: ExprResolver,
    schemas,
    stream_codes,
    extensions,
    config,
    filter_fns,
    rewritten,
    collector,
    having_re,
    host_filters=None,
):
    kind, args = window
    inp = q.input
    part_attr = None
    if q.partition_with:
        part_attr = dict(q.partition_with).get(inp.stream_id)
        if part_attr is None:
            raise SiddhiQLError(
                f"stream {inp.stream_id!r} has no partition key"
            )
    if kind == "session":
        # vectorised, not a scan: compiler/session_window.py
        from .session_window import compile_session_window

        return compile_session_window(
            q, name, args, resolver, stream_codes[inp.stream_id],
            extensions, config, filter_fns, rewritten, collector,
            having_re, host_filters, part_attr=part_attr,
        )
    if kind in ("frequent", "lossyFrequent"):
        if part_attr is not None:
            raise SiddhiQLError(
                f"#window.{kind} inside 'partition with' is not "
                "supported yet"
            )
        return _compile_frequency_window(
            q, name, kind, args, resolver, schemas, stream_codes,
            extensions, filter_fns, rewritten, collector, having_re,
        )
    gb = tuple(ast.bare_group_key(g) for g in q.selector.group_by)
    if gb and (part_attr is None or gb != (part_attr,)):
        raise SiddhiQLError(
            f"group by over #window.{kind} is not supported yet"
        )
    if having_re is not None:
        raise SiddhiQLError(
            f"having over #window.{kind} is not supported yet"
        )
    for a in collector.aggs:
        if a.kind not in ("count", "sum", "avg", "min", "max"):
            raise SiddhiQLError(
                f"{a.kind}() is not supported over #window.{kind}"
            )

    sort_n = None
    sort_key_fn = None
    sort_desc = False
    code_key = None
    encoder = None
    encoded = ()
    if kind == "sort":
        if not args or not isinstance(args[0], ast.Literal):
            raise SiddhiQLError(
                "#window.sort needs (length, attribute[, 'asc'|'desc'])"
            )
        sort_n = int(args[0].value)
        if len(args) < 2:
            raise SiddhiQLError("#window.sort needs a sort attribute")
        ce = compile_expr(args[1], resolver, extensions)
        if not ce.atype.is_numeric:
            raise SiddhiQLError("#window.sort key must be numeric")
        sort_key_fn = ce.fn
        if len(args) > 2:
            if not (
                isinstance(args[2], ast.Literal)
                and args[2].value in ("asc", "desc")
            ):
                raise SiddhiQLError(
                    "#window.sort order must be 'asc' or 'desc'"
                )
            sort_desc = args[2].value == "desc"
    else:  # unique
        if len(args) != 1 or not isinstance(args[0], ast.Attr):
            raise SiddhiQLError(
                "#window.unique needs one key attribute"
            )
        from .window import _group_encoding

        r = resolver.resolve(args[0])
        rs = [r]
        if part_attr is not None:
            # per-partition uniqueness: composite (partition, attr)
            # codes — slot identity is partition-local
            rs = [resolver.resolve(ast.Attr(part_attr)), r]
        code_key, encoder, encoded = _group_encoding(
            name, rs, stream_codes[inp.stream_id], filter_fns
        )
    part_key, part_encoder, part_encoded = None, None, ()
    if part_attr is not None:
        from .window import _group_encoding

        pr = resolver.resolve(ast.Attr(part_attr))
        part_key, part_encoder, part_encoded = _group_encoding(
            name + "@part", [pr], stream_codes[inp.stream_id],
            filter_fns,
        )

    from .window import _SlotResolver

    slot_types = {a.slot: a.out_type for a in collector.aggs}
    slot_resolver = _SlotResolver(resolver, slot_types)
    proj_fns: List = []
    out_fields: List[OutputField] = []
    for item in rewritten:
        ce = compile_expr(item.expr, slot_resolver, extensions)
        proj_fns.append(ce.fn)
        out_fields.append(
            OutputField(item.output_name(), ce.atype, ce.table)
        )

    art = ScanWindowArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        stream_code=stream_codes[inp.stream_id],
        filter_fns=filter_fns,
        kind=kind,
        sort_n=sort_n,
        sort_key_fn=sort_key_fn,
        sort_desc=sort_desc,
        code_key=code_key,
        encoder=encoder,
        aggs=collector.aggs,
        arg_fns=collector.arg_fns,
        arg_types=collector.arg_types,
        proj_fns=proj_fns,
        part_key=part_key,
        part_encoder=part_encoder,
    )
    art.encoded_columns = tuple(encoded) + tuple(part_encoded)
    return art


def _compile_frequency_window(
    q, name, kind, args, resolver, schemas, stream_codes, extensions,
    filter_fns, rewritten, collector, having_re,
):
    inp = q.input
    if q.selector.group_by:
        raise SiddhiQLError(
            f"group by over #window.{kind} is not supported yet"
        )
    if having_re is not None:
        raise SiddhiQLError(
            f"having over #window.{kind} is not supported yet"
        )
    for a in collector.aggs:
        if a.kind not in ("count", "sum", "avg", "min", "max"):
            raise SiddhiQLError(
                f"{a.kind}() is not supported over #window.{kind}"
            )
    support = error = 0.0
    cap = 0
    rest: List[ast.Expr] = []
    if kind == "frequent":
        cap = int(args[0].value)
        if cap <= 0:
            raise SiddhiQLError("#window.frequent count must be > 0")
        rest = list(args[1:])
    else:
        support = float(args[0].value)
        rest = list(args[1:])
        # optional errorBound literal before the attribute list
        if rest and isinstance(rest[0], ast.Literal) and not isinstance(
            rest[0], ast.TimeLiteral
        ):
            error = float(rest[0].value)
            rest = rest[1:]
        else:
            error = support / 10.0  # siddhi's default: support/10
        if not (0.0 < error < support <= 1.0):
            raise SiddhiQLError(
                "#window.lossyFrequent needs 0 < errorBound < "
                "supportThreshold <= 1"
            )
        # fixed device table: 4/error slots comfortably exceeds lossy
        # counting's 1/error working-set bound between prunes
        cap = _bucket(int(np.ceil(4.0 / error)), 16)
    if not rest:
        # no attribute list: siddhi keys frequency on ALL attributes
        rest = [
            ast.Attr(n) for n in schemas[inp.stream_id].field_names
        ]
    for a in rest:
        if not isinstance(a, ast.Attr):
            raise SiddhiQLError(
                f"#window.{kind} key arguments must be attributes"
            )
    from .window import _group_encoding

    rs = [resolver.resolve(a) for a in rest]
    code_key, encoder, encoded = _group_encoding(
        name, rs, stream_codes[inp.stream_id], filter_fns
    )

    from .window import _SlotResolver

    slot_types = {a.slot: a.out_type for a in collector.aggs}
    slot_resolver = _SlotResolver(resolver, slot_types)
    proj_fns: List = []
    out_fields: List[OutputField] = []
    for item in rewritten:
        ce = compile_expr(item.expr, slot_resolver, extensions)
        proj_fns.append(ce.fn)
        out_fields.append(
            OutputField(item.output_name(), ce.atype, ce.table)
        )
    art = FrequencyWindowArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        stream_code=stream_codes[inp.stream_id],
        filter_fns=filter_fns,
        kind=kind,
        cap=cap,
        support=support,
        error=error,
        code_key=code_key,
        encoder=encoder,
        aggs=collector.aggs,
        arg_fns=collector.arg_fns,
        arg_types=collector.arg_types,
        proj_fns=proj_fns,
    )
    art.encoded_columns = encoded
    return art


@dataclass
class FrequencyWindowArtifact:
    """``#window.frequent(count[, attrs])`` and
    ``#window.lossyFrequent(support[, error][, attrs])``.

    siddhi-core's FrequentWindowProcessor is the Misra-Gries heavy-
    hitters sketch; LossyFrequentWindowProcessor is Manku-Motwani lossy
    counting (siddhi-core 4.2.x window namespace; the reference pins the
    engine via pom.xml:45-47). Both keep the LATEST event per tracked
    attribute value; the TPU shape is a fixed-slot device table advanced
    by one ``lax.scan`` over the micro-batch — the same fixed-capacity
    state discipline as the NFA pools.

    * frequent: admit = value tracked, or a free slot exists. A full
      table decrements every counter and evicts zeros (the arriving
      event itself is NOT admitted — Misra-Gries).
    * lossyFrequent: every arrival is tracked (f=1, delta=bucket-1 on
      insert); bucket boundaries (every ceil(1/error) events) evict
      entries with f + delta <= bucket. Emission requires the value's
      frequency f >= (support - error) * N. The device table is a
      fixed ``cap`` slots; if an insert finds no free slot the entry
      with the smallest f+delta is replaced (a bounded-memory
      approximation of the unbounded paper sketch, documented here).

    Emission: aligned rows for ADMITTED arriving events (frequent) /
    arrivals currently meeting the support threshold (lossyFrequent),
    aggregating over the tracked set."""

    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    kind: str  # 'frequent' | 'lossyFrequent'
    cap: int  # table slots (frequent: the count argument)
    support: float  # lossyFrequent support threshold
    error: float  # lossyFrequent error bound
    code_key: str
    encoder: GroupEncoder
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    proj_fns: List
    output_mode: str = "aligned"

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: fixed-slot heavy-hitter sketch —
        the canonical bounded-memory shape; one row per admitted
        arrival."""
        return {
            "name": self.name,
            "kind": "sketch_window",
            "amplification": 1,
            "residency_ms": None,
        }

    def init_state(self) -> Dict:
        C = self.cap
        st = {
            "enabled": jnp.asarray(True),
            "valid": jnp.zeros(C, bool),
            "code": jnp.full(C, -1, jnp.int32),
            "freq": jnp.zeros(C, jnp.int32),
            "seen": jnp.zeros((), jnp.int32),
        }
        if self.kind == "lossyFrequent":
            st["delta"] = jnp.zeros(C, jnp.int32)
        for j, t in enumerate(self.arg_types):
            st[f"a{j}"] = jnp.zeros(C, t.device_dtype)
        return st

    def _agg_rows(self, buf, member) -> Dict[str, jnp.ndarray]:
        cnt = member.sum().astype(jnp.float32)
        out = {}
        for agg in self.aggs:
            if agg.kind == "count":
                out[agg.slot] = cnt.astype(agg.out_type.device_dtype)
                continue
            vals = buf[f"a{agg.arg_idx}"]
            if agg.kind in ("sum", "avg"):
                s = jnp.where(member, vals, 0).astype(jnp.float32).sum()
                r = s if agg.kind == "sum" else s / jnp.maximum(cnt, 1.0)
            elif agg.kind in ("min", "max"):
                ident = _identity(agg.kind, vals.dtype)
                masked = jnp.where(member, vals, ident)
                r = masked.max() if agg.kind == "max" else masked.min()
            else:
                raise SiddhiQLError(
                    f"{agg.kind}() is not supported over "
                    f"#window.{self.kind}"
                )
            out[agg.slot] = jnp.asarray(r).astype(
                agg.out_type.device_dtype
            )
        return out

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        C = self.cap
        codes = env[self.code_key].astype(jnp.int32)
        arg_cols = [
            jnp.broadcast_to(jnp.asarray(fn(env)), (E,)).astype(
                t.device_dtype
            )
            for fn, t in zip(self.arg_fns, self.arg_types)
        ]
        buf0 = {
            k: v for k, v in state.items() if k != "enabled"
        }
        lossy = self.kind == "lossyFrequent"
        width = (
            max(int(np.ceil(1.0 / self.error)), 1) if lossy else 0
        )

        def body(buf, x):
            active, code, *vals = x
            eq = buf["valid"] & (buf["code"] == code)
            hit = eq.any()
            slot_hit = jnp.argmax(eq).astype(jnp.int32)
            free = ~buf["valid"]
            has_free = free.any()
            slot_free = jnp.argmax(free).astype(jnp.int32)
            nb = dict(buf)
            n = buf["seen"] + jnp.where(active, 1, 0)
            nb["seen"] = n
            if lossy:
                bucket = jnp.ceil(
                    n.astype(jnp.float32) / width
                ).astype(jnp.int32)
                # replacement victim when the fixed table is full: the
                # entry lossy counting would evict first (min f+delta)
                slot_victim = jnp.argmin(
                    jnp.where(
                        buf["valid"],
                        buf["freq"] + buf["delta"],
                        2 ** 31 - 1,
                    )
                ).astype(jnp.int32)
                slot = jnp.where(
                    hit, slot_hit,
                    jnp.where(has_free, slot_free, slot_victim),
                )
                admitted = active
                newf = jnp.where(hit, buf["freq"][slot] + 1, 1)
                nb["freq"] = jnp.where(
                    admitted, buf["freq"].at[slot].set(newf), buf["freq"]
                )
                nb["delta"] = jnp.where(
                    admitted & ~hit,
                    buf["delta"].at[slot].set(bucket - 1),
                    buf["delta"],
                )
                nb["valid"] = jnp.where(
                    admitted, buf["valid"].at[slot].set(True),
                    buf["valid"],
                )
                nb["code"] = jnp.where(
                    admitted, buf["code"].at[slot].set(code),
                    buf["code"],
                )
                for j, v in enumerate(vals):
                    nb[f"a{j}"] = jnp.where(
                        admitted, buf[f"a{j}"].at[slot].set(v),
                        buf[f"a{j}"],
                    )
                # bucket boundary: prune entries with f + delta <= b
                boundary = admitted & (n % width == 0)
                keep = nb["freq"] + nb["delta"] > bucket
                nb["valid"] = jnp.where(
                    boundary, nb["valid"] & keep, nb["valid"]
                )
                # emission gate: arriving value's f >= (s-e) * N
                thresh = (self.support - self.error) * n.astype(
                    jnp.float32
                )
                emit = (
                    admitted
                    & nb["valid"][slot]
                    & (nb["freq"][slot].astype(jnp.float32) >= thresh)
                )
                member = nb["valid"] & (
                    nb["freq"].astype(jnp.float32)
                    >= thresh
                )
            else:
                admitted = active & (hit | has_free)
                slot = jnp.where(hit, slot_hit, slot_free)
                newf = jnp.where(hit, buf["freq"][slot] + 1, 1)
                nb["freq"] = jnp.where(
                    admitted, buf["freq"].at[slot].set(newf),
                    # full table, unseen value: Misra-Gries decrement
                    jnp.where(
                        active,
                        jnp.maximum(buf["freq"] - 1, 0),
                        buf["freq"],
                    ),
                )
                nb["valid"] = jnp.where(
                    admitted,
                    buf["valid"].at[slot].set(True),
                    buf["valid"] & (nb["freq"] > 0),
                )
                nb["code"] = jnp.where(
                    admitted, buf["code"].at[slot].set(code), buf["code"]
                )
                for j, v in enumerate(vals):
                    nb[f"a{j}"] = jnp.where(
                        admitted, buf[f"a{j}"].at[slot].set(v),
                        buf[f"a{j}"],
                    )
                emit = admitted
                member = nb["valid"]
            return nb, (emit, self._agg_rows(nb, member))

        xs = (mask, codes, *arg_cols)
        new_buf, (emit, slot_rows) = lax.scan(body, buf0, xs)
        for slot, rows in slot_rows.items():
            env[slot] = rows
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(env)), (E,))
            for p in self.proj_fns
        )
        new_state = dict(new_buf)
        new_state["enabled"] = state["enabled"]
        return new_state, (mask & emit, tape.ts, cols)
