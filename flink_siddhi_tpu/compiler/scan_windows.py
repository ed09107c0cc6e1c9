"""Per-event scan windows: ``#window.sort(N, attr)`` and
``#window.unique(attr)``.

These two windows retain a DATA-DEPENDENT set (top-N by a key; the
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
        return _compile_session_window(
            q, name, args, resolver, stream_codes, extensions,
            filter_fns, rewritten, collector, having_re,
            part_attr=part_attr,
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
class SessionWindowArtifact:
    """``#window.session(gap[, key])``: per-key sessions that close when
    the gap elapses with no event for that key. One ``lax.scan`` over
    the batch with a [G] session table carry (siddhi-core's
    SessionWindowProcessor shape).

    Emission timing: a closed session emits when its key's NEXT event
    arrives past the gap (with ts = sessionEnd + gap) or at end of
    stream — siddhi's timer thread emits at gap expiry instead, so
    between those two points a closed-but-unemitted session is simply
    not yet visible here (same rows, later)."""

    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    gap_ms: int
    code_key: str
    encoder: GroupEncoder
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    proj_map: List  # per select item: ('key',) | ('agg', slot)
    output_mode: str = "packed"

    def _pack(self, n, emit_ts, code_col, slot_vals):
        """(1 + fields, width) int32 block: ts row + one row per select
        item (key codes as i32; float aggregates bitcast; integer
        aggregates rounded — a plain astype of the f32 accumulator)."""
        rows = [emit_ts.astype(jnp.int32)]
        for kind, f in zip(self.proj_map, self.output_schema.fields):
            if kind[0] == "key":
                rows.append(code_col.astype(jnp.int32))
            else:
                v = slot_vals[kind[1]]
                if jnp.issubdtype(
                    jnp.dtype(f.atype.device_dtype), jnp.floating
                ):
                    rows.append(
                        jax.lax.bitcast_convert_type(
                            v.astype(jnp.float32), jnp.int32
                        )
                    )
                else:
                    rows.append(jnp.round(v).astype(jnp.int32))
        return n, jnp.stack(rows)

    def _cap(self) -> int:
        return _bucket(
            len(self.encoder) if self.encoder else 1,
            _MIN_UNIQUE_CAPACITY,
        )

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: per-key session aggregates (no
        events retained); one closed-session row per closing event.
        The session table grows with key cardinality."""
        return {
            "name": self.name,
            "kind": "session_window",
            "amplification": 1,
            "residency_ms": None,
            "grows_with": "keys",
        }

    def init_state(self) -> Dict:
        G = self._cap()
        st = {
            "enabled": jnp.asarray(True),
            "open": jnp.zeros(G, bool),
            "last": jnp.zeros(G, jnp.int32),
            "cnt": jnp.zeros(G, jnp.int32),
        }
        for j, t in enumerate(self.arg_types):
            st[f"s{j}"] = jnp.zeros(G, jnp.float32)
            st[f"mn{j}"] = jnp.full(
                G, _identity("min", t.device_dtype), t.device_dtype
            )
            st[f"mx{j}"] = jnp.full(
                G, _identity("max", t.device_dtype), t.device_dtype
            )
        return st

    def grow_state(self, state: Dict) -> Dict:
        G = self._cap()
        if state["open"].shape[0] >= G:
            return state
        out = {"enabled": state["enabled"]}
        for k, v in state.items():
            if k == "enabled":
                continue
            pad_val = (
                _identity("min" if k.startswith("mn") else "max", v.dtype)
                if k.startswith(("mn", "mx"))
                else jnp.asarray(0, v.dtype)
            )
            old = v.shape[0]
            out[k] = jnp.concatenate(
                [v, jnp.full(G - old, pad_val, v.dtype)]
            )
        return out

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        return tape_capacity + self._cap()

    def _session_rows(self, buf, codes):
        """Slot values of the sessions stored for ``codes``."""
        out = {"cnt": buf["cnt"][codes].astype(jnp.float32)}
        for agg in self.aggs:
            j = agg.arg_idx
            if agg.kind == "count":
                v = buf["cnt"][codes].astype(jnp.float32)
            elif agg.kind == "sum":
                v = buf[f"s{j}"][codes]
            elif agg.kind == "avg":
                v = buf[f"s{j}"][codes] / jnp.maximum(
                    buf["cnt"][codes].astype(jnp.float32), 1.0
                )
            elif agg.kind == "min":
                v = buf[f"mn{j}"][codes].astype(jnp.float32)
            elif agg.kind == "max":
                v = buf[f"mx{j}"][codes].astype(jnp.float32)
            else:
                raise SiddhiQLError(
                    f"{agg.kind}() is not supported over #window.session"
                )
            out[agg.slot] = v
        return out

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        codes = (
            jnp.clip(
                env[self.code_key].astype(jnp.int32), 0, self._cap() - 1
            )
            if self.code_key is not None
            else jnp.zeros(E, jnp.int32)
        )
        arg_cols = [
            jnp.broadcast_to(jnp.asarray(fn(env)), (E,)).astype(
                jnp.float32
            )
            for fn in self.arg_fns
        ]
        buf0 = {k: v for k, v in state.items() if k != "enabled"}

        def body(buf, x):
            active, c, ts = x[0], x[1], x[2]
            vals = x[3:]
            was_open = buf["open"][c]
            closes = active & was_open & (
                ts - buf["last"][c] > jnp.int32(self.gap_ms)
            )
            # emit the CLOSED session (pre-reset values)
            emit_ts = buf["last"][c] + jnp.int32(self.gap_ms)
            emitted = self._session_rows(buf, c)
            fresh = closes | (active & ~was_open)
            nb = dict(buf)
            nb["open"] = jnp.where(
                active, buf["open"].at[c].set(True), buf["open"]
            )
            # straggler defense (same shape as the expired-ring cummax):
            # a cross-batch out-of-order event must not REWIND the
            # session clock — a rewound 'last' would let a later
            # in-order event spuriously close/split the session and
            # regress emit_ts. The monotone max also keeps `closes`
            # judged against the newest activity.
            nb["last"] = jnp.where(
                active,
                buf["last"].at[c].set(
                    jnp.maximum(buf["last"][c], ts)
                ),
                buf["last"],
            )
            cnt0 = jnp.where(fresh, 0, buf["cnt"][c])
            nb["cnt"] = jnp.where(
                active, buf["cnt"].at[c].set(cnt0 + 1), buf["cnt"]
            )
            for j, v in enumerate(vals):
                s0 = jnp.where(fresh, 0.0, buf[f"s{j}"][c])
                nb[f"s{j}"] = jnp.where(
                    active, buf[f"s{j}"].at[c].set(s0 + v), buf[f"s{j}"]
                )
                idn = _identity("min", buf[f"mn{j}"].dtype)
                m0 = jnp.where(fresh, idn, buf[f"mn{j}"][c])
                nb[f"mn{j}"] = jnp.where(
                    active,
                    buf[f"mn{j}"].at[c].set(
                        jnp.minimum(m0, v.astype(buf[f"mn{j}"].dtype))
                    ),
                    buf[f"mn{j}"],
                )
                idx_ = _identity("max", buf[f"mx{j}"].dtype)
                x0 = jnp.where(fresh, idx_, buf[f"mx{j}"][c])
                nb[f"mx{j}"] = jnp.where(
                    active,
                    buf[f"mx{j}"].at[c].set(
                        jnp.maximum(x0, v.astype(buf[f"mx{j}"].dtype))
                    ),
                    buf[f"mx{j}"],
                )
            ys = (closes, emit_ts, c) + tuple(
                emitted[slot]
                for slot in sorted(emitted)
                if slot != "cnt"
            )
            return nb, ys

        xs = (mask, codes, tape.ts) + tuple(arg_cols)
        new_buf, ys = lax.scan(body, buf0, xs)
        closes, emit_ts, ccode = ys[0], ys[1], ys[2]
        slot_names = [s for s in sorted(
            {a.slot for a in self.aggs}
        )]
        slot_vals = dict(zip(slot_names, ys[3:3 + len(slot_names)]))
        n = closes.sum().astype(jnp.int32)
        pos = jnp.cumsum(closes.astype(jnp.int32)) - 1
        dest = jnp.where(closes, pos, E)
        W = E

        def compact(col, dtype=jnp.float32):
            return (
                jnp.zeros(W, dtype)
                .at[dest]
                .set(col.astype(dtype), mode="drop")
            )

        out_ts = compact(emit_ts, jnp.int32)
        c_code = compact(ccode, jnp.int32)
        c_slots = {
            k: compact(v) for k, v in slot_vals.items()
        }
        new_state = dict(new_buf)
        new_state["enabled"] = state["enabled"]
        return new_state, self._pack(n, out_ts, c_code, c_slots)

    @property
    def flush_is_noop(self) -> bool:
        return False

    def flush(self, state: Dict) -> Tuple[Dict, Tuple]:
        """End of stream: every open session closes (time passes every
        deadline — the engine-wide flush rule)."""
        G = self._cap()
        open_ = state["open"]
        n = open_.sum().astype(jnp.int32)
        pos = jnp.cumsum(open_.astype(jnp.int32)) - 1
        dest = jnp.where(open_, pos, G)
        codes = jnp.arange(G, dtype=jnp.int32)
        rows = self._session_rows(state, codes)
        emit_ts = state["last"] + jnp.int32(self.gap_ms)

        def compact(col, dtype=jnp.float32):
            return (
                jnp.zeros(G, dtype)
                .at[dest]
                .set(col.astype(dtype), mode="drop")
            )

        c_code = compact(codes, jnp.int32)
        c_slots = {k: compact(v) for k, v in rows.items()}
        new_state = dict(state)
        new_state["open"] = jnp.zeros(G, bool)
        return new_state, self._pack(
            n, compact(emit_ts, jnp.int32), c_code, c_slots
        )

    def decode_packed(self, n: int, block: "np.ndarray"):
        """Key columns decode codes back through the encoder."""
        schema = self.output_schema
        from .output import emission_order

        order = emission_order(block[0], n)
        ts_list = (
            np.asarray(block[0, :n])[order].astype(np.int64).tolist()
        )
        col_lists = []
        for c, (f, kind) in enumerate(
            zip(schema.fields, self.proj_map)
        ):
            raw = np.asarray(block[1 + c, :n])[order]
            if kind[0] == "key":
                # append-only encoder: extend the cached LUT (same
                # pattern as the sliding-window group-code decode)
                cache = getattr(self, "_lut_cache", None)
                if cache is None:
                    cache = self._lut_cache = {}
                lut = cache.setdefault(c, [])
                for i in range(len(lut), len(self.encoder)):
                    lut.append(f.decode(self.encoder.value(i)[0]))
                col_lists.append(
                    [lut[int(v)] if 0 <= int(v) < len(lut) else None
                     for v in raw.tolist()]
                )
            else:
                if np.dtype(f.atype.device_dtype) == np.dtype(np.float32):
                    raw = raw.view(np.float32)
                col_lists.append(f.decode_column(raw))
        rows = (
            list(zip(ts_list, map(tuple, zip(*col_lists))))
            if col_lists
            else [(t, ()) for t in ts_list]
        )
        return [(schema, rows)]


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


def _compile_session_window(
    q, name, args, resolver, stream_codes, extensions,
    filter_fns, rewritten, collector, having_re, part_attr=None,
):
    gap_ms, key_attr = args
    inp = q.input
    if part_attr is not None:
        # 'partition with' sessions: the partition key IS the session
        # key (each partition instance tracks its own gap), which is
        # exactly the keyed-session artifact below
        if key_attr is not None and key_attr.name != part_attr:
            raise SiddhiQLError(
                "#window.session inside 'partition with' must key the "
                "session by the partition attribute (or omit the key)"
            )
        key_attr = ast.Attr(part_attr)
    if having_re is not None:
        raise SiddhiQLError(
            "having over #window.session is not supported yet"
        )
    code_key, encoder, encoded = None, None, ()
    if key_attr is not None:
        r = resolver.resolve(key_attr)
        from .window import _group_encoding

        code_key, encoder, encoded = _group_encoding(
            name, [r], stream_codes[inp.stream_id], filter_fns
        )
    gb = tuple(
        ast.bare_group_key(g) for g in q.selector.group_by
    )
    if gb and (key_attr is None or gb != (key_attr.name,)):
        raise SiddhiQLError(
            "group by on #window.session must be the session key"
        )
    slot_names = {a.slot for a in collector.aggs}
    proj_map = []
    out_fields: List[OutputField] = []
    key_idx = None
    for i, item in enumerate(rewritten):
        e = item.expr
        if isinstance(e, ast.Attr) and e.name in slot_names:
            agg = next(a for a in collector.aggs if a.slot == e.name)
            proj_map.append(("agg", e.name))
            out_fields.append(
                OutputField(item.output_name(), agg.out_type, None)
            )
        elif (
            isinstance(e, ast.Attr)
            and key_attr is not None
            and e.name == key_attr.name
        ):
            ra = resolver.resolve(e)
            proj_map.append(("key",))
            out_fields.append(
                OutputField(item.output_name(), ra.atype, ra.table)
            )
        else:
            raise SiddhiQLError(
                "#window.session select items must be the session key "
                "or aggregations (a closed session has no single "
                "current event to read other attributes from)"
            )
    if not collector.aggs:
        raise SiddhiQLError(
            "#window.session without aggregation emits nothing; "
            "aggregate the session (e.g. count())"
        )
    for a in collector.aggs:
        if a.kind not in ("count", "sum", "avg", "min", "max"):
            raise SiddhiQLError(
                f"{a.kind}() is not supported over #window.session"
            )
    art = SessionWindowArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        stream_code=stream_codes[inp.stream_id],
        filter_fns=filter_fns,
        gap_ms=int(gap_ms),
        code_key=code_key,
        encoder=encoder,
        aggs=collector.aggs,
        arg_fns=collector.arg_fns,
        arg_types=collector.arg_types,
        proj_map=proj_map,
    )
    art.encoded_columns = encoded
    return art
