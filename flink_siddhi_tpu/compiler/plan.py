"""Whole-plan compilation: SiddhiQL text -> one jitted device step.

The analog of the reference's plan pipeline — enriched-plan assembly
(SiddhiOperatorContext.getAllEnrichedExecutionPlan, :109-119), fail-fast
validation (AbstractSiddhiOperator.java:291-299), and per-plan runtime
creation (startSiddhiManager, :301-313) — except the product is not N
embedded interpreters but ONE compiled function: every query in the plan is
an artifact contributing to a single ``step(states, tape) ->
(states, outputs)`` that XLA fuses and the runtime jits once per tape bucket.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query import ast, parse_plan
from ..query.lexer import SiddhiQLError
from ..query.planner import StreamPartition, infer_stream_partitions
from ..schema.stream_schema import StreamSchema
from ..schema.types import AttributeType
from .config import DEFAULT_CONFIG, EngineConfig
from ..extensions.registry import ExtensionRegistry, builtin_registry
from ..runtime.tape import TapeSpec
from .compact import front_compact, to_word
from .expr import ExprResolver
from .select import compile_select


@dataclass(frozen=True)
class ChainedInput:
    """A query whose input stream is ANOTHER query's output (query
    chaining, ``insert into mid`` -> ``from mid#window...``): the
    consumer reads a synthetic tape built from the producer's emissions
    inside the same device step — the reference's multi-query
    composition style (package-info.java:19-51), with batch-granular
    propagation instead of per-event."""

    producer: str  # producing artifact's name
    stream_id: str  # the intermediate stream
    code: int  # stream code on the synthetic tape
    fields: Tuple  # producer OutputSchema fields (name/type order)
    mode: str  # producer output_mode: buffered | aligned | packed


@dataclass
class CompiledPlan:
    plan_id: str
    spec: TapeSpec
    artifacts: List  # QueryArtifact protocol: init_state / step / output_*
    schemas: Dict[str, StreamSchema]
    partitions: Dict[str, StreamPartition]
    source_ast: ast.ExecutionPlan
    table_schemas: Dict[str, StreamSchema] = field(default_factory=dict)
    config: EngineConfig = DEFAULT_CONFIG
    # consumer artifact name -> its chained (internal) input descriptor
    chained: Dict[str, ChainedInput] = field(default_factory=dict)
    # artifacts that run time-SEGMENTED across shards (their input
    # streams route with kind 'segment'; see planner._segmentable_chain)
    segment_artifacts: frozenset = frozenset()
    # original CQL + extension registry: lets callers recompile with a
    # different EngineConfig (e.g. ShardedJob auto-disabling lazy
    # projection, which changes the wire format itself)
    source_text: str = ""
    extensions: object = None
    # output rate limiting per output stream (host emission layer)
    output_rates: Dict[str, object] = field(default_factory=dict)
    # 'output snapshot': per output stream, the row positions of the
    # group-by keys (the snapshot emits one current row per key)
    snapshot_keys: Dict[str, tuple] = field(default_factory=dict)
    # compile-window cap: XLA compile time grows with tape width, and a
    # wide multi-query stack at a 512k tape compiles for many MINUTES.
    # When set, the executor steps oversized micro-batches in chunks of
    # this capacity instead of compiling one huge program (the ingest
    # batch size is unchanged; only the compiled window shrinks).
    tape_capacity_limit: Optional[int] = None
    # times grow_state re-bucketed a state table (each a retrace)
    grow_count: int = 0

    def signature(self, capacity: int = 128) -> str:
        """The shape-bucket class key (``analysis/admit.plan_signature``)
        memoized per capacity — the control plane's AOT-cache key and
        the admission summary's ``signature`` field hash the same plan
        more than once per admit, and the eval_shape walk behind it is
        the expensive half."""
        memo = self.__dict__.setdefault("_signature_memo", {})
        from ..runtime.tape import bucket_size

        cap = bucket_size(int(capacity))
        sig = memo.get(cap)
        if sig is None:
            from ..analysis.admit import plan_signature

            sig = memo[cap] = plan_signature(self, capacity=cap)
        return sig

    def recompiled(self, **config_overrides) -> "CompiledPlan":
        """Recompile this plan from its original CQL with EngineConfig
        overrides (state shapes may change; use before a runtime is
        created, never mid-run)."""
        import dataclasses as _dc

        if not self.source_text:
            raise ValueError(
                "plan has no recorded source text; recompile manually"
            )
        return compile_plan(
            self.source_text,
            # external schemas only: DDL/internal streams re-derive
            {
                sid: sch
                for sid, sch in self.schemas.items()
                if sid in self.spec.stream_codes
            },
            extensions=self.extensions,
            plan_id=self.plan_id,
            config=_dc.replace(self.config, **config_overrides),
        )

    def init_state(self) -> Dict:
        from .table import init_table_state

        states = {a.name: a.init_state() for a in self.artifacts}
        if self.table_schemas:
            states["@tables"] = {
                tid: init_table_state(
                    tid, sch, self.config.table_capacity
                )
                for tid, sch in self.table_schemas.items()
            }
        return states

    # fst:hotpath device=states,tape
    def step(
        self, states: Dict, tape, axis_name: Optional[str] = None
    ) -> Tuple[Dict, Dict]:
        """Advance every query one micro-batch. Pure; jit-able. Tables are
        threaded through the artifacts in query order, so later queries see
        earlier queries' table writes (batch-granular sequencing); chained
        consumers read a synthetic tape built from their producer's
        emissions this same step. Under a sharded mesh (``axis_name``
        set), segment-parallel artifacts hand partial matches across
        shards with collectives."""
        new_states = {}
        outputs = {}
        tables = states.get("@tables", {})
        for a in self.artifacts:
            ci = self.chained.get(a.name)
            a_tape = (
                tape
                if ci is None
                else _synthetic_tape(outputs[ci.producer], ci)
            )
            if getattr(a, "uses_tables", False):
                s, tables, out = a.step_tables(
                    states[a.name], tables, a_tape
                )
            elif (
                axis_name is not None
                and a.name in self.segment_artifacts
            ):
                s, out = a.step_segmented(
                    states[a.name], a_tape, axis_name
                )
            else:
                s, out = a.step(states[a.name], a_tape)
            new_states[a.name] = s
            outputs[a.name] = out
        if "@tables" in states:
            new_states["@tables"] = tables
        return new_states, outputs

    def grow_state(self, states: Dict) -> Dict:
        """Re-bucket group-state tables after host interning discovered new
        groups (triggers a one-off retrace, amortized across the run)."""
        out = dict(states)
        for a in self.artifacts:
            grow = getattr(a, "grow_state", None)
            if grow is not None:
                out[a.name] = grow(states[a.name])
                if out[a.name] is not states[a.name] and (
                    _leaf_shapes(out[a.name]) != _leaf_shapes(states[a.name])
                ):
                    self.grow_count += 1
        return out

    @property
    def has_flush(self) -> bool:
        """Whether end-of-stream flush can do ANY work. When False the
        host runtime skips the flush program entirely — even an
        empty-output flush costs several fixed-latency fetches."""
        for a in self.artifacts:
            if getattr(a, "flush_tables", None) is not None:
                return True
            if getattr(a, "flush", None) is None:
                continue
            noop = getattr(a, "flush_is_noop", None)
            if noop is None or not noop:
                return True
        return False

    # fst:hotpath device=states
    def flush(self, states: Dict) -> Tuple[Dict, Dict]:
        """End-of-stream flush (timeBatch final windows etc.). Artifacts
        writing to tables flush THROUGH the table state (windowed table
        inserts land their final rows)."""
        new_states = dict(states)
        outputs = {}
        tables = states.get("@tables", {})
        for a in self.artifacts:
            flt = getattr(a, "flush_tables", None)
            if flt is not None:
                s, tables, _out = flt(states[a.name], tables)
                new_states[a.name] = s
                continue
            fl = getattr(a, "flush", None)
            if fl is not None:
                s, out = fl(states[a.name])
                new_states[a.name] = s
                outputs[a.name] = out
        if "@tables" in states:
            new_states["@tables"] = tables
        return new_states, outputs

    # -- device-side output accumulation ------------------------------------
    # Every device->host fetch is a synchronous round trip that stalls
    # the dispatch pipeline, so the hot loop must never fetch. Each
    # artifact's per-batch
    # emissions are appended on device into one int32 matrix per plan
    # (ts row + one bitcast row per output column); the host drains it with
    # exactly TWO fetches (counts vector, then the used buffer slice),
    # amortized over hundreds of micro-batches.


    def acc_layout(self) -> List[Tuple[int, int]]:
        """(first_row, n_rows) per artifact in the packed buffer."""
        out = []
        row = 0
        for a in self.artifacts:
            # default: ts + columns; stacked artifacts add a query-id row
            # (getattr's default would evaluate output_schema eagerly,
            # which dynamic groups can't do before their first member)
            n_rows = (
                a.acc_rows
                if hasattr(a, "acc_rows")
                else 1 + len(a.output_schema.fields)
            )
            out.append((row, n_rows))
            row += n_rows
        return out

    def acc_capacity(self) -> int:
        total_rows = sum(r for _, r in self.acc_layout()) or 1
        cap = self.config.acc_budget_bytes // (total_rows * 4)
        return int(max(1 << 16, min(1 << 23, cap)))

    def init_acc(self) -> Dict:
        """Zeroed accumulator. Call under jit to materialize on device
        without a host->device transfer."""
        layout = self.acc_layout()
        total_rows = sum(r for _, r in layout) or 1
        a_count = max(len(self.artifacts), 1)
        return {
            # meta[0] = per-artifact emission counts, meta[1] = overflow,
            # meta[2] = aligned appends (front-compactions), meta[3] = of
            # them, those whose mask was a prefix and scattered nothing
            # meta[4], meta[5] = what an artifact's steps counted (its
            # state leaf ``stepped``, named by its ``step_counters``: a
            # time window's members that left, and those it lost)
            # (single array so a host drain-check costs ONE fetch)
            "meta": jnp.zeros((6, a_count), dtype=jnp.int32),
            "buf": jnp.zeros((total_rows, self.acc_capacity()),
                             dtype=jnp.int32),
        }

    # fst:hotpath device=states,acc,tape
    def step_acc(self, states: Dict, acc: Dict, tape,
                 axis_name: Optional[str] = None) -> Tuple[Dict, Dict]:
        """step() + on-device append of every emission into ``acc``."""
        new_states, outputs = self.step(states, tape, axis_name)
        return self._append_outputs(new_states, acc, outputs)

    @jax.named_scope("fst.acc_append")
    # fst:hotpath device=new_states,acc,outputs
    def _append_outputs(self, new_states: Dict, acc: Dict,
                        outputs: Dict) -> Tuple[Dict, Dict]:
        """The accumulator write: every artifact's emission block is
        appended to ``acc`` on the device."""
        buf = acc["buf"]
        cap = buf.shape[1]
        ns, over, compactions, identity = acc["meta"][:4]
        stepped = acc["meta"][4:]
        new_n, new_over = [], []
        for ai, (a, (row0, _r)) in enumerate(
            zip(self.artifacts, self.acc_layout())
        ):
            out = outputs[a.name]
            if getattr(a, "step_counters", None):
                stepped = stepped.at[:, ai].add(new_states[a.name]["stepped"])
            if a.output_mode == "packed":
                # artifact already emits the accumulator block layout;
                # an optional third element counts matches it had to drop
                # before packing (stacked emission buffer overflow)
                n, block = out[0], out[1]
                pre_dropped = (
                    out[2].astype(jnp.int32)
                    if len(out) > 2
                    else jnp.int32(0)
                )
                over = over.at[ai].add(pre_dropped)
                n = n.astype(jnp.int32)
            elif a.output_mode == "aligned":
                mask, ts, cols = out
                src = jnp.stack(
                    [to_word(r)
                     for r in [ts] + [jnp.asarray(c) for c in cols]]
                )
                # all rows compact through ONE sort keyed on the mask and
                # one gather, or through neither where the mask is a
                # prefix already
                n, block, is_prefix = front_compact(mask, src)
                compactions = compactions.at[ai].add(1)
                identity = identity.at[ai].add(is_prefix.astype(jnp.int32))
            else:
                n, ts, cols = out
                n = n.astype(jnp.int32)
                block = jnp.stack(
                    [to_word(r)
                     for r in [ts] + [jnp.asarray(c) for c in cols]]
                )
            v = int(block.shape[1])
            n_true = n
            if v > cap:
                # block wider than the whole accumulator (huge batch or
                # tiny budget): degrade to drain-every-batch granularity;
                # rows beyond cap are genuinely dropped and counted
                block = block[:, :cap]
                v = cap
            n = jnp.minimum(n, jnp.int32(v))
            fits = ns[ai] + jnp.int32(v) <= cap
            off = jnp.where(fits, ns[ai], 0)
            # O(v) append: read the current v-wide region, select, write
            # it back — never materializing the whole capacity-wide slab
            # (donation makes the dynamic_update_slice in-place, so the
            # per-step traffic is block-sized, not accumulator-sized)
            cur = jax.lax.dynamic_slice(
                buf, (row0, off), (block.shape[0], v)
            )
            newblk = jnp.where(fits, block, cur)
            buf = jax.lax.dynamic_update_slice(
                buf, newblk, (row0, off)
            )
            new_n.append(jnp.where(fits, ns[ai] + n, ns[ai]))
            new_over.append(
                over[ai] + jnp.where(fits, n_true - n, n_true)
            )
        if not self.artifacts:
            return new_states, acc
        return new_states, {
            "meta": jnp.concatenate([
                jnp.stack(
                    [jnp.stack(new_n), jnp.stack(new_over),
                     compactions, identity]),
                stepped,
            ]),
            "buf": buf,
        }

    def drain_decode(self, counts: np.ndarray, data: np.ndarray,
                     lookup=None, columnar_streams=frozenset(),
                     lookup_np=None) -> Dict[str, List]:
        """Host side of a drain: unpack the fetched buffer slice into
        per-artifact lists of (output_schema, decoded payload). ``data``
        is ``buf[:, :max(counts)]`` already on host. Stacked multi-query
        artifacts route their rows to each member's own stream;
        ``lookup`` resolves lazy-projected ordinals.

        A payload is a row list by default; for artifacts whose output
        stream is in ``columnar_streams`` (every consumer opted into the
        columnar protocol — see Job._columnar_streams) and that support
        a columnar decode, it is a :class:`ColumnBatch` instead —
        zero per-row tuples. ``lookup_np`` is the vectorized ring
        resolver the columnar path uses."""
        out: Dict[str, List] = {}
        for ai, (a, (row0, n_rows)) in enumerate(
            zip(self.artifacts, self.acc_layout())
        ):
            n = int(counts[ai])
            if n == 0:
                out[a.name] = []
                continue
            block = data[row0:row0 + n_rows, :n]
            if hasattr(a, "decode_packed"):
                # columnar only for artifacts declaring the hook — their
                # output_schema is a plain attribute (groups route to
                # many streams and may not expose one; they stay rows)
                if hasattr(a, "decode_packed_columns") and (
                    a.output_schema.stream_id in columnar_streams
                ):
                    out[a.name] = a.decode_packed_columns(
                        n, block, lookup_np=lookup_np
                    )
                elif getattr(a, "wants_lookup", False):
                    out[a.name] = a.decode_packed(n, block, lookup=lookup)
                else:
                    out[a.name] = a.decode_packed(n, block)
                continue
            if a.output_schema.stream_id in columnar_streams:
                out[a.name] = [(
                    a.output_schema,
                    a.output_schema.decode_packed_columns(n, block),
                )]
            else:
                out[a.name] = [(
                    a.output_schema,
                    a.output_schema.decode_packed_block(n, block),
                )]
        return out

    @property
    def input_stream_ids(self) -> List[str]:
        return list(self.spec.stream_codes)

    def artifact(self, name: str):
        for a in self.artifacts:
            if a.name == name:
                return a
        raise KeyError(name)

    def output_streams(self) -> Dict[str, List]:
        """stream_id -> [OutputSchema] writing to it (a stacked group
        contributes every member's schema)."""
        by_stream: Dict[str, List] = {}
        for a in self.artifacts:
            if hasattr(a, "members"):
                # stacked groups hold artifacts; dynamic groups hold
                # (plan_id, schema) tuples with None for free slots
                schemas = [
                    m.output_schema if hasattr(m, "output_schema") else m[1]
                    for m in a.members
                    if m is not None
                ]
            else:
                schemas = [a.output_schema]
            for sch in schemas:
                by_stream.setdefault(sch.stream_id, []).append(sch)
        return by_stream


# windows whose first argument is the attribute they read as time
TIME_WINDOWS = ("hop", "externaltime", "externaltimebatch")


def _time_arg_of(w: ast.Window):
    """The attribute window ``w`` reads as time, or None: the first
    argument of ``TIME_WINDOWS`` and of the three-argument
    ``#window.session(tsAttribute, gap, key)``."""
    name = w.name.split(".")[-1].lower()
    if name in TIME_WINDOWS or (name == "session" and len(w.args) == 3):
        return w.args[0]
    return None


def _leaf_shapes(state) -> List[Tuple]:
    return [np.shape(x) for x in jax.tree.leaves(state)]


# fst:hotpath device=out
def _synthetic_tape(out, ci: ChainedInput):
    """Producer emissions -> the consumer's input Tape, inside the same
    jitted step. All three artifact output modes convert losslessly:
    buffered (n, ts, cols), aligned (mask, ts, cols), packed (n, block
    with bitcast i32 rows)."""
    from ..runtime.tape import Tape, time_key

    if ci.mode == "aligned":
        mask, ts, cols = out
        valid = jnp.asarray(mask)
        width = int(valid.shape[0])
        col_vals = [jnp.asarray(c) for c in cols]
    elif ci.mode == "buffered":
        n, ts, cols = out
        width = int(ts.shape[0])
        valid = jnp.arange(width, dtype=jnp.int32) < n
        col_vals = [jnp.asarray(c) for c in cols]
    else:  # packed: ts row + one bitcast i32 row per output column
        n, block = out[0], out[1]
        width = int(block.shape[1])
        ts = block[0]
        valid = jnp.arange(width, dtype=jnp.int32) < n
        col_vals = []
        for i, f in enumerate(ci.fields):
            row = block[1 + i]
            dt = np.dtype(f.atype.device_dtype)
            if dt == np.dtype(np.float32):
                row = jax.lax.bitcast_convert_type(row, jnp.float32)
            else:
                row = row.astype(dt)
            col_vals.append(row)
    # producer emission buffers are in SLOT order; the consumer must see
    # stream time (order-sensitive consumers — per-event cumulative
    # prefixes — would otherwise accumulate in buffer order). Stable
    # sort keeps emission order within a timestamp.
    ts = jnp.asarray(ts).astype(jnp.int32)
    order = jnp.argsort(
        jnp.where(valid, ts, jnp.int32(2 ** 31 - 1)), stable=True
    )
    ts = ts[order]
    valid = valid[order]
    col_vals = [v[order] for v in col_vals]
    stream = jnp.where(
        valid, jnp.int32(ci.code), jnp.int32(-1)
    )
    cols_map = {
        f"{ci.stream_id}.{f.name}": v
        for f, v in zip(ci.fields, col_vals)
    }
    # a window over this stream reads a long as time from its time_key
    # (on the input tape: a rebased copy; here: what the producer gave)
    for f, v in zip(ci.fields, col_vals):
        if f.atype == AttributeType.LONG:
            cols_map[time_key(f"{ci.stream_id}.{f.name}")] = v
    return Tape(ts, stream, valid, cols_map)


def compile_plan(
    plan_text: str,
    schemas: Dict[str, StreamSchema],
    extensions: Optional[ExtensionRegistry] = None,
    plan_id: str = "plan",
    config: Optional[EngineConfig] = None,
) -> CompiledPlan:
    """Parse + validate + compile a full execution plan.

    ``schemas``: externally registered streams (SiddhiCEP.registerStream
    parity); ``define stream`` DDL inside the plan text adds to them.
    """
    if extensions is None:
        extensions = builtin_registry()
    if config is None:
        config = DEFAULT_CONFIG
    parsed = parse_plan(plan_text)

    # plan-internal DDL shares the environment's string dictionary (taken
    # from any registered schema) so string codes are comparable across
    # streams, tables, and query constants
    shared_strings = None
    for sch in schemas.values():
        for t in sch.string_tables.values():
            shared_strings = t
            break
        if shared_strings is not None:
            break
    if shared_strings is None:
        from ..schema.strings import StringTable

        shared_strings = StringTable()

    all_schemas = dict(schemas)
    for sd in parsed.stream_defs:
        if sd.stream_id not in all_schemas:
            all_schemas[sd.stream_id] = StreamSchema(
                list(sd.fields), shared_strings=shared_strings
            )
    table_schemas = {
        td.table_id: StreamSchema(
            list(td.fields), shared_strings=shared_strings
        )
        for td in parsed.table_defs
    }

    if not parsed.queries:
        raise SiddhiQLError("execution plan contains no queries")

    # direct `group by` / `having` / aggregation ON a join query: legal
    # SiddhiQL the engine serves by auto-rewriting into the chaining
    # form it already runs — join into a synthesized intermediate
    # stream, aggregate that (same device step, batch-granular hop)
    parsed = _rewrite_aggregated_joins(parsed, table_schemas, all_schemas)
    parsed = _rewrite_windowed_mutations(parsed, table_schemas)
    parsed = _rewrite_all_events(parsed)

    # fail fast on undefined inputs (UndefinedStreamException parity,
    # SiddhiCEP.java:134-140). A stream produced by an EARLIER query's
    # `insert into` is a valid chained input (query composition): the
    # consumer reads the producer's emissions inside the same step.
    producer_of: Dict[str, int] = {}
    multi_producer = set()
    for qi, q in enumerate(parsed.queries):
        if q.output_stream in producer_of:
            multi_producer.add(q.output_stream)
        else:
            producer_of[q.output_stream] = qi

    input_ids: List[str] = []
    internal_ids: List[str] = []
    for qi, q in enumerate(parsed.queries):
        for sid in q.input_stream_ids():
            if sid in table_schemas:
                continue  # table join side, not a stream input
            if sid in all_schemas:
                if sid not in input_ids:
                    input_ids.append(sid)
                continue
            pq = producer_of.get(sid)
            if pq is not None and pq < qi:
                if sid in multi_producer:
                    raise SiddhiQLError(
                        f"chained stream {sid!r} has multiple producer "
                        "queries; define it as a stream and union instead"
                    )
                if not isinstance(q.input, ast.StreamInput):
                    raise SiddhiQLError(
                        f"chained stream {sid!r} can only feed a plain "
                        "windowed/filtered query (joins and patterns over "
                        "intermediate streams are not supported yet)"
                    )
                if sid not in internal_ids:
                    internal_ids.append(sid)
                continue
            raise SiddhiQLError(
                f"input stream {sid!r} is not defined or registered"
            )

    stream_codes = {sid: i for i, sid in enumerate(input_ids)}
    internal_codes = {
        sid: len(input_ids) + j for j, sid in enumerate(internal_ids)
    }
    # materialize only fields some query REFERENCES (by field name,
    # conservatively across streams): every unreferenced column shipped
    # is bytes over the host->device link for nothing. ``select *``
    # anywhere disables pruning (the set is unknowable).
    referenced = _referenced_field_names(parsed)
    columns = []
    column_types = {}
    for sid in input_ids:
        sch = all_schemas[sid]
        for fname, ftype in zip(sch.field_names, sch.field_types):
            if referenced is not None and fname not in referenced:
                continue
            key = f"{sid}.{fname}"
            columns.append(key)
            column_types[key] = ftype

    artifacts = []
    used_names = set()
    encoded = []
    chained: Dict[str, ChainedInput] = {}
    merged_codes = {**stream_codes, **internal_codes}
    for qi, q in enumerate(parsed.queries):
        qname = q.name or f"query_{qi}"
        if qname in used_names:
            raise SiddhiQLError(f"duplicate query name {qname!r}")
        used_names.add(qname)
        art = _compile_query(
            q, qname, all_schemas, merged_codes, extensions,
            table_schemas, config,
        )
        inp = q.input
        if (
            isinstance(inp, ast.StreamInput)
            and inp.stream_id in internal_codes
        ):
            new_enc = []
            for enc in getattr(art, "encoded_columns", ()):
                if any(
                    k.split(".", 1)[0] == inp.stream_id
                    for k in enc.in_keys
                ):
                    enc = _rewire_chained_group(
                        art, enc, q, inp.stream_id, all_schemas,
                        merged_codes,
                    )
                new_enc.append(enc)
            if new_enc:
                art.encoded_columns = tuple(new_enc)
            producer = artifacts[producer_of[inp.stream_id]]
            if getattr(producer, "_nullable", False):
                raise SiddhiQLError(
                    f"chained stream {inp.stream_id!r} comes from an "
                    "outer join whose unmatched rows carry nulls; only "
                    "inner-join / stream producers can be chained"
                )
            chained[qname] = ChainedInput(
                producer=producer.name,
                stream_id=inp.stream_id,
                code=internal_codes[inp.stream_id],
                fields=tuple(producer.output_schema.fields),
                mode=producer.output_mode,
            )
        encoded.extend(getattr(art, "encoded_columns", ()))
        artifacts.append(art)
        # an intermediate stream becomes visible as a schema for the
        # queries AFTER its producer (validation already ordered this)
        if (
            q.output_stream in internal_codes
            and q.output_stream not in all_schemas
        ):
            all_schemas[q.output_stream] = StreamSchema(
                [(f.name, f.atype) for f in art.output_schema.fields],
                shared_strings=shared_strings,
            )

    # multi-query parallelism: structurally-identical chain patterns are
    # stacked onto a device query axis and advanced by one vmapped program
    # (SURVEY.md §2.7-(5)). Chained producers must keep their own
    # artifact (consumers read their outputs by name).
    from .nfa import group_chain_artifacts

    artifacts = group_chain_artifacts(
        artifacts,
        exclude=frozenset(ci.producer for ci in chained.values()),
        column_types=column_types,
    )

    # late materialization (opt-in): a single chain plan whose
    # projection-only columns stay host-side — the biggest lever on
    # bytes over the host->device link (the wire drops to the predicate
    # columns + timestamps)
    device_columns = None
    host_preds = ()
    if (
        config.lazy_projection or config.pred_pushdown
    ) and len(artifacts) == 1:
        from .nfa import ChainPatternArtifact, chain_wire_opts
        from .select import SelectArtifact, select_wire_opts

        res = None
        if isinstance(artifacts[0], ChainPatternArtifact):
            res = chain_wire_opts(artifacts[0], config)
        elif isinstance(artifacts[0], SelectArtifact):
            res = select_wire_opts(artifacts[0], config)
        else:
            from .window import SlidingWindowArtifact, window_wire_opts

            if isinstance(artifacts[0], SlidingWindowArtifact):
                res = window_wire_opts(artifacts[0], config)
        if res is not None:
            needed, host_preds = res
            device_columns = tuple(
                k for k in columns if k in needed
            )
    # artifact-declared host-computed columns (e.g. #window.cron's
    # per-event window ids — calendar math stays on the host)
    host_preds = tuple(host_preds) + tuple(
        hc
        for art in artifacts
        for hc in getattr(art, "host_columns", ())
    )

    # long attributes that a window reads as time get a copy on the
    # job's clock (runtime/tape.py time_key), which is not cut to 32
    # bits; the raw column stays only where something reads its value
    time_columns = tuple(sorted({
        k
        for art in artifacts
        for k in getattr(art, "time_columns", ())
        if k in column_types  # (not a chained stream's: _synthetic_tape)
    }))
    values_read = _referenced_field_names(parsed, time_args=False)
    if values_read is not None:
        columns = [
            k for k in columns
            if k not in time_columns
            or k.split(".", 1)[1] in values_read
        ]

    if len(artifacts) == 1:
        # a column the plan's one artifact reads on the host alone (a
        # window join's right key: interned, never read on the device)
        columns = [
            k for k in columns
            if k not in getattr(artifacts[0], "host_only_columns", ())
        ]

    spec = TapeSpec(
        stream_codes, tuple(columns), column_types, tuple(encoded),
        device_columns=device_columns,
        host_preds=tuple(host_preds),
        time_columns=time_columns,
    )

    partitions = infer_stream_partitions(parsed.queries)
    # segment partitioning holds only when the consuming artifact can do
    # the cross-shard handoff (a stacked group, slot NFA, non-every, or
    # lazy chain cannot); otherwise fall back to owner-pinning
    def _pattern_streams(a) -> set:
        spec_a = getattr(a, "spec", None)
        if spec_a is not None and hasattr(spec_a, "elements"):
            return {el.stream_id for el in spec_a.elements}
        members = getattr(a, "members", None)
        if members:
            return {
                el.stream_id for m in members for el in m.spec.elements
            }
        return set()

    segment_names = set()
    seg_capable: set = set()
    seg_incapable: set = set()
    for a in artifacts:
        sids = _pattern_streams(a)
        if not sids:
            continue
        if getattr(a, "supports_segment", False) and hasattr(
            a, "step_segmented"
        ):
            seg_capable |= sids
        else:
            seg_incapable |= sids
    for sid, part in list(partitions.items()):
        if part.kind != "segment":
            continue
        if sid in seg_incapable or sid not in seg_capable:
            partitions[sid] = StreamPartition("broadcast")
    for a in artifacts:
        sids = _pattern_streams(a)
        if (
            sids
            and getattr(a, "supports_segment", False)
            and hasattr(a, "step_segmented")
            and all(
                partitions.get(sid) == StreamPartition("segment")
                for sid in sids
            )
        ):
            segment_names.add(a.name)
    # compile-window cap for wide multi-query stacks: XLA compile time
    # grows with tape width * query count — a 64-query stack at a 512k
    # tape compiles for minutes. Chunked stepping keeps compiles in the
    # tens of seconds at a negligible per-chunk dispatch cost.
    cap_limit = config.max_tape_capacity
    if cap_limit is None:
        from .nfa import StackedChainArtifact

        for a in artifacts:
            q_n = len(getattr(a, "members", ()) or ())
            if isinstance(a, StackedChainArtifact) and q_n >= 16:
                cap_limit = 131072
                break

    output_rates = {}
    snapshot_keys: Dict[str, tuple] = {}
    writers: Dict[str, int] = {}
    for q in parsed.queries:
        writers[q.output_stream] = writers.get(q.output_stream, 0) + 1
    for q in parsed.queries:
        r = q.output_rate
        if r is None:
            continue
        if r.mode == "snapshot":
            # periodic CURRENT-VALUE emission: one row per group with
            # the latest aggregate (siddhi's snapshot limiter over an
            # aggregation). Plain window-contents snapshots (dumping
            # every retained event) would need device window dumps —
            # reject those loudly rather than emit something else.
            has_agg = q.selector.group_by or any(
                ast.contains_aggregate(i.expr)
                for i in q.selector.items
            )
            if not has_agg:
                raise SiddhiQLError(
                    "'output snapshot every ...' is supported for "
                    "aggregation queries (periodic current aggregate "
                    "per group); a plain window-contents snapshot is "
                    "not supported yet"
                )
            gb = {ast.bare_group_key(g) for g in q.selector.group_by}
            keys = []
            projected = set()
            for i, item in enumerate(q.selector.items):
                if (
                    isinstance(item.expr, ast.Attr)
                    and item.expr.name in gb
                ):
                    keys.append(i)
                    projected.add(item.expr.name)
            if gb - projected:
                # EVERY group key must be in the row, or distinct
                # groups overwrite one snapshot slot — silently wrong
                raise SiddhiQLError(
                    "'output snapshot' on a group-by query must "
                    "project every group key in the select "
                    f"(missing: {sorted(gb - projected)}); snapshot "
                    "rows are keyed by them"
                )
            snapshot_keys[q.output_stream] = tuple(keys)
        if writers[q.output_stream] > 1:
            # the host limiter is keyed by stream; interleaving a second
            # writer through one query's limiter would silently throttle
            # it (Siddhi limiters are per-query)
            raise SiddhiQLError(
                f"output rate limiting on {q.output_stream!r} with "
                "multiple writer queries is not supported yet"
            )
        if q.output_stream in internal_codes:
            # chained consumers read producer emissions ON DEVICE; the
            # host emission limiter cannot thin that path — refusing
            # beats silently computing a different answer
            raise SiddhiQLError(
                f"output rate limiting on chained stream "
                f"{q.output_stream!r} is not supported (the downstream "
                "query consumes the unthinned device emissions)"
            )
        if q.output_stream in table_schemas:
            # table writes apply on device; the host limiter cannot
            # throttle them — refuse rather than silently ignore
            raise SiddhiQLError(
                "output rate limiting on a table write is not supported"
            )
        output_rates[q.output_stream] = r

    plan = CompiledPlan(
        plan_id=plan_id,
        spec=spec,
        artifacts=artifacts,
        schemas=all_schemas,
        partitions=partitions,
        source_ast=parsed,
        table_schemas=table_schemas,
        config=config,
        chained=chained,
        segment_artifacts=frozenset(segment_names),
        source_text=plan_text,
        extensions=extensions,
        tape_capacity_limit=cap_limit,
        output_rates=output_rates,
        snapshot_keys=snapshot_keys,
    )
    # compiled-plan verification (Siddhi validates every plan at parse
    # time; we validate the artifact stack before it reaches the
    # device). Tiered cost: FST_VERIFY_PLANS=1 (the test lane,
    # tests/conftest.py) runs the static NFA/stack checks on EVERY
    # compile for ~free; config.verify_plans=True or
    # FST_VERIFY_PLANS=full adds the eval_shape schema+donation tier
    # (~0.1s/plan, still no compile); =0 force-disables everything
    # (bench hot-path escape hatch). docs/static_analysis.md.
    import os as _os

    _env = _os.environ.get("FST_VERIFY_PLANS")
    if (config.verify_plans or _env in ("1", "full")) and _env != "0":
        from ..analysis.plancheck import verify_plan

        verify_plan(
            plan, trace=bool(config.verify_plans) or _env == "full"
        )
    # admission analysis (analysis/admit.py) rides the same tier
    # ladder: =1 validates every artifact's cost_info() hook for ~free
    # on every test-lane compile; =full / verify_plans adds the
    # footprint + shape-bucket signature (eval_shape, no compile); a
    # configured AdmissionBudgets turns findings into a hard reject —
    # the control plane's per-tenant envelope (docs/static_analysis.md).
    if (
        config.verify_plans
        or config.admission_budgets is not None
        or _env in ("1", "full")
    ) and _env != "0":
        from ..analysis.admit import admit_plan

        admit_plan(
            plan,
            budgets=config.admission_budgets,
            deep=bool(config.verify_plans) or _env == "full",
        )
    return plan


def _rewrite_partitioned(q: ast.Query, schemas) -> ast.Query:
    """Lower ``partition with (key of S) begin ... end`` semantics.

    Patterns: every non-first element gets an implicit cross-element
    equality filter ``el.key == e0.key`` — a partial match only advances
    on its own key's events, which is exactly Siddhi's per-partition NFA
    instance. Combined with key-hash routing (planner: groupby on the
    key), this also scales patterns across shards with exact results
    (reference analog: keyBy passthrough, SiddhiStream.java:88-97).
    Aggregations: the key joins the group-by clause (per-key state).
    """
    import dataclasses

    if not q.partition_with:
        return q
    keymap = dict(q.partition_with)
    inp = q.input
    # refuses what it would ignore (the parser has refused any other
    # annotation): @purge where the partition's state is not the
    # per-key length window's
    if q.partition_purge is not None and not (
        isinstance(inp, ast.StreamInput)
        and [w.name.split(".")[-1].lower() for w in inp.windows]
        == ["length"]
        and q.output_events == "current"
        and any(ast.contains_aggregate(i.expr) for i in q.selector.items)
    ):
        raise SiddhiQLError(
            "@purge on a partition is honoured for a per-key length "
            "window with aggregates ('partition with (k of S) begin from "
            "S#window.length(n) select ... end': docs/partition_window.md)"
            "; this partition's state does not expire"
        )
    if isinstance(inp, ast.StreamInput):
        if inp.stream_id not in keymap:
            raise SiddhiQLError(
                f"stream {inp.stream_id!r} has no partition key; add "
                f"'<attr> of {inp.stream_id}' to the partition clause"
            )
        attr = keymap[inp.stream_id]
        if attr not in schemas[inp.stream_id]:
            raise SiddhiQLError(
                f"partition key {attr!r} is not an attribute of "
                f"{inp.stream_id!r}"
            )
        sel = q.selector
        has_agg = sel.group_by or any(
            ast.contains_aggregate(i.expr) for i in sel.items
        )
        if inp.windows:
            # per-partition window: EACH key's window holds that key's
            # last C events (NOT a group-by over one shared window) —
            # compiles to the per-key window artifact, which reads the
            # partition key from group_by (the canonical Siddhi
            # partition use; README.md:77-96)
            if q.output_events != "current":
                # per-key EXPIRY order differs from a shared window's;
                # silently compiling to shared-window expiry would be
                # exactly the wrong-answer class the partition carve-out
                # exists to prevent
                raise SiddhiQLError(
                    "'insert expired events into' inside 'partition "
                    "with' is not supported yet"
                )
            if not has_agg:
                # plain windowed projection emits arriving CURRENT
                # events unchanged; partitioning changes nothing
                return dataclasses.replace(q, partition_with=())
            bare = tuple(ast.bare_group_key(n) for n in sel.group_by)
            if attr not in bare:
                sel = dataclasses.replace(
                    sel, group_by=tuple(sel.group_by) + (attr,)
                )
            return dataclasses.replace(q, selector=sel)
        if has_agg and attr not in tuple(
            ast.bare_group_key(n) for n in sel.group_by
        ):
            sel = dataclasses.replace(
                sel, group_by=tuple(sel.group_by) + (attr,)
            )
            return dataclasses.replace(q, selector=sel)
        return q
    if isinstance(inp, ast.JoinInput):
        raise SiddhiQLError(
            "joins inside 'partition with' are not supported yet"
        )
    # pattern / sequence
    if inp.kind == "sequence":
        raise SiddhiQLError(
            "sequences inside 'partition with' are not supported yet "
            "(strict continuity is per-partition, not global)"
        )
    if not inp.every_:
        raise SiddhiQLError(
            "non-'every' patterns inside 'partition with' are not "
            "supported yet (the single-match rule is per partition key, "
            "but the engine's match gate is per instance)"
        )
    els = inp.elements
    el0 = els[0]
    if (el0.min_count, el0.max_count) != (1, 1):
        raise SiddhiQLError(
            "the first element of a partitioned pattern cannot be "
            "quantified yet"
        )
    if len(els) > 1 and els[1].group_link is not None:
        raise SiddhiQLError(
            "an 'and'/'or' group as the first step of a partitioned "
            "pattern is not supported yet"
        )
    for sid in {el.stream_id for el in els}:
        if sid not in keymap:
            raise SiddhiQLError(
                f"stream {sid!r} has no partition key; add "
                f"'<attr> of {sid}' to the partition clause"
            )
    new_els = [el0]
    attr0 = keymap[el0.stream_id]
    if attr0 not in schemas[el0.stream_id]:
        raise SiddhiQLError(
            f"partition key {attr0!r} is not an attribute of "
            f"{el0.stream_id!r}"
        )
    for el in els[1:]:
        if el.negated:
            raise SiddhiQLError(
                "absent ('not') elements inside 'partition with' "
                "patterns are not supported yet"
            )
        eq = ast.Binary(
            "==",
            ast.Attr(keymap[el.stream_id], qualifier=el.alias),
            ast.Attr(attr0, qualifier=el0.alias),
        )
        filt = (
            eq if el.filter is None else ast.Binary("and", el.filter, eq)
        )
        new_els.append(dataclasses.replace(el, filter=filt))
    return dataclasses.replace(
        q, input=dataclasses.replace(inp, elements=tuple(new_els))
    )


def _compile_query(
    q: ast.Query,
    name: str,
    schemas: Dict[str, StreamSchema],
    stream_codes: Dict[str, int],
    extensions: ExtensionRegistry,
    table_schemas: Optional[Dict[str, StreamSchema]] = None,
    config: EngineConfig = DEFAULT_CONFIG,
):
    table_schemas = table_schemas or {}
    q = _rewrite_partitioned(q, schemas)
    if q.output_stream in table_schemas or q.output_action in (
        "update", "delete",
    ):
        from .table import compile_table_write

        if q.output_stream not in table_schemas:
            raise SiddhiQLError(
                f"{q.output_action} target {q.output_stream!r} is not a "
                "defined table"
            )
        return compile_table_write(
            q, name, schemas, table_schemas, stream_codes, extensions,
            config,
        )
    inp = q.input
    if (
        isinstance(inp, ast.StreamInput)
        and inp.stream_id not in table_schemas  # table reads reject below
        and len(inp.windows) == 1
        and inp.windows[0].name.split(".")[-1].lower() == "delay"
        and q.output_events == "current"
    ):
        from .window import compile_delay_window

        # #window.delay(t): events pass through t ms late — the exact
        # emission schedule of a time-window's EXPIRED stream (entry ts
        # + span), reusing that machinery wholesale
        return compile_delay_window(
            q, name, schemas, stream_codes, extensions, config
        )
    if q.output_events != "current":
        from .window import compile_expired_window

        # `insert expired events into`: emit events as they LEAVE the
        # window. Round-3 verdict: this was silently parsed as current
        # events — the worst kind of wrong answer.
        return compile_expired_window(
            q, name, schemas, stream_codes, extensions, config
        )
    if isinstance(inp, ast.JoinInput) and (
        inp.left.stream_id in table_schemas
        or inp.right.stream_id in table_schemas
    ):
        from .table import compile_table_join

        return compile_table_join(
            q, name, schemas, table_schemas, stream_codes, extensions,
            config,
        )
    if isinstance(inp, ast.StreamInput):
        if inp.stream_id in table_schemas:
            raise SiddhiQLError(
                f"cannot read table {inp.stream_id!r} as a stream; join a "
                "stream against it instead"
            )
        has_agg = any(
            ast.contains_aggregate(i.expr) for i in q.selector.items
        )
        if inp.windows or has_agg or q.selector.group_by:
            from .window import compile_window_query

            return compile_window_query(
                q, name, schemas, stream_codes, extensions, config
            )
        ref = inp.ref_name
        resolver = ExprResolver(
            {ref: (inp.stream_id, schemas[inp.stream_id])},
            default_scope=ref,
        )
        if ref != inp.stream_id:
            resolver = ExprResolver(
                {
                    ref: (inp.stream_id, schemas[inp.stream_id]),
                    inp.stream_id: (inp.stream_id, schemas[inp.stream_id]),
                },
                default_scope=ref,
            )
        return compile_select(
            q, name, resolver, schemas, stream_codes[inp.stream_id],
            extensions,
        )
    if isinstance(inp, ast.PatternInput):
        from .nfa import compile_pattern_query

        return compile_pattern_query(
            q, name, schemas, stream_codes, extensions, config
        )
    if isinstance(inp, ast.JoinInput):
        from .window_join import compile_window_join, is_window_join

        if is_window_join(inp):
            # both sides under one tumbling window, the ``on`` equality
            # a key: folded per key on the device, not as a pair grid
            return compile_window_join(
                q, name, schemas, stream_codes, extensions, config
            )
        from .join import compile_join_query

        return compile_join_query(
            q, name, schemas, stream_codes, extensions, config
        )
    raise SiddhiQLError(f"unsupported input clause {type(inp).__name__}")


def _rewrite_aggregated_joins(parsed, table_schemas, all_schemas):
    """Expand ``from A join B ... select sum(x) group by k`` into the
    two-query chaining form: the join projects every referenced raw
    column into a synthesized intermediate stream; the aggregation runs
    over that stream. The reference composes multi-query plans the same
    way (package-info.java:19-51); this makes the single-query spelling
    — legal SiddhiQL — compile instead of raising a chaining hint."""
    import dataclasses

    from .window_join import is_window_join

    out = []
    changed = False
    for q in parsed.queries:
        inp = q.input
        # (a window join aggregates per key itself: window_join.py)
        is_stream_join = isinstance(inp, ast.JoinInput) and not (
            inp.left.stream_id in table_schemas
            or inp.right.stream_id in table_schemas
            or is_window_join(inp)
        )
        sel = q.selector
        has_agg = any(
            ast.contains_aggregate(i.expr) for i in sel.items
        ) or bool(sel.group_by) or sel.having is not None
        if not (is_stream_join and has_agg) or q.output_action != "insert":
            out.append(q)
            continue
        if sel.is_star:
            raise SiddhiQLError(
                "select * with aggregation over a join is ambiguous; "
                "name the columns"
            )
        changed = True
        mid = f"@j:{q.output_stream}:{len(out)}"
        side_of = {
            inp.left.ref_name: inp.left.stream_id,
            inp.left.stream_id: inp.left.stream_id,
            inp.right.ref_name: inp.right.stream_id,
            inp.right.stream_id: inp.right.stream_id,
        }
        group_sources: Dict[str, str] = {}

        # every raw attr the outer selector/having reads gets a flat
        # alias on the intermediate stream
        mangled: Dict[Tuple, str] = {}
        join_items: List[ast.SelectItem] = []

        def flat(attr: ast.Attr) -> str:
            key = (attr.qualifier, attr.name)
            name = mangled.get(key)
            if name is None:
                name = (
                    f"{attr.qualifier}_{attr.name}"
                    if attr.qualifier
                    else attr.name
                )
                # collisions (e.g. `a_b` vs qualifier a, name b): suffix
                while any(i.alias == name for i in join_items):
                    name += "_"
                mangled[key] = name
                join_items.append(ast.SelectItem(attr, name))
                # provenance: which SOURCE column this flat field carries
                if attr.qualifier is not None:
                    sid = side_of.get(attr.qualifier)
                    if sid is not None:
                        group_sources[name] = f"{sid}.{attr.name}"
                else:
                    hits = [
                        sid
                        for sid in (
                            inp.left.stream_id, inp.right.stream_id
                        )
                        if sid in all_schemas
                        and attr.name in all_schemas[sid]
                    ]
                    if len(set(hits)) == 1:
                        group_sources[name] = f"{hits[0]}.{attr.name}"
            return name

        def _flat_attr(a: ast.Attr) -> ast.Attr:
            if a.index is not None:
                raise SiddhiQLError(
                    "indexed references are not valid on join queries"
                )
            return ast.Attr(flat(a))

        def rewrite(e: ast.Expr) -> ast.Expr:
            return ast.map_expr(e, _flat_attr)

        new_items = tuple(
            ast.SelectItem(rewrite(i.expr), i.output_name())
            for i in sel.items
        )
        out_aliases = {i.output_name() for i in sel.items}

        def rewrite_having(e: ast.Expr) -> ast.Expr:
            # having may reference SELECT aliases — those resolve
            # downstream against the aggregation's own output slots,
            # not against the join's raw columns
            return ast.map_expr(
                e,
                lambda a: (
                    a
                    if a.qualifier is None and a.name in out_aliases
                    else _flat_attr(a)
                ),
            )

        new_having = (
            rewrite_having(sel.having) if sel.having is not None else None
        )
        # group keys carry onto the intermediate stream under their
        # flattened alias (qualified keys keep their side)
        new_group = tuple(
            flat(ast.split_group_key(g)) for g in sel.group_by
        )

        join_q = dataclasses.replace(
            q,
            selector=ast.Selector(tuple(join_items)),
            output_stream=mid,
            name=(f"{q.name}@join" if q.name else None),
            output_rate=None,
        )
        agg_q = dataclasses.replace(
            q,
            input=ast.StreamInput(mid),
            selector=ast.Selector(new_items, new_group, new_having),
            group_sources=tuple(sorted(group_sources.items())),
        )
        out.extend([join_q, agg_q])
    if not changed:
        return parsed
    return dataclasses.replace(parsed, queries=tuple(out))


def _rewire_chained_group(art, enc, q, mid_sid, all_schemas, codes):
    """Group-by over a CHAINED stream: the group values exist only on
    device, so the host cannot build the code column. When the key's
    SOURCE column is known (synthesized join rewrites record it) and
    numeric, rewire: intern over the source column (intern-only, no wire
    column) and have the artifact map values -> codes on device from
    the synced sorted table."""
    import dataclasses as _dc

    from .window import CumulativeAggArtifact

    unsupported = SiddhiQLError(
        f"group by over chained stream {mid_sid!r} is not supported "
        "for this query shape (group keys are interned host-side but "
        "intermediate values exist only on device); group in the "
        "upstream query instead"
    )
    sources = dict(q.group_sources)
    if (
        not isinstance(art, CumulativeAggArtifact)
        or len(enc.in_keys) != 1
    ):
        raise unsupported
    mid_field = enc.in_keys[0].split(".", 1)[1]
    src_key = sources.get(mid_field)
    if src_key is None:
        raise unsupported
    src_sid, src_field = src_key.split(".", 1)
    atype = all_schemas[src_sid].field_type(src_field)
    if not (atype.is_numeric or atype.is_encoded):
        raise unsupported  # no ordered device representation to map
    # STRING/OBJECT keys work exactly like numerics here: both host
    # batches and device columns carry the shared-dictionary int32
    # CODES (schema/types.py is_encoded), so interning the source
    # column's codes and mapping value->group on device through the
    # synced sorted table is the same int32 searchsorted; group-key
    # output decode goes code -> string through the field decoder
    art.chained_group_src = enc.in_keys[0]
    art.chained_group_dtype = atype.device_dtype
    return _dc.replace(
        enc,
        in_keys=(src_key,),
        stream_code=codes[src_sid],
        select_fn=None,  # intern the source superset
        materialize=False,
    )


def _rewrite_all_events(parsed):
    """``insert all events into X``: siddhi emits BOTH arriving
    (current) and leaving (expired) window events into one stream.
    Re-expressed as two queries writing the same output — a current-
    events pass-through and the expired-events artifact — which is
    exactly what siddhi-core's StreamJunction receives from a window
    processor in ALL_EVENTS mode."""
    import dataclasses

    out = []
    changed = False
    for q in parsed.queries:
        if q.output_events != "all":
            out.append(q)
            continue
        if q.output_rate is not None:
            # the split halves would share one stream limiter, thinning
            # interleaved current/expired rows as one sequence — and
            # the multi-writer check would blame a "second query" the
            # user never wrote. Name the real combination instead.
            raise SiddhiQLError(
                "'insert all events into' combined with 'output ... "
                "every ...' is not supported; rate-limit the current-"
                "events and expired-events queries separately"
            )
        changed = True
        base = q.name or f"allq{len(out)}"
        out.append(
            dataclasses.replace(
                q, output_events="current", name=f"{base}@cur"
            )
        )
        out.append(
            dataclasses.replace(
                q, output_events="expired", name=f"{base}@exp"
            )
        )
    if not changed:
        return parsed
    return dataclasses.replace(parsed, queries=tuple(out))


def _rewrite_windowed_mutations(parsed, table_schemas):
    """``from S#window.x(...) select ... update T on ...`` (and delete):
    siddhi-core evaluates the window chain before the table mutation.
    Re-expressed through chaining: the windowed/aggregated selection
    emits into a synthesized intermediate stream; a plain mutate query
    consumes it (same device step)."""
    import dataclasses

    out = []
    changed = False
    for q in parsed.queries:
        inp = q.input
        windowed = (
            q.output_action in ("update", "delete")
            and q.output_stream in table_schemas
            and isinstance(inp, ast.StreamInput)
            and (
                inp.windows
                or q.selector.group_by
                or q.selector.having is not None
                or any(
                    ast.contains_aggregate(i.expr)
                    for i in q.selector.items
                )
            )
        )
        if not windowed:
            out.append(q)
            continue
        changed = True
        mid = f"@t:{q.output_stream}:{len(out)}"
        win_q = dataclasses.replace(
            q,
            output_stream=mid,
            output_action="insert",
            on_condition=None,
            name=(f"{q.name}@win" if q.name else None),
            output_rate=None,  # rate-limiting applies to the MUTATION
        )
        # the mutate's projection carries only fields the mutation can
        # use: table columns and on-condition references (the windowed
        # query may also emit having-only fields like a count alias)
        tcols = set(table_schemas[q.output_stream].field_names)
        on_names = {
            a.name
            for a in ast.iter_attrs(q.on_condition)
            if q.on_condition is not None
        } if q.on_condition is not None else set()
        kept = tuple(
            ast.SelectItem(ast.Attr(i.output_name()), i.output_name())
            for i in q.selector.items
            if i.output_name() in tcols or i.output_name() in on_names
        )
        if not kept:
            raise SiddhiQLError(
                f"windowed {q.output_action} into {q.output_stream!r} "
                "selects no table column or on-condition field"
            )
        mut_q = dataclasses.replace(
            q,
            input=ast.StreamInput(mid),
            selector=ast.Selector(kept),
        )
        out.extend([win_q, mut_q])
    if not changed:
        return parsed
    return dataclasses.replace(parsed, queries=tuple(out))


def _referenced_field_names(parsed, time_args: bool = True):
    """Field names any query can read, or None when unknowable
    (``select *``). Name-level (not stream-qualified) and therefore
    conservative: a name used on ANY stream keeps that column on every
    stream carrying it. ``time_args=False`` leaves out the attribute a
    window reads as time (``_time_arg_of``): the names read as values.
    A session's ``min()`` / ``max()`` of its own time attribute is no
    such read: it is the session's first and last time on the clock."""
    names = set()

    def add_expr(e):
        if e is None:
            return
        for a in ast.iter_attrs(e):
            names.add(a.name)

    for q in parsed.queries:
        sel = q.selector
        if sel.is_star:
            return None
        clock = None
        if not time_args and isinstance(q.input, ast.StreamInput):
            clock = next(
                (w.args[0] for w in q.input.windows
                 if w.name.split(".")[-1].lower() == "session"
                 and _time_arg_of(w) is not None), None)
        for item in sel.items:
            e = item.expr
            if (
                clock is not None and isinstance(e, ast.Call)
                and e.name.lower() in ("min", "max")
                and e.args == (clock,)
            ):
                continue
            add_expr(e)
        for g in sel.group_by:
            names.add(ast.bare_group_key(g))
        add_expr(sel.having)
        add_expr(q.on_condition)
        for _sid, attr in q.partition_with:
            names.add(attr)
        for _f, src in q.group_sources:
            names.add(src.split(".", 1)[1])
        inp = q.input
        sides = []
        if isinstance(inp, ast.StreamInput):
            sides = [inp]
        elif isinstance(inp, ast.JoinInput):
            sides = [inp.left, inp.right]
            add_expr(inp.on)
        elif isinstance(inp, ast.PatternInput):
            for el in inp.elements:
                add_expr(el.filter)
        for side in sides:
            for f in side.filters:
                add_expr(f)
            for w in side.windows:
                args = w.args
                if not time_args and _time_arg_of(w) is not None:
                    args = args[1:]
                for arg in args:
                    add_expr(arg)
    return names
