"""Windowed two-stream joins compiled to masked pair matrices.

Reference surface: windowed joins with ``on`` conditions
(SiddhiCEPITCase.java:306-327, 413-439 — ``from A#window.length(5) join
B#window.time(500) on a.x == b.y``), which siddhi-core evaluates per arriving
event against the opposite window's buffered events. Note the reference's
*dynamic* path rejects joins outright (SiddhiExecutionPlanner.java:99-100);
static-path support is the parity bar.

Device shape: each side keeps a ring of its last C matching events (columns
referenced by the join + projections, carried across micro-batches). Per
micro-batch, each direction builds ONE (E, C+E) pair mask — arriving events
of one side × the other side's combined ring+batch — with window membership
expressed as global-ordinal bounds (length windows) or timestamp bounds (time
windows), the ``on`` condition evaluated by broadcasting the compiled
expression over (E,1)×(1,C+E) column views, and matching pairs compacted into
a fixed-capacity output buffer. Every ordered pair is emitted exactly once:
by whichever event arrives later.

Outer joins emit the arriving event with zero-filled columns for the missing
side (the engine has no device-side null; SURVEY.md §7 hard part 1 applies —
a null-mask column is a planned refinement).

Which query takes which path (``plan._compile_query``): a join whose sides
carry ``#window.length`` or ``#window.time`` (or no window) is compiled here,
pairs out, a ``time`` or window-less side holding the last
``EngineConfig.join_window_capacity`` rows; with ``group by`` / aggregates it
is first rewritten into this join plus an aggregation over its output
(``plan._rewrite_aggregated_joins``). A join with ``#window.hop`` on a side is
the tumbling-window equi-join of ``compiler/window_join.py``: keyed per
window on the device, one row per key, cost by the events and not by the
pair grid (docs/window_join.md). A table side goes to ``compiler/table.py``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
from jax import lax

import numpy as np

from ..query import ast
from ..query.lexer import SiddhiQLError
from ..schema.types import AttributeType
from .expr import ColumnEnv, ExprResolver, ResolvedAttr, compile_expr
from .output import OutputField, OutputSchema
from .window import _window_of

JOIN_OUT_FACTOR = 4  # output buffer capacity = factor * tape capacity


class _JoinResolver:
    """Side-qualified attribute resolution for join pair expressions.

    Every reference resolves to an env key unique to its SIDE
    (``l:S.x`` / ``r:S.x``) so self-joins (`from S as a join S as b`)
    can tell ``a.x`` from ``b.x``; ``used`` records each env key's
    (side tag, tape column key, type) for ring buffering."""

    def __init__(self, left_si, right_si, schemas) -> None:
        self._by_ref: Dict[str, Tuple[str, str, object]] = {}
        for tag, si in (("l", left_si), ("r", right_si)):
            if si.ref_name in self._by_ref:
                raise SiddhiQLError(
                    "self-join sides need distinct aliases: "
                    f"'from {si.stream_id} as a join {si.stream_id} as b'"
                )
            self._by_ref[si.ref_name] = (
                tag, si.stream_id, schemas[si.stream_id]
            )
        # stream-id qualifiers are allowed when exactly one side uses
        # that stream (and the id is not already a ref name)
        by_sid: Dict[str, List] = {}
        for ent in self._by_ref.values():
            by_sid.setdefault(ent[1], []).append(ent)
        for sid, ents in by_sid.items():
            if sid not in self._by_ref and len(ents) == 1:
                self._by_ref[sid] = ents[0]
        self.used: Dict[str, Tuple[str, str, AttributeType]] = {}

    def resolve(self, attr: ast.Attr) -> ResolvedAttr:
        if attr.index is not None:
            raise SiddhiQLError(
                "indexed references are not valid in join expressions"
            )
        if attr.qualifier is not None:
            ent = self._by_ref.get(attr.qualifier)
            if ent is None:
                raise SiddhiQLError(
                    f"unknown stream reference {attr.qualifier!r}"
                )
            hits = [ent]
        else:
            seen = set()
            hits = []
            for ref, ent in self._by_ref.items():
                if ent[0] in seen:
                    continue
                if attr.name in ent[2]:
                    seen.add(ent[0])
                    hits.append(ent)
            if not hits:
                raise SiddhiQLError(f"unknown attribute {attr.name!r}")
            if len(hits) > 1:
                raise SiddhiQLError(
                    f"ambiguous attribute {attr.name!r}; qualify it with "
                    "a stream alias"
                )
        tag, sid, schema = hits[0]
        if attr.name not in schema:
            raise SiddhiQLError(
                f"stream {sid!r} has no attribute {attr.name!r}"
            )
        atype = schema.field_type(attr.name)
        key = f"{tag}:{sid}.{attr.name}"
        self.used[key] = (tag, f"{sid}.{attr.name}", atype)
        return ResolvedAttr(
            key, atype, schema.string_tables.get(attr.name)
        )


@dataclass
class _Side:
    stream_id: str
    ref: str
    stream_code: int
    filter_fns: List[Callable]
    window_mode: str  # 'length' | 'time'
    window_n: int  # length bound (ring capacity for time/unbounded)
    time_ms: Optional[int]
    # (env_key, tape_key) buffered in this side's ring — env keys are
    # side-prefixed so a self-join's two rings stay distinct
    cols: List[Tuple[str, str]]
    col_types: List[AttributeType]
    outer: bool  # emit this side's unmatched arrivals
    # no window clause declared: retention is semantically unbounded
    # and only truncated by the ring (admission's ADM112 surface)
    unbounded: bool = False


@dataclass
class JoinArtifact:
    name: str
    output_schema: OutputSchema
    left: _Side
    right: _Side
    on_fn: Optional[Callable]
    within: Optional[int]
    proj_fns: List[Callable]
    # per projection: the side tags ('l'/'r') it references — outer-join
    # rows decode None for projections over the missing side
    proj_tags: Tuple[frozenset, ...] = ()
    output_mode: str = "buffered"
    out_factor: int = JOIN_OUT_FACTOR

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        """Widest per-cycle emission block (drain-cadence contract)."""
        return self.out_factor * tape_capacity

    def cost_info(self) -> Dict:
        """Admission-cost descriptor (analysis/admit.py): one arriving
        event can pair with every retained row of the OPPOSITE ring —
        the semantic output demand admission budgets against (the
        emission buffer truncates beyond out_factor*E with counted
        overflow). A window-less side retains unbounded history
        (ADM112); time sides retain for their span; 'within' bounds
        the pair distance, which caps residency when both sides would
        otherwise hold longer."""
        residencies = []
        unbounded_sides = []
        for side in (self.left, self.right):
            if side.unbounded:
                unbounded_sides.append(side.stream_id)
                residencies.append(float("inf"))
            elif side.window_mode == "time" and side.time_ms is not None:
                residencies.append(float(side.time_ms))
        res: object = max(residencies) if residencies else None
        if (
            res is not None
            and self.within is not None
            and float(self.within) < res
        ):
            res = float(self.within)
            unbounded_sides = []
        info = {
            "name": self.name,
            "kind": "join",
            "amplification": int(
                max(self.left.window_n, self.right.window_n)
                + (1 if self._nullable else 0)
            ),
            "residency_ms": res,
        }
        if unbounded_sides:
            info["unbounded"] = (
                f"join side(s) {unbounded_sides} declare no window — "
                "retention is semantically unbounded and silently "
                "truncated at ring capacity "
                f"{[self.left.window_n, self.right.window_n]}"
            )
        return info

    @property
    def _nullable(self) -> bool:
        return self.left.outer or self.right.outer

    @property
    def acc_rows(self) -> int:
        return (
            1
            + len(self.output_schema.fields)
            + (1 if self._nullable else 0)
        )

    def decode_packed(self, n: int, block: "np.ndarray"):
        """Accumulator block -> rows; outer joins carry a trailing
        missing-side row (0 = pair, 1 = right missing, 2 = left missing)
        nullifying projections over the absent side (Siddhi null, not a
        zero-filled value)."""
        schema = self.output_schema
        C = len(schema.fields)
        if not self._nullable:
            return [(schema, schema.decode_packed_block(n, block))]
        from .output import emission_order

        # the missing-side row must follow decode's row permutation
        missing = np.asarray(block[1 + C, :n])[emission_order(block[0], n)]
        rows = schema.decode_packed_block(n, block[: 1 + C])
        out = []
        for i, (ts_v, row) in enumerate(rows):
            m = int(missing[i])
            if m:
                gone = "r" if m == 1 else "l"
                row = tuple(
                    None if gone in tags else v
                    for v, tags in zip(row, self.proj_tags)
                )
            out.append((ts_v, row))
        return [(schema, out)]

    def init_state(self) -> Dict:
        st = {"enabled": jnp.asarray(True),
              "overflow": jnp.asarray(0, jnp.int32)}
        for tag, side in (("l", self.left), ("r", self.right)):
            C = side.window_n
            st[f"{tag}_valid"] = jnp.zeros(C, bool)
            st[f"{tag}_ts"] = jnp.zeros(C, jnp.int32)
            st[f"{tag}_seen"] = jnp.asarray(0, jnp.int32)
            for j, t in enumerate(side.col_types):
                st[f"{tag}_c{j}"] = jnp.zeros(C, t.device_dtype)
        return st

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        E = tape.capacity

        sides = {}
        for tag, side in (("l", self.left), ("r", self.right)):
            mask = tape.valid & (tape.stream == side.stream_code)
            for f in side.filter_fns:
                mask = mask & f(env)
            mask = mask & state["enabled"]
            order = jnp.argsort(jnp.logical_not(mask))
            M = mask.sum()
            C = side.window_n
            carry = state[f"{tag}_seen"]
            comb = {
                env_key: jnp.concatenate(
                    [state[f"{tag}_c{j}"],
                     env[tape_key][order].astype(
                         state[f"{tag}_c{j}"].dtype
                     )]
                )
                for j, (env_key, tape_key) in enumerate(side.cols)
            }
            ts_comb = jnp.concatenate(
                [state[f"{tag}_ts"], tape.ts[order]]
            )
            valid_comb = jnp.concatenate(
                [state[f"{tag}_valid"], jnp.arange(E) < M]
            )
            # global ordinal of each combined entry (ring holds the last C)
            ord_comb = jnp.concatenate(
                [carry - C + jnp.arange(C, dtype=jnp.int32),
                 carry + jnp.arange(E, dtype=jnp.int32)]
            )
            sides[tag] = dict(
                side=side, mask=mask, M=M, comb=comb, ts=ts_comb,
                valid=valid_comb, ords=ord_comb,
                cum=carry + jnp.cumsum(mask).astype(jnp.int32),
                # tape position of each in-batch combined entry (-1 for
                # carried ring entries): identifies THE SAME event across
                # a self-join's two sides regardless of per-side filters
                posid=jnp.concatenate(
                    [jnp.full(C, -1, jnp.int32), order.astype(jnp.int32)]
                ),
            )

        segs = []  # (flags, ts, cols) per emission segment
        for atag, btag in (("l", "r"), ("r", "l")):
            segs.extend(
                self._direction(sides[atag], sides[btag], env, tape.ts, E)
            )

        # concatenate all segments and compact into the output buffer
        cap = self.out_factor * E
        n_out = len(self.proj_fns) + (1 if self._nullable else 0)
        flags = jnp.concatenate([s[0] for s in segs])
        ts_all = jnp.concatenate([s[1] for s in segs])
        cols_all = tuple(
            jnp.concatenate([s[2][i] for s in segs])
            for i in range(n_out)
        )
        order = jnp.argsort(jnp.logical_not(flags))[:cap]
        n = flags.sum().astype(jnp.int32)
        out = (
            jnp.minimum(n, cap),
            ts_all[order],
            tuple(c[order] for c in cols_all),
        )

        new_state = dict(state)
        new_state["overflow"] = state["overflow"] + jnp.maximum(n - cap, 0)
        for tag in ("l", "r"):
            s = sides[tag]
            C = s["side"].window_n
            M = s["M"]
            for j, (env_key, _tk) in enumerate(s["side"].cols):
                new_state[f"{tag}_c{j}"] = lax.dynamic_slice(
                    s["comb"][env_key], (M,), (C,)
                )
            new_state[f"{tag}_ts"] = lax.dynamic_slice(s["ts"], (M,), (C,))
            new_state[f"{tag}_valid"] = lax.dynamic_slice(
                s["valid"], (M,), (C,)
            )
            new_state[f"{tag}_seen"] = state[f"{tag}_seen"] + M
        return new_state, out

    def _direction(self, a, b, env: ColumnEnv, ts_i, E: int):
        """Pairs emitted when an ``a``-side event arrives: each arriving
        a-event (tape position i) × the b-side window as of that event.
        Window membership is ordinal bounds: a b-entry is visible iff its
        global ordinal is below the b-count at position i (arrival-before,
        which also dedups in-batch pairs across the two directions) and
        within the last-n for length windows."""
        aside: _Side = a["side"]
        bside: _Side = b["side"]
        member = b["valid"][None, :] & a["mask"][:, None]
        member = member & (b["ords"][None, :] < b["cum"][:, None])
        if aside.stream_code == bside.stream_code:
            # self-join: an event never pairs with itself (it would
            # otherwise appear once per direction); identity = same tape
            # position, robust to differing per-side filters
            member = member & (
                b["posid"][None, :]
                != jnp.arange(E, dtype=jnp.int32)[:, None]
            )
        if bside.window_mode == "length":
            member = member & (
                b["ords"][None, :] >= b["cum"][:, None] - bside.window_n
            )
        else:  # time window
            member = member & (
                b["ts"][None, :] > ts_i[:, None] - bside.time_ms
            )
        if self.within is not None:
            member = member & (
                jnp.abs(ts_i[:, None] - b["ts"][None, :]) <= self.within
            )

        pair_env: ColumnEnv = {}
        for env_key, tape_key in aside.cols:
            pair_env[env_key] = env[tape_key][:, None]
        for env_key, _tk in bside.cols:
            pair_env[env_key] = b["comb"][env_key][None, :]
        if self.on_fn is not None:
            member = member & self.on_fn(pair_env)

        N = member.shape[1]
        flags = member.reshape(-1)
        ts_mat = jnp.broadcast_to(ts_i[:, None], (E, N)).reshape(-1)
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(pair_env)), (E, N)).reshape(-1)
            for p in self.proj_fns
        )
        if self._nullable:
            cols = cols + (jnp.zeros(E * N, jnp.int32),)  # 0 = real pair
        segs = [(flags, ts_mat, cols)]

        if aside.outer:
            unmatched = a["mask"] & ~member.any(axis=1)
            null_env: ColumnEnv = {}
            for env_key, tape_key in aside.cols:
                null_env[env_key] = env[tape_key]
            for env_key, _tk in bside.cols:
                null_env[env_key] = jnp.zeros(
                    1, b["comb"][env_key].dtype
                )
            ncols = tuple(
                jnp.broadcast_to(jnp.asarray(p(null_env)), (E,))
                for p in self.proj_fns
            )
            # missing-side marker: 1 = right side absent, 2 = left absent
            missing = 1 if bside is self.right else 2
            ncols = ncols + (jnp.full(E, missing, jnp.int32),)
            segs.append((unmatched, ts_i, ncols))
        return segs


def compile_join_query(
    q: ast.Query,
    name: str,
    schemas,
    stream_codes: Dict[str, int],
    extensions,
    config=None,
):
    from .config import DEFAULT_CONFIG

    config = config or DEFAULT_CONFIG
    inp = q.input
    assert isinstance(inp, ast.JoinInput)
    li, ri = inp.left, inp.right
    # self-joins are supported: the resolver side-prefixes env keys so
    # `from S as a join S as b on a.x == b.y` keeps the sides distinct
    resolver = _JoinResolver(li, ri, schemas)

    def side_of(si: ast.StreamInput, outer: bool) -> _Side:
        sres = ExprResolver(
            {si.ref_name: (si.stream_id, schemas[si.stream_id])},
            default_scope=si.ref_name,
        )
        fns = []
        for f in si.filters:
            ce = compile_expr(f, sres, extensions)
            if ce.atype != AttributeType.BOOL:
                raise SiddhiQLError("stream filter must be boolean")
            fns.append(ce.fn)
        w = _window_of(si)
        ring = config.join_window_capacity
        unbounded = False
        if w is None:
            mode, n, tms = "length", ring, None
            unbounded = True
        elif w[0] == "length":
            mode, n, tms = "length", w[1], None
        elif w[0] == "time":
            mode, n, tms = "time", ring, w[1]
        else:
            raise SiddhiQLError(
                f"window #{w[0]} is not supported on a join input "
                "(length/time only)"
            )
        return _Side(
            stream_id=si.stream_id,
            ref=si.ref_name,
            stream_code=stream_codes[si.stream_id],
            filter_fns=fns,
            window_mode=mode,
            window_n=n,
            time_ms=tms,
            cols=[],
            col_types=[],
            outer=outer,
            unbounded=unbounded,
        )

    jt = inp.join_type
    left = side_of(li, jt in ("left outer join", "full outer join"))
    right = side_of(ri, jt in ("right outer join", "full outer join"))

    items = q.selector.items
    if q.selector.is_star:
        items = tuple(
            ast.SelectItem(ast.Attr(f, qualifier=si.ref_name), f"{si.ref_name}_{f}")
            for si in (li, ri)
            for f in schemas[si.stream_id].field_names
        )
    for item in items:
        if ast.contains_aggregate(item.expr):
            raise SiddhiQLError(
                "aggregations over join outputs are not supported yet; "
                "join into an intermediate stream and aggregate that"
            )
    if q.selector.group_by or q.selector.having is not None:
        raise SiddhiQLError(
            "group by / having on a join query is not supported yet"
        )

    on_fn = None
    if inp.on is not None:
        ce = compile_expr(inp.on, resolver, extensions)
        if ce.atype != AttributeType.BOOL:
            raise SiddhiQLError("join 'on' condition must be boolean")
        on_fn = ce.fn

    proj_fns = []
    out_fields = []
    proj_tags: List[frozenset] = []
    for item in items:
        proj_tags.append(
            frozenset(
                resolver.used[resolver.resolve(a).key][0]
                for a in ast.iter_attrs(item.expr)
                if not a.name.startswith("@")
            )
        )
        ce = compile_expr(item.expr, resolver, extensions)
        proj_fns.append(ce.fn)
        out_fields.append(OutputField(item.output_name(), ce.atype, ce.table))

    # which columns each side must buffer in its ring (side-prefixed
    # env keys recorded by the resolver during on/projection compiles)
    for env_key, (tag, tape_key, atype) in sorted(resolver.used.items()):
        side = left if tag == "l" else right
        side.cols.append((env_key, tape_key))
        side.col_types.append(atype)

    art = JoinArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        left=left,
        right=right,
        on_fn=on_fn,
        within=inp.within,
        proj_fns=proj_fns,
        proj_tags=tuple(proj_tags),
        out_factor=config.join_out_factor,
    )
    art.encoded_columns = ()
    return art
