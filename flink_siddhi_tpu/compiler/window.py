"""Windows + aggregations + group-by/having compiled to segment reductions.

Reference semantics being re-expressed (SURVEY.md §2.10): Siddhi sliding
windows emit one aggregated row per *arriving* event over the events currently
in the window (``#window.length(n)``, ``#window.time(t)``, used at
SiddhiCEPITCase.java:315-316,427-428 and group-by at :492-504); batch windows
(``lengthBatch``/``timeBatch``) emit per-group rows when a window tumbles;
aggregation with no window is cumulative from stream start. The reference gets
all of this from per-event JVM hash maps inside siddhi-core; here each shape
becomes a data-parallel device plan:

* sliding windows: ring buffer of the last C matching events carried across
  micro-batches; per batch ONE (E, C) gather builds every event's window, and
  masked reductions over the window axis produce every aggregate at once;
* cumulative: dense group codes (host-interned, schema/encoders.py) + a
  sort-based segmented prefix scan for per-event running values + a
  ``segment_sum``/``min``/``max`` update of the per-group state table;
* batch windows: events map to a (batch-slot, group) segment grid;
  ``segment_*`` reductions aggregate the grid, completed rows flush to a
  fixed-capacity output buffer, the incomplete row is the carry.

Everything is static-shape, branch-free, and jit-compatible: data-dependent
structure (how many events match, how many groups, how many flushes) lives in
masks and fixed-capacity buffers, never in shapes (SURVEY.md §7 hard part 1).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..query import ast
from ..query.lexer import SiddhiQLError
from ..schema.encoders import GroupEncoder
from ..schema.types import AttributeType
from ..runtime.tape import EncodedColumn, time_key
from .compact import batch_rows, front_compact
from .expr import (
    ColumnEnv,
    CompiledExpr,
    ExprResolver,
    ResolvedAttr,
    compile_expr,
    promote,
)
from .output import OutputField, OutputSchema
from .window_merge import blocked_tiling, static_merge, tile_fold

# Bounded slot counts for data-dependent structures (documented limits; a
# production config system can raise them per plan).
TIME_WINDOW_CAPACITY = 512  # max events concurrently inside a #window.time
# the (events x capacity) window matrix of min / max / distinctCount over
# time and of externalTime: beyond this many slots the plan is refused
MATRIX_WINDOW_MAX = 1 << 16
TIME_BATCH_SLOTS = 64  # max distinct timeBatch windows touched per micro-batch
MIN_GROUP_CAPACITY = 64


# --------------------------------------------------------------------------
# Aggregate extraction / expression rewriting
# --------------------------------------------------------------------------

_SUMLIKE_TYPES = {
    AttributeType.INT: AttributeType.LONG,
    AttributeType.LONG: AttributeType.LONG,
    AttributeType.FLOAT: AttributeType.DOUBLE,
    AttributeType.DOUBLE: AttributeType.DOUBLE,
}


@dataclass
class _Agg:
    kind: str  # sum count avg min max stddev distinctcount
    arg_idx: int  # index into distinct arg expressions; -1 = none (count())
    out_type: AttributeType
    slot: str  # env key "@aggN"


class _AggCollector:
    """Dedups aggregate calls and their argument expressions."""

    def __init__(self, resolver: ExprResolver, extensions) -> None:
        self.resolver = resolver
        self.extensions = extensions
        self.aggs: List[_Agg] = []
        self.arg_fns: List[Callable] = []
        self.arg_types: List[AttributeType] = []
        self.arg_exprs: List[ast.Expr] = []
        self._agg_keys: Dict[str, int] = {}
        self._arg_keys: Dict[str, int] = {}

    def _arg_index(self, expr: ast.Expr) -> Tuple[int, AttributeType]:
        key = repr(expr)
        if key in self._arg_keys:
            i = self._arg_keys[key]
            return i, self.arg_types[i]
        ce = compile_expr(expr, self.resolver, self.extensions)
        if not ce.atype.is_numeric and ce.atype != AttributeType.STRING:
            raise SiddhiQLError(
                f"cannot aggregate over type {ce.atype.value}"
            )
        i = len(self.arg_fns)
        self._arg_keys[key] = i
        self.arg_fns.append(ce.fn)
        self.arg_types.append(ce.atype)
        self.arg_exprs.append(expr)
        return i, ce.atype

    def intern(self, call: ast.Call) -> _Agg:
        key = repr(call)
        if key in self._agg_keys:
            return self.aggs[self._agg_keys[key]]
        kind = call.name.lower()
        if kind == "count":
            if len(call.args) > 1:
                raise SiddhiQLError("count() takes at most one argument")
            arg_idx, out_type = -1, AttributeType.LONG
        else:
            if len(call.args) != 1:
                raise SiddhiQLError(f"{kind}() takes exactly one argument")
            arg_idx, arg_type = self._arg_index(call.args[0])
            if kind == "sum":
                if arg_type not in _SUMLIKE_TYPES:
                    raise SiddhiQLError("sum() needs a numeric argument")
                out_type = _SUMLIKE_TYPES[arg_type]
            elif kind in ("avg", "stddev"):
                if not arg_type.is_numeric:
                    raise SiddhiQLError(f"{kind}() needs a numeric argument")
                out_type = AttributeType.DOUBLE
            elif kind in ("min", "max"):
                if not arg_type.is_numeric:
                    raise SiddhiQLError(f"{kind}() needs a numeric argument")
                out_type = arg_type
            elif kind == "distinctcount":
                out_type = AttributeType.LONG
            else:
                raise SiddhiQLError(f"unknown aggregation {call.name!r}")
        agg = _Agg(kind, arg_idx, out_type, f"@agg{len(self.aggs)}")
        self._agg_keys[key] = len(self.aggs)
        self.aggs.append(agg)
        return agg

    def rewrite(self, expr: ast.Expr) -> ast.Expr:
        """Replace aggregate calls with slot references."""
        if ast.is_aggregate_call(expr):
            return ast.Attr(self.intern(expr).slot)
        if isinstance(expr, ast.Unary):
            return ast.Unary(expr.op, self.rewrite(expr.operand))
        if isinstance(expr, ast.Binary):
            return ast.Binary(
                expr.op, self.rewrite(expr.left), self.rewrite(expr.right)
            )
        if isinstance(expr, ast.Call):
            return ast.Call(
                expr.name,
                tuple(self.rewrite(a) for a in expr.args),
                expr.namespace,
            )
        return expr


class _SlotResolver:
    """Resolver layering synthetic env slots (@aggN, select aliases) over the
    stream resolver."""

    def __init__(self, base, slots: Dict[str, AttributeType]) -> None:
        self._base = base
        self._slots = dict(slots)

    def resolve(self, attr: ast.Attr) -> ResolvedAttr:
        if attr.qualifier is None and attr.index is None:
            if attr.name in self._slots:
                return ResolvedAttr(attr.name, self._slots[attr.name], None)
        return self._base.resolve(attr)


def _referenced_keys(
    expr: ast.Expr, resolver, out: Dict[str, AttributeType]
) -> None:
    """Collect tape column keys a rewritten expression reads (skips slots)."""
    if isinstance(expr, ast.Attr):
        if not expr.name.startswith("@"):
            r = resolver.resolve(expr)
            out[r.key] = r.atype
        return
    if isinstance(expr, ast.Unary):
        _referenced_keys(expr.operand, resolver, out)
    elif isinstance(expr, ast.Binary):
        _referenced_keys(expr.left, resolver, out)
        _referenced_keys(expr.right, resolver, out)
    elif isinstance(expr, ast.Call):
        for a in expr.args:
            _referenced_keys(a, resolver, out)


# --------------------------------------------------------------------------
# Shared reduction helpers
# --------------------------------------------------------------------------

def _identity(kind: str, dtype) -> jnp.ndarray:
    if kind == "min":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).max, dtype)
    if kind == "max":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(-jnp.inf, dtype)
        return jnp.asarray(jnp.iinfo(dtype).min, dtype)
    return jnp.asarray(0, dtype)


def _seg_scan(flags, vals, combine_vals):
    """Inclusive segmented scan: runs restart where ``flags`` is True."""

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, combine_vals(va, vb))

    _, out = lax.associative_scan(comb, (flags, vals))
    return out


def _seg_scan_sum_kahan(flags, vals):
    """Compensated inclusive segmented SUM scan: each element carries a
    (sum, err) pair combined with Neumaier two-sum, so an f32 prefix over
    a long run keeps ~f64 accuracy instead of losing every addend below
    the running magnitude's rounding grain. (Two-sum composition is not
    exactly associative; the residual of re-association is itself
    compensated, leaving errors at the 1-ulp-of-err scale.) Returns
    (sum, err) arrays; the corrected prefix is their sum."""

    def comb(a, b):
        fa, sa, ca = a
        fb, sb, cb = b
        t = sa + sb
        err = jnp.where(
            jnp.abs(sa) >= jnp.abs(sb), (sa - t) + sb, (sb - t) + sa
        )
        s = jnp.where(fb, sb, t)
        c = jnp.where(fb, cb, ca + cb + err)
        return fa | fb, s, c

    _, s, c = lax.associative_scan(
        comb, (flags, vals, jnp.zeros_like(vals))
    )
    return s, c


def _acc_stats_for(aggs: Sequence[_Agg]) -> Dict[int, set]:
    """arg_idx -> set of accumulator stats needed ('sum','sumsq','min','max')."""
    need: Dict[int, set] = {}
    for a in aggs:
        if a.arg_idx < 0:
            continue
        s = need.setdefault(a.arg_idx, set())
        if a.kind in ("sum", "avg"):
            s.add("sum")
        elif a.kind == "stddev":
            s.update(("sum", "sumsq"))
        elif a.kind in ("min", "max"):
            s.add(a.kind)
        elif a.kind == "distinctcount":
            raise SiddhiQLError(
                "distinctCount() requires a sliding window "
                "(#window.length/#window.time)"
            )
    return need


class AlignedBlocks:
    """An aligned artifact whose rows the accumulator takes a whole
    tape at a time (the per-key length window, the processing-time
    window): it says itself how many cycles fit."""

    def safe_cycles(self, tape_capacity: int, state: Dict, cap: int) -> int:
        """Cycles the accumulator of ``cap`` rows holds without a swap.
        An aligned block is as wide as the tape whatever ``having``
        keeps, and ``k`` cycles leave at most ``k`` tapes of rows, so
        the next block finds room while ``k * tape_capacity <= cap``:
        the worst case itself, so it takes the whole accumulator;
        rounded down to a power of two, a swap falls on a segment's
        end."""
        k = cap // max(tape_capacity, 1)
        return 1 << (max(k, 1).bit_length() - 1)


# --------------------------------------------------------------------------
# Sliding windows (length / time / externalTime): (E, C) window-matrix plan
# --------------------------------------------------------------------------

@dataclass
class SlidingWindowArtifact:
    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    window_mode: str  # 'length' | 'time'
    capacity: int  # ring slots C (== W for length windows)
    time_ms: Optional[int]  # window span for 'time'
    ts_key: Optional[str]  # externalTime attribute column; None -> tape ts
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    group_fns: List[Callable]
    group_dtypes: List
    proj_fns: List
    proj_types: List[AttributeType]
    having_fn: Optional[Callable]
    output_mode: str = "aligned"
    # dense group codes (host-interned): lets the blocked (sort-free)
    # path one-hot groups onto the MXU instead of argsorting the tape
    code_key: Optional[str] = None
    encoder: Optional[GroupEncoder] = None
    # wire-opt metadata (window_wire_opts): per select item, the tape
    # key when it is a plain attribute reference; every key it reads;
    # and — once activated — the GROUP-KEY INDEX whose code the item
    # emits instead of the raw column (decode maps codes back through
    # the encoder, so the raw group column never ships)
    proj_srcs: Tuple = ()
    proj_refs: Tuple = ()
    filter_keys: frozenset = frozenset()
    group_keys_: Tuple = ()
    group_code_proj: Tuple = ()

    def init_state(self) -> Dict:
        C = self.capacity
        ring = {
            "ts": jnp.zeros(C, jnp.int32),
            "valid": jnp.zeros(C, bool),
        }
        for j, t in enumerate(self.arg_types):
            ring[f"a{j}"] = jnp.zeros(C, t.device_dtype)
        if self._blocked():
            state = {"enabled": jnp.asarray(True)}
            ring["gc"] = jnp.zeros(C, jnp.int32)
            state["ring"] = ring
            # one-hot width placeholder: grow_state re-buckets it as the
            # host encoder discovers groups (one-off retrace per bucket)
            state["groups"] = jnp.zeros(self._gcap(), jnp.int32)
            return state
        for j, dt in enumerate(self.group_dtypes):
            ring[f"g{j}"] = jnp.zeros(C, dt)
        return {"enabled": jnp.asarray(True), "ring": ring}

    def _gcap(self) -> int:
        from ..runtime.tape import bucket_size

        n = len(self.encoder) if self.encoder is not None else 1
        return bucket_size(max(n, 1), minimum=128)

    def grow_state(self, state: Dict) -> Dict:
        if "groups" not in state:
            return state
        if state["groups"].shape[0] >= self._gcap():
            return state
        out = dict(state)
        out["groups"] = jnp.zeros(self._gcap(), jnp.int32)
        return out

    def cost_info(self) -> Dict:
        """Admission-cost descriptor (analysis/admit.py): one aligned
        row per input event; retention is the ring (length windows
        evict by count, time windows by span)."""
        info = {
            "name": self.name,
            "kind": "window",
            "amplification": 1,
            "residency_ms": (
                int(self.time_ms)
                if self.window_mode == "time" and self.time_ms is not None
                else None
            ),
        }
        if self.encoder is not None:
            info["grows_with"] = "groups"
        return info

    def _blocked(self) -> bool:
        """Sort-free tiled path of a LENGTH window: per-group running
        sums over the merged arrival/expiry sequence via one-hot /
        same-group matmuls (MXU work) instead of multi-key argsorts
        (the slow op class on TPU — ~5 sorts of 2(C+E) elements
        dominated this step). The merge is static (``merge_form``,
        window_merge.py).

        Integer sum/avg arguments run EXACTLY through the same matmuls
        by base-2^11 digit decomposition (each digit plane's tile sum
        stays < 2^21, f32-exact; across-tile accumulation is modular
        int32, so the recombined sum wraps exactly like native int32).
        min/max (FIFO expiry makes a window's
        live members the LAST cnt same-group arrivals, a suffix
        property) ride a sparse-table range query over ONE composite-
        key argsort. A processing-time window with count / sum / avg /
        stddev is a ``TimeWindowArtifact`` (time_window.py); min / max
        over time, ``timeLength`` and externalTime (user timestamps
        have no ordering guarantee at all) keep the matrix path."""
        if self.window_mode != "length":
            return False
        if self.group_fns and self.code_key is None:
            return False
        return all(
            a.kind in ("count", "sum", "avg", "stddev", "min", "max")
            for a in self.aggs
        )

    @property
    def merge_form(self) -> Optional[str]:
        """Which merge of arrivals and expiries the step compiles:
        ``'static'`` for a length window on the blocked path
        (window_merge.py), ``'ring'`` for a processing-time window
        (time_window.py: ranked on the device), None for the matrix
        path. The query fixes it; the run loop books it per
        dispatched batch (``window.merge_steps``, ``..._static``)."""
        return "static" if self._blocked() else None

    @jax.named_scope("fst.window_fold")
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        if self._blocked():
            return self._step_blocked(state, tape)
        return self._step_matrix(state, tape)

    def decode_packed(self, n: int, block: "np.ndarray"):
        """Group-coded projection columns decode back through the
        encoder (the raw group column never shipped)."""
        schema = self.output_schema
        gcp = self.group_code_proj
        if not gcp or all(g is None for g in gcp):
            return [(schema, schema.decode_packed_block(n, block))]
        from .output import emission_order

        order = emission_order(block[0], n)
        ts_list = (
            np.asarray(block[0, :n])[order].astype(np.int64).tolist()
        )
        col_lists = []
        for c, f in enumerate(schema.fields):
            raw = np.asarray(block[1 + c, :n])[order]
            gi = gcp[c]
            if gi is not None:
                # append-only encoder: extend the cached LUT instead of
                # rebuilding O(groups) decodes per drain
                cache = getattr(self, "_lut_cache", None)
                if cache is None:
                    cache = self._lut_cache = {}
                lut = cache.setdefault(c, [])
                for i in range(len(lut), len(self.encoder)):
                    lut.append(f.decode(self.encoder.value(i)[gi]))
                col_lists.append([lut[int(v)] for v in raw.tolist()])
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

    def decode_packed_columns(self, n: int, block: "np.ndarray",
                              lookup_np=None):
        """Columnar twin of :meth:`decode_packed`: group codes decode
        through an object-array LUT in one fancy index instead of a
        per-value loop."""
        schema = self.output_schema
        gcp = self.group_code_proj
        if not gcp or all(g is None for g in gcp):
            return [(schema, schema.decode_packed_columns(n, block))]
        from .output import ColumnBatch, emission_order

        order = emission_order(block[0], n)
        ts_out = np.asarray(block[0, :n])[order].astype(np.int64)
        cache = getattr(self, "_lut_cache", None)
        if cache is None:
            cache = self._lut_cache = {}
        arr_cache = getattr(self, "_lut_arr_cache", None)
        if arr_cache is None:
            arr_cache = self._lut_arr_cache = {}
        cols = {}
        for c, f in enumerate(schema.fields):
            raw = np.asarray(block[1 + c, :n])[order]
            gi = gcp[c]
            if gi is not None:
                lut = cache.setdefault(c, [])
                for i in range(len(lut), len(self.encoder)):
                    lut.append(f.decode(self.encoder.value(i)[gi]))
                arr = arr_cache.get(c)
                if arr is None or len(arr) != len(lut):
                    arr = np.empty(len(lut), dtype=object)
                    arr[:] = lut
                    arr_cache[c] = arr
                cols[f.name] = arr[raw.astype(np.int64)]
            else:
                if np.dtype(f.atype.device_dtype) == np.dtype(np.float32):
                    raw = raw.view(np.float32)
                cols[f.name] = f.decode_column_np(raw)
        return [(schema, ColumnBatch(ts_out, cols))]

    # -- blocked (no sort by group) sliding aggregation -------------------
    def _step_blocked(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        """Windowed per-group sums without a sort of the tape by group.

        Same semantics as ``_step_matrix`` (window = last C matching
        events / time span; aggregates over the emitting event's group),
        other machinery. Arrivals front-compact through one sort of the
        tape's positions keyed on the mask alone and one gather of the
        fold's columns in that order, or not at all where the mask is a
        prefix already (compact.py). The concat sequence, ring ++
        arrivals, is then
        merged with its own expiries (window_merge.py): a length
        window's order is fixed by C and E, so ``static_merge`` cuts
        the tiles from the sequence with slices and no index array
        exists. ``tile_fold`` computes the
        per-group running sum of the merged sequence in tiles: a [t,G]
        one-hot matmul gives per-tile group totals whose exclusive scan
        is the across-tile carry (read back with the one gather a
        length window's step has left), and a same-group matmul under
        the merge's precedence matrix gives the within-tile prefix."""
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        C = self.capacity
        ring = state["ring"]
        G = state["groups"].shape[0]

        def tape_col(col, dtype=None):
            col = jnp.broadcast_to(jnp.asarray(col), (E,))
            return col if dtype is None else col.astype(dtype)

        # value columns: one per agg arg needing sums, plus squares for
        # stddev, plus an implicit count column. INTEGER sum args are
        # decomposed into three base-2^11 digit planes: each plane's
        # per-tile matmul sum stays < 2^21 (f32-exact); the across-tile
        # carry then runs in modular int32, and the recombination
        # d0 + (d1<<11) + (d2<<22) reproduces native int32 wrap-around
        # exactly (two's-complement arithmetic-shift identity).
        need_sq = sorted(
            {a.arg_idx for a in self.aggs if a.kind == "stddev"}
        )
        need_sum = sorted(
            {
                a.arg_idx
                for a in self.aggs
                if a.kind in ("sum", "avg", "stddev")
            }
        )
        int_sum = {
            j
            for j in need_sum
            if not jnp.issubdtype(
                np.dtype(self.arg_types[j].device_dtype), jnp.floating
            )
        }

        # every column the fold reads in arrival order (the ring's copy
        # of each argument included) goes through ONE front-compaction
        tape_cols = {"ts": tape_col(tape.ts)}
        for j in need_sum:
            tape_cols[f"s{j}"] = tape_col(self.arg_fns[j](env))
        for j in need_sq:
            tape_cols[f"q{j}"] = tape_col(self.arg_fns[j](env), jnp.float32)
        for j in range(len(self.arg_types)):
            tape_cols[f"a{j}"] = tape_col(
                self.arg_fns[j](env), ring[f"a{j}"].dtype
            )
        if self.code_key is not None:
            tape_cols["gc"] = tape_col(env[self.code_key], jnp.int32)
        M, arrivals, is_prefix = front_compact(mask, tape_cols)

        def digits(v):
            v = v.astype(jnp.int32)
            return (
                (v & 0x7FF).astype(jnp.float32),
                ((v >> 11) & 0x7FF).astype(jnp.float32),
                (v >> 22).astype(jnp.float32),
            )

        vcols = []  # batch-side planes (compacted, f32)
        rcols = []  # ring-side planes (f32)
        vmap: Dict[str, int] = {}
        int_planes: List[int] = []  # plane indices carried in int32

        def plane(name, batch, ringv, isint=False):
            if isint:
                int_planes.append(len(vcols))
            vmap[name] = len(vcols)
            vcols.append(batch)
            rcols.append(ringv)

        for j in need_sum:
            bv = arrivals[f"s{j}"]
            rv = ring[f"a{j}"]
            if j in int_sum:
                for d, (bd, rd) in enumerate(
                    zip(digits(bv), digits(rv))
                ):
                    plane(f"s{j}:{d}", bd, rd, isint=True)
            else:
                plane(
                    f"s{j}",
                    bv.astype(jnp.float32),
                    rv.astype(jnp.float32),
                )
        for j in need_sq:
            v = arrivals[f"q{j}"]
            rv = ring[f"a{j}"].astype(jnp.float32)
            plane(f"q{j}", v * v, rv * rv)
        plane("cnt", jnp.ones(E, jnp.float32), jnp.ones(C, jnp.float32))

        if self.code_key is not None:
            codes_b = arrivals["gc"]
            ring_gc = ring["gc"]
        else:
            codes_b = jnp.zeros(E, jnp.int32)
            ring_gc = jnp.zeros(C, jnp.int32)
        ts_b = arrivals["ts"]
        live_b = jnp.arange(E, dtype=jnp.int32) < M

        # concat sequence: ring (oldest C) ++ this batch's arrivals
        N = C + E
        codes = jnp.concatenate([ring_gc, codes_b])
        ts_n = jnp.concatenate([ring["ts"], ts_b])
        live = jnp.concatenate([ring["valid"], live_b])
        V_n = jnp.stack(
            [
                jnp.concatenate([rv, bv])
                for rv, bv in zip(rcols, vcols)
            ],
            axis=1,
        )  # [N, K]

        # the merge of arrivals and expiries: a length window's order is
        # a fact of C and E
        tile, chunk = blocked_tiling()
        merged = static_merge(codes, live, V_n, C, tile, chunk)
        planes = tile_fold(merged, G, int_planes, chunk)

        def wcol(name):
            return planes[vmap[name]]

        def int_sum_of(j):
            return (
                wcol(f"s{j}:0")
                + (wcol(f"s{j}:1") << 11)
                + (wcol(f"s{j}:2") << 22)
            )

        cnt = wcol("cnt")
        minmax = [a for a in self.aggs if a.kind in ("min", "max")]
        ext = (
            self._blocked_extrema(
                minmax, ring, codes, live, arrivals, cnt, N
            )
            if minmax
            else {}
        )
        concat_rows = {}  # per aggregate, one value per concat position
        for agg in self.aggs:
            if agg.kind == "count":
                rows = cnt
            elif agg.kind in ("min", "max"):
                rows = ext[(agg.kind, agg.arg_idx)]
            elif agg.kind == "sum":
                if agg.arg_idx in int_sum:
                    rows = int_sum_of(agg.arg_idx)
                else:
                    rows = wcol(f"s{agg.arg_idx}")
                    if not jnp.issubdtype(
                        agg.out_type.device_dtype, jnp.floating
                    ):
                        rows = jnp.round(rows)
            elif agg.kind == "avg":
                num = (
                    int_sum_of(agg.arg_idx).astype(jnp.float32)
                    if agg.arg_idx in int_sum
                    else wcol(f"s{agg.arg_idx}")
                )
                rows = num / jnp.maximum(cnt, 1.0)
            else:  # stddev
                c_ = jnp.maximum(cnt, 1.0)
                mean = (
                    int_sum_of(agg.arg_idx).astype(jnp.float32)
                    if agg.arg_idx in int_sum
                    else wcol(f"s{agg.arg_idx}")
                ) / c_
                rows = jnp.sqrt(
                    jnp.maximum(
                        wcol(f"q{agg.arg_idx}") / c_ - mean * mean,
                        0.0,
                    )
                )
            concat_rows[agg.slot] = rows
        # back to tape order: ONE gather for all aggregates (a slice
        # where the mask is a prefix)
        by_slot = batch_rows(mask, is_prefix, concat_rows, C)
        for agg in self.aggs:
            env[agg.slot] = jnp.where(mask, by_slot[agg.slot], 0).astype(
                agg.out_type.device_dtype
            )

        out_mask, cols = self._project(env, mask, E)

        # FIFO ring: last C live entries of [ring ++ arrivals]
        new_ring = {
            "ts": lax.dynamic_slice(ts_n, (M,), (C,)),
            "valid": lax.dynamic_slice(live, (M,), (C,)),
        }
        for j, _t in enumerate(self.arg_types):
            cat = jnp.concatenate([ring[f"a{j}"], arrivals[f"a{j}"]])
            new_ring[f"a{j}"] = lax.dynamic_slice(cat, (M,), (C,))
        if self.code_key is not None:
            cat = jnp.concatenate([ring_gc, codes_b])
            new_ring["gc"] = lax.dynamic_slice(cat, (M,), (C,))
        else:
            new_ring["gc"] = jnp.zeros(C, jnp.int32)
        new_state = {
            "enabled": state["enabled"],
            "ring": new_ring,
            "groups": state["groups"],
        }
        return new_state, (out_mask, tape.ts, cols)

    def _project(self, env, mask, E: int):
        """``(out_mask, cols)`` of a step whose aggregates are in
        ``env``: the select items over the tape's width (a group-by
        column that travels as its code, ``group_code_proj``, is the
        code) and ``having`` over them."""
        gcp = self.group_code_proj or (None,) * len(self.proj_fns)
        cols = tuple(
            jnp.broadcast_to(
                jnp.asarray(
                    env[self.code_key] if gi is not None else p(env)
                ),
                (E,),
            )
            for p, gi in zip(self.proj_fns, gcp)
        )
        out_mask = mask
        if self.having_fn is not None:
            henv = dict(env)
            for f, c_ in zip(self.output_schema.fields, cols):
                henv[f"@out:{f.name}"] = c_
            out_mask = out_mask & self.having_fn(henv)
        return out_mask, cols

    def _blocked_extrema(
        self, minmax, ring, codes, live, arrivals, cnt, N
    ) -> Dict:
        """min/max for blocked LENGTH windows: FIFO expiry makes a
        window's live members the LAST cnt same-group arrivals — a
        contiguous range after a group-major (position-stable,
        invalid-last) ordering — answered by a sparse table: log-depth
        build, two gathers per arrival. The multi-key stable sorts of
        the retired prefix path collapse to ONE argsort on a composite
        (dense group code, position) key."""
        pos = jnp.arange(N, dtype=jnp.int32)
        # concat order IS position order, so a STABLE sort by (invalid-
        # last, group code) alone yields group-major position-stable
        # order — one int32 sort, no composite key
        key = jnp.where(live, codes, jnp.int32(2 ** 31 - 1))
        ao = jnp.argsort(key, stable=True)
        rmq_rank = jnp.zeros(N, jnp.int32).at[ao].set(pos)
        cnt_q = jnp.maximum(cnt.astype(jnp.int32), 1)
        levels = max(1, int(np.ceil(np.log2(max(N, 2)))))
        lvl = jnp.zeros(N, jnp.int32)
        for k in range(1, levels + 1):
            lvl = lvl + (cnt_q >= (1 << k)).astype(jnp.int32)
        pow_l = jnp.int32(1) << lvl
        out: Dict = {}
        for agg in minmax:
            j = agg.arg_idx
            rv = ring[f"a{j}"]
            vals = jnp.concatenate([rv, arrivals[f"a{j}"]])
            combine = jnp.minimum if agg.kind == "min" else jnp.maximum
            if jnp.issubdtype(vals.dtype, jnp.floating):
                ident = jnp.asarray(
                    jnp.inf if agg.kind == "min" else -jnp.inf,
                    vals.dtype,
                )
            else:
                info = np.iinfo(np.dtype(vals.dtype))
                ident = jnp.asarray(
                    info.max if agg.kind == "min" else info.min,
                    vals.dtype,
                )
            a_sorted = jnp.where(live, vals, ident)[ao]
            table = [a_sorted]
            for k in range(levels):
                span = 1 << k
                table.append(
                    combine(
                        table[-1],
                        jnp.concatenate(
                            [
                                jnp.full(span, ident, a_sorted.dtype),
                                table[-1][:-span],
                            ]
                        ),
                    )
                )
            flat = jnp.stack(table).reshape(-1)
            v1 = flat[lvl * N + rmq_rank]
            r2 = jnp.clip(rmq_rank - cnt_q + pow_l, 0, N - 1)
            v2 = flat[lvl * N + r2]
            out[(agg.kind, j)] = combine(v1, v2)
        return out

    def _step_matrix(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        C = self.capacity
        ring = state["ring"]

        order = jnp.argsort(jnp.logical_not(mask))  # matching first, stable
        M = mask.sum()
        rank = jnp.cumsum(mask) - 1  # per-position compacted index

        def cat(ring_col, col):
            col = jnp.broadcast_to(jnp.asarray(col), (E,))
            return jnp.concatenate(
                [ring_col, col[order].astype(ring_col.dtype)]
            )

        c_cols: Dict[str, jnp.ndarray] = {}
        for j, fn in enumerate(self.arg_fns):
            c_cols[f"a{j}"] = cat(ring[f"a{j}"], fn(env))
        for j, fn in enumerate(self.group_fns):
            c_cols[f"g{j}"] = cat(ring[f"g{j}"], fn(env))
        ts_col = env[self.ts_key] if self.ts_key else tape.ts
        c_cols["ts"] = cat(ring["ts"], ts_col)
        cval = jnp.concatenate([ring["valid"], jnp.arange(E) < M])

        # every row k = the last C matching events ending at compacted k
        idx = jnp.arange(E)[:, None] + 1 + jnp.arange(C)[None, :]
        win = {k: v[idx] for k, v in c_cols.items()}
        member = cval[idx]
        if self.window_mode in ("time", "timeLength"):
            cur_ts = win["ts"][:, -1:]
            member = member & (win["ts"] > cur_ts - self.time_ms)
        for j in range(len(self.group_fns)):
            g = win[f"g{j}"]
            member = member & (g == g[:, -1:])

        def unsort(rows, dtype):
            r = rows[jnp.clip(rank, 0)]
            return jnp.where(mask, r, 0).astype(dtype)

        slot_types: Dict[str, AttributeType] = {}
        for agg in self.aggs:
            rows = self._reduce(agg, member, win)
            env[agg.slot] = unsort(rows, agg.out_type.device_dtype)
            slot_types[agg.slot] = agg.out_type

        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(env)), (E,))
            for p in self.proj_fns
        )
        out_mask = mask
        if self.having_fn is not None:
            henv = dict(env)
            for f, c in zip(self.output_schema.fields, cols):
                henv[f"@out:{f.name}"] = c
            out_mask = out_mask & self.having_fn(henv)

        new_ring = {
            k: lax.dynamic_slice(v, (M,), (C,)) for k, v in c_cols.items()
        }
        new_ring["valid"] = lax.dynamic_slice(cval, (M,), (C,))
        new_state = {"enabled": state["enabled"], "ring": new_ring}
        return new_state, (out_mask, tape.ts, cols)

    def _reduce(self, agg: _Agg, member, win):
        if agg.kind == "count":
            return member.sum(axis=1)
        vals = win[f"a{agg.arg_idx}"]
        if agg.kind == "sum":
            return jnp.where(member, vals, 0).sum(axis=1)
        if agg.kind in ("min", "max"):
            ident = _identity(agg.kind, vals.dtype)
            masked = jnp.where(member, vals, ident)
            return masked.min(axis=1) if agg.kind == "min" else masked.max(
                axis=1
            )
        if agg.kind == "avg":
            s = jnp.where(member, vals, 0).astype(jnp.float32).sum(axis=1)
            c = jnp.maximum(member.sum(axis=1), 1)
            return s / c
        if agg.kind == "stddev":
            v = vals.astype(jnp.float32)
            s = jnp.where(member, v, 0).sum(axis=1)
            s2 = jnp.where(member, v * v, 0).sum(axis=1)
            c = jnp.maximum(member.sum(axis=1), 1)
            mean = s / c
            return jnp.sqrt(jnp.maximum(s2 / c - mean * mean, 0.0))
        if agg.kind == "distinctcount":
            # first-occurrence count within each row's window
            eq = vals[:, :, None] == vals[:, None, :]
            both = member[:, :, None] & member[:, None, :]
            earlier = jnp.tril(jnp.ones((eq.shape[1],) * 2, bool), k=-1)
            dup = (eq & both & earlier[None]).any(axis=2)
            return (member & ~dup).sum(axis=1)
        raise AssertionError(agg.kind)


# --------------------------------------------------------------------------
# Cumulative aggregation (no window): per-group state table + segmented scan
# --------------------------------------------------------------------------

@dataclass
class CumulativeAggArtifact:
    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    code_key: Optional[str]  # encoded group column; None -> single group
    encoder: Optional[GroupEncoder]
    proj_fns: List
    having_fn: Optional[Callable]
    output_mode: str = "aligned"
    # chained-input group-by: the group VALUES exist only on device (the
    # producer's emissions), so instead of a host-built code column the
    # device maps values -> codes through a sorted intern table synced
    # from the (intern-only) host encoder each cycle
    chained_group_src: Optional[str] = None
    chained_group_dtype: object = None

    def _stats(self) -> Dict[int, set]:
        return _acc_stats_for(self.aggs)

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: running aggregates — one row per
        event, no events retained (per-group scalar state only)."""
        info = {
            "name": self.name,
            "kind": "aggregate",
            "amplification": 1,
            "residency_ms": 0,
        }
        if self.encoder is not None:
            info["grows_with"] = "groups"
        return info

    def _chained_tables(self, G: int):
        """(sorted values, codes) arrays for the device value->code map.
        Cached on (encoder size, G): grow_state calls this every cycle
        and the rebuild is O(G) host work + two uploads."""
        cached = getattr(self, "_ct_cache", None)
        if cached is not None and cached[0] == (len(self.encoder), G):
            # fresh device buffers each call: the jitted step DONATES
            # its state inputs, so a cached jax array would be a deleted
            # buffer by the second micro-batch
            return jnp.asarray(cached[1]), jnp.asarray(cached[2])
        vals = np.asarray(
            [self.encoder.value(i)[0] for i in range(len(self.encoder))],
            dtype=self.chained_group_dtype,
        )
        order = np.argsort(vals, kind="stable")
        gv = np.full(G, np.inf if np.issubdtype(
            np.dtype(self.chained_group_dtype), np.floating
        ) else np.iinfo(np.dtype(self.chained_group_dtype)).max,
            dtype=self.chained_group_dtype)
        gc = np.zeros(G, np.int32)
        gv[: len(vals)] = vals[order]
        gc[: len(vals)] = order.astype(np.int32)
        self._ct_cache = ((len(self.encoder), G), gv, gc)
        return jnp.asarray(gv), jnp.asarray(gc)

    def _group_codes(self, env, state):
        """Group code per tape position: the host-built code column, or
        the on-device sorted-table lookup for chained inputs."""
        if self.chained_group_src is None:
            return env[self.code_key].astype(jnp.int32)
        vals = env[self.chained_group_src].astype(state["@gv"].dtype)
        pos = jnp.clip(
            jnp.searchsorted(state["@gv"], vals, side="left"),
            0, state["@gv"].shape[0] - 1,
        )
        return state["@gc"][pos]

    def init_state(self) -> Dict:
        G = (
            _bucket(len(self.encoder), MIN_GROUP_CAPACITY)
            if self.encoder is not None
            else 1
        )
        st = {"enabled": jnp.asarray(True), "cnt": jnp.zeros(G, jnp.int32)}
        if self.chained_group_src is not None:
            st["@gv"], st["@gc"] = self._chained_tables(G)
        for arg_idx, stats in self._stats().items():
            dt = self.arg_types[arg_idx].device_dtype
            for s in stats:
                if s in ("sum", "sumsq"):
                    adt = (
                        jnp.float32
                        if jnp.issubdtype(dt, jnp.floating) or s == "sumsq"
                        else jnp.int32
                    )
                    st[f"{s}{arg_idx}"] = jnp.zeros(G, adt)
                    if adt == jnp.float32:
                        # Neumaier compensation: an UNBOUNDED f32 running
                        # sum otherwise silently loses every update once
                        # the accumulated magnitude outgrows the mantissa
                        # (round-3 verdict item 6; Siddhi double is f64
                        # end-to-end)
                        st[f"kc_{s}{arg_idx}"] = jnp.zeros(G, adt)
                else:
                    st[f"{s}{arg_idx}"] = jnp.full(
                        G, _identity(s, dt), dt
                    )
        return st

    def grow_state(self, state: Dict) -> Dict:
        if self.encoder is None:
            return state
        G = state["cnt"].shape[0]
        need = _bucket(len(self.encoder), MIN_GROUP_CAPACITY)
        if need <= G:
            if self.chained_group_src is not None:
                out = dict(state)
                out["@gv"], out["@gc"] = self._chained_tables(G)
                return out
            return state
        out = dict(state)
        for k, v in state.items():
            if k == "enabled" or k.startswith("@g"):
                continue
            pad_val = (
                _identity(k[:3], v.dtype)
                if k.startswith(("min", "max"))
                else jnp.asarray(0, v.dtype)
            )
            out[k] = jnp.concatenate(
                [v, jnp.full(need - G, pad_val, v.dtype)]
            )
        if self.chained_group_src is not None:
            out["@gv"], out["@gc"] = self._chained_tables(need)
        return out

    @jax.named_scope("fst.window_fold")
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        G = state["cnt"].shape[0]

        if self.code_key is not None:
            g = self._group_codes(env, state)
        else:
            g = jnp.zeros(E, jnp.int32)
        segkey = jnp.where(mask, g, G)
        order = jnp.argsort(segkey)  # stable: groups contiguous, pad last
        inv = jnp.argsort(order)
        g_s = segkey[order]
        flags = jnp.concatenate(
            [jnp.ones(1, bool), g_s[1:] != g_s[:-1]]
        )
        gather_g = jnp.clip(g_s, 0, G - 1)

        # per-event running count (prefix within batch + carried totals)
        ones = jnp.ones(E, jnp.int32)
        pre_cnt = _seg_scan(flags, ones, jnp.add) + state["cnt"][gather_g]
        stats_env: Dict[str, jnp.ndarray] = {"cnt": pre_cnt[inv]}

        seg_tot_cnt = jax.ops.segment_sum(
            mask.astype(jnp.int32), segkey, num_segments=G + 1
        )[:G]
        new_state = dict(state)
        new_state["cnt"] = state["cnt"] + seg_tot_cnt

        for arg_idx, stats in self._stats().items():
            v = self.arg_fns[arg_idx](env)
            v = jnp.broadcast_to(jnp.asarray(v), (E,))
            v_s = v[order]
            for s in stats:
                key = f"{s}{arg_idx}"
                acc = state[key]
                if s in ("sum", "sumsq"):
                    vv_s = v_s.astype(acc.dtype)
                    if s == "sumsq":
                        vv_s = vv_s * vv_s
                    vv_s = jnp.where(mask[order], vv_s, 0)
                    kc = state.get(f"kc_{key}")
                    if kc is None:
                        # integer accumulators are exact: plain scan
                        pre = (
                            _seg_scan(flags, vv_s, jnp.add)
                            + acc[gather_g]
                        )
                        stats_env[key] = pre[inv]
                        tot = jax.ops.segment_sum(
                            vv_s[inv], segkey, num_segments=G + 1
                        )[:G]
                        new_state[key] = acc + tot
                    else:
                        # f32 running sums: compensated scan within the
                        # batch + Neumaier two-sum into the carried
                        # accumulator — an unbounded cumulative sum must
                        # not stall once its magnitude outgrows the
                        # mantissa (round-3 verdict item 6)
                        s_scan, c_scan = _seg_scan_sum_kahan(
                            flags, vv_s
                        )
                        base = acc + kc
                        pre = (s_scan + c_scan) + base[gather_g]
                        stats_env[key] = pre[inv]
                        ends = jnp.concatenate(
                            [flags[1:], jnp.ones(1, bool)]
                        )
                        gi = jnp.where(ends & (g_s < G), g_s, G)
                        tot = jnp.zeros(G + 1, acc.dtype).at[gi].add(
                            jnp.where(ends, s_scan, 0), mode="drop"
                        )[:G]
                        tot_c = jnp.zeros(G + 1, acc.dtype).at[gi].add(
                            jnp.where(ends, c_scan, 0), mode="drop"
                        )[:G]
                        t = acc + tot
                        err = jnp.where(
                            jnp.abs(acc) >= jnp.abs(tot),
                            (acc - t) + tot,
                            (tot - t) + acc,
                        )
                        new_state[key] = t
                        new_state[f"kc_{key}"] = kc + err + tot_c
                else:
                    ident = _identity(s, acc.dtype)
                    comb = jnp.minimum if s == "min" else jnp.maximum
                    vv_s = jnp.where(
                        mask[order], v_s.astype(acc.dtype), ident
                    )
                    pre = comb(
                        _seg_scan(flags, vv_s, comb), acc[gather_g]
                    )
                    stats_env[key] = pre[inv]
                    seg_fn = (
                        jax.ops.segment_min
                        if s == "min"
                        else jax.ops.segment_max
                    )
                    tot = seg_fn(
                        jnp.where(mask, v.astype(acc.dtype), ident),
                        segkey,
                        num_segments=G + 1,
                    )[:G]
                    new_state[key] = comb(acc, tot)

        for agg in self.aggs:
            env[agg.slot] = _agg_from_stats(agg, stats_env).astype(
                agg.out_type.device_dtype
            )

        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(env)), (E,))
            for p in self.proj_fns
        )
        out_mask = mask
        if self.having_fn is not None:
            henv = dict(env)
            for f, c in zip(self.output_schema.fields, cols):
                henv[f"@out:{f.name}"] = c
            out_mask = out_mask & self.having_fn(henv)
        return new_state, (out_mask, tape.ts, cols)


def _agg_from_stats(agg: _Agg, stats: Dict[str, jnp.ndarray]):
    cnt = stats["cnt"]
    if agg.kind == "count":
        return cnt
    key = lambda s: stats[f"{s}{agg.arg_idx}"]
    if agg.kind == "sum":
        return key("sum")
    if agg.kind in ("min", "max"):
        return key(agg.kind)
    safe_cnt = jnp.maximum(cnt, 1)
    if agg.kind == "avg":
        return key("sum").astype(jnp.float32) / safe_cnt
    if agg.kind == "stddev":
        mean = key("sum").astype(jnp.float32) / safe_cnt
        m2 = key("sumsq").astype(jnp.float32) / safe_cnt
        return jnp.sqrt(jnp.maximum(m2 - mean * mean, 0.0))
    raise AssertionError(agg.kind)


# --------------------------------------------------------------------------
# Batch (tumbling) windows: lengthBatch / timeBatch segment grids
# --------------------------------------------------------------------------

@dataclass
class BatchWindowArtifact:
    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    window_mode: str  # 'lengthBatch' | 'timeBatch'
    length: Optional[int]  # lengthBatch n
    time_ms: Optional[int]  # timeBatch span
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    code_key: Optional[str]
    encoder: Optional[GroupEncoder]
    # non-aggregate projection inputs: "last event of the group in the
    # window" values, keyed by tape column
    last_keys: List[str]
    last_types: List[AttributeType]
    proj_fns: List
    having_fn: Optional[Callable]
    output_mode: str = "buffered"
    batch_slots: int = TIME_BATCH_SLOTS
    # externalTimeBatch: window boundaries follow this tape column's
    # values instead of event time
    ts_key: Optional[str] = None
    # cron: window boundaries are host-computed per-event window ids
    # (utils/cron.py enumerates Quartz fires; "an emission schedule,
    # not device math"). A window completes when a LATER-window event
    # exists — the event-driven equivalent of the timer firing, same
    # deviation documented for session windows.
    wid_key: Optional[str] = None

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        """Widest per-cycle emission block: every window-grid cell can
        flush (drain-cadence contract)."""
        return self._grid_shape(tape_capacity) * self._G(state)

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: batch windows emit one aggregate
        row per closed window per group — per input event that is
        amortized <= 1; retention is one batch span."""
        res = None
        if self.window_mode == "timeBatch" and self.time_ms is not None:
            res = int(self.time_ms)
        info = {
            "name": self.name,
            "kind": "batch_window",
            "amplification": 1,
            "residency_ms": res,
        }
        if self.encoder is not None:
            info["grows_with"] = "groups"
        return info

    def _G(self, state) -> int:
        return state["cnt"].shape[0]

    def _stats(self) -> Dict[int, set]:
        return _acc_stats_for(self.aggs)

    def init_state(self) -> Dict:
        G = (
            _bucket(len(self.encoder), MIN_GROUP_CAPACITY)
            if self.encoder is not None
            else 1
        )
        st = {
            "enabled": jnp.asarray(True),
            # current (incomplete) window accumulators, per group
            "cnt": jnp.zeros(G, jnp.int32),
            "ts": jnp.zeros(G, jnp.int32),
            "seen": jnp.asarray(0, jnp.int32),  # total matching ever
            "batch": jnp.asarray(-1, jnp.int32),  # current window ordinal
            "t0": jnp.asarray(-1, jnp.int32),  # first-ever event ts
        }
        for arg_idx, stats in self._stats().items():
            dt = self.arg_types[arg_idx].device_dtype
            for s in stats:
                if s in ("sum", "sumsq"):
                    adt = (
                        jnp.float32
                        if jnp.issubdtype(dt, jnp.floating) or s == "sumsq"
                        else jnp.int32
                    )
                    st[f"{s}{arg_idx}"] = jnp.zeros(G, adt)
                else:
                    st[f"{s}{arg_idx}"] = jnp.full(G, _identity(s, dt), dt)
        for j, t in enumerate(self.last_types):
            st[f"last{j}"] = jnp.zeros(G, t.device_dtype)
        return st

    def grow_state(self, state: Dict) -> Dict:
        if self.encoder is None:
            return state
        G = self._G(state)
        need = _bucket(len(self.encoder), MIN_GROUP_CAPACITY)
        if need <= G:
            return state
        out = dict(state)
        for k, v in state.items():
            if v.ndim == 0:
                continue
            pad_val = (
                _identity(k[:3], v.dtype)
                if k.startswith(("min", "max"))
                else jnp.asarray(0, v.dtype)
            )
            out[k] = jnp.concatenate(
                [v, jnp.full(need - G, pad_val, v.dtype)]
            )
        return out

    # -- helpers ------------------------------------------------------------

    def _grid_shape(self, E: int) -> int:
        if self.window_mode == "lengthBatch":
            return E // self.length + 2
        return self.batch_slots + 1

    @jax.named_scope("fst.window_fold")
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        G = self._G(state)
        B = self._grid_shape(E)
        NS = B * G

        if self.code_key is not None:
            g = env[self.code_key].astype(jnp.int32)
        else:
            g = jnp.zeros(E, jnp.int32)

        M = mask.sum()
        rank = jnp.cumsum(mask) - 1  # 0-based matching ordinal in tape

        if self.window_mode == "lengthBatch":
            n = self.length
            seq = state["seen"] + rank  # global matching ordinal
            abs_batch = seq // n
            first_batch = jnp.maximum(state["batch"], 0)
            row = abs_batch - first_batch  # carry merges into row 0
            new_seen = state["seen"] + M
            new_batch = jnp.where(
                new_seen > 0, new_seen // n, jnp.asarray(-1)
            )
            t0 = state["t0"]
            # row r (abs batch first_batch+r) is complete when its last
            # ordinal exists: (first_batch+r+1)*n <= new_seen
            rows = jnp.arange(B, dtype=jnp.int32)
            completed = (first_batch + rows + 1) * n <= new_seen
        else:
            T = self.time_ms
            ts = (
                env[self.ts_key].astype(jnp.int32)
                if self.ts_key is not None
                else tape.ts
            )
            if self.wid_key is not None:  # cron window ids, host-made
                t0 = state["t0"]
                abs_batch = jnp.where(
                    mask, env[self.wid_key].astype(jnp.int32), 0
                ).astype(jnp.int32)
            else:
                first_ts = jnp.where(
                    M > 0,
                    jnp.min(
                        jnp.where(mask, ts, jnp.iinfo(jnp.int32).max)
                    ),
                    0,
                )
                # (not t0 >= 0: a rebased time attribute may start
                # before the job's clock origin)
                t0 = jnp.where(state["seen"] > 0, state["t0"], first_ts)
                abs_batch = jnp.where(
                    mask, (ts - t0) // T, 0
                ).astype(jnp.int32)
            # dense-rank distinct windows in this tape; carry window is row 0
            # (merging when the tape still starts in the carried window)
            sortable = jnp.where(mask, abs_batch, jnp.iinfo(jnp.int32).max)
            order = jnp.argsort(sortable)
            inv = jnp.argsort(order)
            ab_s = sortable[order]
            newrun = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), (ab_s[1:] != ab_s[:-1]).astype(jnp.int32)]
            )
            rank_s = jnp.cumsum(newrun)
            dense = rank_s[inv]  # dense window index within tape, 0-based
            carry_batch = state["batch"]
            tape_first_batch = jnp.where(M > 0, ab_s[0], carry_batch)
            shift = jnp.where(
                (carry_batch >= 0) & (tape_first_batch != carry_batch), 1, 0
            )
            row = dense + shift
            first_batch = jnp.where(carry_batch >= 0, carry_batch, tape_first_batch)
            # absolute batch per row, for completion checks
            rows = jnp.arange(B, dtype=jnp.int32)
            row_batch = jax.ops.segment_max(
                jnp.where(mask, abs_batch, -(2 ** 31) + 1),
                jnp.where(mask, row, B).astype(jnp.int32),
                num_segments=B + 1,
            )[:B]
            row_batch = row_batch.at[0].set(
                jnp.where(carry_batch >= 0, carry_batch, row_batch[0])
            )
            last_ts = jnp.max(jnp.where(mask, ts, -(2 ** 31) + 1))
            max_tape_batch = jnp.max(
                jnp.where(mask, abs_batch, -(2 ** 31) + 1)
            )
            if self.wid_key is not None:
                # cron: a window is complete once a LATER-window event
                # exists (event-driven fire; wall timers don't run on
                # device — the engine-wide emission-timing deviation)
                latest = jnp.maximum(carry_batch, max_tape_batch)
                completed = (
                    (row_batch > -(2 ** 31) + 1) & (row_batch < latest)
                )
            else:
                # a window is complete once an event at/after its end
                # exists
                completed = (
                    (row_batch > -(2 ** 31) + 1)
                    & (last_ts >= t0 + (row_batch + 1) * T)
                )
            new_seen = state["seen"] + M
            new_batch = jnp.where(
                M > 0, jnp.maximum(carry_batch, max_tape_batch), carry_batch
            )

        row = jnp.clip(row, 0, B - 1)
        seg = jnp.where(mask, row * G + g, NS).astype(jnp.int32)

        # --- aggregate the (row, group) grid -------------------------------
        tape_cnt = jax.ops.segment_sum(
            mask.astype(jnp.int32), seg, num_segments=NS + 1
        )[:NS].reshape(B, G)
        had_tape = tape_cnt > 0
        cnt_grid = tape_cnt.at[0].add(state["cnt"])
        ts_grid = jax.ops.segment_max(
            jnp.where(mask, tape.ts, -(2 ** 31) + 1),
            seg,
            num_segments=NS + 1,
        )[:NS].reshape(B, G)
        ts_grid = ts_grid.at[0].set(
            jnp.maximum(ts_grid[0], jnp.where(state["cnt"] > 0, state["ts"], -(2 ** 31) + 1))
        )

        stat_grids: Dict[str, jnp.ndarray] = {}
        for arg_idx, stats in self._stats().items():
            v = jnp.broadcast_to(
                jnp.asarray(self.arg_fns[arg_idx](env)), (E,)
            )
            for s in stats:
                key = f"{s}{arg_idx}"
                acc = state[key]
                if s in ("sum", "sumsq"):
                    vv = v.astype(acc.dtype)
                    if s == "sumsq":
                        vv = vv * vv
                    grid = jax.ops.segment_sum(
                        jnp.where(mask, vv, 0), seg, num_segments=NS + 1
                    )[:NS].reshape(B, G)
                    grid = grid.at[0].add(acc)
                else:
                    ident = _identity(s, acc.dtype)
                    seg_fn = (
                        jax.ops.segment_min
                        if s == "min"
                        else jax.ops.segment_max
                    )
                    comb = jnp.minimum if s == "min" else jnp.maximum
                    grid = seg_fn(
                        jnp.where(mask, v.astype(acc.dtype), ident),
                        seg,
                        num_segments=NS + 1,
                    )[:NS].reshape(B, G)
                    grid = grid.at[0].set(comb(grid[0], acc))
                stat_grids[key] = grid

        # last-event values per cell (for non-aggregate projections)
        ord_grid = jax.ops.segment_max(
            jnp.where(mask, rank, -1), seg, num_segments=NS + 1
        )[:NS]
        last_grids: Dict[str, jnp.ndarray] = {}
        for j, key in enumerate(self.last_keys):
            v = env[key]
            winner = mask & (rank == ord_grid[jnp.clip(seg, 0, NS - 1)])
            sum_dtype = jnp.int32 if v.dtype == bool else v.dtype
            tape_last = jax.ops.segment_sum(
                jnp.where(winner, v, 0).astype(sum_dtype),
                seg,
                num_segments=NS + 1,
            )[:NS].reshape(B, G).astype(v.dtype)
            merged = jnp.where(had_tape, tape_last, 0)
            merged = merged.at[0].set(
                jnp.where(had_tape[0], tape_last[0], state[f"last{j}"])
            )
            last_grids[key] = merged

        # --- flush completed cells ----------------------------------------
        flush = (cnt_grid > 0) & completed[:, None]  # (B, G)
        flat = flush.reshape(NS)
        fenv: ColumnEnv = {}
        for agg in self.aggs:
            stats_flat = {
                k: v.reshape(NS) for k, v in stat_grids.items()
            }
            stats_flat["cnt"] = cnt_grid.reshape(NS)
            fenv[agg.slot] = _agg_from_stats(agg, stats_flat).astype(
                agg.out_type.device_dtype
            )
        for key, grid in last_grids.items():
            fenv[key] = grid.reshape(NS)
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(fenv)), (NS,))
            for p in self.proj_fns
        )
        out_mask = flat
        if self.having_fn is not None:
            henv = dict(fenv)
            for f, c in zip(self.output_schema.fields, cols):
                henv[f"@out:{f.name}"] = c
            out_mask = out_mask & self.having_fn(henv)

        ford = jnp.argsort(jnp.logical_not(out_mask))
        count = out_mask.sum()
        out_ts = ts_grid.reshape(NS)[ford]
        out_cols = tuple(c[ford] for c in cols)

        # --- carry: the last (incomplete) window ---------------------------
        new_state = dict(state)
        new_state["seen"] = new_seen
        new_state["batch"] = new_batch
        new_state["t0"] = t0
        # the incomplete window's row index
        if self.window_mode == "lengthBatch":
            inc_row = jnp.clip(new_batch - first_batch, 0, B - 1)
            inc_live = jnp.ones((), bool)
        else:
            inc_row = jnp.clip(
                jnp.where(M > 0, rank_s[jnp.clip(M - 1, 0)] + shift, 0),
                0,
                B - 1,
            )
            inc_live = ~completed[inc_row]

        def carry_of(grid, zero):
            rowv = grid[inc_row]
            return jnp.where(inc_live, rowv, zero)

        new_state["cnt"] = carry_of(cnt_grid, jnp.zeros(G, jnp.int32))
        new_state["ts"] = carry_of(ts_grid, jnp.zeros(G, jnp.int32)).astype(
            jnp.int32
        )
        for key, grid in stat_grids.items():
            if key.startswith(("min", "max")):
                zero = jnp.full(G, _identity(key[:3], grid.dtype), grid.dtype)
            else:
                zero = jnp.zeros(G, grid.dtype)
            new_state[key] = carry_of(grid, zero)
        for j, key in enumerate(self.last_keys):
            new_state[f"last{j}"] = carry_of(
                last_grids[key], jnp.zeros(G, last_grids[key].dtype)
            ).astype(state[f"last{j}"].dtype)
        return new_state, (count, out_ts, out_cols)

    @property
    def flush_is_noop(self) -> bool:
        return self.window_mode not in ("timeBatch", "cron")

    def flush(self, state: Dict) -> Tuple[Dict, Tuple]:
        """End-of-stream flush of the carried incomplete window (timeBatch
        semantics: the final timer fires; lengthBatch does not flush partial
        windows, matching Siddhi)."""
        G = self._G(state)
        if self.window_mode not in ("timeBatch", "cron"):
            empty = (
                jnp.asarray(0, jnp.int32),
                jnp.zeros(G, jnp.int32),
                tuple(
                    jnp.zeros(G, f.atype.device_dtype)
                    for f in self.output_schema.fields
                ),
            )
            return state, empty
        flushable = state["cnt"] > 0
        stats_flat = {"cnt": state["cnt"]}
        fenv: ColumnEnv = {}
        for key in state:
            if key[:3] in ("sum", "min", "max") or key.startswith("sumsq"):
                stats_flat[key] = state[key]
        for agg in self.aggs:
            fenv[agg.slot] = _agg_from_stats(agg, stats_flat).astype(
                agg.out_type.device_dtype
            )
        for j, key in enumerate(self.last_keys):
            fenv[key] = state[f"last{j}"]
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(fenv)), (G,))
            for p in self.proj_fns
        )
        out_mask = flushable
        if self.having_fn is not None:
            henv = dict(fenv)
            for f, c in zip(self.output_schema.fields, cols):
                henv[f"@out:{f.name}"] = c
            out_mask = out_mask & self.having_fn(henv)
        ford = jnp.argsort(jnp.logical_not(out_mask))
        count = out_mask.sum()
        # closing the window early: every accumulator resets, or the next
        # step would re-add the flushed totals into row 0
        new_state = dict(state)
        for k, v in state.items():
            if v.ndim == 0:
                continue
            if k.startswith(("min", "max")):
                new_state[k] = jnp.full(G, _identity(k[:3], v.dtype), v.dtype)
            else:
                new_state[k] = jnp.zeros(G, v.dtype)
        return new_state, (
            count,
            state["ts"][ford],
            tuple(c[ford] for c in cols),
        )


def _bucket(n: int, minimum: int) -> int:
    b = minimum
    while b < max(n, 1):
        b *= 2
    return b


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

def _window_of(inp: ast.StreamInput):
    """Classify the (at most one) window handler on a stream input."""
    if not inp.windows:
        return None
    if len(inp.windows) > 1:
        raise SiddhiQLError("at most one #window handler per stream input")
    w = inp.windows[0]
    name = w.name.split(".")[-1]
    lname = name.lower()
    if lname in ("length", "lengthbatch"):
        if len(w.args) != 1 or not isinstance(w.args[0], ast.Literal):
            raise SiddhiQLError(f"#window.{name} needs one integer argument")
        return ("length" if lname == "length" else "lengthBatch",
                int(w.args[0].value))
    if lname in ("time", "timebatch"):
        if len(w.args) != 1:
            raise SiddhiQLError(f"#window.{name} needs one time argument")
        return ("time" if lname == "time" else "timeBatch",
                _time_arg(w.args[0]))
    if lname == "externaltime":
        if len(w.args) != 2 or not isinstance(w.args[0], ast.Attr):
            raise SiddhiQLError(
                "#window.externalTime needs (tsAttribute, duration)"
            )
        return ("externalTime", (w.args[0], _time_arg(w.args[1])))
    if lname == "externaltimebatch":
        if len(w.args) != 2 or not isinstance(w.args[0], ast.Attr):
            raise SiddhiQLError(
                "#window.externalTimeBatch needs (tsAttribute, duration)"
            )
        return ("externalTimeBatch", (w.args[0], _time_arg(w.args[1])))
    if lname == "hop":
        if len(w.args) != 3 or not isinstance(w.args[0], ast.Attr):
            raise SiddhiQLError(
                "#window.hop needs (tsAttribute, size, slide)"
            )
        return ("hop", (w.args[0], _time_arg(w.args[1]),
                        _time_arg(w.args[2])))
    if lname == "session":
        # (gap[, key]) reads the event's own timestamp; (tsAttribute,
        # gap, key) names the event-time attribute first, as hop does
        args = w.args
        if not args or len(args) > 3:
            raise SiddhiQLError(
                "#window.session needs (gap[, keyAttribute]) or "
                "(tsAttribute, gap, keyAttribute)"
            )
        ts_attr = None
        if len(args) == 3:
            ts_attr, args = args[0], args[1:]
        if not all(isinstance(a, ast.Attr) for a in (ts_attr, *args[1:])
                   if a is not None):
            raise SiddhiQLError(
                "#window.session: the time attribute and the key must "
                "be attributes"
            )
        key = args[1] if len(args) == 2 else None
        return ("session", (_time_arg(args[0]), key, ts_attr))
    if lname == "delay":
        if len(w.args) != 1:
            raise SiddhiQLError("#window.delay needs one time argument")
        return ("delay", _time_arg(w.args[0]))
    if lname == "timelength":
        if len(w.args) != 2 or not isinstance(w.args[1], ast.Literal):
            raise SiddhiQLError(
                "#window.timeLength needs (duration, count)"
            )
        return ("timeLength", (_time_arg(w.args[0]), int(w.args[1].value)))
    if lname in ("sort", "unique"):
        return (lname, tuple(w.args))
    if lname == "frequent":
        if not w.args or not isinstance(w.args[0], ast.Literal):
            raise SiddhiQLError(
                "#window.frequent needs (count[, attributes...])"
            )
        return ("frequent", tuple(w.args))
    if lname == "lossyfrequent":
        if not w.args or not isinstance(w.args[0], ast.Literal):
            raise SiddhiQLError(
                "#window.lossyFrequent needs "
                "(supportThreshold[, errorBound][, attributes...])"
            )
        return ("lossyFrequent", tuple(w.args))
    if lname == "cron":
        if len(w.args) != 1 or not isinstance(w.args[0], ast.Literal):
            raise SiddhiQLError(
                "#window.cron needs one cron-expression string"
            )
        return ("cron", str(w.args[0].value))
    raise SiddhiQLError(f"unsupported window #window.{w.name}")


def _matrix_capacity(config, what: str) -> int:
    """``time_window_capacity`` for a window on the matrix path, which
    builds an (events x capacity) matrix a step."""
    cap = int(config.time_window_capacity)
    if cap > MATRIX_WINDOW_MAX:
        raise SiddhiQLError(
            f"{what} holds its members in an (events x capacity) "
            f"matrix: time_window_capacity {cap} is over the "
            f"{MATRIX_WINDOW_MAX} it can take (count / sum / avg / "
            "stddev over #window.time run in a ring of "
            "time_ring_capacity slots, of any size)"
        )
    return cap


def _time_arg(a: ast.Expr) -> int:
    if isinstance(a, ast.TimeLiteral):
        return a.ms
    if isinstance(a, ast.Literal) and isinstance(a.value, int):
        return a.value
    raise SiddhiQLError("expected a time duration argument")


def compile_window_query(
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
    assert isinstance(inp, ast.StreamInput)
    ref = inp.ref_name
    scopes = {ref: (inp.stream_id, schemas[inp.stream_id])}
    if ref != inp.stream_id:
        scopes[inp.stream_id] = (inp.stream_id, schemas[inp.stream_id])
    resolver = ExprResolver(scopes, default_scope=ref)

    filter_fns = []
    for f in inp.filters:
        ce = compile_expr(f, resolver, extensions)
        if ce.atype != AttributeType.BOOL:
            raise SiddhiQLError("stream filter must be boolean")
        filter_fns.append(ce.fn)

    items = q.selector.items
    schema = schemas[inp.stream_id]
    if q.selector.is_star:
        items = tuple(
            ast.SelectItem(ast.Attr(n), None) for n in schema.field_names
        )

    group_names = q.selector.group_by
    collector = _AggCollector(resolver, extensions)
    rewritten = [
        ast.SelectItem(collector.rewrite(i.expr), i.alias) for i in items
    ]
    having_re = (
        collector.rewrite(q.selector.having)
        if q.selector.having is not None
        else None
    )

    host_filters = host_filter_fns(inp.filters, resolver)
    window = _window_of(inp)
    if window is not None and window[0] == "hop":
        from .hop_window import compile_hop_window

        return compile_hop_window(
            q, name, window, resolver, stream_codes[inp.stream_id],
            extensions, config, filter_fns, items, host_filters,
        )
    if not collector.aggs and not group_names:
        # window with plain projection: current-event output == stateless
        # select (Siddhi emits arriving events unchanged for `insert into`)
        from .select import compile_select

        return compile_select(
            q, name, resolver, schemas, stream_codes[inp.stream_id],
            extensions,
        )

    slot_types = {a.slot: a.out_type for a in collector.aggs}
    slot_resolver = _SlotResolver(resolver, slot_types)

    proj_fns: List = []
    out_fields: List[OutputField] = []
    for item in rewritten:
        ce = compile_expr(item.expr, slot_resolver, extensions)
        proj_fns.append(ce.fn)
        out_fields.append(OutputField(item.output_name(), ce.atype, ce.table))

    having_fn = None
    if having_re is not None:
        # having may reference select aliases; map alias -> @out slot
        alias_slots = {f.name: f.atype for f in out_fields}

        class _HavingResolver:
            def resolve(self, attr: ast.Attr) -> ResolvedAttr:
                if attr.qualifier is None and attr.index is None:
                    if attr.name in slot_types:
                        return ResolvedAttr(
                            attr.name, slot_types[attr.name], None
                        )
                    if attr.name in alias_slots:
                        return ResolvedAttr(
                            f"@out:{attr.name}", alias_slots[attr.name], None
                        )
                return resolver.resolve(attr)

        ce = compile_expr(having_re, _HavingResolver(), extensions)
        if ce.atype != AttributeType.BOOL:
            raise SiddhiQLError("having clause must be boolean")
        having_fn = ce.fn

    out_schema = OutputSchema(q.output_stream, tuple(out_fields))
    sc = stream_codes[inp.stream_id]

    group_resolved = [
        resolver.resolve(ast.split_group_key(n)) for n in group_names
    ]

    if window is not None and window[0] in (
        "sort", "unique", "session", "frequent", "lossyFrequent",
    ):
        from .scan_windows import compile_scan_window

        return compile_scan_window(
            q, name, window, resolver, schemas, stream_codes, extensions,
            config, filter_fns, rewritten, collector, having_re,
            host_filters,
        )

    if q.partition_with and window is not None and window[0] == "time":
        # per-key TIME window == shared time window + group-by on the
        # key: wall-clock expiry is key-independent (an event leaves
        # the window T ms after arrival whoever else arrived), so each
        # key's member set is identical either way — unlike length
        # windows (global last-C vs per-key last-C) or externalTime
        # (stream time advances with the partition's own events).
        # _rewrite_partitioned already added the key to group_by.
        pass
    elif q.partition_with and window is not None:
        # per-partition window: each key's OWN last-C window
        if window[0] != "length":
            raise SiddhiQLError(
                f"#window.{window[0]} inside 'partition with' is not "
                "supported yet (length and time windows only)"
            )
        attr = dict(q.partition_with).get(inp.stream_id)
        if tuple(ast.bare_group_key(n) for n in group_names) != (attr,):
            raise SiddhiQLError(
                "additional 'group by' inside a partitioned window "
                "query is not supported yet (the partition key is the "
                "grouping)"
            )
        cap = int(window[1])
        if cap < 1:
            raise SiddhiQLError("#window.length needs a positive length")
        for a in collector.aggs:
            if a.kind not in ("count", "sum", "avg", "stddev", "min", "max"):
                raise SiddhiQLError(
                    f"{a.kind}() is not supported over a per-partition "
                    "#window.length (count, sum, avg, stddev, min and max "
                    "are)"
                )
        if cap > PERKEY_RING_MAX and any(
            a.kind in ("min", "max") for a in collector.aggs
        ):
            raise SiddhiQLError(
                "min() / max() over a per-partition #window.length read "
                f"the key's last values one by one: at most "
                f"{PERKEY_RING_MAX} of them (group by outside the "
                "partition for a longer window)"
            )
        # @purge: the slots expire on the stream's clock (the events'
        # timestamps) and a reused one starts anew on the device
        purge = q.partition_purge
        tick_ms, retain = purge_ticks(*purge) if purge else (0, None)
        code_key, encoder, encoded = _group_encoding(
            name, group_resolved, sc, filter_fns,
            encoder=GroupEncoder(
                retain_ticks=retain, mark_new=purge is not None),
            host_filters=host_filters, tick_ms=tick_ms,
        )
        art = PerKeyWindowArtifact(
            name=name,
            output_schema=out_schema,
            stream_code=sc,
            filter_fns=filter_fns,
            capacity=cap,
            group_slots=max(
                MIN_GROUP_CAPACITY, int(config.hop_group_slots)),
            aggs=collector.aggs,
            arg_fns=collector.arg_fns,
            arg_types=collector.arg_types,
            code_key=code_key,
            encoder=encoder,
            proj_fns=proj_fns,
            having_fn=having_fn,
        )
        art.encoded_columns = encoded
        return art

    if window is None or window[0] in (
        "length", "time", "externalTime", "timeLength",
    ):
        time_columns = ()
        if window is None:
            mode, cap, time_ms, ts_key = "cumulative", 0, None, None
        elif window[0] == "length":
            mode, cap, time_ms, ts_key = "length", window[1], None, None
        elif window[0] == "time":
            # count / sum / avg / stddev over the stream's clock: the
            # ring of time_window.py, as large as the deployment says
            in_ring = all(
                a.kind in ("count", "sum", "avg", "stddev")
                for a in collector.aggs
            )
            mode, time_ms, ts_key = "time", window[1], None
            cap = (
                config.time_ring_capacity if in_ring
                else _matrix_capacity(config, "min() / max() / "
                                      "distinctCount() over #window.time")
            )
            if time_ms < 1:
                raise SiddhiQLError("#window.time needs a positive span")
        elif window[0] == "timeLength":
            # last-n AND within-t: the window matrix bounds membership
            # to the most recent `count` matching events and the member
            # mask adds the time cut — exactly min(time, length)
            dur, n = window[1]
            mode, cap, time_ms, ts_key = "timeLength", n, dur, None
        else:  # externalTime
            ts_attr, dur = window[1]
            ts_key, time_columns = time_read(resolver.resolve(ts_attr))
            mode, time_ms = "time", dur
            cap = _matrix_capacity(config, "#window.externalTime")
        if mode == "cumulative":
            code_key, encoder, encoded = _group_encoding(
                name, group_resolved, sc, filter_fns,
                host_filters=host_filters,
            )
            art = CumulativeAggArtifact(
                name=name,
                output_schema=out_schema,
                stream_code=sc,
                filter_fns=filter_fns,
                aggs=collector.aggs,
                arg_fns=collector.arg_fns,
                arg_types=collector.arg_types,
                code_key=code_key,
                encoder=encoder,
                proj_fns=proj_fns,
                having_fn=having_fn,
            )
            art.encoded_columns = encoded
            return art
        group_fns = []
        group_dtypes = []
        for r in group_resolved:
            key = r.key
            group_fns.append(lambda env, k=key: env[k])
            group_dtypes.append(r.atype.device_dtype)
        code_key, encoder, encoded = _group_encoding(
            name, group_resolved, sc, filter_fns,
            host_filters=host_filters,
        )
        # wire-opt metadata from the ORIGINAL (pre-rewrite) selector:
        # plain-ref sources, full per-item refs (incl. aggregate args),
        # filter refs
        w_proj_srcs = []
        w_proj_refs = []
        for item in items:
            w_proj_srcs.append(
                resolver.resolve(item.expr).key
                if isinstance(item.expr, ast.Attr)
                and item.expr.index is None
                else None
            )
            w_proj_refs.append(
                frozenset(
                    resolver.resolve(a).key
                    for a in ast.iter_attrs(item.expr)
                )
            )
        w_filter_keys = frozenset(
            resolver.resolve(a).key
            for f in inp.filters
            for a in ast.iter_attrs(f)
        )
        cls = SlidingWindowArtifact
        if window[0] == "time" and in_ring:
            from .time_window import TimeWindowArtifact as cls
        art = cls(
            name=name,
            output_schema=out_schema,
            stream_code=sc,
            filter_fns=filter_fns,
            window_mode=mode if mode != "cumulative" else "length",
            capacity=cap,
            time_ms=time_ms,
            ts_key=ts_key,
            aggs=collector.aggs,
            arg_fns=collector.arg_fns,
            arg_types=collector.arg_types,
            group_fns=group_fns,
            group_dtypes=group_dtypes,
            proj_fns=proj_fns,
            proj_types=[f.atype for f in out_fields],
            having_fn=having_fn,
            code_key=code_key,
            encoder=encoder,
            proj_srcs=tuple(w_proj_srcs),
            proj_refs=tuple(w_proj_refs),
            filter_keys=w_filter_keys,
            group_keys_=tuple(r.key for r in group_resolved),
        )
        art.time_columns = time_columns
        if art._blocked():
            # the sort-free tiled path consumes dense host-interned
            # group codes off the tape
            art.encoded_columns = encoded
        else:
            # sort/matrix paths read raw group columns; don't pay host
            # interning for a code column nobody reads
            art.code_key = None
            art.encoder = None
            art.encoded_columns = ()
        return art

    # batch windows
    mode, arg = window
    host_cols = ()
    wid_key = None
    if mode == "cron":
        # host-enumerated Quartz fires; per-event window ids ship as a
        # narrow int column and the device runs the ordinary batch grid
        from ..runtime.tape import HostPred
        from ..utils.cron import CronSchedule

        sched = CronSchedule.parse(str(arg))
        wid_key = f"@cron:{name}"
        host_cols = (
            HostPred(
                wid_key,
                lambda henv, _s=sched: _s.window_ids(henv["@ts"]),
                ("@ts",),
                np.int32,
            ),
        )
    batch_ts_key, time_columns = None, ()
    if mode == "externalTimeBatch":
        # same tumbling machinery as timeBatch, but stream time advances
        # with the user's timestamp attribute instead of event time
        ts_attr, dur = arg
        batch_ts_key, time_columns = time_read(resolver.resolve(ts_attr))
        mode, arg = "timeBatch", dur
    code_key, encoder, encoded = _group_encoding(
        name, group_resolved, sc, filter_fns,
        host_filters=host_filters,
    )
    # non-aggregate projection inputs need per-cell "last event" values.
    # having may reference SELECT ALIASES (resolved later against the
    # output slots), which are not tape columns — skip them here.
    last_types_map: Dict[str, AttributeType] = {}
    for item in rewritten:
        _referenced_keys(item.expr, resolver, last_types_map)
    if having_re is not None:
        aliases = {
            i.alias for i in rewritten if i.alias is not None
        }
        for attr in ast.iter_attrs(having_re):
            if attr.name.startswith("@") or (
                attr.qualifier is None and attr.name in aliases
            ):
                continue  # slots / select aliases resolve downstream
            r = resolver.resolve(attr)
            last_types_map[r.key] = r.atype
    last_keys = sorted(last_types_map)
    art = BatchWindowArtifact(
        name=name,
        output_schema=out_schema,
        stream_code=sc,
        filter_fns=filter_fns,
        window_mode=mode,
        length=arg if mode == "lengthBatch" else None,
        time_ms=arg if mode == "timeBatch" else None,
        aggs=collector.aggs,
        arg_fns=collector.arg_fns,
        arg_types=collector.arg_types,
        code_key=code_key,
        encoder=encoder,
        last_keys=last_keys,
        last_types=[last_types_map[k] for k in last_keys],
        proj_fns=proj_fns,
        having_fn=having_fn,
        batch_slots=config.time_batch_slots,
        ts_key=batch_ts_key,
        wid_key=wid_key,
    )
    art.encoded_columns = encoded
    art.host_columns = host_cols
    art.time_columns = time_columns
    return art


def time_read(r: ResolvedAttr) -> Tuple[str, Tuple[str, ...]]:
    """Where a window reads attribute ``r`` as time, and what its
    artifact declares as ``time_columns`` for that: a ``long`` is read
    from its copy on the job's clock (``runtime.tape.time_key``: an
    epoch-ms value does not fit the device's int32), any other type
    from the column itself."""
    if r.atype == AttributeType.LONG:
        return time_key(r.key), (r.key,)
    return r.key, ()


def host_filter_fns(filters, resolver) -> Optional[List[Callable]]:
    """The stream filters as numpy closures over the tape's host columns
    (``compile_host_pred``), or None where one of them does not compile
    that way or reads a float: the host compares in float64 and the
    device in float32, and interning has to select exactly the events
    the device's mask will."""
    from .expr import compile_host_pred

    out = []
    for f in filters:
        if any(
            resolver.resolve(a).atype
            in (AttributeType.FLOAT, AttributeType.DOUBLE)
            for a in ast.iter_attrs(f)
        ):
            return None
        he = compile_host_pred(f, resolver)
        if he is None:
            return None
        out.append(he.fn)
    return out


def _select_fn(fns: Sequence[Callable]) -> Optional[Callable]:
    """host columns -> the mask of the rows that pass every filter (None
    where there is none): what an ``EncodedColumn`` interns."""
    fns = list(fns)
    if not fns:
        return None

    def select_fn(cols):
        m = np.ones(len(next(iter(cols.values()))), dtype=bool)
        for f in fns:
            m = m & np.asarray(f(cols))
        return m

    return select_fn


def _group_encoding(
    name: str,
    group_resolved: List[ResolvedAttr],
    stream_code: int,
    filter_fns: Sequence[Callable] = (),
    encoder: Optional[GroupEncoder] = None,
    host_filters: Optional[Sequence[Callable]] = None,
    tick_ms: int = 0,
):
    """Dense group codes for state-table artifacts. Single-column int-like
    keys could index directly, but interning keeps tables dense for arbitrary
    key distributions and multi-column keys. Interning respects the query's
    filters so rejected events never grow the table: ``host_filters``
    (``host_filter_fns``) where the filters compile to numpy, else the
    device's own ``filter_fns``, which cost the host a round trip to the
    device for every batch. ``tick_ms``: the tick of an ``encoder`` whose
    slots expire, on the clock of the events' timestamps."""
    if not group_resolved:
        return None, None, ()
    if encoder is None:
        encoder = GroupEncoder()
    out_key = f"@group:{name}"
    select_fn = _select_fn(
        host_filters if host_filters is not None else filter_fns
    )
    enc = EncodedColumn(
        out_key=out_key,
        in_keys=tuple(r.key for r in group_resolved),
        stream_code=stream_code,
        encoder=encoder,
        select_fn=select_fn,
        # an encoder whose slots expire reads the events' timestamps
        tick_key="@ts" if tick_ms else None,
        tick_ms=tick_ms,
    )
    return out_key, encoder, (enc,)


# --------------------------------------------------------------------------
# Expired-event output: ``insert expired events into O``
# --------------------------------------------------------------------------

@dataclass
class ExpiredWindowArtifact:
    """Emit events as they LEAVE a sliding window (Siddhi's expired
    stream; siddhi-core ships this through any window processor's
    expired-event chunk). Length windows expire an event when the C-th
    matching event after it arrives (emission ts = the displacing
    event's ts); time windows when stream time passes ts + span
    (emission ts = ts + span; end-of-stream flushes the remainder, the
    same "+inf watermark" rule the pattern matcher's timed absence
    uses). Plain projections only — aggregates over the expired stream
    are not part of the benchmarked reference surface and raise at
    compile."""

    name: str
    output_schema: OutputSchema
    output_mode: str  # 'buffered'
    stream_code: int
    filter_fns: List
    window_mode: str  # 'length' | 'time'
    capacity: int
    time_ms: Optional[int]
    proj_fns: List
    ref_keys: List[str]  # tape columns the projections read
    ref_dtypes: Dict[str, object]  # device dtype per ref column

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: each event expires exactly once
        — one expired row out per input event; retention is the window
        it leaves."""
        return {
            "name": self.name,
            "kind": "expired_window",
            "amplification": 1,
            "residency_ms": (
                int(self.time_ms)
                if self.window_mode == "time" and self.time_ms is not None
                else None
            ),
        }

    def init_state(self) -> Dict:
        C = self.capacity
        ring: Dict[str, jnp.ndarray] = {
            "ts": jnp.zeros(C, jnp.int32),
            "count": jnp.zeros((), jnp.int32),
            "overflow": jnp.zeros((), jnp.int32),
        }
        for k in self.ref_keys:
            ring[f"c:{k}"] = jnp.zeros(C, self.ref_dtypes[k])
        return {"enabled": jnp.asarray(True), "ring": ring}

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        return tape_capacity + self.capacity

    def _seq_gather(self, ring_col, arr_col, P0, idx):
        """sequence[j] for the FIFO view ring[0:P0] ++ arrivals: j < P0
        reads the ring, else the arrival at j - P0."""
        C = self.capacity
        src = jnp.where(idx < P0, jnp.clip(idx, 0, C - 1), 0)
        from_ring = ring_col[src]
        ai = jnp.clip(idx - P0, 0, arr_col.shape[0] - 1)
        return jnp.where(idx < P0, from_ring, arr_col[ai])

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
        ring = state["ring"]
        P0 = ring["count"]
        M = mask.sum().astype(jnp.int32)
        rank = jnp.cumsum(mask) - 1
        dest = jnp.where(mask, rank, E)

        def compact(col, dtype=None):
            col = jnp.broadcast_to(jnp.asarray(col), (E,))
            if dtype is not None:
                col = col.astype(dtype)
            return jnp.zeros(E, col.dtype).at[dest].set(col, mode="drop")

        arr_ts = compact(tape.ts)
        arr_cols = {k: compact(env[k]) for k in self.ref_keys}
        total = P0 + M
        W = C + E
        j = jnp.arange(W, dtype=jnp.int32)
        seq_ts = self._seq_gather(ring["ts"], arr_ts, P0, j)

        if self.window_mode == "length":
            n_exp = jnp.clip(total - C, 0, W)
            # entry j is displaced by arrival j + C - P0 of this batch
            di = jnp.clip(j + C - P0, 0, E - 1)
            emit_ts = arr_ts[di]
        else:
            bmax = jnp.max(
                jnp.where(mask, tape.ts, jnp.int32(-(2 ** 30)))
            )
            horizon = bmax - jnp.int32(self.time_ms)
            # expiry over the RUNNING-MAX timestamp so the expired set is
            # always a sequence prefix — a cross-batch straggler (older
            # ts arriving after newer ones) conservatively expires late
            # instead of desyncing the emit/retain split (same defense
            # as the sliding-window paths)
            mono = lax.cummax(
                jnp.where(j < total, seq_ts, jnp.int32(2 ** 31 - 1))
            )
            expired = (mono <= horizon) & (j < total)
            n_exp = expired.sum().astype(jnp.int32)
            emit_ts = seq_ts + jnp.int32(self.time_ms)

        emit_env = {
            k: self._seq_gather(ring[f"c:{k}"], arr_cols[k], P0, j)
            for k in self.ref_keys
        }
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(emit_env)), (W,))
            for p in self.proj_fns
        )

        # survivors: sequence[n_keep_from .. total); clamp to ring cap
        # (time windows can briefly hold more than C — count the drop)
        n_live = jnp.clip(total - n_exp, 0, None)
        dropped = jnp.clip(n_live - C, 0, None)
        n_keep = jnp.minimum(n_live, C)
        base = total - n_keep  # oldest kept entry
        ki = jnp.arange(C, dtype=jnp.int32) + base
        new_ring = {
            "ts": self._seq_gather(ring["ts"], arr_ts, P0, ki),
            "count": n_keep,
            "overflow": ring["overflow"] + dropped,
        }
        for k in self.ref_keys:
            new_ring[f"c:{k}"] = self._seq_gather(
                ring[f"c:{k}"], arr_cols[k], P0, ki
            )
        new_state = {"enabled": state["enabled"], "ring": new_ring}
        return new_state, (n_exp, emit_ts, cols)

    @property
    def flush_is_noop(self) -> bool:
        return self.window_mode != "time"

    def flush(self, state: Dict) -> Tuple[Dict, Tuple]:
        """End of stream: time advances past every pending deadline, so
        all retained entries expire (length windows never flush)."""
        ring = state["ring"]
        C = self.capacity
        if self.window_mode != "time":
            return state, (
                jnp.zeros((), jnp.int32),
                jnp.zeros(1, jnp.int32),
                tuple(
                    jnp.zeros(1, jnp.int32) for _ in self.proj_fns
                ),
            )
        n = ring["count"]
        emit_ts = ring["ts"] + jnp.int32(self.time_ms)
        emit_env = {k: ring[f"c:{k}"] for k in self.ref_keys}
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(emit_env)), (C,))
            for p in self.proj_fns
        )
        new_ring = dict(ring)
        new_ring["count"] = jnp.zeros((), jnp.int32)
        return (
            {"enabled": state["enabled"], "ring": new_ring},
            (n, emit_ts, cols),
        )


def compile_expired_window(
    q: ast.Query,
    name: str,
    schemas,
    stream_codes: Dict[str, int],
    extensions,
    config=None,
):
    from .config import DEFAULT_CONFIG

    config = config or DEFAULT_CONFIG
    # 'all events' never reaches here: _rewrite_all_events (plan.py)
    # splits it into a current-events query + this expired one
    assert q.output_events == "expired", q.output_events
    inp = q.input
    if not isinstance(inp, ast.StreamInput) or not inp.windows:
        raise SiddhiQLError(
            "'insert expired events into' needs a windowed single-stream "
            "input (only windows retain events to expire)"
        )
    if q.selector.group_by or q.selector.having is not None or any(
        ast.contains_aggregate(i.expr) for i in q.selector.items
    ):
        raise SiddhiQLError(
            "aggregations/group by/having over the expired stream are "
            "not supported; select plain attributes"
        )
    window = _window_of(inp)
    if window[0] not in ("length", "time"):
        raise SiddhiQLError(
            f"expired-events output supports #window.length and "
            f"#window.time (got #window.{window[0]})"
        )
    ref = inp.ref_name
    scopes = {ref: (inp.stream_id, schemas[inp.stream_id])}
    if ref != inp.stream_id:
        scopes[inp.stream_id] = (inp.stream_id, schemas[inp.stream_id])
    resolver = ExprResolver(scopes, default_scope=ref)
    filter_fns = []
    for f in inp.filters:
        ce = compile_expr(f, resolver, extensions)
        if ce.atype != AttributeType.BOOL:
            raise SiddhiQLError("stream filter must be boolean")
        filter_fns.append(ce.fn)
    items = q.selector.items
    schema = schemas[inp.stream_id]
    if q.selector.is_star:
        items = tuple(
            ast.SelectItem(ast.Attr(n), None) for n in schema.field_names
        )
    proj_fns: List = []
    out_fields: List[OutputField] = []
    ref_keys: List[str] = []
    ref_dtypes: Dict[str, object] = {}
    for item in items:
        ce = compile_expr(item.expr, resolver, extensions)
        proj_fns.append(ce.fn)
        out_fields.append(
            OutputField(item.output_name(), ce.atype, ce.table)
        )
        for a in ast.iter_attrs(item.expr):
            r = resolver.resolve(a)
            if r.key not in ref_keys:
                ref_keys.append(r.key)
                ref_dtypes[r.key] = r.atype.device_dtype
    mode, arg = window
    cap = arg if mode == "length" else config.time_window_capacity
    art = ExpiredWindowArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        output_mode="buffered",
        stream_code=stream_codes[inp.stream_id],
        filter_fns=filter_fns,
        window_mode=mode,
        capacity=int(cap),
        time_ms=arg if mode == "time" else None,
        proj_fns=proj_fns,
        ref_keys=ref_keys,
        ref_dtypes=ref_dtypes,
    )
    art.encoded_columns = ()
    return art


# --------------------------------------------------------------------------
# Per-key sliding windows: `partition with (k of S) begin ...#window.length`
# --------------------------------------------------------------------------

# the widest per-key window whose min / max the step reads: a slot's
# record holds C words an argument, and an event reduces over them
PERKEY_RING_MAX = 64
# a row of the per-key record table: the lanes of a TPU vector
_LANES = 128
# the tape rows whose table rows the per-key step holds at once
_PERKEY_BLOCK = 1 << 16


def purge_ticks(interval_ms: int, idle_ms: int) -> Tuple[int, int]:
    """(tick_ms, retain_ticks) of a purged partition's slots: a tick is
    the purge interval, and a slot is freed once ``idle.period`` and a
    tick have passed the batch that last touched it, as the batches
    before the one being staged saw the clock. So a key idle for less
    than ``idle.period`` is never forgotten, and one is always forgotten
    once the batch before its return ends ``idle.period + interval``
    after the batch of its last event (docs/partition_window.md has the
    band in between)."""
    return interval_ms, -(-idle_ms // interval_ms) + 1


def _perkey_read(table, slots, n, W: int, need: int):
    """The first ``need`` words of the records of ``slots`` (ascending;
    the first ``n`` count) in ``table``, rows of ``R`` records of ``W``
    words: a list of ``need`` columns, a word each. One gather of table
    rows by ``slot // R``; the gathered rows are turned a tile of 128 by
    128 at a time, so that the slots lie along the lanes, and of the R
    records a row holds the slot's own is taken in registers. A block
    of slots at a time, since a gathered row is 128 lanes whatever a
    record needs, and no block past the ``n``-th slot."""
    E, R = slots.shape[0], table.shape[1] // W
    # whole tiles (admission traces a plan on a tape shorter than one)
    slots = jnp.pad(slots, (0, -E % _LANES), mode="edge")
    padded = slots.shape[0]
    block = _PERKEY_BLOCK if padded % _PERKEY_BLOCK == 0 else padded

    def read_block(i, words):
        g = lax.dynamic_slice(slots, (i * block,), (block,))
        rows = table.at[g // R].get(
            indices_are_sorted=True, mode="promise_in_bounds"
        )
        # [tile, lane of the row, slot]
        tiles = rows.reshape(-1, _LANES, table.shape[1]).transpose(0, 2, 1)
        place = (g % R).reshape(-1, _LANES)
        out = []
        for w, column in enumerate(words):
            word = tiles[:, w]
            for at in range(1, R):
                word = jnp.where(place == at, tiles[:, at * W + w], word)
            out.append(lax.dynamic_update_slice(
                column, word.reshape(-1), (i * block,)
            ))
        return out

    words = lax.fori_loop(
        0, -(-n // block), read_block,
        [jnp.zeros(padded, jnp.int32) for _ in range(need)],
    )
    return [column[:E] for column in words]


@dataclass
class PerKeyWindowArtifact(AlignedBlocks):
    """``partition with (k of S) ... #window.length(C)``: EVERY key has
    its own window of its own last C matching events (Siddhi partition
    semantics — NOT a group-by over one shared window; the round-3
    verdict's canonical partition carve-out).
    ``docs/partition_window.md`` has the query form and the state.

    TPU shape: one stable sort of the batch by slot code (the arguments
    ride along), segmented scans for each event's ordinal ``n`` in its
    key's stream, and per slot of a host-interned table
    (``schema/encoders.py``) ONE RECORD of ``W`` int32 words in the one
    leaf ``rec``:

    * word 0: the key's arrivals so far; ``count()`` is
      ``min(n + 1, C)``;
    * then, for each argument under ``min`` / ``max``, C words: the
      key's last C RAW values, that of ordinal ``m`` at ring position
      ``m % C``, in the argument's own type (an int stays an int, a
      float rides as its bits: no float32 round trip, no rounding).

    ``W`` is ``1 + C * A`` padded to a power of two (past 128 to a
    multiple of it), so that records pack whole into rows of 128 lanes:
    ``rec`` is ``[G * W / 128, 128]``, ``128 / W`` records a row (a
    ``[G, W]`` leaf would be tiled to 128 lanes, ``128 / W`` times the
    memory). A gather on the TPU pays by the lookup and hardly by its
    width, a scatter of rows ten times one of values (PERF.md §7 row
    23). So the step READS a slot's state with one row gather by the
    sorted slot codes and takes the record and its words from the row in
    registers, and WRITES with value scatters into the table's flat view
    at ``slot * W + word``: one for the count at a key's last event, one
    an argument at the key's last ``min(C, its events)`` of the batch.
    An event reduces over its own value, the C - 1 before it in its
    batch (the sorted column shifted, no gather) and the record's ring
    words whose ordinal still lies in its window.

    Sums (``sum`` / ``avg`` / ``stddev``) keep leaves of their own and
    read the count from the record: per-group LOCAL prefix differences,
    windowed_g(n) = S_g(n) - S_g(n - C), where S_g is the key's running
    (Neumaier-compensated) float32 sum: a [G] running total and a
    [G, C] ring of the last C prefix CHECKPOINTS.

    Under ``@purge`` the slots expire (``purge_ticks``) and the encoder
    marks the rows of a key it gave a slot (``mark_new``: ``~slot``):
    the step counts such a key from zero, so nothing the slot's last
    key left is read (every ring word is gated by the count). A
    snapshot taken while the state was ``cnt`` and ``vals<j>`` leaves
    (before PR 45) does not restore into this layout: ``restore``
    refuses it by its leaves."""

    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    capacity: int  # C: per-key window length
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    code_key: str
    encoder: GroupEncoder
    proj_fns: List
    having_fn: Optional[Callable]
    # the table's first size (EngineConfig.hop_group_slots)
    group_slots: int = MIN_GROUP_CAPACITY
    output_mode: str = "aligned"

    def _stats(self) -> Dict[int, set]:
        return _acc_stats_for(self.aggs)

    def _G(self) -> int:
        return _bucket(len(self.encoder), self.group_slots)

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: per-key count-evicted windows —
        one row per event; state grows with key cardinality (under
        ``@purge``: with the keys ``idle.period`` holds; the table
        re-buckets where they outgrow ``hop_group_slots``)."""
        return {
            "name": self.name,
            "kind": "perkey_window",
            "amplification": 1,
            "residency_ms": None,
            "grows_with": "keys",
        }

    def drain_counters(self, payload) -> Dict[str, int]:
        """What a drain delivered: the rows past ``having``."""
        return {"perkey.rows": len(payload)}

    def _ring_args(self) -> List[int]:
        """The arguments whose raw values a record holds."""
        return sorted(
            j for j, st in self._stats().items() if st & {"min", "max"}
        )

    def _record(self) -> Tuple[int, int]:
        """(W, R): the words of a slot's record and the records a row of
        the table holds. ``1 + C * A`` words padded so that
        ``R * W`` is a whole number of vectors."""
        need = 1 + self.capacity * len(self._ring_args())
        if need > _LANES:
            return -(-need // _LANES) * _LANES, 1
        W = 1 << (need - 1).bit_length()
        return W, _LANES // W

    def _slots(self) -> int:
        """The table's slots: whole rows of records."""
        R = self._record()[1]
        return -(-self._G() // R) * R

    def init_state(self) -> Dict:
        G, C = self._slots(), self.capacity
        W, R = self._record()
        st = {
            "enabled": jnp.asarray(True),
            "rec": jnp.zeros((G // R, R * W), jnp.int32),
        }
        for arg_idx, stats in self._stats().items():
            for s in sorted(stats & {"sum", "sumsq"}):
                st[f"S_{s}{arg_idx}"] = jnp.zeros(G, jnp.float32)
                st[f"kc_{s}{arg_idx}"] = jnp.zeros(G, jnp.float32)
                st[f"ring_{s}{arg_idx}"] = jnp.zeros(
                    (G, C), jnp.float32
                )
        return st

    def grow_state(self, state: Dict) -> Dict:
        R = self._record()[1]
        G, need = state["rec"].shape[0] * R, self._slots()
        if need <= G:
            return state
        out = {"enabled": state["enabled"]}
        for k, v in state.items():
            if k == "enabled":
                continue
            # a slot's record and its sums lie side by side: new slots
            # are new rows at the end of every leaf
            more = (need - G) // R if k == "rec" else need - G
            out[k] = jnp.concatenate(
                [v, jnp.zeros((more,) + v.shape[1:], v.dtype)]
            )
        return out

    @jax.named_scope("fst.perkey_fold")
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        C = self.capacity
        W, R = self._record()
        G = state["rec"].shape[0] * R
        stats = self._stats()
        ring_args = self._ring_args()

        # a negative code is ``~slot``: a key this batch was given the
        # slot for, whose state starts anew (``mark_new``)
        code = env[self.code_key].astype(jnp.int32)
        segkey = jnp.where(mask, jnp.where(code < 0, ~code, code), G)
        # one stable sort makes a key's events contiguous and in order;
        # the tape position, the code and the arguments ride along
        arg_ids = sorted(stats)
        g_s, order, code_s, *arg_s = lax.sort(
            [segkey, jnp.arange(E, dtype=jnp.int32), code] + [
                jnp.broadcast_to(
                    jnp.asarray(self.arg_fns[j](env)), (E,)
                ).astype(self.arg_types[j].device_dtype)
                for j in arg_ids
            ],
            num_keys=1, is_stable=True,
        )
        arg_s = dict(zip(arg_ids, arg_s))
        mask_s = g_s < G
        fresh_s = mask_s & (code_s < 0)
        flags = jnp.concatenate(
            [jnp.ones(1, bool), g_s[1:] != g_s[:-1]]
        )
        ends = jnp.concatenate([flags[1:], jnp.ones(1, bool)])
        gather_g = jnp.minimum(g_s, G - 1)

        ones = jnp.ones(E, jnp.int32)
        seg_rank = _seg_scan(flags, ones, jnp.add) - 1  # 0-based local
        # the key's events still to come in this batch: its last
        # min(C, all) are the ones the rings keep
        to_come = (_seg_scan(ends[::-1], ones, jnp.add) - 1)[::-1]
        is_tail = mask_s & (to_come < C)
        # the one read of the table: the words of each event's slot
        rec = _perkey_read(
            state["rec"], gather_g, jnp.sum(mask_s, dtype=jnp.int32),
            W, 1 + C * len(ring_args),
        )
        had = jnp.where(fresh_s, 0, rec[0])  # the key's arrivals before
        local_n = had + seg_rank  # per-key ordinal
        pos = jnp.arange(E, dtype=jnp.int32)

        # the writes are value scatters into the table's flat view, word
        # ``slot * W + w``: here the count, at a key's last event
        new_state = dict(state)
        words = state["rec"].reshape(-1).at[
            jnp.where(mask_s & ends, g_s * W, G * W)
        ].set(local_n + 1, mode="drop")

        # windowed count has a closed form: min(local_n + 1, C)
        stats_s: Dict[str, jnp.ndarray] = {
            "cnt": jnp.minimum(local_n + 1, C)
        }
        fresh_slot = None
        # a record's ring position p holds the key's last ordinal before
        # this batch that is p mod C: it is in an event's window if the
        # key wrote it (it is not negative) and it is less than C before
        # the event's own
        held = [had - 1 - (had - 1 - p) % C for p in range(C)]
        ring_ok = [(m >= 0) & (m > local_n - C) for m in held]

        for arg_idx in arg_ids:
            sums = sorted(stats[arg_idx] & {"sum", "sumsq"})
            if sums and fresh_slot is None:
                # the slots that start anew: their running totals too
                fresh_slot = jnp.zeros(G, bool).at[
                    jnp.where(fresh_s & ends, g_s, G)
                ].set(True, mode="drop")
            v_f = jnp.where(
                mask_s, arg_s[arg_idx].astype(jnp.float32), 0.0
            ) if sums else None
            for s in sums:
                vals = v_f * v_f if s == "sumsq" else v_f
                Skey, kckey, rkey = (
                    f"S_{s}{arg_idx}", f"kc_{s}{arg_idx}",
                    f"ring_{s}{arg_idx}",
                )
                acc = jnp.where(fresh_slot, 0.0, state[Skey])
                kc = jnp.where(fresh_slot, 0.0, state[kckey])
                base = acc + kc
                p_scan, c_scan = _seg_scan_sum_kahan(flags, vals)
                pref = p_scan + c_scan
                S_at = base[gather_g] + pref  # S_g(local_n), inclusive
                # S_g(local_n - C): inside this batch's segment when
                # seg_rank >= C, else the ring checkpoint, else 0
                in_batch = seg_rank >= C
                prev_batch = pref[jnp.clip(pos - C, 0)] + base[gather_g]
                ring = state[rkey]
                slot = jnp.clip(local_n - C, 0) % C
                prev_ring = ring[gather_g, slot]
                S_prev = jnp.where(
                    in_batch,
                    prev_batch,
                    jnp.where(local_n >= C, prev_ring, 0.0),
                )
                stats_s[f"{s}{arg_idx}"] = S_at - S_prev
                # ring update: each key's LAST min(C, seg_len) arrivals
                # checkpoint S(n) into slot n mod C (distinct slots)
                wslot = local_n % C
                flat = ring.reshape(G * C)
                widx = jnp.where(
                    is_tail, gather_g * C + wslot, G * C
                )
                flat = flat.at[widx].set(S_at, mode="drop")
                new_state[rkey] = flat.reshape(G, C)
                # carry totals forward (two-sum)
                gi = jnp.where(ends & mask_s, g_s, G)
                tot = jnp.zeros(G + 1, jnp.float32).at[gi].add(
                    jnp.where(ends, p_scan, 0.0), mode="drop"
                )[:G]
                tot_c = jnp.zeros(G + 1, jnp.float32).at[gi].add(
                    jnp.where(ends, c_scan, 0.0), mode="drop"
                )[:G]
                t = acc + tot
                err = jnp.where(
                    jnp.abs(acc) >= jnp.abs(tot),
                    (acc - t) + tot,
                    (tot - t) + acc,
                )
                new_state[Skey] = t
                new_state[kckey] = kc + err + tot_c
            kinds = sorted(stats[arg_idx] & {"min", "max"})
            if not kinds:
                continue
            # the key's last C raw values: the event's own, the k-th
            # before it from this batch where the key has that many here
            # (the sorted column shifted by k), and the record's ring
            # words that ``ring_ok`` says are still in the window
            v_s = arg_s[arg_idx]
            is_float = jnp.issubdtype(v_s.dtype, jnp.floating)
            members = [(v_s, mask_s)]
            for k in range(1, C):  # C <= PERKEY_RING_MAX < a tape's rows
                here = jnp.concatenate(
                    [jnp.zeros(k, v_s.dtype), v_s[:E - k]]
                )
                members.append((here, seg_rank >= k))
            first = 1 + ring_args.index(arg_idx) * C
            for p in range(C):
                old = rec[first + p]
                members.append((
                    lax.bitcast_convert_type(old, v_s.dtype)
                    if is_float else old.astype(v_s.dtype),
                    ring_ok[p],
                ))
            for kind in kinds:
                ident = _identity(kind, v_s.dtype)
                red = jnp.minimum if kind == "min" else jnp.maximum
                out = jnp.where(members[0][1], members[0][0], ident)
                for val, ok in members[1:]:
                    out = red(out, jnp.where(ok, val, ident))
                stats_s[f"{kind}{arg_idx}"] = out
            words = words.at[
                jnp.where(is_tail, g_s * W + first + local_n % C, G * W)
            ].set(
                lax.bitcast_convert_type(v_s, jnp.int32)
                if is_float else v_s.astype(jnp.int32),
                mode="drop",
            )
        new_state["rec"] = words.reshape(state["rec"].shape)

        # back to tape order: a sort by the tape position
        names = sorted(stats_s)
        _order, *back = lax.sort(
            [order] + [stats_s[n] for n in names], num_keys=1
        )
        stats_env = dict(zip(names, back))

        for agg in self.aggs:
            env[agg.slot] = _agg_from_stats(agg, stats_env).astype(
                agg.out_type.device_dtype
            )
        cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(env)), (E,))
            for p in self.proj_fns
        )
        out_mask = mask
        if self.having_fn is not None:
            henv = dict(env)
            for f, c in zip(self.output_schema.fields, cols):
                henv[f"@out:{f.name}"] = c
            out_mask = out_mask & self.having_fn(henv)
        return new_state, (out_mask, tape.ts, cols)


def window_wire_opts(artifact: "SlidingWindowArtifact", config):
    """Wire optimization for blocked sliding windows: select items that
    are PLAIN references to group-by columns emit the @group CODE (which
    already travels for the grouping) and decode back through the
    encoder — the raw group column drops off the wire entirely. Returns
    (needed_device_columns, ()) or None."""
    if not config.lazy_projection:
        # this IS late materialization (values resolve host-side at
        # decode); keep the same opt-in contract as the select/chain
        # wire opts
        return None
    if not artifact._blocked() or artifact.code_key is None:
        return None
    if artifact.having_fn is not None:
        return None  # having may read the coded output alias
    if not artifact.proj_srcs:
        return None
    gkeys = tuple(artifact.group_keys_)
    gcp = []
    for src in artifact.proj_srcs:
        gcp.append(
            gkeys.index(src)
            if src is not None and src in gkeys
            else None
        )
    if all(g is None for g in gcp):
        return None
    needed = set(artifact.filter_keys)
    if artifact.ts_key is not None:
        needed.add(artifact.ts_key)
    for src, refs, gi in zip(
        artifact.proj_srcs, artifact.proj_refs, gcp
    ):
        if gi is None:
            needed |= set(refs)
    artifact.group_code_proj = tuple(gcp)
    return needed, ()


def compile_delay_window(
    q: ast.Query,
    name: str,
    schemas,
    stream_codes: Dict[str, int],
    extensions,
    config=None,
):
    """``#window.delay(t)``: pass events through t ms late. Identical
    emission schedule to a time window's expired stream (entry ts +
    span), so it IS an ExpiredWindowArtifact with a rewritten window
    (siddhi-core 4.2.40 DelayWindowProcessor parity)."""
    import dataclasses

    inp = q.input
    if q.selector.group_by or q.selector.having is not None or any(
        ast.contains_aggregate(i.expr) for i in q.selector.items
    ):
        raise SiddhiQLError(
            "aggregations over #window.delay are not supported; delay "
            "the aggregated stream instead (chain the queries)"
        )
    delay_ms = _window_of(inp)[1]
    rewritten_inp = dataclasses.replace(
        inp, windows=(ast.Window("time", (ast.TimeLiteral(delay_ms),)),)
    )
    q2 = dataclasses.replace(
        q, input=rewritten_inp, output_events="expired"
    )
    return compile_expired_window(
        q2, name, schemas, stream_codes, extensions, config
    )
