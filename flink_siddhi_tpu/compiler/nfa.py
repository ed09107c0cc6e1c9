"""Pattern / sequence queries compiled to dense, batch-parallel matchers.

The reference gets its pattern engine (``every s1 = A[p] -> s2 = B[q]``,
``A+ , B? within t``) from the embedded JVM ``siddhi-core`` state machines,
fed one event at a time (AbstractSiddhiOperator.java:209-233 ->
InputHandler.send). Here a pattern compiles to one of two TPU formulations,
both consuming the whole micro-batch tape in a single jitted call:

* **Chain matcher** (fast path) — for ``[every] e0 -> e1 -> ... -> eK`` where
  every element is a plain (1,1) occurrence. Per-element predicates are
  evaluated once for the whole batch on the VPU; "next match at/after
  position p" becomes a reverse associative-scan (cummin) per element; every
  partial match then advances through the *whole* chain with K gathers —
  no per-event loop at all. Partial matches that outlive the batch carry in
  a fixed pool of slots.

* **Slot NFA** (general path) — for sequences (``,`` strict continuity) and
  counting quantifiers (``+ ? * <m:n>``). A ``lax.scan`` walks the tape once;
  the carry is a fixed array of partial-match slots advanced with vectorized
  transition rules (greedy absorb-before-advance, optional-skip via
  min-count prefix sums), plus a fixed-capacity match buffer.

Match semantics implemented (pinned against the reference's integration
tests, SiddhiCEPITCase.java:333-382):

* ``every``: each occurrence of the first element starts an independent
  partial match; one event may participate in many partials (A1 A2 B1
  yields (A1,B1) *and* (A2,B1)).
* without ``every``: the pattern matches exactly once (earliest start,
  earliest completion), then disarms.
* ``->`` (pattern): unrelated events between steps are ignored.
* ``,`` (sequence): an event that neither extends the current element nor
  starts the next one kills the partial (after emitting if all remaining
  elements are optional).
* quantifiers are greedy: extending the current element wins over advancing.
* ``within t``: total first-to-last span bounded; expired partials are
  reclaimed (their slots freed) as soon as the watermark proves they can
  never complete.
* Indexed capture refs ``s[0].x`` / ``s[last].x`` resolve to the first/last
  event absorbed by a quantified element; a bare ``s.x`` means ``s[0].x``.

Both engines respect the control plane's enable gate: a disabled query
neither starts nor advances partials (reference: send gated on enabled,
AbstractSiddhiOperator.java:127-132).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..query import ast
from ..query.lexer import SiddhiQLError
from ..schema.types import AttributeType
from .expr import ColumnEnv, ExprResolver, ResolvedAttr, compile_expr
from .output import OutputField, OutputSchema

DEFAULT_PARTIAL_POOL = 1024  # chain matcher: carried partial matches
DEFAULT_SLOTS = 64  # slot NFA: concurrent partial matches
_BIG = np.int32(2**30)


# --------------------------------------------------------------------------
# Capture resolution: select-clause refs -> captured-value env keys
# --------------------------------------------------------------------------

def _cap_key(alias: str, which: str, name: str) -> str:
    return f"{alias}@{which}.{name}"


class CaptureResolver:
    """Resolves select/having attribute refs against pattern captures.

    ``s1.x`` / ``s1[0].x`` -> first absorbed event's value;
    ``s1[last].x`` -> last absorbed event's value. Bare names resolve
    uniquely across elements (ambiguity is an error, as in Siddhi).
    """

    def __init__(self, elements, schemas):
        # alias -> (element index, stream_id, schema); absent ('not')
        # elements never match an event, so they have nothing to select
        self._by_alias: Dict[str, Tuple[int, str, object]] = {}
        self._negated = {el.alias for el in elements if el.negated}
        self._elements = tuple(elements)
        for i, el in enumerate(elements):
            self._by_alias[el.alias] = (i, el.stream_id, schemas[el.stream_id])
        self.referenced: List[Tuple[int, str, str]] = []  # (elem, col, which)

    def _note(self, elem: int, col: str, which: str) -> None:
        key = (elem, col, which)
        if key not in self.referenced:
            self.referenced.append(key)

    def element_of(self, attr: ast.Attr) -> Optional[int]:
        """The element index an attribute reference resolves to, or None
        (unknown / ambiguous). Mirrors resolve()'s rules without raising
        or recording."""
        if attr.qualifier is not None:
            info = self._by_alias.get(attr.qualifier)
            return info[0] if info is not None else None
        hits = [
            info[0]
            for alias, info in self._by_alias.items()
            if attr.name in info[2] and alias not in self._negated
        ]
        return hits[0] if len(hits) == 1 else None

    def resolve(self, attr: ast.Attr) -> ResolvedAttr:
        if attr.qualifier is None:
            hits = [
                (alias, info)
                for alias, info in self._by_alias.items()
                if attr.name in info[2] and alias not in self._negated
            ]
            if not hits:
                raise SiddhiQLError(f"unknown attribute {attr.name!r}")
            if len(hits) > 1:
                raise SiddhiQLError(
                    f"ambiguous attribute {attr.name!r}; qualify it with a "
                    "pattern alias"
                )
            alias, (idx, _sid, schema) = hits[0]
            which = "first"
        else:
            if attr.qualifier not in self._by_alias:
                raise SiddhiQLError(
                    f"unknown pattern alias {attr.qualifier!r}"
                )
            alias = attr.qualifier
            idx, _sid, schema = self._by_alias[alias]
            if attr.index is None or attr.index == 0:
                which = "first"
            elif attr.index == "last":
                which = "last"
            elif isinstance(attr.index, int) and attr.index > 0:
                mx = self._elements[idx].max_count
                if 0 <= mx <= attr.index:
                    raise SiddhiQLError(
                        f"{alias}[{attr.index}] can never exist: the "
                        f"element absorbs at most {mx} event(s)"
                    )
                if attr.index >= 16:
                    raise SiddhiQLError(
                        f"indexed capture {alias}[{attr.index}] exceeds "
                        "the supported index range (< 16)"
                    )
                which = f"idx{attr.index}"
            else:
                raise SiddhiQLError(
                    f"indexed capture {alias}[{attr.index!r}] is not "
                    "supported; use a non-negative index or [last]"
                )
            if attr.name not in schema:
                raise SiddhiQLError(
                    f"stream of alias {alias!r} has no attribute {attr.name!r}"
                )
        if alias in self._negated:
            raise SiddhiQLError(
                f"cannot select from absent ('not') element {alias!r}"
            )
        atype = schema.field_type(attr.name)
        table = schema.string_tables.get(attr.name)
        self._note(idx, attr.name, which)
        return ResolvedAttr(_cap_key(alias, which, attr.name), atype, table)


class _ElemFilterResolver:
    """Resolves an element filter that references earlier elements'
    captures: own attributes -> tape columns (recorded in ``evt_keys``),
    foreign aliases -> capture env keys via the shared CaptureResolver
    (which records the capture for slot state)."""

    def __init__(
        self,
        own_idx: int,
        own_el,
        own_schema,
        elements,
        cap_resolver: "CaptureResolver",
        evt_keys: List[str],
        g_of: Optional[Dict[int, int]] = None,
    ) -> None:
        self._own_idx = own_idx
        self._own = own_el
        self._schema = own_schema
        self._elements = elements
        self._cap = cap_resolver
        self._evt_keys = evt_keys
        self._aliases = {el.alias for el in elements}
        self._g_of = g_of or {}

    def resolve(self, attr: ast.Attr) -> ResolvedAttr:
        q = attr.qualifier
        own = q is None or q == self._own.alias or (
            q == self._own.stream_id and q not in self._aliases
        )
        if own:
            if attr.index is not None:
                raise SiddhiQLError(
                    "indexed references are not valid on the element's "
                    "own attributes in a filter"
                )
            if attr.name not in self._schema:
                raise SiddhiQLError(
                    f"stream {self._own.stream_id!r} has no attribute "
                    f"{attr.name!r}"
                )
            key = f"{self._own.stream_id}.{attr.name}"
            if key not in self._evt_keys:
                self._evt_keys.append(key)
            return ResolvedAttr(
                key,
                self._schema.field_type(attr.name),
                self._schema.string_tables.get(attr.name),
            )
        info = self._cap._by_alias.get(q)
        if info is None:
            raise SiddhiQLError(f"unknown stream reference {q!r}")
        ref_idx = info[0]
        if ref_idx >= self._own_idx:
            raise SiddhiQLError(
                f"element filter of {self._own.alias!r} can only "
                f"reference EARLIER elements; {q!r} has not matched yet"
            )
        if self._g_of and self._g_of.get(ref_idx) == self._g_of.get(
            self._own_idx
        ):
            raise SiddhiQLError(
                f"element filter of {self._own.alias!r} cannot reference "
                f"{q!r}: members of one 'and'/'or' group match in any "
                "order"
            )
        if self._elements[ref_idx].negated:
            raise SiddhiQLError(
                f"cannot reference absent ('not') element {q!r} in a filter"
            )
        return self._cap.resolve(attr)


# --------------------------------------------------------------------------
# Shared compile-time pieces
# --------------------------------------------------------------------------

@dataclass
class _PatternSpec:
    elements: Tuple[ast.PatternElement, ...]
    kind: str  # 'pattern' | 'sequence'
    every: bool
    # grouped `every (A -> B)`: restart only after a complete occurrence
    # (single instance in flight), vs ungrouped every's start-at-every-A
    every_grouped: bool
    within: Optional[int]
    pred_fns: List[Callable[[ColumnEnv], jnp.ndarray]]
    stream_code_of: List[int]
    # captures: (elem idx, col name, 'first'|'last'); col key per element
    captures: List[Tuple[int, str, str]]
    cap_dtype: Dict[Tuple[int, str], np.dtype]
    cap_src_key: Dict[Tuple[int, str], str]  # tape column key
    proj_fns: List
    out_fields: Tuple[OutputField, ...]
    output_stream: str
    # per projection: the (elem, col) capture pair when the projection is a
    # plain capture reference, else None (lets the stacked engine emit
    # straight from the stacked capture buffers with zero per-query ops)
    proj_srcs: Tuple[Optional[Tuple[int, str]], ...] = ()
    # cross-element filters (`s2 = S[price > s1.price]`): per element,
    # the full filter compiled against BOTH the current event's columns
    # and earlier elements' captures; such elements have pred_fns None
    # (the event-only mask is just the stream gate) and are evaluated
    # per-slot inside the scan engine. siddhi-core supports these
    # conditions natively (SURVEY.md §2.10 pattern surface).
    cross_fns: Tuple[Optional[Callable], ...] = ()
    evt_keys: Tuple[str, ...] = ()  # tape columns the cross filters read
    # per element: indices of earlier elements its cross filter reads; a
    # referenced element that was SKIPPED (optional, min 0) must make the
    # filter false (Siddhi: comparisons with null never hold), not read a
    # zero-initialized capture
    cross_refs: Tuple[Tuple[int, ...], ...] = ()
    # logical steps: each group is a tuple of element indices advancing
    # as ONE step ('and': all must arrive, any order; 'or': any one)
    groups: Tuple[Tuple[int, ...], ...] = ()
    group_ops: Tuple[Optional[str], ...] = ()  # None for singletons
    # per projection: 'or'-group member elements it references — exactly
    # one member of an or-group fires, so projections over the OTHER
    # member must decode as None (Siddhi: null), not a zeroed capture
    proj_or_deps: Tuple[Tuple[int, ...], ...] = ()
    # per projection: every (elem, col) capture pair its expression reads
    # (late-materialization eligibility analysis)
    proj_ref_pairs: Tuple[Tuple[Tuple[int, str], ...], ...] = ()
    # per projection: (elem, col, k) for each s[k>=1] indexed reference —
    # decodes None when the element absorbed fewer than k+1 events
    proj_idx_refs: Tuple[Tuple[Tuple[int, str, int], ...], ...] = ()
    # per element: (elem, col, k) indexed refs its cross filter reads — the
    # filter can only hold once the referenced element absorbed > k events
    cross_idx_refs: Tuple[Tuple[Tuple[int, str, int], ...], ...] = ()
    # mid-chain `-> every X`: elements where every matching event FORKS a
    # continuing instance while the matched prefix stays armed
    every_marks: Tuple[bool, ...] = ()
    # first-occurrence-only guards (sequence absence before a quantified
    # element, `A, not B, C+`): per element, the event-only predicate the
    # slot engine additionally requires on the ADVANCE-INTO-element path
    # — count-conditional by construction, since absorbs (count >= 1)
    # never consult it. None = unguarded.
    entry_guard_fns: Tuple[Optional[Callable], ...] = ()
    # wire predicate pushdown: per element, the numpy twin of its
    # event-only filter (None when absent or not host-evaluable)
    host_pred_fns: Tuple = ()

    @property
    def n_elements(self) -> int:
        return len(self.elements)

    @property
    def has_cross(self) -> bool:
        return any(f is not None for f in self.cross_fns)


def _rewrite_sequence_absence(inp: ast.PatternInput) -> ast.PatternInput:
    """``A, not B, C`` in a STRICT sequence: any intervening event
    already breaks contiguity, so the absence collapses into the next
    element's filter — the event after A must be C and must NOT match B
    (when B and C read the same stream; a different-stream B could never
    be that event, so the guard is vacuous). Siddhi sequence absence
    semantics via pure AST rewrite (README.md:77-96 "Sequence
    Processing").

    A QUANTIFIED next element (``A, not B, C+`` / ``C<m:n>`` with
    ``m >= 1``) folds the guard into ``entry_filter`` instead: the
    guard constrains only the first occurrence (the event entering C),
    and the slot engine applies it count-conditionally on the
    advance-into-element path, never on absorbs — later repeats'
    predecessor is the previous repeat, not B's window."""
    import dataclasses

    els = list(inp.elements)
    if els and els[0].negated:
        raise SiddhiQLError(
            "a sequence cannot start with an absent ('not') element"
        )
    if els and els[-1].negated:
        raise SiddhiQLError(
            "a sequence cannot end with an absent ('not') element"
        )
    out: List[ast.PatternElement] = []
    pending: List[ast.PatternElement] = []  # consecutive absent run
    for el in els:
        if el.negated:
            pending.append(el)
            continue
        if pending:
            # every guard of the run applies to THIS (the next
            # non-absent) element's event — folding one absent filter
            # into another absent element would negate it twice
            quantified = (el.min_count, el.max_count) != (1, 1)
            if quantified and el.min_count < 1:
                # a skipped optional consumes no event, so the guard
                # would have to transfer to whichever LATER element
                # takes the next event — a placement the per-element
                # entry-guard fold below cannot express
                raise SiddhiQLError(
                    "absence before an OPTIONAL sequence element "
                    "(min count 0) is not supported: when the element "
                    "is skipped the guard has no event to constrain; "
                    "make the first occurrence mandatory "
                    "(`C*` -> `C+`, `C<0:n>` -> `C<1:n>`) or split it "
                    "out: `A, not B, c1=C, crest=C*`"
                )
            nxt = el
            for ab in pending:
                if ab.stream_id != nxt.stream_id:
                    # strictness makes the guard vacuous: an
                    # other-stream event between the neighbors would
                    # break the sequence by itself
                    continue
                if ab.filter is None:
                    raise SiddhiQLError(
                        f"'not {ab.stream_id}' without a filter before "
                        "a same-stream element can never match; filter "
                        "the absent element"
                    )
                guard = ast.Unary(
                    "not", _rebind_alias(ab.filter, ab.alias, nxt.alias)
                )
                if quantified:
                    # the guard belongs to the FIRST occurrence only —
                    # folding it into the shared per-occurrence filter
                    # would also veto later repeats whose predecessor
                    # is a repeat, not B's window. It lands in
                    # ``entry_filter`` (count-conditional: the slot
                    # engine applies it on the advance-into-element
                    # path and not on absorbs).
                    nxt = dataclasses.replace(
                        nxt,
                        entry_filter=(
                            guard
                            if nxt.entry_filter is None
                            else ast.Binary(
                                "and", nxt.entry_filter, guard
                            )
                        ),
                    )
                else:
                    nxt = dataclasses.replace(
                        nxt,
                        filter=(
                            guard
                            if nxt.filter is None
                            else ast.Binary("and", nxt.filter, guard)
                        ),
                    )
            pending = []
            out.append(nxt)
        else:
            out.append(el)
    return dataclasses.replace(inp, elements=tuple(out))


def _rebind_alias(expr: ast.Expr, old: str, new: str) -> ast.Expr:
    """Rewrite attribute qualifiers ``old.x`` -> ``new.x`` (the absence
    guard evaluates against the NEXT element's event)."""
    import dataclasses

    return ast.map_expr(
        expr,
        lambda a: (
            dataclasses.replace(a, qualifier=new)
            if a.qualifier == old
            else a
        ),
    )


def _build_spec(
    q: ast.Query,
    schemas,
    stream_codes: Dict[str, int],
    extensions,
) -> _PatternSpec:
    inp = q.input
    assert isinstance(inp, ast.PatternInput)
    if inp.kind == "sequence" and any(el.negated for el in inp.elements):
        inp = _rewrite_sequence_absence(inp)
    aliases = [el.alias for el in inp.elements]
    if len(set(aliases)) != len(aliases):
        raise SiddhiQLError("pattern aliases must be unique")

    # logical steps: group_link chains consecutive elements into one step
    groups: List[Tuple[int, ...]] = []
    group_ops: List[Optional[str]] = []
    for i, el in enumerate(inp.elements):
        if el.group_link is None:
            groups.append((i,))
            group_ops.append(None)
        else:
            groups[-1] = groups[-1] + (i,)
            group_ops[-1] = el.group_link
    g_of = {e: g for g, mem in enumerate(groups) for e in mem}
    for g, mem in enumerate(groups):
        if len(mem) == 1:
            continue
        for e in mem:
            el = inp.elements[e]
            if el.negated:
                raise SiddhiQLError(
                    "absent ('not') elements inside 'and'/'or' groups "
                    "are not supported yet"
                )
            if (el.min_count, el.max_count) != (1, 1):
                raise SiddhiQLError(
                    "elements of an 'and'/'or' group cannot be quantified"
                )
    for i, el in enumerate(inp.elements):
        if el.negated:
            # mid-chain absence: `A -> not B -> C` (C must arrive with no
            # B in between); terminal TIMED absence: `A -> not B for 5
            # sec` (emit when the window elapses with no B)
            if inp.kind == "sequence":
                raise SiddhiQLError(
                    "absence ('not') is not supported in sequences"
                )
            if i == 0:
                raise SiddhiQLError(
                    "a pattern cannot start with an absent ('not') element"
                )
            last = i == len(inp.elements) - 1
            if last and el.absent_for is None:
                raise SiddhiQLError(
                    "terminal absence needs a duration: "
                    "'-> not B for 5 sec'"
                )
            if not last and el.absent_for is not None:
                raise SiddhiQLError(
                    "timed absence ('not B for t') must be the last "
                    "pattern element"
                )
            if (el.min_count, el.max_count) != (1, 1):
                raise SiddhiQLError(
                    "absent ('not') elements cannot be quantified"
                )
        elif el.absent_for is not None:
            raise SiddhiQLError(
                "'for <duration>' is only valid on absent ('not') elements"
            )
        if el.stream_id not in stream_codes:
            raise SiddhiQLError(f"stream {el.stream_id!r} is not defined")

    cap_resolver = CaptureResolver(inp.elements, schemas)

    # per-element predicate kernels. A filter referencing ONLY the current
    # event compiles to a whole-batch mask (fast path); one referencing
    # earlier elements' captures (`s2 = S[price > s1.price]`) compiles to
    # a cross fn evaluated per partial-match slot inside the scan engine.
    alias_idx = {el.alias: i for i, el in enumerate(inp.elements)}
    pred_fns: List[Optional[Callable]] = []
    cross_fns: List[Optional[Callable]] = []
    cross_refs: List[Tuple[int, ...]] = []
    cross_idx_refs: List[Tuple[Tuple[int, str, int], ...]] = []
    evt_keys: List[str] = []

    def _indexed_refs(expr) -> Tuple[Tuple[int, str, int], ...]:
        """(elem, col, k) for every s[k>=1] reference in the expression."""
        out = set()
        for a in ast.iter_attrs(expr):
            if (
                a.qualifier is not None
                and a.qualifier in alias_idx
                and isinstance(a.index, int)
                and a.index >= 1
            ):
                out.add((alias_idx[a.qualifier], a.name, a.index))
        return tuple(sorted(out))

    host_pred_fns: List = []
    for i, el in enumerate(inp.elements):
        schema = schemas[el.stream_id]
        if el.filter is None:
            pred_fns.append(None)
            cross_fns.append(None)
            cross_refs.append(())
            cross_idx_refs.append(())
            host_pred_fns.append(None)
            continue
        foreign = {
            a.qualifier
            for a in ast.iter_attrs(el.filter)
            if a.qualifier is not None
            and a.qualifier in alias_idx
            and a.qualifier != el.alias
        }
        if not foreign:
            scopes = {
                el.alias: (el.stream_id, schema),
                el.stream_id: (el.stream_id, schema),
            }
            resolver = ExprResolver(scopes, default_scope=el.alias)
            ce = compile_expr(el.filter, resolver, extensions)
            if ce.atype != AttributeType.BOOL:
                raise SiddhiQLError("pattern element filter must be boolean")
            pred_fns.append(ce.fn)
            cross_fns.append(None)
            cross_refs.append(())
            cross_idx_refs.append(())
            from .expr import compile_host_pred

            host_pred_fns.append(compile_host_pred(el.filter, resolver))
            continue
        if el.negated:
            raise SiddhiQLError(
                "cross-element references are not supported in absent "
                "('not') element filters"
            )
        resolver = _ElemFilterResolver(
            i, el, schema, inp.elements, cap_resolver, evt_keys, g_of
        )
        ce = compile_expr(el.filter, resolver, extensions)
        if ce.atype != AttributeType.BOOL:
            raise SiddhiQLError("pattern element filter must be boolean")
        pred_fns.append(None)  # event-only mask = stream gate
        cross_fns.append(ce.fn)
        cross_refs.append(tuple(sorted(alias_idx[a] for a in foreign)))
        cross_idx_refs.append(_indexed_refs(el.filter))
        host_pred_fns.append(None)

    # first-occurrence entry guards (sequence absence rewrite): compile
    # each against the guarded element's OWN event only — the guard is a
    # rebound `not B` over the entering event, and the absent element's
    # filter was barred from cross references above
    entry_guard_fns: List[Optional[Callable]] = []
    for i, el in enumerate(inp.elements):
        ef = el.entry_filter
        if ef is None:
            entry_guard_fns.append(None)
            continue
        if any(
            a.qualifier is not None
            and a.qualifier in alias_idx
            and a.qualifier != el.alias
            for a in ast.iter_attrs(ef)
        ):
            raise SiddhiQLError(
                "cross-element references are not supported in absent "
                "('not') element filters"
            )
        schema = schemas[el.stream_id]
        resolver = ExprResolver(
            {
                el.alias: (el.stream_id, schema),
                el.stream_id: (el.stream_id, schema),
            },
            default_scope=el.alias,
        )
        ce = compile_expr(ef, resolver, extensions)
        if ce.atype != AttributeType.BOOL:
            raise SiddhiQLError(
                "sequence absence guard must be boolean"
            )
        entry_guard_fns.append(ce.fn)
    if q.selector.is_star:
        raise SiddhiQLError(
            "select * is not valid for pattern queries; name the captures"
        )
    or_members = {
        e
        for g, mem in enumerate(groups)
        if len(mem) > 1 and group_ops[g] == "or"
        for e in mem
    }

    def _or_deps(expr) -> Tuple[int, ...]:
        deps = set()
        for a in ast.iter_attrs(expr):
            elem = cap_resolver.element_of(a)
            if elem is not None and elem in or_members:
                deps.add(elem)
        return tuple(sorted(deps))

    def _item_pairs(expr) -> Tuple[Tuple[int, str], ...]:
        prs = set()
        for a in ast.iter_attrs(expr):
            e = cap_resolver.element_of(a)
            if e is not None:
                prs.add((e, a.name))
        return tuple(sorted(prs))

    proj_fns, out_fields, proj_srcs = [], [], []
    proj_or_deps: List[Tuple[int, ...]] = []
    proj_ref_pairs: List[Tuple[Tuple[int, str], ...]] = []
    proj_idx_refs: List[Tuple[Tuple[int, str, int], ...]] = []
    for item in q.selector.items:
        if ast.contains_aggregate(item.expr):
            raise SiddhiQLError(
                "aggregations over pattern matches are not supported"
            )
        proj_or_deps.append(_or_deps(item.expr))
        proj_ref_pairs.append(_item_pairs(item.expr))
        proj_idx_refs.append(_indexed_refs(item.expr))
        ce = compile_expr(item.expr, cap_resolver, extensions)
        proj_fns.append(ce.fn)
        out_fields.append(OutputField(item.output_name(), ce.atype, ce.table))
        src = None
        if isinstance(item.expr, ast.Attr) and item.expr.index in (
            None, 0, "last",
        ):
            a = item.expr
            if a.qualifier is not None:
                info = cap_resolver._by_alias.get(a.qualifier)
                if info is not None and a.name in info[2]:
                    src = (info[0], a.name)
            else:
                hits = [
                    info
                    for info in cap_resolver._by_alias.values()
                    if a.name in info[2]
                ]
                if len(hits) == 1:
                    src = (hits[0][0], a.name)
        proj_srcs.append(src)
    if q.selector.having is not None:
        raise SiddhiQLError("having is not valid on pattern queries")

    captures = list(cap_resolver.referenced)
    for elem, _col, which in captures:
        if which.startswith("idx") and any(
            elem in mem and len(mem) > 1 for mem in groups
        ):
            raise SiddhiQLError(
                f"indexed capture on {inp.elements[elem].alias!r} is not "
                "supported: 'and'/'or' group members match exactly once"
            )
    cap_dtype, cap_src = {}, {}
    for elem, col, _which in captures:
        el = inp.elements[elem]
        atype = schemas[el.stream_id].field_type(col)
        cap_dtype[(elem, col)] = atype.device_dtype
        cap_src[(elem, col)] = f"{el.stream_id}.{col}"

    return _PatternSpec(
        elements=inp.elements,
        kind=inp.kind,
        every=inp.every_,
        every_grouped=inp.every_grouped,
        within=inp.within,
        pred_fns=pred_fns,
        stream_code_of=[stream_codes[el.stream_id] for el in inp.elements],
        captures=captures,
        cap_dtype=cap_dtype,
        cap_src_key=cap_src,
        proj_fns=proj_fns,
        out_fields=tuple(out_fields),
        output_stream=q.output_stream,
        proj_srcs=tuple(proj_srcs),
        cross_fns=tuple(cross_fns),
        evt_keys=tuple(evt_keys),
        cross_refs=tuple(cross_refs),
        groups=tuple(groups),
        group_ops=tuple(group_ops),
        proj_or_deps=tuple(proj_or_deps),
        proj_ref_pairs=tuple(proj_ref_pairs),
        proj_idx_refs=tuple(proj_idx_refs),
        cross_idx_refs=tuple(cross_idx_refs),
        every_marks=tuple(
            getattr(el, "every_marked", False) for el in inp.elements
        ),
        host_pred_fns=tuple(host_pred_fns),
        entry_guard_fns=tuple(entry_guard_fns),
    )


def _cap_pairs(spec: _PatternSpec) -> List[Tuple[int, str]]:
    seen: List[Tuple[int, str]] = []
    for elem, col, _w in spec.captures:
        if (elem, col) not in seen:
            seen.append((elem, col))
    return seen


def _skey(prefix: str, elem: int, col: str) -> str:
    """Flat string key for state dicts (jit pytrees need uniform key types)."""
    return f"{prefix}:{elem}:{col}"


def _idx_caps(spec: _PatternSpec) -> List[Tuple[int, str, int]]:
    """Distinct (elem, col, k) indexed captures (``s[k>=1].col``), in a
    deterministic order that doubles as the validity-bit layout on the
    mbits wire row (bit K + position)."""
    seen = set()
    for elem, col, which in spec.captures:
        if which.startswith("idx"):
            seen.add((elem, col, int(which[3:])))
    return sorted(seen)


_COMPACT_MIN_E = 4096  # below this, compaction overhead beats the gain


def _compact_width(E: int) -> int:
    """Relevant-event buffer width for chain relevance compaction."""
    return max(2048, E // 8)


def _compact_index(rel, R: int):
    """Scatter-compact the True positions of ``rel`` (bool[E]) into an
    ascending index buffer of width R. Returns (idx, cnt, cvalid);
    positions beyond R are dropped (callers lax.cond on cnt <= R).
    Shared by the single-chain and stacked-chain compaction paths."""
    E = int(rel.shape[0])
    cnt = rel.sum().astype(jnp.int32)
    cpos = jnp.cumsum(rel.astype(jnp.int32)) - 1
    dest = jnp.where(rel & (cpos < R), cpos, R)
    idx = (
        jnp.zeros(R, dtype=jnp.int32)
        .at[dest]
        .set(jnp.arange(E, dtype=jnp.int32), mode="drop")
    )
    cvalid = jnp.arange(R) < jnp.minimum(cnt, R)
    return idx, cnt, cvalid


def _compact_index_batched(rel, R: int):
    """(Q, E) batched variant of ``_compact_index`` as ONE flat scatter.
    A vmapped scatter lowers to a batched scatter XLA serializes badly
    on TPU (it dominated the stacked step); flattening the destination
    space to Q*R restores the cheap single-scatter lowering."""
    Q, E = int(rel.shape[0]), int(rel.shape[1])
    cnt = rel.sum(axis=1).astype(jnp.int32)
    cpos = jnp.cumsum(rel.astype(jnp.int32), axis=1) - 1
    ok = rel & (cpos < R)
    qoff = jnp.arange(Q, dtype=jnp.int32)[:, None] * R
    dest = jnp.where(ok, cpos + qoff, Q * R)
    src = jnp.broadcast_to(
        jnp.arange(E, dtype=jnp.int32)[None, :], (Q, E)
    )
    idx = (
        jnp.zeros(Q * R, dtype=jnp.int32)
        .at[dest.reshape(-1)]
        .set(src.reshape(-1), mode="drop")
        .reshape(Q, R)
    )
    cvalid = (
        jnp.arange(R, dtype=jnp.int32)[None, :]
        < jnp.minimum(cnt, R)[:, None]
    )
    return idx, cnt, cvalid


def _element_preds(spec: _PatternSpec, tape, enabled) -> List[jnp.ndarray]:
    """bool[E] match mask per element, fused over the whole batch."""
    env: ColumnEnv = dict(tape.cols)
    preds = []
    for k in range(spec.n_elements):
        m = tape.valid & (tape.stream == spec.stream_code_of[k])
        fn = spec.pred_fns[k]
        if fn is not None:
            m = m & fn(env)
        preds.append(m & enabled)
    return preds


def _emit_env(spec: _PatternSpec, cap_arrays: Dict) -> ColumnEnv:
    """Capture buffers -> env for the projection kernels."""
    env: ColumnEnv = {}
    for elem, col, which in spec.captures:
        alias = spec.elements[elem].alias
        env[_cap_key(alias, which, col)] = cap_arrays[(elem, col, which)]
    return env


# --------------------------------------------------------------------------
# Engine 1: vectorized chain matcher (all-(1,1) `->` patterns)
# --------------------------------------------------------------------------

def _as_i32(arr):
    if arr.dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(arr, jnp.int32)
    return arr.astype(jnp.int32)


def _from_i32(row, dtype):
    if dtype == jnp.float32:
        return jax.lax.bitcast_convert_type(row, jnp.float32)
    return row.astype(dtype)


def _spec_check_info(name: str, spec: "_PatternSpec", **extra) -> Dict:
    """One pattern's transition tables in the neutral dict form
    analysis.plancheck consumes — the compiler's side of the plancheck
    contract (the verifier never reaches into private spec fields)."""
    cfg = _ChainCfg.of(spec)
    info = dict(
        name=name,
        n_elements=spec.n_elements,
        positive=cfg.positive,
        guards=cfg.guards,
        t_guard=cfg.t_guard,
        negated=tuple(el.negated for el in spec.elements),
        quantifiers=tuple(
            (el.min_count, el.max_count) for el in spec.elements
        ),
        # first-occurrence guards (sequence absence before a quantified
        # element): PLC203 pins their placement — quantified, non-first,
        # mandatory-min elements only
        entry_guards=tuple(
            k
            for k, f in enumerate(spec.entry_guard_fns or ())
            if f is not None
        ),
    )
    info.update(extra)
    return info


def _pattern_cost(name: str, spec: "_PatternSpec", pool: int) -> Dict:
    """One pattern's admission-cost descriptor (analysis/admit.py).

    Residency: ``within`` when declared; without it an ``every``
    pattern (incl. mid-chain ``-> every`` forks) arms partials that
    NEVER expire — unbounded slot residency, the ADM110 reject class.
    A non-every pattern keeps a single instance in flight, so its
    unexpired state is one slot, not a growing population."""
    every = spec.every or any(spec.every_marks or ())
    if spec.within is not None:
        res: object = float(spec.within)
        unbounded = None
    elif every:
        res = float("inf")
        unbounded = (
            "'every' pattern with no 'within' clause: armed partial "
            f"matches never expire and pin the {pool}-slot pool "
            "(matches beyond it drop with counted overflow)"
        )
    else:
        res = None
        unbounded = None
    info = {
        "name": name,
        "kind": "pattern",
        "amplification": int(pool) if every else 1,
        "residency_ms": res,
    }
    if unbounded is not None:
        info["unbounded"] = unbounded
    return info


@dataclass(frozen=True)
class _ChainCfg:
    """Static (hashable) chain-matcher configuration — everything the
    vmappable core needs besides data. Two queries with equal cfg can run
    stacked on a query axis (StackedChainArtifact).

    ``positive`` are the original element indices the chain advances
    through; ``guards[k]`` are the absent ('not') elements between
    positive steps k-1 and k — a guard match before the step-k match
    kills the partial (mid-chain absence, `A -> not B -> C`)."""

    K: int  # number of POSITIVE elements
    every: bool
    has_within: bool
    pairs: Tuple[Tuple[int, str], ...]
    cap_dtypes: Tuple[str, ...]  # numpy dtype names, per pair
    positive: Tuple[int, ...] = ()
    guards: Tuple[Tuple[int, ...], ...] = ()  # per positive step
    # terminal timed absence (`... -> not B for t`): the guard element's
    # index; partials that finish all positive steps WAIT, and emit at
    # (last positive ts + t) unless a guard match lands inside the window
    t_guard: Optional[int] = None

    @staticmethod
    def of(spec: "_PatternSpec") -> "_ChainCfg":
        pairs = tuple(_cap_pairs(spec))
        positive = tuple(
            i for i, el in enumerate(spec.elements) if not el.negated
        )
        guards: List[Tuple[int, ...]] = []
        for k, elem in enumerate(positive):
            lo = positive[k - 1] if k else -1
            guards.append(
                tuple(
                    g
                    for g in range(lo + 1, elem)
                    if spec.elements[g].negated
                )
            )
        last = spec.elements[-1]
        t_guard = (
            len(spec.elements) - 1
            if last.negated and last.absent_for is not None
            else None
        )
        return _ChainCfg(
            K=len(positive),
            every=spec.every,
            has_within=spec.within is not None,
            pairs=pairs,
            cap_dtypes=tuple(
                np.dtype(spec.cap_dtype[p]).name for p in pairs
            ),
            positive=positive,
            guards=tuple(guards),
            t_guard=t_guard,
        )


@jax.named_scope("fst.pattern_scan")
# fst:hotpath device=state,preds,cap_srcs,within_val,ts,valid,tfor_val,batch_max
def _chain_core(
    cfg: _ChainCfg,
    P: int,
    state: Dict,
    preds,  # bool[n_elements, E] — positive AND guard rows, by
    # ORIGINAL element index (cfg.K counts positive elements only)
    cap_srcs: Dict,  # pair -> value[E]
    within_val,  # int32 scalar (ignored unless cfg.has_within)
    ts,  # int32[E]
    valid,  # bool[E]
    use_pallas: bool = False,  # single-query callers only (not vmappable)
    tfor_val=None,  # int32 scalar (required when cfg.t_guard is set)
    batch_max=None,  # int32 scalar: max valid ts of the FULL batch (a
    # relevance-compacted caller passes it so within-expiry and absence
    # deadlines still see the whole batch's time horizon)
):
    """One micro-batch of the chain matcher for ONE query: advance carried
    partials + fresh starts through all elements, find completions, and
    compact survivors back into the pool. Pure function of arrays + static
    cfg, so a stacked group of structurally-identical queries runs it
    under jax.vmap over the leading query axis.

    Returns (new_state, complete[V], emit_ts[V], caps{pair: [V]}).
    """
    K = cfg.K
    E = ts.shape[0]
    V = P + E
    pairs = list(cfg.pairs)
    cap_dtypes = {
        p: np.dtype(n) for p, n in zip(cfg.pairs, cfg.cap_dtypes)
    }
    positive = cfg.positive
    guards = cfg.guards
    assert len(positive) == K and len(guards) == K
    arange = jnp.arange(E, dtype=jnp.int32)

    # next_idx[e][p] = min q >= p with preds[e][q], else E; padded so a
    # gather at position E (or beyond-batch) safely reads "no match".
    # Needed for every positive target AND every absence guard; all the
    # reverse cummins fuse into one Pallas pass on TPU.
    scan_rows = list(positive[1:]) + [
        g for gs in guards for g in gs
    ]
    if cfg.t_guard is not None:
        scan_rows.append(cfg.t_guard)
    idxs = [
        jnp.where(preds[e], arange, E) for e in scan_rows
    ]
    if use_pallas and idxs:
        from .pallas_ops import multi_reverse_cummin

        scans = multi_reverse_cummin(idxs)
    else:
        scans = [
            jax.lax.associative_scan(jnp.minimum, idx, reverse=True)
            for idx in idxs
        ]
    nxt = {
        e: jnp.concatenate([s, jnp.asarray([E], dtype=jnp.int32)])
        for e, s in zip(scan_rows, scans)
    }
    ts_pad = jnp.concatenate([ts, jnp.asarray([0], dtype=jnp.int32)])
    env_pad = {
        pair: jnp.concatenate(
            [cap_srcs[pair], jnp.zeros(1, dtype=cap_srcs[pair].dtype)]
        )
        for pair in pairs
    }

    # fresh starts: one candidate per tape position matching element 0
    starts = preds[0]
    if not cfg.every:
        starts = starts & ~state["done"]
    v_active = jnp.concatenate([state["active"], starts])
    v_step = jnp.concatenate([state["step"], jnp.ones(E, dtype=jnp.int32)])
    # search position: carried partials resume at batch start
    v_pos = jnp.concatenate([jnp.zeros(P, dtype=jnp.int32), arange + 1])
    v_start = jnp.concatenate([state["start"], ts])
    # fresh starts already completed element 0 at their own position, so a
    # single-element pattern (K == 1) emits at the start event's ts; K > 1
    # overwrites this on the final advance. With a terminal timed absence
    # the pool carries emit_ts (the waiting deadline's base) across batches.
    carried_emit = (
        state["emit_ts"]
        if cfg.t_guard is not None
        else jnp.zeros(P, dtype=jnp.int32)
    )
    v_emit_ts = jnp.concatenate([carried_emit, ts])
    caps = {}
    for pair in pairs:
        elem, _col = pair
        src = env_pad[pair][:E]
        fresh = (
            src if elem == 0 else jnp.zeros(E, dtype=cap_dtypes[pair])
        )
        caps[pair] = jnp.concatenate([state[_skey("cap", *pair)], fresh])

    # advance every partial through all remaining positive elements
    # (K-1 gathers); absence guards between steps kill a partial when a
    # guard event arrives at or before the step's own match
    for k in range(1, K):
        elem = positive[k]
        at_k = v_active & (v_step == k)
        j = nxt[elem][jnp.clip(v_pos, 0, E)]
        found = at_k & (j < E)
        for g in guards[k]:
            jg = nxt[g][jnp.clip(v_pos, 0, E)]
            violated = at_k & (jg <= j) & (jg < E)
            v_active = v_active & ~violated
            found = found & ~violated
        ts_j = ts_pad[j]
        if cfg.has_within:
            ok = (ts_j - v_start) <= within_val
            dead = found & ~ok
            found = found & ok
            v_active = v_active & ~dead
        for pair in pairs:
            if pair[0] == elem:
                v = env_pad[pair][j]
                caps[pair] = jnp.where(found, v, caps[pair])
        v_step = jnp.where(found, k + 1, v_step)
        v_pos = jnp.where(found, j + 1, v_pos)
        if k == K - 1:
            v_emit_ts = jnp.where(found, ts_j, v_emit_ts)

    if batch_max is None:
        batch_max = jnp.max(jnp.where(valid, ts, -_BIG))
    still_waiting = None
    if cfg.t_guard is not None:
        # partials that finished every positive step WAIT for the absence
        # window: a guard match inside (last_ts, last_ts + t] kills them
        # (strictly after the last positive event — same-timestamp guards
        # do not, matching the oracle's t1 < t2); once batch time proves
        # the window elapsed guard-free, they mature and emit at the
        # deadline
        waiting = v_active & (v_step == K)
        deadline = v_emit_ts + tfor_val
        # first guard with ts STRICTLY inside (last_ts, last_ts + t]: a
        # same-timestamp guard must neither kill (oracle: t1 < t2) nor
        # mask later in-window guards, so the search starts at the first
        # position whose ts exceeds last_ts (the tape is ts-sorted)
        past_emit = jnp.searchsorted(
            ts, v_emit_ts, side="right"
        ).astype(jnp.int32)
        jg = nxt[cfg.t_guard][
            jnp.clip(jnp.maximum(v_pos, past_emit), 0, E)
        ]
        guard_hit = waiting & (jg < E) & (ts_pad[jg] <= deadline)
        matured = waiting & ~guard_hit & (deadline <= batch_max)
        complete = matured
        v_emit_ts = jnp.where(matured, deadline, v_emit_ts)
        still_waiting = waiting & ~guard_hit & ~matured
    else:
        complete = v_active & (v_step == K)
    if not cfg.every:
        # exactly one match: earliest start, then earliest completion
        # (two-stage int32 argmin; device has no int64)
        start_key = jnp.where(complete, v_start, _BIG)
        min_start = jnp.min(start_key)
        emit_key = jnp.where(
            complete & (v_start == min_start), v_emit_ts, _BIG
        )
        winner = jnp.argmin(emit_key)
        one = jnp.zeros(V, dtype=bool).at[winner].set(True)
        complete = complete & one & ~state["done"]
        new_done = state["done"] | complete.any()
        if still_waiting is not None:
            # the single match is taken: waiting partials are void
            still_waiting = still_waiting & ~new_done
    else:
        new_done = state["done"]

    # survivors -> new pool: one-scatter compaction over a stacked
    # (state-row, V) matrix. The v ordering (carried pool first, then
    # fresh starts in tape order) is already oldest-start-first for
    # time-ordered batches, so on overflow the newest partials drop.
    survive = v_active & (v_step < K)
    if cfg.has_within:
        survive = survive & ((batch_max - v_start) <= within_val)
    if still_waiting is not None:
        survive = survive | still_waiting
    keep_pos = jnp.cumsum(survive.astype(jnp.int32)) - 1
    pool_dest = jnp.where(survive & (keep_pos < P), keep_pos, P)
    n_survive = survive.sum().astype(jnp.int32)

    fixed_rows = [_as_i32(survive), v_step, v_start]
    fixed_fill = [0, 1, 0]
    if cfg.t_guard is not None:
        fixed_rows.append(v_emit_ts)
        fixed_fill.append(0)
    n_fixed = len(fixed_rows)
    pool_rows = jnp.stack(
        fixed_rows + [_as_i32(caps[pair]) for pair in pairs]
    )
    pool_fill = jnp.concatenate(
        [
            jnp.asarray(fixed_fill, dtype=jnp.int32),
            jnp.zeros(len(pairs), dtype=jnp.int32),
        ]
    )
    pool_packed = (
        jnp.broadcast_to(pool_fill[:, None], (pool_rows.shape[0], P))
        .at[:, pool_dest]
        .set(pool_rows, mode="drop")
    )
    new_state = {
        "enabled": state["enabled"],
        "active": pool_packed[0].astype(bool),
        "step": pool_packed[1],
        "start": pool_packed[2],
        "done": new_done,
        "overflow": state["overflow"]
        + jnp.maximum(n_survive - P, 0).astype(jnp.int32),
    }
    if cfg.t_guard is not None:
        new_state["emit_ts"] = pool_packed[3]
    for j, pair in enumerate(pairs):
        new_state[_skey("cap", *pair)] = _from_i32(
            pool_packed[n_fixed + j], cap_dtypes[pair]
        )
    return new_state, complete, v_emit_ts, caps


def _is_chain(spec: _PatternSpec) -> bool:
    return (
        spec.kind == "pattern"
        and all(
            el.min_count == 1 and el.max_count == 1
            for el in spec.elements
        )
        and all(len(g) == 1 for g in spec.groups)
        and not any(spec.every_marks)  # forking needs the slot engine
    )


@dataclass
class ChainPatternArtifact:
    """``[every] e0 -> e1 -> ... -> eK``, each element exactly once.

    step() is loop-free over events: per-element "next match at/after p"
    indexes come from one reverse cummin each, and every partial (carried +
    newly started) advances through all remaining steps with K gathers.
    """

    name: str
    spec: _PatternSpec
    output_schema: OutputSchema
    # 'packed': step returns (n, (1+C, V) int32 block) — ts row 0, one
    # bitcast row per projection — the accumulator append layout
    output_mode: str = "packed"
    pool: int = DEFAULT_PARTIAL_POOL
    # late materialization: these capture pairs are PROJECTION-ONLY, so
    # their columns never ship to the device — the matcher captures the
    # event's global ordinal instead, and decode looks the value up in
    # the host's retained batches (fewer bytes over the host->device
    # link; see runtime/executor._LazyRing)
    lazy_pairs: Tuple[Tuple[int, str], ...] = ()
    # wire predicate pushdown: element indices whose event-only filters
    # are host-evaluated and shipped as packed mask bits ("@p:<i>" cols)
    pushed_preds: Tuple[int, ...] = ()

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        """Widest per-cycle emission block (drain-cadence contract)."""
        return tape_capacity + self.pool

    def nfa_check_info(self) -> List[Dict]:
        """Transition-table descriptors for analysis.plancheck (PLC2xx:
        positive/guard partition, quantifier bounds, bitmask width)."""
        return [_spec_check_info(self.name, self.spec)]

    def cost_info(self) -> Dict:
        """Admission-cost descriptor (analysis/admit.py): under
        ``every`` each trigger event arms a fresh partial, and one
        later event can complete EVERY armed prefix at once — worst
        case ``pool`` rows per input event, and without ``within`` the
        armed partials never expire (the ADM110 unbounded-residency
        surface)."""
        return _pattern_cost(self.name, self.spec, self.pool)

    def _row_plan(self):
        """Emission block layout. Legacy: [ts, one row per projection].
        Lazy plans compact it: projections that emit the SAME element's
        ordinal share one row, and the ts row is dropped entirely when
        it derives from the completing element's ordinal (the host ring
        retains rebased timestamps; see executor ``@ts``). Fewer match
        bytes over the device->host link per drain — the headline
        pattern's block shrinks 4 rows -> 2.

        Returns (rows, row_of, ts_row, ts_ord_row): ``rows`` is a list of
        ("ts"|"ord"|"proj", proj_idx) sources, ``row_of[c]`` the block row
        of projection c, ``ts_row`` the ts row index or None, and
        ``ts_ord_row`` the row whose ordinals recover the emission ts
        when ``ts_row`` is None."""
        spec = self.spec
        C = len(spec.proj_fns)
        if not self.lazy_pairs:
            rows = [("ts", None)] + [("proj", c) for c in range(C)]
            return rows, list(range(1, 1 + C)), 0, None

        lazyset = set(self.lazy_pairs)

        def dedupable(elem: int) -> bool:
            # one ordinal == one event: only elements matching exactly
            # once (unquantified, non-negated, singleton group) qualify
            el = spec.elements[elem]
            if (el.min_count, el.max_count) != (1, 1) or el.negated:
                return False
            return not any(
                elem in g and len(g) > 1 for g in spec.groups
            )

        last = spec.n_elements - 1
        drop_ts = (
            self._tfor_ms() is None
            and dedupable(last)
            and any(
                src is not None
                and src in lazyset
                and src[0] == last
                for src in spec.proj_srcs
            )
        )
        rows = []
        row_of = [0] * C
        ts_row = None
        if not drop_ts:
            ts_row = 0
            rows.append(("ts", None))
        ord_row: Dict[int, int] = {}
        for c, src in enumerate(spec.proj_srcs):
            if (
                src is not None
                and src in lazyset
                and dedupable(src[0])
            ):
                e = src[0]
                if e in ord_row:
                    row_of[c] = ord_row[e]
                    continue
                ord_row[e] = row_of[c] = len(rows)
                rows.append(("ord", c))
            else:
                row_of[c] = len(rows)
                rows.append(("proj", c))
        return rows, row_of, ts_row, (
            ord_row.get(last) if drop_ts else None
        )

    @property
    def acc_rows(self) -> int:
        return len(self._row_plan()[0])

    @property
    def ring_needs_ts(self) -> bool:
        """True when decode recovers emission timestamps from the host
        ring (the executor then retains a rebased ``@ts`` column)."""
        return bool(self.lazy_pairs) and self._row_plan()[2] is None

    def _emit_block(self, emit_ts, emit_env, width: int):
        """Stack the emission rows per ``_row_plan`` ("ord" rows evaluate
        their representative projection — identical values by the dedup
        criterion)."""
        spec = self.spec
        out = []
        for kind, c in self._row_plan()[0]:
            if kind == "ts":
                out.append(_as_i32(emit_ts))
            else:
                out.append(
                    _as_i32(
                        jnp.broadcast_to(
                            jnp.asarray(spec.proj_fns[c](emit_env)),
                            (width,),
                        )
                    )
                )
        return jnp.stack(out)

    def _tfor_ms(self) -> Optional[int]:
        last = self.spec.elements[-1]
        return last.absent_for if last.negated else None

    def _cap_dtype(self, pair) -> np.dtype:
        if pair in self.lazy_pairs:
            return np.dtype(np.int32)  # global event ordinal
        return np.dtype(self.spec.cap_dtype[pair])

    def _cfg(self) -> "_ChainCfg":
        import dataclasses

        cfg = _ChainCfg.of(self.spec)
        if self.lazy_pairs:
            cfg = dataclasses.replace(
                cfg,
                cap_dtypes=tuple(
                    self._cap_dtype(p).name for p in cfg.pairs
                ),
            )
        return cfg

    def init_state(self) -> Dict:
        P = self.pool
        K = self.spec.n_elements
        state = {
            "enabled": jnp.asarray(True),
            "active": jnp.zeros(P, dtype=bool),
            "step": jnp.ones(P, dtype=jnp.int32),  # next element to match
            "start": jnp.zeros(P, dtype=jnp.int32),
            "done": jnp.asarray(False),  # non-every: already matched
            "overflow": jnp.asarray(0, dtype=jnp.int32),
        }
        if self._tfor_ms() is not None:
            # timed-absence waiting partials carry their deadline base
            state["emit_ts"] = jnp.zeros(P, dtype=jnp.int32)
        if self.lazy_pairs:
            state["seen"] = jnp.asarray(0, dtype=jnp.int32)
        for pair in _cap_pairs(self.spec):
            state[_skey("cap", *pair)] = jnp.zeros(
                P, dtype=self._cap_dtype(pair)
            )
        return state

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        spec = self.spec
        E = tape.capacity
        P = self.pool
        V = P + E  # virtual partial set: carried pool ++ fresh starts
        pairs = _cap_pairs(spec)

        preds = jnp.stack(_element_preds(spec, tape, state["enabled"]))
        if self.lazy_pairs:
            # capture the event's GLOBAL ordinal for projection-only
            # columns; the column itself never shipped to the device
            ordinals = state["seen"] + jnp.arange(E, dtype=jnp.int32)
            cap_srcs = {
                pair: (
                    ordinals
                    if pair in self.lazy_pairs
                    else tape.cols[spec.cap_src_key[pair]]
                )
                for pair in pairs
            }
            seen_next = state["seen"] + tape.valid.sum().astype(jnp.int32)
            state = {k: v for k, v in state.items() if k != "seen"}
        else:
            cap_srcs = {
                pair: tape.cols[spec.cap_src_key[pair]] for pair in pairs
            }
            seen_next = None
        within_val = jnp.int32(
            spec.within if spec.within is not None else 0
        )
        tfor_val = jnp.int32(self._tfor_ms() or 0)
        cfg = self._cfg()
        C = len(spec.proj_fns)
        # within-expiry / absence deadlines always see the full batch's
        # time horizon, even on the relevance-compacted path
        bm_full = jnp.max(jnp.where(tape.valid, tape.ts, -_BIG))

        def run(ts, valid, preds_m, srcs):
            """Core + emission packing; the packed block is padded to the
            full (1+C, P+E) accumulator layout so the compacted and full
            paths return identical shapes (lax.cond requirement)."""
            st, complete, v_emit_ts, caps = _chain_core(
                cfg, P, state, preds_m, srcs, within_val, ts, valid,
                use_pallas=True, tfor_val=tfor_val, batch_max=bm_full,
            )
            v = int(ts.shape[0]) + P
            n_matches = complete.sum().astype(jnp.int32)
            emit_pos = jnp.cumsum(complete.astype(jnp.int32)) - 1
            emit_dest = jnp.where(complete, emit_pos, V)  # V -> dropped
            emit_env = _emit_env(
                spec,
                {
                    (elem, col, which): caps[(elem, col)]
                    for elem, col, which in spec.captures
                },
            )
            emit_rows = self._emit_block(v_emit_ts, emit_env, v)
            packed = (
                jnp.zeros((self.acc_rows, V), dtype=jnp.int32)
                .at[:, emit_dest]
                .set(emit_rows, mode="drop")
            )
            return st, n_matches, packed

        # Relevance compaction: '->' ignores events matching no element,
        # and the chain advance is V-sized pointer-chase gathers (the
        # slow op class on TPU) — shrinking V from P+E to P+E//8 cuts the
        # step ~4x on selective workloads. A lax.cond falls back to the
        # full-width core in the (rare) batch where more than E//8 events
        # are relevant.
        if E >= _COMPACT_MIN_E:
            R = _compact_width(E)
            rel = preds.any(axis=0) & tape.valid
            idx, cnt, cvalid = _compact_index(rel, R)
            state, n_matches, packed = jax.lax.cond(
                cnt <= R,
                lambda: run(
                    tape.ts[idx],
                    cvalid,
                    preds[:, idx] & cvalid[None, :],
                    {p_: s_[idx] for p_, s_ in cap_srcs.items()},
                ),
                lambda: run(tape.ts, tape.valid, preds, cap_srcs),
            )
        else:
            state, n_matches, packed = run(
                tape.ts, tape.valid, preds, cap_srcs
            )
        if seen_next is not None:
            state["seen"] = seen_next
        return state, (n_matches, packed)

    # -- segment parallelism (sequence parallelism for CEP) ---------------
    # The unkeyed-every chain is the one pattern class with no key axis to
    # shard on; its batch math is already order-parallel, so the stream
    # itself time-segments across shards: each shard matches its slice,
    # and partials that survive a segment hop shard-to-shard through the
    # later segments (lax.ppermute pipeline). Exact results — unlike the
    # reference, whose random channels make unkeyed matches subtask-local
    # (DynamicPartitioner.java:53-55).

    @property
    def supports_segment(self) -> bool:
        return (
            self.spec.every
            and not self.spec.every_grouped
            and self._tfor_ms() is None
            and not self.lazy_pairs
        )

    def _pool_keys(self) -> List[str]:
        keys = ["active", "step", "start"]
        for pair in _cap_pairs(self.spec):
            keys.append(_skey("cap", *pair))
        return keys

    @staticmethod
    def _merge_pools(a: Dict, b: Dict, P: int) -> Tuple[Dict, Any]:
        """Compact two P-row pools into one (oldest first); returns the
        merged pool and the count of dropped overflow rows."""
        cat = {
            k: jnp.concatenate([a[k], b[k]]) for k in a
        }
        alive = cat["active"]
        pos = jnp.cumsum(alive.astype(jnp.int32)) - 1
        dest = jnp.where(alive & (pos < P), pos, P)
        out = {
            k: jnp.zeros(P, dtype=v.dtype).at[dest].set(v, mode="drop")
            for k, v in cat.items()
        }
        dropped = jnp.maximum(
            alive.sum().astype(jnp.int32) - P, 0
        )
        return out, dropped

    def step_segmented(
        self, state: Dict, tape, axis_name: str
    ) -> Tuple[Dict, Tuple]:
        """Sharded step: this shard holds one time-contiguous SEGMENT of
        the batch. Local fresh starts (plus, on shard 0, the carried
        pool) advance through the local segment; surviving partials hop
        rightward shard-by-shard, advancing through each later segment
        and emitting completions on the shard where they complete. The
        final survivors land back on shard 0 as the next batch's carried
        pool."""
        spec = self.spec
        E = tape.capacity
        P = self.pool
        cfg = self._cfg()
        C = len(spec.proj_fns)
        # jax.lax.axis_size is a later-jax export; psum of a python 1
        # folds to the same static mesh-axis size on 0.4.x
        S = (
            jax.lax.axis_size(axis_name)
            if hasattr(jax.lax, "axis_size")
            else int(jax.lax.psum(1, axis_name))
        )
        sidx = jax.lax.axis_index(axis_name)

        preds = jnp.stack(_element_preds(spec, tape, state["enabled"]))
        pairs = _cap_pairs(spec)
        cap_srcs = {
            pair: tape.cols[spec.cap_src_key[pair]] for pair in pairs
        }
        within_val = jnp.int32(spec.within or 0)

        # only shard 0's carried pool is live (handoff convention)
        st_in = dict(state)
        st_in["active"] = state["active"] & (sidx == 0)

        runs = []  # (complete, emit_ts, caps) per run, to pack once

        def run_core(st, preds_m):
            # within-pruning horizon = the LOCAL segment max (the core's
            # default): a partial whose deadline reaches into later
            # segments must survive to hop there — the advance's own
            # within check still rejects late completions
            new_st, complete, v_emit_ts, caps = _chain_core(
                cfg, P, st, preds_m, cap_srcs, within_val,
                tape.ts, tape.valid, use_pallas=False,
                tfor_val=jnp.int32(0),
            )
            runs.append((complete, v_emit_ts, caps))
            return new_st

        new_state = run_core(st_in, preds)

        # hop pipeline: residues travel right; starts are disabled (each
        # event already started an instance on its own segment's run)
        preds_hop = preds.at[cfg.positive[0]].set(False)
        trav = {k: new_state[k] for k in self._pool_keys()}
        term = {k: jnp.zeros_like(v) for k, v in trav.items()}
        # overflow: start from the local run's counter (it already
        # includes this batch's local pool drops) and add each hop run's
        # increment plus the terminal-merge drops
        overflow_acc = new_state["overflow"]
        dropped_total = jnp.int32(0)
        perm = [(s, s + 1) for s in range(S - 1)]
        is_last = sidx == S - 1
        # the last shard's own local residue has no later segments to
        # traverse: bank it now (its hop send would have no receiver)
        bank0 = dict(trav)
        bank0["active"] = trav["active"] & is_last
        term, dropped = self._merge_pools(term, bank0, P)
        dropped_total = dropped_total + dropped
        trav["active"] = trav["active"] & ~is_last
        for _hop in range(max(S - 1, 0)):
            trav = jax.tree.map(
                lambda x: jax.lax.ppermute(x, axis_name, perm), trav
            )
            hop_st = dict(new_state)
            hop_st.update(trav)
            hop_st["done"] = jnp.asarray(False)
            adv = run_core(hop_st, preds_hop)
            overflow_acc = overflow_acc + (
                adv["overflow"] - hop_st["overflow"]
            )
            surv = {k: adv[k] for k in self._pool_keys()}
            # the last shard banks survivors (they traversed every later
            # segment); inner shards pass them on. Inactive rows' values
            # are never read, so gating `active` suffices.
            bank = dict(surv)
            bank["active"] = surv["active"] & is_last
            term, dropped = self._merge_pools(term, bank, P)
            dropped_total = dropped_total + dropped
            trav = dict(surv)
            trav["active"] = surv["active"] & ~is_last

        # survivors return to shard 0 as the next batch's pool
        if S > 1:
            term = jax.tree.map(
                lambda x: jax.lax.ppermute(
                    x, axis_name, [(S - 1, 0)]
                ),
                term,
            )
        else:
            term = {k: new_state[k] for k in self._pool_keys()}
        for k, v in term.items():
            new_state[k] = v
        new_state["overflow"] = overflow_acc + dropped_total
        new_state["done"] = jnp.asarray(False)

        # pack all runs' completions into ONE emission block
        complete = jnp.concatenate([r[0] for r in runs])
        emit_ts = jnp.concatenate([r[1] for r in runs])
        caps_cat = {
            pair: jnp.concatenate([r[2][pair] for r in runs])
            for pair in pairs
        }
        W = int(complete.shape[0])
        n_matches = complete.sum().astype(jnp.int32)
        pos = jnp.cumsum(complete.astype(jnp.int32)) - 1
        dest = jnp.where(complete, pos, W)
        emit_env = _emit_env(
            spec,
            {
                (elem, col, which): caps_cat[(elem, col)]
                for elem, col, which in spec.captures
            },
        )
        emit_rows = self._emit_block(emit_ts, emit_env, W)
        packed = (
            jnp.zeros((self.acc_rows, W), dtype=jnp.int32)
            .at[:, dest]
            .set(emit_rows, mode="drop")
        )
        return new_state, (n_matches, packed)

    @property
    def wants_lookup(self) -> bool:
        return bool(self.lazy_pairs)

    @property
    def lazy_src_keys(self) -> Tuple[str, ...]:
        """Tape-column keys whose values the host ring must retain."""
        return tuple(
            sorted({self.spec.cap_src_key[p] for p in self.lazy_pairs})
        )

    def decode_packed(self, n: int, block: "np.ndarray", lookup=None):
        """With lazy pairs, ordinal rows resolve against the host's
        retained batches; evicted ordinals decode as None (bounded-memory
        policy, like every other engine cap). On the compact layout the
        emission ts itself recovers from the completing element's ordinal
        (ring column ``@ts``)."""
        schema = self.output_schema
        if not self.lazy_pairs:
            return [(schema, schema.decode_packed_block(n, block))]
        from .output import emission_order

        _rows, row_of, ts_row, ts_ord_row = self._row_plan()
        if ts_row is not None:
            ts_arr = np.asarray(block[ts_row, :n]).astype(np.int64)
        else:
            ords = np.asarray(block[ts_ord_row, :n])
            tvals = (
                lookup("@ts", ords) if lookup is not None else [None] * n
            )
            # an evicted ordinal loses its emission ts too: decode 0
            # (its values decode None anyway)
            ts_arr = np.asarray(
                [0 if v is None else int(v) for v in tvals], np.int64
            )
        order = emission_order(ts_arr, n)
        ts_list = ts_arr[order].tolist()
        col_lists = []
        for c, f in enumerate(schema.fields):
            raw = np.asarray(block[row_of[c], :n])[order]
            src = self.spec.proj_srcs[c]
            if src is not None and src in self.lazy_pairs:
                vals = (
                    lookup(self.spec.cap_src_key[src], raw)
                    if lookup is not None
                    else [None] * n
                )
                if f.table is not None:
                    vals = [
                        None if v is None else f.table.value(int(v))
                        for v in vals
                    ]
                else:
                    vals = [
                        None if v is None
                        else (v.item() if hasattr(v, "item") else v)
                        for v in vals
                    ]
                col_lists.append(vals)
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

    def decode_packed_columns(
        self, n: int, block: "np.ndarray", lookup_np=None
    ):
        """Columnar twin of :meth:`decode_packed` (the sink fast lane):
        same emission_order permutation and lazy-ordinal semantics, but
        the product is typed numpy columns — lazy values resolve through
        the ring's vectorized ``lookup_np`` instead of a per-value loop."""
        from .output import ColumnBatch, emission_order
        from .select import _lazy_column_np

        schema = self.output_schema
        if not self.lazy_pairs:
            return [(schema, schema.decode_packed_columns(n, block))]
        _rows, row_of, ts_row, ts_ord_row = self._row_plan()
        if ts_row is not None:
            ts_arr = np.asarray(block[ts_row, :n]).astype(np.int64)
        else:
            ords = np.asarray(block[ts_ord_row, :n])
            tvals = (
                lookup_np("@ts", ords)
                if lookup_np is not None
                else np.full(n, None, dtype=object)
            )
            if tvals.dtype == object:  # evicted ordinals decode ts 0
                ts_arr = np.asarray(
                    [0 if v is None else int(v) for v in tvals.tolist()],
                    np.int64,
                )
            else:
                ts_arr = tvals.astype(np.int64)
        order = emission_order(ts_arr, n)
        ts_out = ts_arr[order]
        cols = {}
        for c, f in enumerate(schema.fields):
            raw = np.asarray(block[row_of[c], :n])[order]
            src = self.spec.proj_srcs[c]
            if src is not None and src in self.lazy_pairs:
                cols[f.name] = _lazy_column_np(
                    raw, f, lookup_np, self.spec.cap_src_key[src]
                )
            else:
                if np.dtype(f.atype.device_dtype) == np.dtype(np.float32):
                    raw = raw.view(np.float32)
                cols[f.name] = f.decode_column_np(raw)
        return [(schema, ColumnBatch(ts_out, cols))]

    @property
    def flush_is_noop(self) -> bool:
        return self._tfor_ms() is None

    def flush(self, state: Dict) -> Tuple[Dict, Tuple]:
        """End-of-stream: with a terminal timed absence, stream end means
        time advances past every pending deadline guard-free (the +inf
        watermark), so all waiting partials mature and emit."""
        spec = self.spec
        P = self.pool
        C = len(spec.proj_fns)
        tfor = self._tfor_ms()
        if tfor is None:
            return state, (
                jnp.asarray(0, jnp.int32),
                jnp.zeros((self.acc_rows, 1), jnp.int32),
            )
        K = _ChainCfg.of(spec).K
        waiting = state["active"] & (state["step"] == K)
        deadline = state["emit_ts"] + jnp.int32(tfor)
        if not spec.every:
            # exactly-one-match rule holds at end of stream too: nothing
            # if already matched, else the earliest-start (then earliest
            # deadline) waiting partial
            waiting = waiting & ~state["done"]
            start_key = jnp.where(waiting, state["start"], _BIG)
            min_start = jnp.min(start_key)
            dl_key = jnp.where(
                waiting & (state["start"] == min_start), deadline, _BIG
            )
            winner = jnp.argmin(dl_key)
            one = jnp.zeros(P, dtype=bool).at[winner].set(True)
            waiting = waiting & one
        n = waiting.sum().astype(jnp.int32)
        pos = jnp.cumsum(waiting.astype(jnp.int32)) - 1
        dest = jnp.where(waiting, pos, P)
        emit_env = _emit_env(
            spec,
            {
                (e, c, w): state[_skey("cap", e, c)]
                for e, c, w in spec.captures
            },
        )
        rows = self._emit_block(deadline, emit_env, P)
        packed = jnp.zeros_like(rows).at[:, dest].set(rows, mode="drop")
        new_state = dict(state)
        new_state["active"] = state["active"] & ~waiting
        return new_state, (n, packed)


# --------------------------------------------------------------------------
# Engine 1b: stacked chain matcher — N structurally-identical chain queries
# advanced by ONE vmapped program (multi-query parallelism, the reference's
# one-runtime-per-plan fan-out re-expressed as a device query axis;
# SURVEY.md §2.7-(5), AbstractSiddhiOperator.java:112,301-313)
# --------------------------------------------------------------------------

@dataclass
class StackedChainArtifact:
    """A group of chain patterns sharing one ``_ChainCfg``: their per-query
    predicates/captures/projections are stacked as data and the chain
    advance runs once under ``jax.vmap`` over the query axis — per-step
    device op count is O(1) in the number of queries, not O(Q).

    Emissions from all member queries compact through one scatter into a
    single packed block with a query-id row; the host splits rows back to
    each member's output stream at decode time."""

    name: str
    members: List[ChainPatternArtifact]
    output_mode: str = "packed"
    # emission buffer width = min(Q, out_cap_factor)*E + Q*pool: lossless
    # for stacks up to out_cap_factor queries, bounded (with a drained
    # overflow counter) beyond that
    out_cap_factor: int = 8
    column_types: Optional[Dict] = None

    def __post_init__(self):
        self.pool = self.members[0].pool
        self._cfg = _ChainCfg.of(self.members[0].spec)
        assert all(
            _ChainCfg.of(m.spec) == self._cfg for m in self.members
        ), "stacked members must share a chain signature"
        self._vec_info = self._build_vec_preds()

    def nfa_check_info(self) -> List[Dict]:
        return [
            _spec_check_info(f"{self.name}[{m.name}]", m.spec)
            for m in self.members
        ]

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: one event feeds EVERY stacked
        member, so the group's worst-case output demand is the sum of
        the members' (the emission buffer truncates beyond
        min(Q, out_cap_factor)*E + Q*pool with counted overflow)."""
        member_costs = [
            _pattern_cost(f"{self.name}[{m.name}]", m.spec, m.pool)
            for m in self.members
        ]
        res: object = None
        unbounded = None
        for mc in member_costs:
            r = mc["residency_ms"]
            if r is not None:
                res = r if res is None else max(res, r)
            if unbounded is None and "unbounded" in mc:
                unbounded = mc["unbounded"]
        info = {
            "name": self.name,
            "kind": "pattern",
            "amplification": int(
                sum(mc["amplification"] for mc in member_costs)
            ),
            "residency_ms": res,
            "members": [mc["name"] for mc in member_costs],
        }
        if unbounded is not None:
            info["unbounded"] = unbounded
        return info

    def _build_vec_preds(self):
        """Per-element conjunct vectors for the broadcast predicate path:
        when every member's element-k filter flattens to the same
        ``attr OP literal`` conjunct keys (numeric literals), the Q*K
        closure evaluations collapse to a handful of (Q, E) broadcast
        compares — Q separate HLO ops per element defeat XLA fusion and
        dominated the stacked step. None = fall back to closures."""
        specs = [m.spec for m in self.members]
        K = specs[0].n_elements
        Q = len(self.members)
        info = []
        for k in range(K):
            el0 = specs[0].elements[k]
            if el0.negated or (el0.min_count, el0.max_count) != (1, 1):
                return None
            if specs[0].pred_fns[k] is None:
                if any(s.pred_fns[k] is not None for s in specs):
                    return None
                if any(s.elements[k].filter is not None for s in specs):
                    return None  # cross filters stay on the slot path
                info.append(())
                continue
            per_member = []
            for s in specs:
                el = s.elements[k]
                if el.filter is None:
                    return None
                conj = _template_conjuncts(el, self.column_types)
                if conj is None:
                    return None
                per_member.append(conj)
            n_conj = len(per_member[0])
            if any(len(c) != n_conj for c in per_member):
                return None
            conjs = []
            for j in range(n_conj):
                keys = {c[j][0] for c in per_member}
                if len(keys) != 1:
                    return None
                vals = [c[j][2] for c in per_member]
                if any(isinstance(v, (str, bool)) for v in vals):
                    return None  # interned/string literals: closure path
                # preserve integer literals exactly: routing them
                # through float64 would corrupt int64 values past 2^53
                vals_np = (
                    np.asarray(vals, np.int64)
                    if all(isinstance(v, int) for v in vals)
                    else np.asarray(vals, np.float64)
                )
                conjs.append(
                    (
                        next(iter(keys)),
                        np.asarray(
                            [c[j][1] for c in per_member], np.int32
                        ),
                        vals_np,
                    )
                )
            info.append(tuple(conjs))
        return tuple(info)

    def _vec_preds(self, tape, enabled):
        """(Q, K, E) element masks via broadcast compares."""
        Q = len(self.members)
        spec0 = self.members[0].spec
        E = tape.capacity
        out = []
        ops = (
            jnp.equal, jnp.not_equal, jnp.less, jnp.less_equal,
            jnp.greater, jnp.greater_equal,
        )
        for k, conjs in enumerate(self._vec_info):
            base = tape.valid & (
                tape.stream == spec0.stream_code_of[k]
            )
            mk = jnp.broadcast_to(base[None, :], (Q, E))
            for key, opcodes, vals in conjs:
                col = tape.cols[key]
                lits = jnp.asarray(vals).astype(col.dtype)[:, None]
                colb = col[None, :]
                distinct = sorted(set(opcodes.tolist()))
                cm = None
                if len(distinct) == 1:
                    cm = ops[distinct[0]](colb, lits)
                else:
                    opc = jnp.asarray(opcodes)[:, None]
                    for oc in distinct:
                        m = ops[oc](colb, lits)
                        cm = (
                            m
                            if cm is None
                            else jnp.where(opc == oc, m, cm)
                        )
                mk = mk & cm
            out.append(mk & enabled[:, None])
        return jnp.stack(out, axis=1)

    @property
    def output_schema(self) -> OutputSchema:
        # representative — members share field structure; decode routes
        # rows to each member's own stream via the qid row
        return self.members[0].output_schema

    @property
    def acc_rows(self) -> int:
        return 2 + len(self.output_schema.fields)  # ts + qid + columns

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        q = len(self.members)
        return (
            min(q, self.out_cap_factor) * tape_capacity + q * self.pool
        )

    def init_state(self) -> Dict:
        Q = len(self.members)
        P = self.pool
        state = {
            "enabled": jnp.ones(Q, dtype=bool),
            "active": jnp.zeros((Q, P), dtype=bool),
            "step": jnp.ones((Q, P), dtype=jnp.int32),
            "start": jnp.zeros((Q, P), dtype=jnp.int32),
            "done": jnp.zeros(Q, dtype=bool),
            "overflow": jnp.zeros(Q, dtype=jnp.int32),
        }
        if self._cfg.t_guard is not None:
            state["emit_ts"] = jnp.zeros((Q, P), dtype=jnp.int32)
        spec0 = self.members[0].spec
        for pair in _cap_pairs(spec0):
            state[_skey("cap", *pair)] = jnp.zeros(
                (Q, P), dtype=spec0.cap_dtype[pair]
            )
        return state

    # query-axis chunk width for the memory-bounded full path: the
    # vmapped core materializes O(chunk * (P+E) * pairs) intermediates,
    # so chunking caps peak HBM at ~chunk/Q of the naive all-Q vmap
    CHUNK_Q = 8

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        cfg = self._cfg
        E = tape.capacity
        P = self.pool
        Q = len(self.members)

        if self._vec_info is not None:
            preds = self._vec_preds(tape, state["enabled"])  # (Q, K, E)
        else:
            preds = jnp.stack(
                [
                    jnp.stack(
                        _element_preds(m.spec, tape, state["enabled"][qi])
                    )
                    for qi, m in enumerate(self.members)
                ]
            )  # (Q, K, E)
        cap_srcs = {
            pair: jnp.stack(
                [
                    tape.cols[m.spec.cap_src_key[pair]]
                    for m in self.members
                ]
            )
            for pair in cfg.pairs
        }
        within_vec = jnp.asarray(
            [m.spec.within or 0 for m in self.members], dtype=jnp.int32
        )
        tfor_vec = jnp.asarray(
            [m._tfor_ms() or 0 for m in self.members], dtype=jnp.int32
        )
        # within/absence horizons always see the full batch (the
        # compacted path's ts only covers each query's relevant events)
        bm_full = jnp.max(jnp.where(tape.valid, tape.ts, -_BIG))

        def core_v(st, pr, cs, wv, tv, ts, valid):
            return _chain_core(
                cfg, P, st, pr, cs, wv, ts, valid,
                tfor_val=tv, batch_max=bm_full,
            )

        def emit_pack(new_state, complete, emit_ts, caps):
            """Pack per-query completions into the fixed-width emission
            block; works for any per-query width V_ (compacted or full),
            so both lax.cond branches return identical shapes."""
            V_ = int(complete.shape[1])
            qid_row = jnp.broadcast_to(
                jnp.arange(Q, dtype=jnp.int32)[:, None], (Q, V_)
            )
            # projections: when every member's column c is the same plain
            # capture reference (the overwhelmingly common select shape),
            # the stacked output rows ARE the stacked capture buffers —
            # zero per-query ops. Otherwise per-member eval.
            col_srcs = []
            uniform = True
            for c in range(len(self.members[0].spec.proj_fns)):
                srcs = {m.spec.proj_srcs[c] for m in self.members}
                if len(srcs) == 1 and None not in srcs:
                    col_srcs.append(next(iter(srcs)))
                else:
                    uniform = False
                    break
            if uniform:
                stacked_rows = [_as_i32(emit_ts), qid_row] + [
                    _as_i32(caps[pair]) for pair in col_srcs
                ]
                flat_rows = jnp.stack(
                    [r.reshape(Q * V_) for r in stacked_rows]
                )
                R = len(stacked_rows)
            else:
                rows_per_q = []
                for qi, m in enumerate(self.members):
                    env = _emit_env(
                        m.spec,
                        {
                            (e, c, w): caps[(e, c)][qi]
                            for e, c, w in m.spec.captures
                        },
                    )
                    rows_per_q.append(
                        jnp.stack(
                            [
                                _as_i32(emit_ts[qi]),
                                jnp.full(V_, qi, dtype=jnp.int32),
                            ]
                            + [
                                _as_i32(
                                    jnp.broadcast_to(
                                        jnp.asarray(p(env)), (V_,)
                                    )
                                )
                                for p in m.spec.proj_fns
                            ]
                        )
                    )
                R = rows_per_q[0].shape[0]
                flat_rows = (
                    jnp.stack(rows_per_q)
                    .transpose(1, 0, 2)
                    .reshape(R, Q * V_)
                )
            cflat = complete.reshape(Q * V_)
            n_total = cflat.sum().astype(jnp.int32)
            out_w = min(
                Q * (P + E),
                min(Q, self.out_cap_factor) * E + Q * P,
            )
            pos = jnp.cumsum(cflat.astype(jnp.int32)) - 1
            dest = jnp.where(cflat & (pos < out_w), pos, out_w)
            packed = (
                jnp.zeros((R, out_w), dtype=jnp.int32)
                .at[:, dest]
                .set(flat_rows, mode="drop")
            )
            n_emitted = jnp.minimum(n_total, jnp.int32(out_w))
            # matches beyond the emission buffer are genuinely dropped;
            # the third element feeds the drained overflow counter
            return new_state, (n_emitted, packed, n_total - n_emitted)

        def run_full():
            """Memory-bounded full-width path: chunk the query axis
            under lax.map so peak HBM is O(CHUNK_Q * V) instead of
            O(Q * V)."""
            ch = min(self.CHUNK_Q, Q)
            if Q <= ch:
                out = jax.vmap(
                    lambda st, pr, cs, wv, tv: core_v(
                        st, pr, cs, wv, tv, tape.ts, tape.valid
                    )
                )(state, preds, cap_srcs, within_vec, tfor_vec)
                return emit_pack(*out)
            nc = -(-Q // ch)
            pad = nc * ch - Q

            def pad_q(x):
                if pad == 0:
                    return x
                return jnp.concatenate(
                    [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)]
                )

            def chunked(tree):
                return jax.tree.map(
                    lambda x: pad_q(x).reshape(
                        (nc, ch) + x.shape[1:]
                    ),
                    tree,
                )

            outs = jax.lax.map(
                lambda args: jax.vmap(
                    lambda st, pr, cs, wv, tv: core_v(
                        st, pr, cs, wv, tv, tape.ts, tape.valid
                    )
                )(*args),
                (
                    chunked(state),
                    chunked(preds),
                    chunked(cap_srcs),
                    chunked(within_vec),
                    chunked(tfor_vec),
                ),
            )
            unchunk = jax.tree.map(
                lambda x: x.reshape((nc * ch,) + x.shape[2:])[:Q], outs
            )
            return emit_pack(*unchunk)

        # Per-query relevance compaction ('->' chains ignore events that
        # match none of the query's elements): each query advances over
        # its own compacted window, cutting the V-sized pointer-chase
        # gathers AND the per-query intermediates. Stacked members are
        # selective by construction (structurally-identical literal
        # filters), so the window is E//16 — tighter than the single
        # chain's E//8 — and one shared lax.cond falls back to the
        # chunked full path in the (rare) batch where any query has
        # more relevant events.
        if E >= _COMPACT_MIN_E:
            Rw = max(2048, E // 16)
            rel = preds.any(axis=1) & tape.valid[None, :]  # (Q, E)
            idxs, cnts, cvalid = _compact_index_batched(rel, Rw)

            def run_compact():
                ts_c = tape.ts[idxs]  # (Q, Rw)
                preds_c = (
                    jnp.take_along_axis(
                        preds, idxs[:, None, :], axis=2
                    )
                    & cvalid[:, None, :]
                )
                srcs_c = {
                    pair: jnp.take_along_axis(arr, idxs, axis=1)
                    for pair, arr in cap_srcs.items()
                }
                out = jax.vmap(
                    lambda st, pr, cs, wv, tv, ts, vd: core_v(
                        st, pr, cs, wv, tv, ts, vd
                    )
                )(
                    state, preds_c, srcs_c, within_vec, tfor_vec,
                    ts_c, cvalid,
                )
                return emit_pack(*out)

            return jax.lax.cond(
                jnp.max(cnts) <= Rw, run_compact, run_full
            )
        return run_full()

    def decode_packed(self, n: int, block: np.ndarray):
        """Split a fetched packed block into per-member (schema, rows)."""
        return _decode_qid_block(
            n, block,
            ((qi, m.output_schema) for qi, m in enumerate(self.members)),
        )

    @property
    def flush_is_noop(self) -> bool:
        return self._cfg.t_guard is None

    def flush(self, state: Dict) -> Tuple[Dict, Tuple]:
        """Timed-absence maturation at end of stream (per member query)."""
        Q = len(self.members)
        P = self.pool
        C = len(self.members[0].spec.proj_fns)
        if self._cfg.t_guard is None:
            return state, (
                jnp.asarray(0, jnp.int32),
                jnp.zeros((2 + C, 1), jnp.int32),
                jnp.asarray(0, jnp.int32),
            )
        per_q = []
        new_state = dict(state)
        new_active = []
        for qi, m in enumerate(self.members):
            sub = {
                k: v[qi]
                for k, v in state.items()
            }
            st2, (n_q, packed_q) = m.flush(sub)
            new_active.append(st2["active"])
            qid = jnp.full(P, qi, dtype=jnp.int32)
            per_q.append(
                (n_q, jnp.concatenate(
                    [packed_q[:1], qid[None, :], packed_q[1:]], axis=0
                ))
            )
        new_state["active"] = jnp.stack(new_active)
        # concatenate member emissions front-compacted per member; the
        # packed blocks are already zero-padded past each n_q, so stack
        # them side by side and compact once
        blocks = jnp.concatenate([b for _, b in per_q], axis=1)  # (2+C, Q*P)
        keep = jnp.concatenate(
            [
                jnp.arange(P, dtype=jnp.int32) < n_q
                for n_q, _ in per_q
            ]
        )
        n_total = keep.sum().astype(jnp.int32)
        pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
        dest = jnp.where(keep, pos, Q * P)
        packed = (
            jnp.zeros_like(blocks).at[:, dest].set(blocks, mode="drop")
        )
        return new_state, (n_total, packed, jnp.asarray(0, jnp.int32))


# --------------------------------------------------------------------------
# Engine 1c: dynamic (parametric) chain group — runtime query add/remove as
# a DATA update, not an XLA recompile (SURVEY.md §7 hard part 4). The group
# pre-allocates padded query slots; a structurally-identical chain query
# (same shape, per-element `attr == literal` filters over the same
# attributes) folds into a free slot by writing its literals/within into
# per-slot device arrays. Reference analog: the add path of
# AbstractSiddhiOperator.onEventReceived (:416-424), which pays a full
# SiddhiQL compile per add.
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ChainTemplate:
    """The static shape shared by all members of a dynamic chain group.
    Everything here is traced into the compiled program; everything NOT
    here (filter literals, comparison OPERATORS, within values, enable
    flags) is state — so `price > 100`, `price <= 5`, and `id == 2` over
    the same column all fold into one slot family."""

    K: int
    every: bool
    has_within: bool
    stream_ids: Tuple[str, ...]  # per element
    # per element: tape col key per conjunct (up to 2, e.g. a range
    # `lo < x and x < hi`); () = unfiltered element
    filter_keys: Tuple[Tuple[str, ...], ...]
    pairs: Tuple[Tuple[int, str], ...]
    cap_dtypes: Tuple[str, ...]
    proj_srcs: Tuple[Tuple[int, str], ...]


# comparison operators evaluable with a per-slot DATA code (admit writes
# the code; the device evaluates all variants and selects)
_CMP_OPS = ("==", "!=", "<", "<=", ">", ">=")
_CMP_CODE = {op: i for i, op in enumerate(_CMP_OPS)}


def _template_conjuncts(el, column_types):
    """Flatten an element filter into <=2 ``attr OP literal`` conjuncts
    (None when the filter doesn't fit the parametric family)."""
    conj: List = []
    stack = [el.filter]
    while stack:
        f = stack.pop()
        if isinstance(f, ast.Binary) and f.op == "and":
            stack.append(f.left)
            stack.append(f.right)
            continue
        if not isinstance(f, ast.Binary) or f.op not in _CMP_CODE:
            return None
        a, lit, op = f.left, f.right, f.op
        if isinstance(a, ast.Literal) and isinstance(lit, ast.Attr):
            # `5 < x` -> `x > 5`
            a, lit = lit, a
            op = {"<": ">", "<=": ">=", ">": "<", ">=": "<="}.get(op, op)
        if not (
            isinstance(a, ast.Attr)
            and a.qualifier in (None, el.alias, el.stream_id)
            and a.index is None
            and isinstance(lit, ast.Literal)
        ):
            return None
        key = f"{el.stream_id}.{a.name}"
        val = lit.value
        if column_types is not None:
            atype = column_types.get(key)
            if atype is None:
                return None
            if atype == AttributeType.STRING and op not in ("==", "!="):
                return None  # interned codes have no meaningful order
            if (
                np.dtype(atype.device_dtype).kind in "iu"
                and isinstance(val, float)
                and not float(val).is_integer()
            ):
                return None  # param would truncate in the column dtype
        conj.append((key, _CMP_CODE[op], val))
    if len(conj) > 2:
        return None
    conj.sort(key=lambda c: c[0])  # deterministic key order
    return conj


def chain_template_of(
    artifact, column_types: Optional[Dict] = None
) -> Optional[Tuple["ChainTemplate", List, int]]:
    """(template, per-element literal params, within_ms) when the chain
    fits the parametric family, else None. With ``column_types``, a
    literal that does not losslessly convert to its column's device type
    rejects the template (a truncated param would match DIFFERENT events
    than the statically-compiled query, which promotes to a common type)."""
    if not isinstance(artifact, ChainPatternArtifact):
        return None
    if artifact.lazy_pairs or artifact.pushed_preds:
        # a lazy-projected / predicate-pushed plan's tape lacks the raw
        # columns the parametric group would read; it keeps its own
        # runtime
        return None
    spec = artifact.spec
    if spec.kind != "pattern" or spec.has_cross:
        return None
    if any(len(g) > 1 for g in spec.groups):
        return None
    if any(
        el.negated or (el.min_count, el.max_count) != (1, 1)
        for el in spec.elements
    ):
        return None
    if not spec.proj_srcs or any(s is None for s in spec.proj_srcs):
        return None
    filter_keys: List[Tuple[str, ...]] = []
    params: List = []
    for el in spec.elements:
        if el.filter is None:
            filter_keys.append(())
            params.append(())
            continue
        conj = _template_conjuncts(el, column_types)
        if conj is None:
            return None
        filter_keys.append(tuple(key for key, _op, _v in conj))
        params.append(tuple((op, v) for _key, op, v in conj))
    pairs = tuple(_cap_pairs(spec))
    return (
        ChainTemplate(
            K=spec.n_elements,
            every=spec.every,
            has_within=spec.within is not None,
            stream_ids=tuple(el.stream_id for el in spec.elements),
            filter_keys=tuple(filter_keys),
            pairs=pairs,
            cap_dtypes=tuple(
                np.dtype(spec.cap_dtype[p]).name for p in pairs
            ),
            proj_srcs=tuple(spec.proj_srcs),
        ),
        params,
        spec.within or 0,
    )


DYN_QUERY_SLOTS = 8  # pre-padded slots per dynamic chain group


@dataclass
class DynamicChainGroup:
    """Padded parametric chain group: up to ``capacity`` structurally-
    identical chain queries advanced by ONE vmapped program; per-query
    predicates are `tape_col == param[q]` with params in device state,
    so add/update/remove/enable are data writes."""

    name: str
    template: ChainTemplate
    stream_code_of: Tuple[int, ...]  # codes in the HOST plan's spec
    column_types: Dict[str, object]  # tape col key -> AttributeType
    members: List  # per slot: None | (plan_id, OutputSchema)
    pool: int = DEFAULT_PARTIAL_POOL
    capacity: int = DYN_QUERY_SLOTS
    output_mode: str = "packed"
    out_cap_factor: int = 8

    @property
    def output_schema(self) -> OutputSchema:
        for m in self.members:
            if m is not None:
                return m[1]
        raise RuntimeError("dynamic chain group has no members")

    @property
    def acc_rows(self) -> int:
        return 2 + len(self.template.proj_srcs)  # ts + qid + columns

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        q = self.capacity
        return (
            min(q, self.out_cap_factor) * tape_capacity + q * self.pool
        )

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: the padded group's worst case is
        every slot occupied and every slot's pool completable by one
        event. Per-member ``within`` values are device DATA (each
        member's own compile was admitted separately before folding);
        ``has_within=False`` under ``every`` is the unbounded-residency
        class for the whole slot family."""
        t = self.template
        per_member = self.pool if t.every else 1
        info = {
            "name": self.name,
            "kind": "pattern",
            "amplification": int(self.capacity * per_member),
            "residency_ms": (
                None if t.has_within else
                (float("inf") if t.every else None)
            ),
        }
        if t.every and not t.has_within:
            info["unbounded"] = (
                "dynamic chain group compiled without 'within' "
                "support: every member's armed partials never expire"
            )
        return info

    def _param_dtype(self, key: str):
        return self.column_types[key].device_dtype

    def init_state(self) -> Dict:
        Qc, P = self.capacity, self.pool
        st = {
            "enabled": jnp.zeros(Qc, dtype=bool),
            "active": jnp.zeros((Qc, P), dtype=bool),
            "step": jnp.ones((Qc, P), dtype=jnp.int32),
            "start": jnp.zeros((Qc, P), dtype=jnp.int32),
            "done": jnp.zeros(Qc, dtype=bool),
            "overflow": jnp.zeros(Qc, dtype=jnp.int32),
        }
        if self.template.has_within:
            st["within"] = jnp.zeros(Qc, dtype=jnp.int32)
        for k, keys in enumerate(self.template.filter_keys):
            for j, key in enumerate(keys):
                st[f"param{k}_{j}"] = jnp.zeros(
                    Qc, dtype=self._param_dtype(key)
                )
                st[f"op{k}_{j}"] = jnp.zeros(Qc, dtype=jnp.int32)
        for pair, dt in zip(self.template.pairs, self.template.cap_dtypes):
            st[_skey("cap", *pair)] = jnp.zeros((Qc, P), dtype=np.dtype(dt))
        return st

    # -- host-side slot management (applied to rt.states by the Job) ----
    def free_slot(self) -> Optional[int]:
        for s, m in enumerate(self.members):
            if m is None:
                return s
        return None

    def admit(self, state: Dict, slot: int, plan_id: str, schema,
              params: List, within_ms: int, string_tables) -> Dict:
        """Write one query into ``slot`` — pure data updates."""
        self.members[slot] = (plan_id, schema)
        st = dict(state)
        st["enabled"] = state["enabled"].at[slot].set(True)
        st["done"] = st["done"].at[slot].set(False)
        st["active"] = st["active"].at[slot].set(False)
        st["overflow"] = st["overflow"].at[slot].set(0)
        if self.template.has_within:
            st["within"] = st["within"].at[slot].set(within_ms)
        for k, (keys, el_params) in enumerate(
            zip(self.template.filter_keys, params)
        ):
            for j, (key, (op, val)) in enumerate(zip(keys, el_params)):
                atype = self.column_types[key]
                if atype == AttributeType.STRING:
                    val = string_tables[key].intern(val)
                st[f"param{k}_{j}"] = (
                    st[f"param{k}_{j}"].at[slot].set(val)
                )
                st[f"op{k}_{j}"] = st[f"op{k}_{j}"].at[slot].set(op)
        return st

    def evict(self, state: Dict, slot: int) -> Dict:
        self.members[slot] = None
        st = dict(state)
        st["enabled"] = state["enabled"].at[slot].set(False)
        st["active"] = st["active"].at[slot].set(False)
        return st

    def set_enabled(self, state: Dict, slot: int, on: bool) -> Dict:
        st = dict(state)
        st["enabled"] = state["enabled"].at[slot].set(on)
        return st

    # -- device step ----------------------------------------------------
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        t = self.template
        Qc, P, K = self.capacity, self.pool, t.K
        E = tape.capacity
        V = P + E

        rows = []
        for k in range(K):
            base = tape.valid & (tape.stream == self.stream_code_of[k])
            row = jnp.broadcast_to(base, (Qc, E))
            for j, key in enumerate(t.filter_keys[k]):
                col = tape.cols[key][None, :]
                pk = state[f"param{k}_{j}"][:, None]
                op = state[f"op{k}_{j}"][:, None]  # (Qc, 1)
                # the operator is per-slot DATA: evaluate every variant
                # and select by code (cheap VPU elementwise work)
                variants = [
                    col == pk, col != pk, col < pk,
                    col <= pk, col > pk, col >= pk,
                ]
                cmp = variants[0]
                for ci in range(1, len(variants)):
                    cmp = jnp.where(op == ci, variants[ci], cmp)
                row = row & cmp
            rows.append(row & state["enabled"][:, None])
        preds = jnp.stack(rows, axis=1)  # (Qc, K, E)

        cap_srcs = {
            pair: jnp.broadcast_to(
                tape.cols[f"{t.stream_ids[pair[0]]}.{pair[1]}"], (Qc, E)
            )
            for pair in t.pairs
        }
        within_vec = (
            state["within"]
            if t.has_within
            else jnp.zeros(Qc, dtype=jnp.int32)
        )
        cfg = _ChainCfg(
            K=K,
            every=t.every,
            has_within=t.has_within,
            pairs=t.pairs,
            cap_dtypes=t.cap_dtypes,
            positive=tuple(range(K)),
            guards=((),) * K,
        )
        core_keys = [
            "enabled", "active", "step", "start", "done", "overflow"
        ] + [_skey("cap", *p) for p in t.pairs]
        core_state = {k: state[k] for k in core_keys}

        new_core, complete, emit_ts, caps = self._vmapped(
            cfg, P, core_state, preds, cap_srcs, within_vec, tape
        )

        new_state = dict(state)
        new_state.update(new_core)

        # uniform emission: qid row + stacked capture buffers
        qid_row = jnp.broadcast_to(
            jnp.arange(Qc, dtype=jnp.int32)[:, None], (Qc, V)
        )
        stacked_rows = [_as_i32(emit_ts), qid_row] + [
            _as_i32(caps[pair]) for pair in t.proj_srcs
        ]
        flat_rows = jnp.stack([r.reshape(Qc * V) for r in stacked_rows])
        R = len(stacked_rows)
        flags = complete.reshape(Qc * V)
        out_w = min(Qc, self.out_cap_factor) * E + Qc * P
        n_total = flags.sum().astype(jnp.int32)
        posn = jnp.cumsum(flags.astype(jnp.int32)) - 1
        dest = jnp.where(flags & (posn < out_w), posn, out_w)
        packed = (
            jnp.zeros((R, out_w), dtype=jnp.int32)
            .at[:, dest]
            .set(flat_rows, mode="drop")
        )
        n_emitted = jnp.minimum(n_total, jnp.int32(out_w))
        return new_state, (n_emitted, packed, n_total - n_emitted)

    def _vmapped(self, cfg, P, core_state, preds, cap_srcs, within_vec,
                 tape):
        return jax.vmap(
            lambda st, pr, cs, wv: _chain_core(
                cfg, P, st, pr, cs, wv, tape.ts, tape.valid
            )
        )(core_state, preds, cap_srcs, within_vec)

    def decode_packed(self, n: int, block: np.ndarray):
        """Split the packed block by query slot -> member streams."""
        return _decode_qid_block(
            n, block,
            (
                (s, m[1])
                for s, m in enumerate(self.members)
                if m is not None
            ),
        )


def apply_lazy_projection(
    artifact: "ChainPatternArtifact",
    skip_pred_elements: frozenset = frozenset(),
):
    """Late materialization for a chain plan: capture pairs that are
    PROJECTION-ONLY (their column feeds no predicate, and every select
    item reading them is a plain reference) switch to ordinal capture,
    and their columns drop off the device tape entirely. Returns the set
    of tape columns the device still needs, or None when nothing is
    lazy-eligible. ``skip_pred_elements``: elements whose filters were
    pushed to the host wire — their columns no longer pin the tape."""
    spec = artifact.spec
    pred_cols = set()
    for i, el in enumerate(spec.elements):
        if el.filter is None or i in skip_pred_elements:
            continue
        for a in ast.iter_attrs(el.filter):
            pred_cols.add(f"{el.stream_id}.{a.name}")
    pairs = _cap_pairs(spec)
    lazy = []
    for pair in pairs:
        key = spec.cap_src_key[pair]
        if key in pred_cols:
            continue
        plain = True
        for i, prs in enumerate(spec.proj_ref_pairs):
            if pair in prs and spec.proj_srcs[i] != pair:
                plain = False  # computed expression needs the value
                break
        if plain:
            lazy.append(pair)
    if not lazy:
        return None
    artifact.lazy_pairs = tuple(sorted(lazy))
    needed = set(pred_cols)
    for pair in pairs:
        if pair not in artifact.lazy_pairs:
            needed.add(spec.cap_src_key[pair])
    needed |= set(spec.evt_keys)  # cross filters read these off the tape
    return needed


def chain_wire_opts(artifact: "ChainPatternArtifact", config):
    """Wire optimizations for a chain plan, in order: predicate pushdown
    (host-evaluable event-only element filters collapse to one packed
    mask bit per element) then late materialization (with pushed
    predicate columns now lazy-eligible). Returns (needed_device_columns,
    host_preds) or None when nothing applies."""
    from ..runtime.tape import HostPred

    spec = artifact.spec
    host_preds = []
    pushed = []
    if config.pred_pushdown:
        candidates = [
            i
            for i, he in enumerate(spec.host_pred_fns)
            if he is not None and spec.pred_fns[i] is not None
        ]
        # push only elements whose masks FREE wire columns. Columns that
        # stay regardless: cross-filter event reads, unpushable element
        # predicates, and capture sources that cannot go lazy (computed
        # projections, or lazy projection disabled).
        kept_base = set(spec.evt_keys)
        for i, el in enumerate(spec.elements):
            if el.filter is None or i in candidates:
                continue
            for a in ast.iter_attrs(el.filter):
                kept_base.add(f"{el.stream_id}.{a.name}")
        for pair in _cap_pairs(spec):
            if not config.lazy_projection:
                kept_base.add(spec.cap_src_key[pair])
                continue
            for pi, prs in enumerate(spec.proj_ref_pairs):
                if pair in prs and spec.proj_srcs[pi] != pair:
                    kept_base.add(spec.cap_src_key[pair])
                    break
        for i in candidates:
            he = spec.host_pred_fns[i]
            if not (set(he.refs) - kept_base):
                continue  # frees nothing: keep the device predicate
            key = f"@p:{i}"
            host_preds.append(HostPred(key, he.fn, he.refs))
            spec.pred_fns[i] = lambda env, k=key: env[k]
            pushed.append(i)
        artifact.pushed_preds = tuple(pushed)

    lazy_needed = None
    if config.lazy_projection:
        lazy_needed = apply_lazy_projection(
            artifact, skip_pred_elements=frozenset(pushed)
        )

    if not host_preds and lazy_needed is None:
        return None
    if lazy_needed is not None:
        needed = set(lazy_needed)
    else:
        needed = set(spec.evt_keys)
        for i, el in enumerate(spec.elements):
            if el.filter is None or i in pushed:
                continue
            for a in ast.iter_attrs(el.filter):
                needed.add(f"{el.stream_id}.{a.name}")
        for pair in _cap_pairs(spec):
            needed.add(spec.cap_src_key[pair])
    return needed, tuple(host_preds)


def _decode_qid_block(n: int, block, slot_schemas):
    """Split a packed (ts, qid, cols...) block by the qid row into
    per-slot (schema, rows) lists. ``slot_schemas``: iterable of
    (slot, OutputSchema)."""
    out = []
    qid = block[1, :n]
    for slot, schema in slot_schemas:
        sel = np.nonzero(qid == slot)[0]
        if sel.size == 0:
            continue
        sub = block[:, :n][:, sel]
        out.append(
            (schema, schema.decode_packed_block(
                int(sel.size), sub, data_row=2
            ))
        )
    return out


def group_chain_artifacts(
    artifacts: List, exclude=frozenset(), column_types=None
) -> List:
    """Replace runs of structurally-identical ChainPatternArtifacts with
    one StackedChainArtifact (multi-query parallelism). Artifacts in
    ``exclude`` (e.g. chained-query producers, read by name) stay
    standalone. ``column_types`` enables the vectorized predicate path
    (per-element broadcast compare against a literal vector instead of
    Q*K separate closure ops)."""
    groups: Dict = {}
    for a in artifacts:
        if isinstance(a, ChainPatternArtifact) and a.name not in exclude:
            key = (
                _ChainCfg.of(a.spec),
                a.pool,
                tuple(
                    np.dtype(f.atype.device_dtype).name
                    for f in a.output_schema.fields
                ),
            )
            groups.setdefault(key, []).append(a)
    stacked_of = {}
    for key, members in groups.items():
        if len(members) >= 2:
            stacked = StackedChainArtifact(
                name="@stack:" + members[0].name,
                members=members,
                column_types=column_types,
            )
            for m in members:
                stacked_of[m.name] = stacked
    if not stacked_of:
        return artifacts
    out, added = [], set()
    for a in artifacts:
        s = stacked_of.get(getattr(a, "name", None))
        if s is None:
            out.append(a)
        elif s.name not in added:
            out.append(s)
            added.add(s.name)
    return out


# --------------------------------------------------------------------------
# Engine 2: slot NFA (sequences, quantifiers)
# --------------------------------------------------------------------------

@dataclass
class SlotNFAArtifact:
    """General pattern/sequence matcher: lax.scan over the tape advancing a
    fixed pool of partial-match slots with greedy quantifier semantics."""

    name: str
    spec: _PatternSpec
    output_schema: OutputSchema
    output_mode: str = "buffered"
    slots: int = DEFAULT_SLOTS

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        """Widest per-cycle emission block (drain-cadence contract)."""
        return tape_capacity + self.slots

    @property
    def _needs_mbits(self) -> bool:
        """Projections over 'or'-group members need the emitting slot's
        matched bitmask on the wire so the unfired member decodes None;
        indexed captures ride their validity bits on the same word."""
        return any(self.spec.proj_or_deps) or bool(self._idx)

    @property
    def acc_rows(self) -> int:
        return (
            1
            + len(self.output_schema.fields)
            + (1 if self._needs_mbits else 0)
        )

    def decode_packed(self, n: int, block: "np.ndarray"):
        """Accumulator block -> rows; with or-groups, the trailing mbits
        row nullifies projections whose fired-member bit is absent."""
        schema = self.output_schema
        C = len(schema.fields)
        if not self._needs_mbits:
            return [(schema, schema.decode_packed_block(n, block))]
        from .output import emission_order

        # the mbits row must follow decode's row permutation
        mbits = np.asarray(block[1 + C, :n])[emission_order(block[0], n)]
        rows = schema.decode_packed_block(n, block[: 1 + C])
        deps = self.spec.proj_or_deps or ((),) * C
        idx_refs = self.spec.proj_idx_refs or ((),) * C
        K = self.spec.n_elements
        bit_of = {cap: K + j for j, cap in enumerate(self._idx)}
        out = []
        for i, (ts_v, row) in enumerate(rows):
            mb = int(mbits[i])
            row = tuple(
                None
                if (d and any(not (mb >> e) & 1 for e in d))
                or any(not (mb >> bit_of[r]) & 1 for r in ir)
                else v
                for v, d, ir in zip(row, deps, idx_refs)
            )
            out.append((ts_v, row))
        return [(schema, out)]

    def __post_init__(self):
        spec = self.spec
        self._idx = _idx_caps(spec)
        if spec.n_elements + len(self._idx) > 31:
            raise SiddhiQLError(
                "too many pattern elements + indexed captures for the "
                "match-bitmask wire word (limit 31)"
            )
        # mid-chain `-> every X` fork points, by GROUP index
        marks = spec.every_marks or (False,) * spec.n_elements
        if any(marks) and spec.kind != "pattern":
            raise SiddhiQLError(
                "mid-chain 'every' is only valid in '->' patterns"
            )
        if marks and marks[0]:
            raise SiddhiQLError(
                "use leading 'every' for the first pattern element"
            )
        last = spec.elements[-1]
        if spec.kind == "pattern" and last.max_count < 0:
            raise SiddhiQLError(
                "a '->' pattern cannot end with an unbounded quantifier "
                "(the match would never complete); bound it with <m:n>"
            )
        # step machinery is indexed by logical GROUP: singletons keep
        # their element's quantifier; 'and' groups need all n members
        # (any order, distinct members enforced per absorb); 'or' groups
        # need any one
        self._groups = spec.groups or tuple(
            (i,) for i in range(spec.n_elements)
        )
        self._gops = spec.group_ops or (None,) * len(self._groups)
        self._g_of = {
            e: g for g, mem in enumerate(self._groups) for e in mem
        }
        self._marked_groups = tuple(
            g
            for g, mem in enumerate(self._groups)
            if len(mem) == 1 and marks[mem[0]]
        )
        mins, maxs = [], []
        for mem, op in zip(self._groups, self._gops):
            if len(mem) == 1:
                el = spec.elements[mem[0]]
                mins.append(el.min_count)
                maxs.append(
                    el.max_count if el.max_count >= 0 else 2**30
                )
            elif op == "and":
                mins.append(len(mem))
                maxs.append(len(mem))
            else:  # 'or'
                mins.append(1)
                maxs.append(1)
        self._mins = np.array(mins, dtype=np.int32)
        self._maxs = np.array(maxs, dtype=np.int32)
        # prefix[i] = sum of min counts of groups [0, i); lets
        # "all groups in (a, b] optional" be a subtraction
        self._min_prefix = np.concatenate(
            [[0], np.cumsum(self._mins)]
        ).astype(np.int32)

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: the slot engine's partial-match
        population is its ``slots`` pool — same every/within residency
        semantics as the chain matcher."""
        return _pattern_cost(self.name, self.spec, self.slots)

    def nfa_check_info(self) -> List[Dict]:
        """Slot-engine tables for analysis.plancheck: the generic chain
        descriptors plus the group/min-prefix machinery the scan body
        indexes by (PLC207/208/209)."""
        return [
            _spec_check_info(
                self.name,
                self.spec,
                groups=self._groups,
                min_prefix=self._min_prefix,
                mask_bits=self.spec.n_elements + len(self._idx),
            )
        ]

    def init_state(self) -> Dict:
        S = self.slots
        state = {
            "enabled": jnp.asarray(True),
            "active": jnp.zeros(S, dtype=bool),
            "step": jnp.zeros(S, dtype=jnp.int32),
            "count": jnp.zeros(S, dtype=jnp.int32),
            "start": jnp.zeros(S, dtype=jnp.int32),
            "last": jnp.zeros(S, dtype=jnp.int32),
            # bitmask of elements the slot has actually matched (vs
            # skipped optionals) — gates cross-element filter references
            "matched": jnp.zeros(S, dtype=jnp.int32),
            "done": jnp.asarray(False),
            "started": jnp.asarray(False),
            "overflow": jnp.asarray(0, dtype=jnp.int32),
        }
        for pair in _cap_pairs(self.spec):
            dt = self.spec.cap_dtype[pair]
            state[_skey("first", *pair)] = jnp.zeros(S, dtype=dt)
            state[_skey("last", *pair)] = jnp.zeros(S, dtype=dt)
        for elem, col, k in self._idx:
            dt = self.spec.cap_dtype[(elem, col)]
            state[_skey(f"idx{k}", elem, col)] = jnp.zeros(S, dtype=dt)
            state[_skey(f"idxv{k}", elem, col)] = jnp.zeros(S, dtype=bool)
        return state

    # -- transition helpers (all vectorized over slots) ---------------------
    def _skipfree(self, a, b):
        """True when every element with index in (a, b) has min_count 0."""
        pre = jnp.asarray(self._min_prefix)
        return (pre[b] - pre[jnp.clip(a + 1, 0, len(self._mins))]) == 0

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        spec = self.spec
        K = spec.n_elements
        GM = self._groups
        gops = self._gops
        G = len(GM)
        S = self.slots
        E = tape.capacity
        M = E + S  # match buffer capacity
        pairs = _cap_pairs(spec)
        mins = jnp.asarray(self._mins)
        maxs = jnp.asarray(self._maxs)

        preds = _element_preds(spec, tape, state["enabled"])
        pred_mat = jnp.stack(preds, axis=1)  # [E, K]
        # first-occurrence entry guards (sequence absence before a
        # quantified element): a stricter per-event mask consulted only
        # on the advance-into-element path below — absorbs keep the
        # plain mask, which is what makes the guard count-conditional
        egf = spec.entry_guard_fns or ()
        if any(f is not None for f in egf):
            genv: ColumnEnv = dict(tape.cols)
            entry_mat = jnp.stack(
                [
                    preds[k] if f is None else preds[k] & f(genv)
                    for k, f in enumerate(egf)
                ],
                axis=1,
            )
        else:
            entry_mat = pred_mat
        cap_srcs = {
            pair: tape.cols[spec.cap_src_key[pair]] for pair in pairs
        }

        # scan-carry zeros derive from a (possibly shard-varying) input so
        # the carry's varying type matches under shard_map (a fresh
        # replicated constant would trip the scan-vma check)
        zero_i = tape.ts[0].astype(jnp.int32) * 0
        buf_init = {
            "ts": jnp.zeros(M, dtype=jnp.int32) + zero_i,
            "n": zero_i,
        }
        if self._needs_mbits:
            buf_init["mbits"] = jnp.zeros(M, dtype=jnp.int32) + zero_i
        for elem, col, which in spec.captures:
            dt = spec.cap_dtype[(elem, col)]
            buf_init[_skey(which, elem, col)] = (
                jnp.zeros(M, dtype=dt) + zero_i.astype(dt)
            )

        def body(carry, x):
            st, buf = carry
            ts_e, valid_e, m, m_entry, caps_e = x  # m, m_entry: bool[K]

            active = st["active"]
            step = st["step"]
            count = st["count"]

            # cross-element filters: evaluate this event against each
            # slot's captured values -> ok[k] is bool[S]
            cross_ok: Dict[int, jnp.ndarray] = {}
            if spec.has_cross:
                cenv: ColumnEnv = {
                    key: caps_e[f"evt:{key}"] for key in spec.evt_keys
                }
                for elem, col, which in spec.captures:
                    alias = spec.elements[elem].alias
                    cenv[_cap_key(alias, which, col)] = st[
                        _skey(which, elem, col)
                    ]
                for k, fn in enumerate(spec.cross_fns):
                    if fn is not None:
                        ok = jnp.broadcast_to(jnp.asarray(fn(cenv)), (S,))
                        # a referenced element that was skipped (optional)
                        # has no capture: the filter can never hold
                        ref_mask = 0
                        for r in spec.cross_refs[k]:
                            ref_mask |= 1 << r
                        if ref_mask:
                            ok = ok & (
                                (st["matched"] & ref_mask) == ref_mask
                            )
                        # indexed refs additionally require the referenced
                        # element to have absorbed > kk events
                        if spec.cross_idx_refs:
                            for e2, c2, k2 in spec.cross_idx_refs[k]:
                                ok = ok & st[_skey(f"idxv{k2}", e2, c2)]
                        cross_ok[k] = ok

            # per-slot effective member predicates, then per-GROUP masks:
            # entry (advance into the group: any member) and need (absorb
            # at the group: 'and' groups require a still-unmatched member)
            def has_bit(e):
                return (st["matched"] & jnp.int32(1 << e)) != 0

            eff = []
            eff_entry = []  # entry-guarded variant (advance path only)
            for e in range(K):
                v = jnp.broadcast_to(m[e], (S,))
                ve = jnp.broadcast_to(m_entry[e], (S,))
                if e in cross_ok:
                    v = v & cross_ok[e]
                    ve = ve & cross_ok[e]
                eff.append(v)
                eff_entry.append(ve)
            entry_g, need_g = [], []
            for g, (mem, op) in enumerate(zip(GM, gops)):
                # entry (advance INTO the group) consults the
                # first-occurrence guard; need (absorb AT the group,
                # count >= 1) deliberately does not
                ent = eff_entry[mem[0]]
                nee = eff[mem[0]]
                for e in mem[1:]:
                    ent = ent | eff_entry[e]
                    nee = nee | eff[e]
                if len(mem) > 1 and op == "and":
                    nee = eff[mem[0]] & ~has_bit(mem[0])
                    for e in mem[1:]:
                        nee = nee | (eff[e] & ~has_bit(e))
                entry_g.append(ent)
                need_g.append(nee)

            if spec.within is not None:
                alive = (ts_e - st["start"]) <= jnp.int32(spec.within)
                active = active & (alive | ~valid_e)
            m_at = jnp.zeros(S, dtype=bool)
            for g in range(G):
                m_at = jnp.where(step == g, need_g[g], m_at)
            absorb = active & valid_e & m_at & (count < maxs[step])

            # advance target: smallest t > step whose predicate matches,
            # with only optional groups skipped in between
            can_leave = count >= mins[step]
            adv_t = jnp.full(S, G, dtype=jnp.int32)
            for t in range(G - 1, 0, -1):
                reach = (
                    active
                    & valid_e
                    & (step < t)
                    & can_leave
                    & self._skipfree(step, t)
                    & entry_g[t]
                )
                adv_t = jnp.where(reach, t, adv_t)
            advance = ~absorb & (adv_t < G)  # greedy: absorb wins

            # completion from current position: all later groups optional
            completable = active & can_leave & self._skipfree(step, G)
            at_last_full = (
                active
                & (step == G - 1)
                & (count + absorb.astype(jnp.int32) >= maxs[G - 1])
                & (count + absorb.astype(jnp.int32) >= mins[G - 1])
            )
            moved_to_last = (
                advance & (adv_t == G - 1) & (self._maxs[G - 1] == 1)
            )

            if spec.kind == "sequence":
                miss = active & valid_e & ~absorb & ~advance
                emit_on_break = miss & completable
                killed = miss
            else:
                emit_on_break = jnp.zeros(S, dtype=bool)
                killed = jnp.zeros(S, dtype=bool)

            emit = emit_on_break | at_last_full | moved_to_last

            # apply absorb/advance
            new_count = jnp.where(absorb, count + 1, count)
            new_step = jnp.where(advance, adv_t, step)
            new_count = jnp.where(advance, 1, new_count)
            new_last = jnp.where(absorb | advance, ts_e, st["last"])

            # which MEMBER fired: one element per absorb/advance, lowest
            # matching (for 'and' groups, lowest still-unmatched) wins
            fire: Dict[int, jnp.ndarray] = {}
            for g, (mem, op) in enumerate(zip(GM, gops)):
                at_g = (absorb & (step == g)) | (advance & (adv_t == g))
                taken = jnp.zeros(S, dtype=bool)
                for e in mem:
                    cand = eff[e]
                    if len(mem) > 1 and op == "and":
                        cand = cand & ~has_bit(e)
                    f = at_g & cand & ~taken
                    taken = taken | f
                    fire[e] = f
            new_matched = st["matched"]
            for e in range(K):
                new_matched = jnp.where(
                    fire[e],
                    new_matched | jnp.int32(1 << e),
                    new_matched,
                )

            new_first = {}
            new_lastc = {}
            for pair in pairs:
                elem = pair[0]
                g = self._g_of[elem]
                f0 = st[_skey("first", *pair)]
                l0 = st[_skey("last", *pair)]
                took = fire[elem]
                if len(GM[g]) == 1:
                    first_take = took & (
                        (advance & (adv_t == g)) | (count == 0)
                    )
                else:
                    first_take = took  # group members fire once each
                new_first[pair] = jnp.where(
                    first_take, caps_e[_skey("src", *pair)], f0
                )
                new_lastc[pair] = jnp.where(
                    took, caps_e[_skey("src", *pair)], l0
                )

            # indexed captures: the (k+1)-th event the element absorbs —
            # fire via absorb leaves new_count == old count + 1; fire via
            # advance/arm resets new_count to 1, so k >= 1 never writes
            new_idx: Dict[Tuple[int, str, int], jnp.ndarray] = {}
            new_idxv: Dict[Tuple[int, str, int], jnp.ndarray] = {}
            for elem, col, k in self._idx:
                wr = fire[elem] & (new_count == jnp.int32(k + 1))
                new_idx[(elem, col, k)] = jnp.where(
                    wr,
                    caps_e[_skey("src", elem, col)],
                    st[_skey(f"idx{k}", elem, col)],
                )
                new_idxv[(elem, col, k)] = (
                    st[_skey(f"idxv{k}", elem, col)] | wr
                )

            # emissions: scatter completed slots into the match buffer
            emit_ts = jnp.where(
                emit_on_break, st["last"], ts_e
            )  # break emits as-of previous event
            n0 = buf["n"]
            offs = jnp.cumsum(emit.astype(jnp.int32)) - 1
            pos = jnp.where(emit, n0 + offs, M)  # M = dropped (overflow)
            new_buf = dict(buf)
            new_buf["ts"] = buf["ts"].at[pos].set(emit_ts, mode="drop")
            if self._needs_mbits:
                wire = new_matched
                for j, cap in enumerate(self._idx):
                    wire = wire | jnp.where(
                        new_idxv[cap], jnp.int32(1 << (K + j)), 0
                    )
                new_buf["mbits"] = buf["mbits"].at[pos].set(
                    wire, mode="drop"
                )
            for elem, col, which in spec.captures:
                bkey = _skey(which, elem, col)
                if which == "first":
                    vals = new_first[(elem, col)]
                elif which == "last":
                    vals = new_lastc[(elem, col)]
                else:
                    vals = new_idx[(elem, col, int(which[3:]))]
                new_buf[bkey] = buf[bkey].at[pos].set(vals, mode="drop")
            new_buf["n"] = jnp.minimum(
                n0 + emit.sum().astype(jnp.int32), M
            )

            # mid-chain `-> every X` forks: an advance into a marked
            # group must not CONSUME the matched prefix — the advanced
            # instance moves to a fresh slot (or emits directly when the
            # marked element completes the pattern) and the prefix slot
            # reverts, staying armed for the next X event
            fork = jnp.zeros(S, dtype=bool)
            for g in self._marked_groups:
                fork = fork | (advance & (adv_t == g))

            freed = (emit & ~fork) | killed
            active2 = active & ~freed
            fork_overflow = jnp.int32(0)

            if self._marked_groups:
                fork_alloc = fork & ~moved_to_last
                free = ~active2
                free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
                alloc_rank = (
                    jnp.cumsum(fork_alloc.astype(jnp.int32)) - 1
                )
                # rank -> free slot index (unfilled ranks stay S: drop)
                r2s = (
                    jnp.full(S, S, dtype=jnp.int32)
                    .at[jnp.where(free, free_rank, S)]
                    .set(jnp.arange(S, dtype=jnp.int32), mode="drop")
                )
                target = jnp.where(
                    fork_alloc,
                    r2s[jnp.clip(alloc_rank, 0, S - 1)],
                    S,
                )
                placed = fork_alloc & (target < S)
                fork_overflow = (
                    (fork_alloc & ~placed).sum().astype(jnp.int32)
                )
                # scatter the ADVANCED state into the fork targets,
                # then revert the originals to their pre-advance state
                active2 = active2.at[target].set(True, mode="drop")
                new_step = new_step.at[target].set(
                    new_step, mode="drop"
                )
                new_step = jnp.where(fork, step, new_step)
                new_count = new_count.at[target].set(
                    new_count, mode="drop"
                )
                new_count = jnp.where(fork, count, new_count)
                new_start = st["start"].at[target].set(
                    st["start"], mode="drop"
                )
                new_last = new_last.at[target].set(
                    new_last, mode="drop"
                )
                new_last = jnp.where(fork, st["last"], new_last)
                new_matched = new_matched.at[target].set(
                    new_matched, mode="drop"
                )
                new_matched = jnp.where(
                    fork, st["matched"], new_matched
                )
                for pair in pairs:
                    new_first[pair] = new_first[pair].at[target].set(
                        new_first[pair], mode="drop"
                    )
                    new_first[pair] = jnp.where(
                        fork, st[_skey("first", *pair)], new_first[pair]
                    )
                    new_lastc[pair] = new_lastc[pair].at[target].set(
                        new_lastc[pair], mode="drop"
                    )
                    new_lastc[pair] = jnp.where(
                        fork, st[_skey("last", *pair)], new_lastc[pair]
                    )
                for cap in self._idx:
                    new_idx[cap] = new_idx[cap].at[target].set(
                        new_idx[cap], mode="drop"
                    )
                    new_idxv[cap] = new_idxv[cap].at[target].set(
                        new_idxv[cap], mode="drop"
                    )
            else:
                new_start = st["start"]

            # arm a new slot on a first-element match; for non-every,
            # "started" only holds while the armed partial is still alive
            # (or the single match is done) — a killed/expired partial
            # re-arms matching on the next start event
            started_now = st["started"] & (active2.any() | st["done"])
            # arming matches ANY member of group 0 (cross refs cannot
            # appear there); the lowest matching member is the one armed
            m0 = m[GM[0][0]]
            for e in GM[0][1:]:
                m0 = m0 | m[e]
            arm_sel: Dict[int, jnp.ndarray] = {}
            arm_taken = jnp.asarray(False)
            for e in GM[0]:
                s_e = m[e] & ~arm_taken
                arm_taken = arm_taken | m[e]
                arm_sel[e] = s_e
            if spec.every:
                any_done = st["done"]
                want_start = m0 & valid_e
                if spec.every_grouped:
                    # grouped every: one instance in flight; restart only
                    # once no partial is active (complete/killed/expired).
                    # The completing event itself must NOT arm the next
                    # occurrence (Siddhi: restart with subsequent events),
                    # so a same-event emit also blocks arming.
                    want_start = (
                        want_start & ~active2.any() & ~emit.any()
                    )
            else:
                any_done = st["done"] | emit.any()
                want_start = m0 & valid_e & ~started_now & ~any_done
            free_slot = jnp.argmin(active2.astype(jnp.int32))
            has_free = ~active2[free_slot]
            do_start = want_start & has_free
            one_hot = (
                jnp.zeros(S, dtype=bool).at[free_slot].set(True) & do_start
            )
            active3 = active2 | one_hot
            new_step = jnp.where(one_hot, 0, new_step)
            new_count = jnp.where(one_hot, 1, new_count)
            new_start = jnp.where(one_hot, ts_e, new_start)
            new_last = jnp.where(one_hot, ts_e, new_last)
            arm_bits = jnp.int32(0)
            for e in GM[0]:
                arm_bits = jnp.where(
                    arm_sel[e], jnp.int32(1 << e), arm_bits
                )
            new_matched = jnp.where(one_hot, arm_bits, new_matched)
            for pair in pairs:
                if pair[0] in GM[0]:
                    armed_here = one_hot & arm_sel[pair[0]]
                    new_first[pair] = jnp.where(
                        armed_here,
                        caps_e[_skey("src", *pair)],
                        new_first[pair],
                    )
                    new_lastc[pair] = jnp.where(
                        armed_here,
                        caps_e[_skey("src", *pair)],
                        new_lastc[pair],
                    )
            for cap in self._idx:
                # a re-armed slot starts a fresh element run: its indexed
                # captures from the previous occupant are invalid
                new_idxv[cap] = new_idxv[cap] & ~one_hot
            # a start-element event that fully satisfies a 1-element pattern
            # (K==1, max 1) completes immediately on the next event's break /
            # absorb logic; K==1 plain patterns use the chain engine anyway.

            new_st = dict(st)
            new_st.update(
                active=active3,
                step=new_step,
                count=new_count,
                start=new_start,
                last=new_last,
                matched=new_matched,
                done=any_done,
                started=started_now | want_start,
                overflow=st["overflow"]
                + (want_start & ~has_free).astype(jnp.int32)
                + fork_overflow,
            )
            for pair in pairs:
                new_st[_skey("first", *pair)] = new_first[pair]
                new_st[_skey("last", *pair)] = new_lastc[pair]
            for elem, col, k in self._idx:
                new_st[_skey(f"idx{k}", elem, col)] = new_idx[
                    (elem, col, k)
                ]
                new_st[_skey(f"idxv{k}", elem, col)] = new_idxv[
                    (elem, col, k)
                ]
            return (new_st, new_buf), None

        @jax.named_scope("fst.pattern_scan")
        def scan(carry, xs_):
            return jax.lax.scan(body, carry, xs_)

        xcols = {_skey("src", *pair): cap_srcs[pair] for pair in pairs}
        for key in spec.evt_keys:
            xcols[f"evt:{key}"] = tape.cols[key]
        xs = (tape.ts, tape.valid, pred_mat, entry_mat, xcols)
        # Relevance compaction (pattern kind only): '->' ignores events
        # matching no element, so the sequential scan — the expensive part,
        # ~E dependent steps — only needs the events whose predicate row is
        # non-empty. They compact into an E//8 buffer; a lax.cond falls
        # back to the full scan in the (rare) batch where more than E//8
        # events are relevant. Sequences must see every event (strict
        # continuity: an irrelevant event kills partials), so they keep
        # the full scan.
        if spec.kind == "pattern" and E >= 4096:
            R = max(2048, E // 8)
            rel = pred_mat.any(axis=1) & tape.valid
            cnt = rel.sum().astype(jnp.int32)
            cpos = jnp.cumsum(rel.astype(jnp.int32)) - 1
            dest = jnp.where(rel & (cpos < R), cpos, R)
            idx = (
                jnp.zeros(R, dtype=jnp.int32)
                .at[dest]
                .set(jnp.arange(E, dtype=jnp.int32), mode="drop")
            )
            cvalid = jnp.arange(R) < jnp.minimum(cnt, R)
            xs_c = (
                tape.ts[idx],
                cvalid,
                pred_mat[idx] & cvalid[:, None],
                entry_mat[idx] & cvalid[:, None],
                {k: v[idx] for k, v in xcols.items()},
            )
            (new_state, buf), _ = jax.lax.cond(
                cnt <= R,
                lambda carry: scan(carry, xs_c),
                lambda carry: scan(carry, xs),
                (state, buf_init),
            )
        else:
            (new_state, buf), _ = scan((state, buf_init), xs)

        emit_env = _emit_env(
            spec,
            {
                (elem, col, which): buf[_skey(which, elem, col)]
                for elem, col, which in spec.captures
            },
        )
        out_cols = tuple(
            jnp.broadcast_to(jnp.asarray(p(emit_env)), (M,))
            for p in spec.proj_fns
        )
        if self._needs_mbits:
            # trailing wire row: the emitting slot's matched bitmask
            # (decode_packed strips it and nullifies unfired or-members)
            out_cols = out_cols + (buf["mbits"],)
        return new_state, (buf["n"], buf["ts"], out_cols)


# --------------------------------------------------------------------------
# Entry point
# --------------------------------------------------------------------------

def compile_pattern_query(
    q: ast.Query,
    name: str,
    schemas,
    stream_codes: Dict[str, int],
    extensions,
    config=None,
):
    from .config import DEFAULT_CONFIG

    config = config or DEFAULT_CONFIG
    spec = _build_spec(q, schemas, stream_codes, extensions)
    out_schema = OutputSchema(spec.output_stream, spec.out_fields)
    # grouped every needs per-partial arming state -> slot engine
    if _is_chain(spec) and not spec.has_cross and not spec.every_grouped:
        return ChainPatternArtifact(
            name=name, spec=spec, output_schema=out_schema,
            pool=config.pattern_pool,
        )
    if any(el.negated for el in spec.elements):
        raise SiddhiQLError(
            "absence ('not') elements require a plain chain pattern "
            "(no quantifiers or cross-element references)"
        )
    if (
        len(spec.groups) == 1
        and len(spec.groups[0]) > 1
        and spec.group_ops[0] == "or"
    ):
        raise SiddhiQLError(
            "a pattern that is ONE 'or' group matches single events; "
            "use a filter union (two queries into one output) instead"
        )
    # cross-element filters and and/or groups route to the slot engine
    # even for plain chains: per-slot evaluation needs each partial's
    # captures / member-matched bits
    return SlotNFAArtifact(
        name=name, spec=spec, output_schema=out_schema,
        slots=config.pattern_slots,
    )
