"""Single-core per-event reference interpreter (the measured baseline).

The repo's benchmarks used to grade themselves against a PINNED estimate
of the in-JVM Siddhi runtime (500k events/sec) that nobody had measured
— BASELINE.md documents that the reference publishes no numbers. This
module is the falsifiable stand-in: a straightforward per-event engine
in the exact architectural shape of siddhi-core's hot path (one event at
a time through filter processors / NFA partial-match lists / window
processors with running aggregates —
``AbstractSiddhiOperator.processElement`` feeding siddhi-core,
reference: operator/AbstractSiddhiOperator.java:209-233), written
against the same parsed CQL AST the TPU engine compiles.

Tests replay the identical event stream (``workloads.make_batches``)
through it and through the engine and compare rows
(tests/test_baseline_crosscheck.py, tests/test_baseline_workloads.py).
It is deliberately the SIMPLE obvious
implementation — per-event dispatch, dict state, no vectorization — the
way the JVM engine processes events (which JIT-compiles to far faster
code than CPython; BASELINE.md keeps the JVM-estimate ratio alongside
for that reason).
"""

from __future__ import annotations

import operator
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..query import ast
from ..query.lexer import SiddhiQLError
from ..query.parser import parse_plan


_OPS = {
    "==": operator.eq, "!=": operator.ne, "<": operator.lt,
    "<=": operator.le, ">": operator.gt, ">=": operator.ge,
    "+": operator.add, "-": operator.sub, "*": operator.mul,
    "%": operator.mod,
}


def _compile_scalar(expr: ast.Expr) -> Callable[[Dict[str, Any]], Any]:
    """AST -> per-event Python closure over a field dict."""
    if isinstance(expr, ast.Literal):
        v = expr.value
        return lambda ev: v
    if isinstance(expr, ast.TimeLiteral):
        v = expr.ms
        return lambda ev: v
    if isinstance(expr, ast.Attr):
        name = expr.name
        if getattr(expr, "index", None) not in (None, 0):
            # sequence captures store FIRST-occurrence fields only;
            # silently serving s[k]/s[last] from them would corrupt
            # the oracle
            raise SiddhiQLError(
                "baseline interpreter: only s.x / s[0].x references"
            )
        if expr.qualifier is not None:
            key = f"{expr.qualifier}.{name}"
            return lambda ev: ev[key] if key in ev else ev[name]
        return lambda ev: ev[name]
    if isinstance(expr, ast.Unary):
        f = _compile_scalar(expr.operand)
        if expr.op == "not":
            return lambda ev: not f(ev)
        return lambda ev: -f(ev)
    if isinstance(expr, ast.Binary):
        lf = _compile_scalar(expr.left)
        rf = _compile_scalar(expr.right)
        if expr.op == "and":
            return lambda ev: lf(ev) and rf(ev)
        if expr.op == "or":
            return lambda ev: lf(ev) or rf(ev)
        if expr.op == "/":
            return lambda ev: lf(ev) / rf(ev)
        op = _OPS[expr.op]
        return lambda ev: op(lf(ev), rf(ev))
    raise SiddhiQLError(f"baseline interpreter: unsupported {expr!r}")


class _Select:
    def __init__(self, q: ast.Query):
        inp = q.input
        self.filters = [_compile_scalar(f) for f in inp.filters]
        self.projs = [
            _compile_scalar(it.expr) for it in q.selector.items
        ]
        self.out = q.output_stream

    def on_event(self, ev, ts, emit):
        for f in self.filters:
            if not f(ev):
                return
        emit(self.out, ts, tuple(p(ev) for p in self.projs))


class _Chain:
    """``every e0 -> e1 [-> ...] [within W]`` NFA: a list of partial
    matches, advanced per event (the JVM engine's partial-match chain)."""

    def __init__(self, q: ast.Query):
        inp = q.input
        self.within = inp.within
        self.elements = []
        for el in inp.elements:
            flt = (
                _compile_scalar(el.filter)
                if el.filter is not None
                else None
            )
            self.elements.append((el.alias, flt))
        self.projs = [
            _compile_scalar(it.expr) for it in q.selector.items
        ]
        self.out = q.output_stream
        self.partials: List[Tuple[int, int, Dict[str, Any]]] = []
        # (next_element_idx, start_ts, captures)

    def on_event(self, ev, ts, emit):
        K = len(self.elements)
        w = self.within
        # expire, then try to advance every partial (oldest first)
        out_partials = []
        for step, start_ts, caps in self.partials:
            if w is not None and ts - start_ts > w:
                continue
            alias, flt = self.elements[step]
            if flt is None or flt(ev):
                caps = dict(caps)
                for k, v in ev.items():
                    caps[f"{alias}.{k}"] = v
                if step + 1 == K:
                    row = tuple(p(caps) for p in self.projs)
                    emit(self.out, ts, row)
                    continue
                out_partials.append((step + 1, start_ts, caps))
            else:
                out_partials.append((step, start_ts, caps))
        self.partials = out_partials
        # every-semantics: each e0 match starts a fresh instance
        alias0, flt0 = self.elements[0]
        if flt0 is None or flt0(ev):
            caps = {f"{alias0}.{k}": v for k, v in ev.items()}
            if K == 1:
                emit(self.out, ts, tuple(p(caps) for p in self.projs))
            else:
                self.partials.append((1, ts, caps))


class _Sequence:
    """Strict sequence (``,``) interpreter: quantifiers with greedy
    absorb-before-advance, optional-skip, break-kill (emitting when
    every remaining element is optional), and absence (``not B``)
    applied as a veto on the NEXT positive element's ENTRY event only —
    the per-event twin of the slot engine's count-conditional entry
    guard (compiler/nfa.py `_rewrite_sequence_absence`), kept obviously
    correct so randomized oracle tests can cross-check the device
    engine against it."""

    def __init__(self, q: ast.Query):
        inp = q.input
        self.every = inp.every_
        # positive steps: (alias, stream, filter, min, max, guards);
        # guards are the same-stream absent elements immediately before
        # this step — each vetoes the step's first (entering) event
        self.steps: List[Tuple] = []
        pending: List[Tuple[str, Optional[Callable]]] = []
        for el in inp.elements:
            flt = (
                _compile_scalar(el.filter)
                if el.filter is not None
                else None
            )
            if el.negated:
                pending.append((el.stream_id, flt))
                continue
            guards = [
                gf for gs, gf in pending if gs == el.stream_id
            ]  # different-stream absences are vacuous under strictness
            self.steps.append(
                (
                    el.alias,
                    el.stream_id,
                    flt,
                    el.min_count,
                    el.max_count,
                    guards,
                )
            )
            pending = []
        self.projs = [
            _compile_scalar(it.expr) for it in q.selector.items
        ]
        self.out = q.output_stream
        # (step_idx, count, caps); caps holds FIRST-occurrence fields
        # (bare ``s.x`` means ``s[0].x``)
        self.partials: List[Tuple[int, int, Dict[str, Any]]] = []
        self.done = False

    def _min_sum(self, a: int, b: int) -> int:
        return sum(self.steps[i][3] for i in range(a + 1, b))

    def _matches(self, step: int, ev) -> bool:
        # single-input-stream interpreter (like _Chain): stream routing
        # is the caller's concern, filters decide here
        flt = self.steps[step][2]
        return flt is None or bool(flt(ev))

    def _blocked(self, step: int, ev) -> bool:
        return any(g is None or bool(g(ev)) for g in self.steps[step][5])

    def _capture(self, caps, step, ev, first: bool) -> None:
        alias = self.steps[step][0]
        if first:
            for k, v in ev.items():
                caps[f"{alias}.{k}"] = v

    def _close(self, caps, ts, emit) -> None:
        emit(self.out, ts, tuple(p(caps) for p in self.projs))
        self.done = True

    def on_event(self, ev, ts, emit):
        K = len(self.steps)
        survivors = []
        for step, count, caps in self.partials:
            _a, _s, _f, mn, mx, _g = self.steps[step]
            if self._matches(step, ev) and (mx < 0 or count < mx):
                # absorb: count >= 1 here, so entry guards don't apply
                if step == K - 1 and count + 1 == mx:
                    self._close(caps, ts, emit)
                else:
                    survivors.append((step, count + 1, caps))
                continue
            advanced = False
            if count >= mn:
                for tgt in range(step + 1, K):
                    if (
                        self._min_sum(step, tgt) == 0
                        and self._matches(tgt, ev)
                        and not self._blocked(tgt, ev)
                    ):
                        caps2 = dict(caps)
                        self._capture(caps2, tgt, ev, first=True)
                        if tgt == K - 1 and self.steps[tgt][4] == 1:
                            self._close(caps2, ts, emit)
                        else:
                            survivors.append((tgt, 1, caps2))
                        advanced = True
                        break
            if advanced:
                continue
            # break: emit iff every remaining element is optional
            if count >= mn and self._min_sum(step, K) == 0:
                self._close(caps, ts, emit)
        self.partials = survivors
        can_arm = self.every or (not self.done and not self.partials)
        if can_arm and self._matches(0, ev):
            caps = {}
            self._capture(caps, 0, ev, first=True)
            if K == 1 and self.steps[0][4] == 1:
                self._close(caps, ts, emit)
            else:
                self.partials.append((0, 1, caps))


class _LengthWindowGroupBy:
    """``#window.length(C) select ... group by k``: ring of the last C
    events + per-group running sums and counts (add on arrival,
    subtract on eviction; min / max read the ring's members of the
    group), emitting the group's row per event — siddhi-core's
    LengthWindowProcessor + GroupByKeyGenerator shape."""

    def __init__(self, q: ast.Query, capacity: int):
        inp = q.input
        self.filters = [_compile_scalar(f) for f in inp.filters]
        self.cap = capacity
        self.group_keys = [
            k.split(".", 1)[-1] for k in q.selector.group_by
        ]
        self.ring: deque = deque()
        self.sums: Dict[Any, List[float]] = {}
        self.counts: Dict[Any, int] = {}
        # each select item: ('group' | 'sum' | 'min' | 'max', fn) |
        # ('count', None)
        self.items = []
        for it in q.selector.items:
            e = it.expr
            if isinstance(e, ast.Call) and e.name in ("sum", "min", "max"):
                self.items.append((e.name, _compile_scalar(e.args[0])))
            elif isinstance(e, ast.Call) and e.name == "count":
                self.items.append(("count", None))
            else:
                self.items.append(("group", _compile_scalar(e)))
        self.out = q.output_stream

    def on_event(self, ev, ts, emit):
        for f in self.filters:
            if not f(ev):
                return
        key = tuple(ev[k] for k in self.group_keys)
        # an aggregate's argument per item (None for the others)
        vals = [
            fn(ev) if kind in ("sum", "min", "max") else None
            for kind, fn in self.items
        ]
        self.ring.append((key, vals))
        sums = self.sums.setdefault(key, [0.0] * len(vals))
        self.counts[key] = self.counts.get(key, 0) + 1
        evicted = (
            self.ring.popleft() if len(self.ring) > self.cap else None
        )
        if evicted is not None:
            self.counts[evicted[0]] -= 1
        row = []
        for i, (kind, fn) in enumerate(self.items):
            if kind == "sum":
                sums[i] += vals[i]
                if evicted is not None:
                    self.sums[evicted[0]][i] -= evicted[1][i]
                row.append(sums[i])
            elif kind == "count":
                row.append(self.counts[key])
            elif kind in ("min", "max"):
                # the window's members of this group, oldest first
                members = [v[i] for k, v in self.ring if k == key]
                row.append(min(members) if kind == "min" else max(members))
            else:
                row.append(fn(ev))
        emit(self.out, ts, tuple(row))


def _window_aggregate(kind: str, vals: list):
    """One aggregate over a window's members, oldest first."""
    if kind == "count":
        return len(vals)
    if kind == "sum":
        return sum(vals)
    if kind in ("min", "max"):
        return min(vals) if kind == "min" else max(vals)
    mean = sum(vals) / len(vals)
    if kind == "avg":
        return mean
    if kind == "stddev":  # of the window's members, not a sample's
        return (sum((v - mean) ** 2 for v in vals) / len(vals)) ** 0.5
    raise SiddhiQLError(f"baseline interpreter: unsupported {kind}()")


def _span_ms(a) -> int:
    """A window's duration argument in ms."""
    return a.ms if isinstance(a, ast.TimeLiteral) else int(a.value)


class _AggLift:
    """What the windows that recompute their aggregates per arrival
    share: the select items and ``having`` with each aggregate call
    lifted to a slot of the event's env, and the row of one arrival
    over its window's members."""

    def _lift_query(self, q: ast.Query) -> None:
        self.aggs = []  # (slot, kind, argument fn or None)
        self.items = [
            (it.output_name(), _compile_scalar(self._lift(it.expr)))
            for it in q.selector.items
        ]
        self.having = (
            _compile_scalar(self._lift(q.selector.having))
            if q.selector.having is not None else None
        )
        self.out = q.output_stream

    def _lift(self, e):
        """Aggregate calls -> slots of the event's env."""
        if ast.is_aggregate_call(e):
            slot = f"@agg{len(self.aggs)}"
            self.aggs.append((
                slot, e.name.lower(),
                _compile_scalar(e.args[0]) if e.args else None,
            ))
            return ast.Attr(slot)
        if isinstance(e, ast.Unary):
            return ast.Unary(e.op, self._lift(e.operand))
        if isinstance(e, ast.Binary):
            return ast.Binary(e.op, self._lift(e.left), self._lift(e.right))
        return e

    def _emit_row(self, ev, rows, ts, emit) -> None:
        """``ev``'s row over ``rows``, its window's members, oldest
        first, where it passes ``having``."""
        env = dict(ev)
        for slot, kind, fn in self.aggs:
            env[slot] = _window_aggregate(
                kind, [fn(r) if fn is not None else 1 for r in rows])
        row = []
        for alias, fn in self.items:
            env[alias] = fn(env)
            row.append(env[alias])
        if self.having is None or self.having(env):
            emit(self.out, ts, tuple(row))


class _TimeWindowGroupBy(_AggLift):
    """``S[f]#window.time(t) select ... group by k [having ...]``
    (``docs/time_window.md``): the members in a deque in arrival
    order, on the stream's clock (the timestamps, as a running
    maximum). At an arrival stamped T every member stamped
    ``<= T - t`` leaves first, then the arrival joins, then its row
    carries its group's aggregates, recomputed from the group's live
    members, itself among them. ``capacity``: the ring of the engine
    (``EngineConfig.time_ring_capacity``), where a test wants its
    answer when it is full: the oldest member is lost to the newest."""

    def __init__(self, q: ast.Query, span_ms: int,
                 capacity: Optional[int] = None):
        inp = q.input
        self.filters = [_compile_scalar(f) for f in inp.filters]
        self.span, self.cap = span_ms, capacity
        self.group_keys = [
            k.split(".", 1)[-1] for k in q.selector.group_by
        ]
        self._lift_query(q)
        self.members: deque = deque()  # (stamp, group, event)
        self.groups: Dict[Any, deque] = {}
        self.clock = None
        self.evicted = 0

    def on_event(self, ev, ts, emit):
        for f in self.filters:
            if not f(ev):
                return
        self.clock = ts if self.clock is None else max(self.clock, ts)
        while self.members and (
            self.members[0][0] <= self.clock - self.span
        ):
            self.groups[self.members.popleft()[1]].popleft()
        key = tuple(ev[k] for k in self.group_keys)
        if self.cap is not None and len(self.members) == self.cap:
            self.groups[self.members.popleft()[1]].popleft()
            self.evicted += 1
        self.members.append((self.clock, key, ev))
        rows = self.groups.setdefault(key, deque())
        rows.append(ev)
        self._emit_row(ev, rows, ts, emit)


class _PerKeyLengthWindow(_AggLift):
    """``partition with (k of S) begin from S[f]#window.length(C) select
    ... [having ...] end``: per key a deque of that key's last C rows
    (siddhi-core runs one LengthWindowProcessor per partition
    instance), every aggregate recomputed from the deque on each
    arrival, one row per event that passes ``having``. ``@purge`` is
    applied per event on the stream's clock (the timestamps): a key
    whose last event lies ``idle.period + interval`` or more behind is
    forgotten before its event is taken. Siddhi's purge, and the
    engine's, may forget a key from ``idle.period`` on: inside that
    band the answer is not specified (docs/partition_window.md), and
    this takes its upper end."""

    def __init__(self, q: ast.Query, capacity: int):
        inp = q.input
        self.filters = [_compile_scalar(f) for f in inp.filters]
        self.cap = capacity
        self.key = dict(q.partition_with)[inp.stream_id]
        purge = q.partition_purge
        self.forget_after = sum(purge) if purge else None
        self._lift_query(q)
        self.rows: Dict[Any, deque] = {}
        self.last: Dict[Any, int] = {}

    def on_event(self, ev, ts, emit):
        for f in self.filters:
            if not f(ev):
                return
        key = ev[self.key]
        if (
            self.forget_after is not None and key in self.last
            and ts - self.last[key] >= self.forget_after
        ):
            del self.rows[key]
        self.last[key] = ts
        rows = self.rows.setdefault(key, deque(maxlen=self.cap))
        rows.append(ev)
        self._emit_row(ev, rows, ts, emit)


class _HopWindowGroupBy:
    """``#window.hop(ts, size, slide) select k, count() ... group by k
    [having ... windowMax(x) ...]``: a dict of per-group counts per pane
    of the slide. An event at or past a window's end closes it: a
    group's count is the sum of its panes', ``windowMax`` is taken over
    the window's groups, and the rows that pass ``having`` leave in
    group-key order, stamped with the window's last millisecond
    (compiler/hop_window.py states the semantics)."""

    def __init__(self, q: ast.Query, win: ast.Window):
        inp = q.input
        self.filters = [_compile_scalar(f) for f in inp.filters]
        ts_attr, size, slide = win.args
        self.ts_name = ts_attr.name
        self.slide = slide.ms
        self.n_panes = size.ms // slide.ms
        self.group_keys = [
            k.split(".", 1)[-1] for k in q.selector.group_by
        ]
        # each select item: (alias, fn over the group's last event), or
        # (alias, None) for count()
        self.items = []
        for it in q.selector.items:
            e = it.expr
            is_count = isinstance(e, ast.Call) and e.name == "count"
            self.items.append(
                (it.output_name(), None if is_count else _compile_scalar(e))
            )
        self.window_maxes = []  # (slot, fn over a row's env)
        self.having = None
        if q.selector.having is not None:
            self.having = _compile_scalar(
                self._lift(q.selector.having)
            )
        self.out = q.output_stream
        self.panes: Dict[int, Dict[Any, list]] = {}  # key -> [event, count]
        self.cur: Optional[int] = None

    def _lift(self, e):
        if isinstance(e, ast.Call) and e.name.lower() == "windowmax":
            slot = f"@wmax{len(self.window_maxes)}"
            self.window_maxes.append((slot, _compile_scalar(e.args[0])))
            return ast.Attr(slot)
        if isinstance(e, ast.Unary):
            return ast.Unary(e.op, self._lift(e.operand))
        if isinstance(e, ast.Binary):
            return ast.Binary(e.op, self._lift(e.left), self._lift(e.right))
        return e

    def _close(self, q: int, emit) -> None:
        """The window that ends where pane q starts."""
        groups: Dict[Any, list] = {}
        for p in range(q - self.n_panes, q):
            for key, (ev, cnt) in self.panes.get(p, {}).items():
                g = groups.setdefault(key, [ev, 0])
                g[1] += cnt
        self.panes.pop(q - self.n_panes, None)
        rows = []
        for key in sorted(groups):
            ev, cnt = groups[key]
            env = dict(ev)
            for alias, fn in self.items:
                env[alias] = cnt if fn is None else fn(ev)
            rows.append(env)
        for slot, fn in self.window_maxes:
            best = max(fn(env) for env in rows) if rows else None
            for env in rows:
                env[slot] = best
        for env in rows:
            if self.having is None or self.having(env):
                emit(self.out, q * self.slide - 1,
                     tuple(env[alias] for alias, _f in self.items))

    def on_event(self, ev, ts, emit):
        for f in self.filters:
            if not f(ev):
                return
        p = max(ev[self.ts_name] // self.slide,
                self.cur if self.cur is not None else -(2 ** 62))
        if self.cur is None:
            self.cur = p
        while self.cur < p:
            if not self.panes:  # a gap in the stream: nothing to close
                self.cur = p
                break
            self.cur += 1
            self._close(self.cur, emit)
        key = tuple(ev[k] for k in self.group_keys)
        g = self.panes.setdefault(p, {}).setdefault(key, [ev, 0])
        g[0] = ev
        g[1] += 1


class _WindowJoin:
    """``from L[f]#window.hop(ts, size, size) as l join
    R[f]#window.hop(ts, size, size) as r on l.k == r.k select l.k,
    count() group by l.k``: per tumbling window two dicts, the left and
    the right events of each key. An event of either side at or past a
    window's end closes it: one row for every key both sides touched, in
    key order, ``count()`` the key's pairs, stamped with the window's
    last millisecond (compiler/window_join.py states the semantics)."""

    def __init__(self, q: ast.Query):
        inp = q.input
        self.sides = []  # (filters, ts attribute, key attribute)
        for si in (inp.left, inp.right):
            ts_attr, size, slide = si.windows[0].args
            if size.ms != slide.ms:
                raise SiddhiQLError(
                    "baseline interpreter: a window join tumbles"
                )
            self.size = size.ms
            key = next(
                a for a in (inp.on.left, inp.on.right)
                if a.qualifier == si.ref_name
            )
            self.sides.append((
                [_compile_scalar(f) for f in si.filters],
                ts_attr.name, key.name,
            ))
        # each select item: True the key, False count()
        self.items = [
            not isinstance(it.expr, ast.Call) for it in q.selector.items
        ]
        self.out = q.output_stream
        self.panes: Dict[int, Tuple[Dict, Dict]] = {}
        self.cur: Optional[int] = None

    def _close(self, q: int, emit) -> None:
        left, right = self.panes.pop(q - 1, ({}, {}))
        for key in sorted(left):
            if key in right:
                pairs = left[key] * right[key]
                emit(self.out, q * self.size - 1,
                     tuple(key if k else pairs for k in self.items))

    def on_event(self, ev, ts, emit):
        for side, (filters, ts_name, key_name) in enumerate(self.sides):
            if all(f(ev) for f in filters):
                break
        else:
            return
        p = max(ev[ts_name] // self.size,
                self.cur if self.cur is not None else -(2 ** 62))
        if self.cur is None:
            self.cur = p
        while self.cur < p:
            if not self.panes:  # a gap in the stream: nothing to close
                self.cur = p
                break
            self.cur += 1
            self._close(self.cur, emit)
        counts = self.panes.setdefault(p, ({}, {}))[side]
        counts[ev[key_name]] = counts.get(ev[key_name], 0) + 1


class _SessionWindow:
    """``#window.session(gap[, key])``, ``#window.session(ts, gap,
    key)`` and the ``partition with`` form: a dict of open sessions,
    oldest ``last`` first. The clock is the newest time seen (an older
    event counts at the clock); a key's event less than ``gap`` after
    its session's last joins it, else the session is over; after every
    event each session with ``clock - last >= gap`` closes and emits
    one row, stamped ``last + gap - 1``, the event's closings in (stamp,
    key) order (compiler/session_window.py states the semantics).
    ``flush`` closes what is open."""

    def __init__(self, q: ast.Query, win: ast.Window):
        inp = q.input
        self.filters = [_compile_scalar(f) for f in inp.filters]
        args = win.args
        self.ts_name = None
        if len(args) == 3:
            self.ts_name, args = args[0].name, args[1:]
        gap = args[0]
        self.gap = gap.ms if isinstance(gap, ast.TimeLiteral) else gap.value
        self.key_name = (
            args[1].name if len(args) == 2
            else dict(q.partition_with).get(inp.stream_id)
        )
        # each select item: ('key', None) | (aggregate, fn of the event)
        self.items = []
        for it in q.selector.items:
            e = it.expr
            if isinstance(e, ast.Call):
                arg = e.args[0] if e.args else None
                on_clock = (
                    isinstance(arg, ast.Attr) and arg.name == self.ts_name
                )
                self.items.append((
                    e.name.lower() + ("@clock" if on_clock else ""),
                    _compile_scalar(arg) if arg is not None else None,
                ))
            else:
                self.items.append(("key", None))
        self.out = q.output_stream
        self.open: Dict[Any, list] = {}  # key -> [first, last, values]
        self.clock: Optional[int] = None

    def _row(self, key, s):
        first, last, vals = s
        row = []
        for (kind, _fn), v in zip(self.items, vals):
            if kind == "key":
                row.append(key)
            elif kind == "count":
                row.append(len(v))
            elif kind == "sum":
                row.append(sum(v))
            elif kind == "avg":
                row.append(sum(v) / len(v))
            elif kind in ("min@clock", "max@clock"):
                row.append(first if kind[:3] == "min" else last)
            else:
                row.append(min(v) if kind == "min" else max(v))
        return last + self.gap - 1, key, tuple(row)

    def on_event(self, ev, ts, emit):
        for f in self.filters:
            if not f(ev):
                return
        t = ev[self.ts_name] if self.ts_name is not None else ts
        if self.clock is not None:
            t = max(t, self.clock)
        self.clock = t
        key = ev[self.key_name] if self.key_name is not None else None
        over = []
        s = self.open.pop(key, None)
        if s is not None and t - s[1] >= self.gap:
            over.append(self._row(key, s))
            s = None
        if s is None:
            s = [t, t, [[] for _ in self.items]]
        s[1] = t
        for (_kind, fn), v in zip(self.items, s[2]):
            v.append(fn(ev) if fn is not None else 1)
        self.open[key] = s  # the newest ``last`` is at the end
        while self.open:
            k = next(iter(self.open))
            if t - self.open[k][1] < self.gap:
                break
            over.append(self._row(k, self.open.pop(k)))
        for stamp, _k, row in sorted(over, key=lambda r: r[:2]):
            emit(self.out, stamp, row)

    def flush(self, emit):
        over = [self._row(k, s) for k, s in self.open.items()]
        self.open = {}
        for stamp, _k, row in sorted(over, key=lambda r: r[:2]):
            emit(self.out, stamp, row)


class BaselineEngine:
    """Per-event interpreter for the benchmark CQL surface: stateless
    filters, every-chains with within, strict sequences (quantifiers +
    absence), sliding length-window group-by aggregation, the per-key
    length window of a partition (with ``@purge``), the processing-time
    window (``#window.time`` with group-by; ``time_ring_capacity``
    gives it the engine's ring where a test fills it), the hop
    window with its per-window maximum, the session window (both
    spellings and ``partition with``; ``flush()`` closes what is open)
    and the tumbling-window join
    (over one stream: the interpreter routes no streams).
    Multi-query plans fan each event through every query, one runtime
    per query (the reference's operator design)."""

    def __init__(self, cql: str, field_names: List[str],
                 time_ring_capacity: Optional[int] = None):
        plan = parse_plan(cql)
        self.field_names = list(field_names)
        self.handlers = []
        for q in plan.queries:
            inp = q.input
            if isinstance(inp, ast.PatternInput):
                if inp.kind == "sequence":
                    self.handlers.append(_Sequence(q))
                else:
                    self.handlers.append(_Chain(q))
            elif isinstance(inp, ast.StreamInput):
                if inp.windows:
                    win = inp.windows[0]
                    if win.name == "hop":
                        self.handlers.append(_HopWindowGroupBy(q, win))
                        continue
                    if win.name == "session":
                        self.handlers.append(_SessionWindow(q, win))
                        continue
                    if win.name == "time":
                        self.handlers.append(_TimeWindowGroupBy(
                            q, _span_ms(win.args[0]), time_ring_capacity))
                        continue
                    if win.name != "length":
                        raise SiddhiQLError(
                            "baseline interpreter: only length, time, "
                            "hop and session windows"
                        )
                    cap = win.args[0]
                    assert isinstance(cap, ast.Literal)
                    self.handlers.append(
                        (_PerKeyLengthWindow if q.partition_with
                         else _LengthWindowGroupBy)(q, int(cap.value))
                    )
                else:
                    self.handlers.append(_Select(q))
            elif isinstance(inp, ast.JoinInput) and all(
                si.windows and si.windows[0].name == "hop"
                for si in (inp.left, inp.right)
            ):
                self.handlers.append(_WindowJoin(q))
            else:
                raise SiddhiQLError(
                    "baseline interpreter: unsupported input"
                )
        self.emitted = 0

    def _emit(self, out, ts, row):
        self.emitted += 1

    def process(self, ev: Dict[str, Any], ts: int) -> None:
        emit = self._emit
        for h in self.handlers:
            h.on_event(ev, ts, emit)

    def flush(self) -> None:
        """End of stream: the handlers that hold rows back emit them."""
        for h in self.handlers:
            if hasattr(h, "flush"):
                h.flush(self._emit)

    def run_columns(self, cols: Dict[str, list], ts_list: list) -> int:
        """Replay columnar data per event (zip to dicts on the fly)."""
        names = list(cols)
        seqs = [cols[n] for n in names]
        process = self.process
        n = 0
        for ts, vals in zip(ts_list, zip(*seqs)):
            process(dict(zip(names, vals)), ts)
            n += 1
        return n
