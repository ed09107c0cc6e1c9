"""fstrace: static thread-ownership & lock-discipline analysis.

PR 12's control plane made the engine genuinely concurrent — a REST
service thread, the run-loop thread, the supervisor restart path, the
drain fetch worker and async staging all touch ``Job`` — but its core
safety rule ("state mutates only via control events applied on the
run-loop thread") was a convention. Two shipped bugs were exactly this
class: the PR 7 ApiVersions backoff sleeping under the client lock, and
the restore-aliasing race the fault tests caught. This pass makes the
convention machine-checked.

Four rules (registry: findings.py; reference: docs/static_analysis.md):

* **FST201** — state owned by the run-loop thread (written by code
  reachable from a ``# fst:thread-root name=run-loop`` entry point) is
  written from a differently-named root without going through the
  control queue.
* **FST202** — a mutable container attribute reached from >= 2 thread
  roots (at least one write) that is neither lock-guarded at every
  access nor annotated ``# fst:threadsafe <reason>``.
* **FST203** — a blocking call (sleep, socket recv/accept, queue.get,
  jitted dispatch, block_until_ready) while a lock is held. Purely
  lexical: needs no root annotations.
* **FST204** — check-then-act on an attribute that is lock-guarded
  elsewhere in its class, from a branch not holding the lock.

Annotations (reasons are mandatory, like ``fst:ephemeral`` — a bare
mark is itself a finding):

* ``# fst:thread-root name=<thread>`` on (or directly above) a ``def``
  declares a thread entry point. All code conservatively reachable
  from it runs on that named thread; several defs may share a name
  (every REST handler is ``service``). ``run-loop`` is the ownership
  domain FST201 enforces.
* ``# fst:threadsafe <reason>`` on (or above) an attribute assignment
  (conventionally its ``__init__`` declaration) declares the
  attribute safe to share, and WHY (single-writer + GIL-atomic
  snapshot reads, an internal lock, ...). Also accepted on a specific
  access line, and on an ``if`` line for FST204.
* ``# fst:blocking-ok <reason>`` on (or above) a blocking call line —
  or on the ``def`` line to cover a whole function — accepts a
  deliberate blocking call under a lock (the kafka.py negotiation
  loop's constant short sleep is the canonical, documented case).

Dataflow is deliberately conservative and NAME-BASED, like the rest of
fstlint: ``self.x`` resolves within the class (and textual bases);
``obj.method()`` resolves by method name when at most a handful of
indexed classes define it (ambiguous names drop the edge); attribute
ownership joins on the terminal attribute name (``job._plans`` and
``self._plans`` are the same state — the distinctive ``_plans``-style
names this repo uses make cross-type collisions unlikely, and a
collision errs loud, not silent). Lock context is lexical (``with
<lock>:`` where the context expression's terminal name contains
"lock"), extended by the repo's ``*_locked`` naming convention and by
helpers whose every same-module call site already holds a lock.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .findings import Finding
from .rules import ModuleInfo, scan_module

_ROOT_MARK = re.compile(r"#\s*fst:thread-root\s+name=([\w.-]+)")
_THREADSAFE_MARK = re.compile(r"#\s*fst:threadsafe\b[ \t]*(.*)")
_BLOCKING_OK_MARK = re.compile(r"#\s*fst:blocking-ok\b[ \t]*(.*)")
_RUNLOOP_ONLY_MARK = re.compile(r"#\s*fst:runloop-only\b")

# mutating container/attribute methods: `x.attr.append(...)` is a
# WRITE to attr (the structure mutates in place)
_MUTATORS = {
    "append", "extend", "insert", "remove", "pop", "popleft",
    "appendleft", "clear", "update", "setdefault", "add", "discard",
    "sort", "reverse",
}

# container constructors/literals: attributes declared with these in
# __init__ are "mutable shared structure" for FST202 (scalars are
# GIL-atomic to read and excluded — torn reads are not a CPython
# hazard; racy *iteration/mutation* of containers is)
_CONTAINER_CALLS = {
    "dict", "list", "set", "deque", "defaultdict", "OrderedDict",
    "Counter",
}

# blocking calls for FST203, by terminal name of the called attr/name
_BLOCKING_TAILS = {"sleep", "recv", "recv_into", "accept",
                   "block_until_ready"}

# resolve obj.method() by name only when at most this many indexed
# classes define the method — past that the name is too generic and
# the edge is dropped (documented conservatism)
_MAX_NAME_CANDIDATES = 4


def _tail(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _is_lockish(expr: ast.AST) -> bool:
    """Context-manager expression that looks like a lock acquire."""
    t = _tail(expr.func) if isinstance(expr, ast.Call) else _tail(expr)
    return t is not None and "lock" in t.lower()


def _line_mark(
    lines: Sequence[str], lineno: int, mark: re.Pattern
) -> Optional[str]:
    """Payload of an annotation on `lineno` or the line above; None
    when absent, '' when bare."""
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = mark.search(lines[ln - 1])
            if m:
                return (m.group(1) or "").strip()
    return None


def _hint_match(recv: Optional[str], cls_name: str) -> bool:
    """Receiver-name <-> class-name plausibility for by-name call
    resolution: `service.job.metrics()` may target class Job (or
    ShardedJob), `self.control.push()` targets ControlQueueSource —
    while `b.build()` targets nothing nameable and the edge drops.
    Purely lexical (underscores stripped, containment either way); the
    conservatism errs toward DROPPING edges, which under-approximates
    reach — rules that fire are then high-confidence, and the
    run-loop's own surface is covered by `self` resolution anyway."""
    if recv is None:
        return False
    r = recv.lower().replace("_", "")
    c = cls_name.lower().replace("_", "")
    return len(r) >= 3 and (r in c or c in r)


@dataclass(frozen=True)
class _Access:
    attr: str
    write: bool
    line: int
    locked: bool
    cls: Optional[str]  # class whose method performed the access
    on_self: bool
    recv: Optional[str] = None  # terminal receiver name (None = self)


@dataclass
class _Func:
    key: Tuple[str, Optional[str], str]  # (path, class, name)
    node: ast.AST
    path: str
    cls: Optional[str]
    is_property: bool = False
    root_name: Optional[str] = None
    lock_named: bool = False  # *_locked convention
    runloop_only: bool = False  # fst:runloop-only walk boundary
    blocking_ok: Optional[str] = None  # def-level fst:blocking-ok
    accesses: List[_Access] = field(default_factory=list)
    # call edges: (kind, name, locked, recv) — kind 'name' = module-
    # level function, 'self' = method on own class, 'attr' = by-name
    # resolution gated on the receiver hint
    calls: List[Tuple[str, str, bool, Optional[str]]] = field(
        default_factory=list
    )
    # lexical blocking calls: (line, what, locked)
    blocking: List[Tuple[int, str, bool]] = field(default_factory=list)
    # check-then-act candidates: (line, attr, body_write_line)
    check_act: List[Tuple[int, str]] = field(default_factory=list)
    # call sites OF this function (filled in a second pass): each True
    # when the site itself held a lock
    called_from_locked: List[bool] = field(default_factory=list)


@dataclass
class _Module:
    path: str
    lines: List[str]
    info: ModuleInfo
    funcs: Dict[Tuple[Optional[str], str], _Func] = field(
        default_factory=dict
    )
    bases: Dict[str, List[str]] = field(default_factory=dict)
    lock_attrs: Set[str] = field(default_factory=set)
    container_attrs: Set[str] = field(default_factory=set)
    # attr -> (reason, line): fst:threadsafe declarations
    threadsafe: Dict[str, Tuple[str, int]] = field(default_factory=dict)
    bare_threadsafe: List[int] = field(default_factory=list)
    bare_blocking_ok: List[int] = field(default_factory=list)


class _FuncVisitor:
    """Single linear walk of one function body collecting accesses,
    call edges, blocking calls and check-then-act shapes, with lexical
    lock-context tracking."""

    def __init__(self, fn: _Func, mod: _Module):
        self.fn = fn
        self.mod = mod

    def run(self) -> None:
        body = getattr(self.fn.node, "body", [])
        self._block(body, locked=self.fn.lock_named)

    # -- statement walk ----------------------------------------------------
    def _block(self, body: Iterable[ast.stmt], locked: bool) -> None:
        for st in body:
            if isinstance(
                st, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                # nested defs get their own _Func (closures included via
                # index construction); their bodies run later
                continue
            self._statement(st, locked)
            if isinstance(st, ast.With):
                inner = locked or any(
                    _is_lockish(it.context_expr) for it in st.items
                )
                self._block(st.body, inner)
                continue
            if isinstance(st, ast.If):
                self._check_then_act(st, locked)
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(st, attr, None)
                if sub:
                    self._block(sub, locked)
            for h in getattr(st, "handlers", ()):
                self._block(h.body, locked)

    def _statement(self, st: ast.stmt, locked: bool) -> None:
        # writes: assignment targets (incl. subscript stores on an
        # attribute) and aug-assigns
        write_ids: Set[int] = set()
        targets: List[ast.AST] = []
        if isinstance(st, ast.Assign):
            targets = list(st.targets)
        elif isinstance(st, (ast.AugAssign, ast.AnnAssign)):
            targets = [st.target]
        elif isinstance(st, ast.Delete):
            targets = list(st.targets)
        elif isinstance(st, ast.For):
            targets = [st.target]
        flat: List[ast.AST] = []
        for t in targets:
            flat.extend(
                t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
            )
        for t in flat:
            node = t
            if isinstance(node, ast.Subscript):
                node = node.value  # x.attr[k] = v writes attr
            if isinstance(node, ast.Attribute):
                self._record(node, True, locked)
                write_ids.add(id(node))
        # everything attached to this statement (header exprs only for
        # compound statements — nested blocks re-walked above)
        for f_name, value in ast.iter_fields(st):
            if f_name in ("body", "orelse", "finalbody", "handlers"):
                continue
            nodes = (
                [value]
                if isinstance(value, ast.AST)
                else [v for v in value if isinstance(v, ast.AST)]
                if isinstance(value, list)
                else []
            )
            for sub in nodes:
                for node in ast.walk(sub):
                    self._expr(node, locked, write_ids)

    def _expr(self, node: ast.AST, locked: bool, write_ids: Set[int]):
        if isinstance(node, ast.Call):
            self._call(node, locked)
        if isinstance(node, ast.Attribute) and id(node) not in write_ids:
            if isinstance(getattr(node, "ctx", None), ast.Load):
                self._record(node, False, locked)

    # -- recording ----------------------------------------------------------
    def _record(self, node: ast.Attribute, write: bool, locked: bool):
        on_self = (
            isinstance(node.value, ast.Name) and node.value.id == "self"
        )
        # line-level fst:threadsafe accepts one specific access
        if _line_mark(
            self.mod.lines, node.lineno, _THREADSAFE_MARK
        ):
            return
        self.fn.accesses.append(
            _Access(
                node.attr, write, node.lineno, locked,
                self.fn.cls, on_self,
                None if on_self else _tail(node.value),
            )
        )

    def _call(self, node: ast.Call, locked: bool) -> None:
        fn = self.fn
        f = node.func
        # blocking-call classification (FST203)
        tail = _tail(f)
        what = None
        if tail in _BLOCKING_TAILS:
            what = f"{tail}()"
        elif tail == "get" and isinstance(f, ast.Attribute):
            recv = _tail(f.value)
            if recv is not None and (
                recv.lower().endswith(("queue", "_q")) or recv == "q"
            ):
                what = f"{recv}.get()"
        elif tail is not None and tail in self.mod.info.jitted:
            what = f"jitted call {tail!r}"
        if what is not None:
            ok = _line_mark(
                self.mod.lines, node.lineno, _BLOCKING_OK_MARK
            )
            if ok is None and fn.blocking_ok is None:
                fn.blocking.append((node.lineno, what, locked))
            elif ok == "":
                self.mod.bare_blocking_ok.append(node.lineno)
        # call edges
        if isinstance(f, ast.Name):
            fn.calls.append(("name", f.id, locked, None))
        elif isinstance(f, ast.Attribute):
            if isinstance(f.value, ast.Name) and f.value.id == "self":
                fn.calls.append(("self", f.attr, locked, None))
            else:
                fn.calls.append(
                    ("attr", f.attr, locked, _tail(f.value))
                )
        # mutating method on an attribute: x.attr.append(...)
        if (
            isinstance(f, ast.Attribute)
            and f.attr in _MUTATORS
            and isinstance(f.value, ast.Attribute)
        ):
            self._record(f.value, True, locked)

    # -- FST204 shape -------------------------------------------------------
    def _check_then_act(self, st: ast.If, locked: bool) -> None:
        if locked:
            return
        if _line_mark(self.mod.lines, st.lineno, _THREADSAFE_MARK):
            return
        test_attrs = {
            n.attr
            for n in ast.walk(st.test)
            if isinstance(n, ast.Attribute)
            and isinstance(n.value, ast.Name)
            and n.value.id == "self"
        }
        if not test_attrs:
            return
        body_writes: Set[str] = set()
        for sub in st.body:
            if isinstance(sub, ast.With) and any(
                _is_lockish(it.context_expr) for it in sub.items
            ):
                continue  # the act re-acquires the lock: fine
            for n in ast.walk(sub):
                t = None
                if isinstance(n, (ast.Assign, ast.AugAssign)):
                    tgts = (
                        n.targets
                        if isinstance(n, ast.Assign)
                        else [n.target]
                    )
                    for tg in tgts:
                        if isinstance(tg, ast.Subscript):
                            tg = tg.value
                        if (
                            isinstance(tg, ast.Attribute)
                            and isinstance(tg.value, ast.Name)
                            and tg.value.id == "self"
                        ):
                            t = tg.attr
                            if t in test_attrs:
                                body_writes.add(t)
                if isinstance(n, ast.Call) and isinstance(
                    n.func, ast.Attribute
                ):
                    v = n.func.value
                    if (
                        n.func.attr in _MUTATORS
                        and isinstance(v, ast.Attribute)
                        and isinstance(v.value, ast.Name)
                        and v.value.id == "self"
                        and v.attr in test_attrs
                    ):
                        body_writes.add(v.attr)
        for attr in sorted(body_writes):
            self.fn.check_act.append((st.lineno, attr))


# --------------------------------------------------------------------------
# index construction
# --------------------------------------------------------------------------


def _index_module(path: str, source: str) -> Optional[_Module]:
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError:
        return None  # fstlint reports FST000 separately
    lines = source.splitlines()
    mod = _Module(path, lines, scan_module(tree))

    def add_func(node, cls: Optional[str]):
        is_prop = any(
            _tail(d) == "property" for d in node.decorator_list
        )
        fn = _Func(
            key=(path, cls, node.name),
            node=node, path=path, cls=cls,
            is_property=is_prop,
            lock_named=node.name.endswith("_locked"),
        )
        root = _line_mark(lines, node.lineno, _ROOT_MARK)
        if root is None and node.decorator_list:
            first = min(d.lineno for d in node.decorator_list)
            root = _line_mark(lines, first - 1, _ROOT_MARK)
        fn.root_name = root or None
        for ln in (node.lineno, node.lineno - 1):
            if 1 <= ln <= len(lines) and _RUNLOOP_ONLY_MARK.search(
                lines[ln - 1]
            ):
                fn.runloop_only = True
        ok = _line_mark(lines, node.lineno, _BLOCKING_OK_MARK)
        if ok == "":
            mod.bare_blocking_ok.append(node.lineno)
        elif ok:
            fn.blocking_ok = ok
        mod.funcs[(cls, node.name)] = fn
        # nested defs (closures, handler classes in __init__) are
        # indexed under the same class scope so self-resolution inside
        # them still lands on the enclosing semantics when names match
        for sub in ast.walk(node):
            if sub is not node and isinstance(
                sub, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                if (cls, sub.name) not in mod.funcs:
                    add_func(sub, cls)

    for st in tree.body:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
            add_func(st, None)
        elif isinstance(st, ast.ClassDef):
            mod.bases[st.name] = [
                b for b in map(_tail, st.bases) if b is not None
            ]
            for sub in st.body:
                if isinstance(
                    sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    add_func(sub, st.name)
                elif isinstance(sub, ast.ClassDef):
                    for s2 in sub.body:
                        if isinstance(
                            s2, (ast.FunctionDef, ast.AsyncFunctionDef)
                        ):
                            add_func(s2, sub.name)

    # __init__ declarations: lock attrs, container attrs, fst:threadsafe
    for (cls, name), fn in list(mod.funcs.items()):
        if cls is None:
            continue
        for st in ast.walk(fn.node):
            if not isinstance(st, (ast.Assign, ast.AnnAssign)):
                continue
            tgts = (
                st.targets if isinstance(st, ast.Assign) else [st.target]
            )
            for t in tgts:
                if not (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    continue
                v = st.value
                vt = _tail(v.func) if isinstance(v, ast.Call) else None
                if vt in ("Lock", "RLock"):
                    mod.lock_attrs.add(t.attr)
                if name == "__init__":
                    if isinstance(
                        v, (ast.Dict, ast.List, ast.Set, ast.ListComp,
                            ast.DictComp, ast.SetComp)
                    ) or vt in _CONTAINER_CALLS:
                        mod.container_attrs.add(t.attr)
                mark = _line_mark(lines, st.lineno, _THREADSAFE_MARK)
                if mark == "":
                    mod.bare_threadsafe.append(st.lineno)
                elif mark:
                    mod.threadsafe.setdefault(
                        t.attr, (mark, st.lineno)
                    )

    for fn in mod.funcs.values():
        _FuncVisitor(fn, mod).run()
    return mod


# --------------------------------------------------------------------------
# the whole-set analysis
# --------------------------------------------------------------------------


class ThreadAnalysis:
    def __init__(self, sources: Dict[str, str]):
        self.mods: Dict[str, _Module] = {}
        for path in sorted(sources):
            m = _index_module(path, sources[path])
            if m is not None:
                self.mods[path] = m
        # by-name method/property tables for conservative resolution
        self.methods: Dict[str, List[_Func]] = {}
        self.props: Dict[str, List[_Func]] = {}
        self.lock_attrs: Set[str] = set()
        self.container_attrs: Set[str] = set()
        self.threadsafe: Dict[str, Tuple[str, str, int]] = {}
        for m in self.mods.values():
            self.lock_attrs |= m.lock_attrs
            self.container_attrs |= m.container_attrs
            for attr, (reason, line) in m.threadsafe.items():
                self.threadsafe.setdefault(attr, (reason, m.path, line))
            for (cls, name), fn in m.funcs.items():
                if cls is not None:
                    (self.props if fn.is_property else self.methods
                     ).setdefault(name, []).append(fn)

    # -- call-graph resolution ---------------------------------------------
    def _resolve(
        self, fn: _Func, kind: str, name: str, recv: Optional[str]
    ) -> List[_Func]:
        mod = self.mods[fn.path]
        if kind == "name":
            hit = mod.funcs.get((None, name))
            return [hit] if hit is not None else []
        if kind == "self":
            cls = fn.cls
            seen = set()
            while cls is not None and cls not in seen:
                seen.add(cls)
                hit = mod.funcs.get((cls, name))
                if hit is not None:
                    return [hit]
                bases = mod.bases.get(cls, [])
                cls = bases[0] if bases else None
            return []
        cands = [
            c
            for c in self.methods.get(name, [])
            if c.cls is not None and _hint_match(recv, c.cls)
        ]
        if 0 < len(cands) <= _MAX_NAME_CANDIDATES:
            return cands
        return []

    def _reach(self, roots: List[_Func], thread: str) -> List[_Func]:
        out: List[_Func] = []
        seen: Set[Tuple[str, Optional[str], str]] = set()
        stack = list(roots)
        boundary = thread != "run-loop"
        while stack:
            fn = stack.pop()
            if fn.key in seen:
                continue
            if boundary and fn.runloop_only:
                continue  # declared run-loop-private surface
            seen.add(fn.key)
            out.append(fn)
            edges = list(fn.calls)
            # property loads count as calls (plan_ids, finished, ...)
            for acc in fn.accesses:
                edges.append(
                    ("attr", acc.attr, acc.locked, acc.recv)
                )
            for kind, name, _locked, recv in edges:
                for nxt in self._resolve(fn, kind, name, recv):
                    if nxt.key not in seen:
                        stack.append(nxt)
                if kind == "attr":
                    for nxt in self.props.get(name, []):
                        if (
                            nxt.key not in seen
                            and nxt.cls is not None
                            and _hint_match(recv, nxt.cls)
                        ):
                            stack.append(nxt)
        return out

    # -- rules --------------------------------------------------------------
    def run(self) -> List[Finding]:
        findings: List[Finding] = []
        findings.extend(self._bare_marks())
        per_thread = self._per_thread_accesses()
        findings.extend(self._fst201(per_thread))
        findings.extend(self._fst202(per_thread))
        findings.extend(self._fst203())
        findings.extend(self._fst204())
        return findings

    def _bare_marks(self) -> List[Finding]:
        out = []
        for m in self.mods.values():
            for ln in m.bare_threadsafe:
                out.append(
                    Finding(
                        m.path, ln, "FST202",
                        "`# fst:threadsafe` without a reason — explain "
                        "WHY this state is safe to share (single "
                        "writer + GIL-atomic snapshot reads, an "
                        "internal lock, ...); like baseline "
                        "suppressions, the reason is mandatory",
                    )
                )
            for ln in m.bare_blocking_ok:
                out.append(
                    Finding(
                        m.path, ln, "FST203",
                        "`# fst:blocking-ok` without a reason — "
                        "explain why blocking while holding the lock "
                        "is acceptable here; the reason is mandatory",
                    )
                )
        return out

    def _roots_by_name(self) -> Dict[str, List[_Func]]:
        roots: Dict[str, List[_Func]] = {}
        for m in self.mods.values():
            for fn in m.funcs.values():
                if fn.root_name:
                    roots.setdefault(fn.root_name, []).append(fn)
        return roots

    def _per_thread_accesses(
        self,
    ) -> Dict[str, List[Tuple[_Func, _Access]]]:
        out: Dict[str, List[Tuple[_Func, _Access]]] = {}
        for name, roots in self._roots_by_name().items():
            pairs: List[Tuple[_Func, _Access]] = []
            for fn in self._reach(roots, name):
                for acc in fn.accesses:
                    pairs.append((fn, acc))
            out[name] = pairs
        return out

    def _is_threadsafe(self, attr: str) -> bool:
        return attr in self.threadsafe

    def _fst201(self, per_thread) -> List[Finding]:
        # ownership covers the run-loop's LOCK-FREE writes: state the
        # run loop mutates under a lock has a synchronization story
        # already (FST202 audits its completeness); the ownership
        # discipline exists for the lock-free single-writer state
        owned: Set[str] = set()
        for fn, acc in per_thread.get("run-loop", ()):
            if (
                acc.write
                and not acc.locked
                and acc.attr not in self.lock_attrs
            ):
                owned.add(acc.attr)
        out: List[Finding] = []
        seen: Set[Tuple[str, int, str]] = set()
        for thread, pairs in per_thread.items():
            if thread == "run-loop":
                continue
            for fn, acc in pairs:
                if not acc.write or acc.attr not in owned:
                    continue
                if acc.locked:
                    continue  # synchronized write: FST202's domain
                if self._is_threadsafe(acc.attr):
                    continue
                key = (fn.path, acc.line, acc.attr)
                if key in seen:
                    continue
                seen.add(key)
                out.append(
                    Finding(
                        fn.path, acc.line, "FST201",
                        f"`{acc.attr}` is run-loop-owned state "
                        f"(written by code reachable from a run-loop "
                        f"thread root) but is written here from the "
                        f"{thread!r} thread root — route the mutation "
                        "through the control queue (control events "
                        "apply at micro-batch boundaries) or annotate "
                        "the attribute `# fst:threadsafe <reason>`",
                    )
                )
        return out

    def _fst202(self, per_thread) -> List[Finding]:
        # attr -> {thread: [(fn, acc)]}
        by_attr: Dict[str, Dict[str, List[Tuple[_Func, _Access]]]] = {}
        for thread, pairs in per_thread.items():
            for fn, acc in pairs:
                by_attr.setdefault(acc.attr, {}).setdefault(
                    thread, []
                ).append((fn, acc))
        # attrs whose off-thread UNLOCKED writes FST201 already reported
        # (same owned definition): don't double-report
        owned_written_off_thread: Set[str] = set()
        owned: Set[str] = set()
        for fn, acc in per_thread.get("run-loop", ()):
            if acc.write and not acc.locked:
                owned.add(acc.attr)
        for thread, pairs in per_thread.items():
            if thread == "run-loop":
                continue
            for fn, acc in pairs:
                if acc.write and not acc.locked and acc.attr in owned:
                    owned_written_off_thread.add(acc.attr)
        out: List[Finding] = []
        for attr, threads in sorted(by_attr.items()):
            if len(threads) < 2:
                continue
            if attr in self.lock_attrs:
                continue
            if attr not in self.container_attrs:
                continue
            if self._is_threadsafe(attr):
                continue
            if attr in owned_written_off_thread:
                continue  # FST201's finding; don't double-report
            accs = [a for pairs in threads.values() for a in pairs]
            # at least one UNLOCKED write: when every write holds the
            # lock, unlocked reads elsewhere are either the same
            # structure's snapshot pattern or (more often) a same-named
            # thread-confined value object — near-zero false positives
            # beats flagging the read-side of a locked writer
            if not any(
                acc.write and not acc.locked for _fn, acc in accs
            ):
                continue
            unguarded = [
                (fn, acc) for fn, acc in accs if not acc.locked
            ]
            if not unguarded:
                continue
            fn, acc = min(
                unguarded, key=lambda p: (p[1].line, p[0].path)
            )
            out.append(
                Finding(
                    fn.path, acc.line, "FST202",
                    f"mutable shared structure `{attr}` is reached "
                    f"from {len(threads)} thread roots "
                    f"({', '.join(sorted(threads))}) with writes, but "
                    "this access holds no lock — guard every access "
                    "with one lock, or annotate the declaration "
                    "`# fst:threadsafe <reason>` (reason mandatory)",
                )
            )
        return out

    def _fst203(self) -> List[Finding]:
        out: List[Finding] = []
        for m in self.mods.values():
            lock_ctx = self._lock_context_funcs(m)
            for fn in m.funcs.values():
                in_ctx = fn.key in lock_ctx
                for line, what, locked in fn.blocking:
                    if locked or in_ctx:
                        out.append(
                            Finding(
                                m.path, line, "FST203",
                                f"blocking {what} while a lock is "
                                "held — every other thread queuing on "
                                "the lock waits out the block (the "
                                "ApiVersions backoff-under-lock bug "
                                "class); move the block outside the "
                                "lock or annotate `# fst:blocking-ok "
                                "<reason>`",
                            )
                        )
        return out

    def _lock_context_funcs(self, m: _Module) -> Set[Tuple]:
        """Functions that always run with a lock held: *_locked names,
        plus helpers whose every same-module call site holds one
        (iterated to a fixpoint)."""
        ctx: Set[Tuple] = {
            fn.key for fn in m.funcs.values() if fn.lock_named
        }
        for _ in range(len(m.funcs)):
            changed = False
            # call sites per callee name (self/name edges only — the
            # by-name cross-class resolution is too coarse here)
            sites: Dict[Tuple, List[bool]] = {}
            for fn in m.funcs.values():
                fn_ctx = fn.key in ctx
                for kind, name, locked, _recv in fn.calls:
                    if kind == "name":
                        callee = m.funcs.get((None, name))
                    elif kind == "self" and fn.cls is not None:
                        callee = m.funcs.get((fn.cls, name))
                    else:
                        continue
                    if callee is None:
                        continue
                    sites.setdefault(callee.key, []).append(
                        locked or fn_ctx
                    )
            for key, flags in sites.items():
                if key not in ctx and flags and all(flags):
                    ctx.add(key)
                    changed = True
            if not changed:
                break
        return ctx

    def _fst204(self) -> List[Finding]:
        out: List[Finding] = []
        for m in self.mods.values():
            lock_ctx = self._lock_context_funcs(m)
            # per class: attrs ever accessed under a lock
            guarded: Dict[str, Set[str]] = {}
            for fn in m.funcs.values():
                if fn.cls is None:
                    continue
                in_ctx = fn.key in lock_ctx
                for acc in fn.accesses:
                    if acc.on_self and (acc.locked or in_ctx):
                        guarded.setdefault(fn.cls, set()).add(acc.attr)
            for fn in m.funcs.values():
                if fn.cls is None or fn.key in lock_ctx:
                    continue
                g = guarded.get(fn.cls, set())
                for line, attr in fn.check_act:
                    if attr in g and attr not in m.lock_attrs:
                        out.append(
                            Finding(
                                m.path, line, "FST204",
                                f"check-then-act on `{attr}` outside "
                                "the lock that guards it elsewhere in "
                                f"{fn.cls}: the checked condition can "
                                "be stale by the time the mutation "
                                "lands — hold the lock across the "
                                "test and the act (or annotate the "
                                "`if` line `# fst:threadsafe "
                                "<reason>`)",
                            )
                        )
        return out


def analyze_sources(sources: Dict[str, str]) -> List[Finding]:
    """FST201-204 over a set of modules (path -> source). Paths should
    be repo-root-relative; findings carry them verbatim."""
    return sorted(set(ThreadAnalysis(sources).run()))
