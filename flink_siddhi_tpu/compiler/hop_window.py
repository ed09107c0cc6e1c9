"""``#window.hop(tsAttribute, size, slide)``: a hopping (sliding by
steps) window over an event-time attribute, as SQL's ``HOP``.

A window of ``size`` that slides by ``slide`` is ``size / slide`` panes
of the slide; a group's count over a closing window is the sum of its
panes' counts. The device keeps one row of counts per pane in a ring of
``size / slide + runway`` rows by ``G`` group slots. One micro-batch

* **folds** its events into the ring with one scatter-add
  (``fst.hop_fold``): the cost is the events it sees, not the panes x
  groups grid that the tumbling machinery (``BatchWindowArtifact``)
  builds per step;
* **closes** every window whose end an event has reached
  (``fst.hop_max``): sums the window's rows, reduces each ``windowMax``
  of the ``having`` clause over the window's live groups, and packs
  only the rows that pass into a buffer of ``EMIT_ROWS`` rows, ordered
  by group key. The pane that leaves is zeroed, so its ring row is
  clean for the pane that takes it.

Windows end at multiples of the slide on the attribute's own clock
(epoch-aligned for every slide that divides a day: the attribute rides
the job's clock from ``runtime.tape.time_origin``). A window closes
when an event that passes the filters arrives at or after its end; a
window that holds no event emits nothing; nothing is flushed at the end
of the stream (no timer fires on the device). A row is stamped with its
window's last millisecond. An event older than the newest pane counts
in the newest pane: nothing is shed.

Group slots are host-interned codes (``schema/encoders.py``) that
**expire**: a key whose last pane has left its last window gives its
slot to the next new key, so ``G`` is the keys a window holds. The
slot's key values live on the device (``key<j>``), written by the
events themselves, so a row carries the key its slot had when its
window closed.
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
from ..runtime.tape import DAY_MS, time_key
from ..schema.encoders import GroupEncoder
from ..schema.types import AttributeType
from .expr import ColumnEnv, ResolvedAttr, compile_expr
from .output import OutputField, OutputSchema
from .window import (
    _AggCollector,
    _SlotResolver,
    _bucket,
    _group_encoding,
    _identity,
)

_I32_MIN = -(2 ** 31)
_I32_MAX = 2 ** 31 - 1
# rows one micro-batch can emit: the rows that pass ``having`` of every
# window it closes. More are dropped and counted
# (``faults.emissions_dropped``)
EMIT_ROWS = 4096


def pane_clock(mask, ts, slide: int, state: Dict):
    """Where a micro-batch stands on a pane ring's clock: each event's
    pane of ``slide`` ms (a late event: the newest pane), whether any
    event passed, the newest pane before the batch (its first event's,
    on a fresh ring) and the newest pane the batch reaches."""
    pane = jnp.floor_divide(ts.astype(jnp.int32), slide)
    any_ev = mask.any()
    first = jnp.min(jnp.where(mask, pane, _I32_MAX))
    cur0 = jnp.where(state["started"], state["cur"], first)
    pane = jnp.maximum(pane, cur0)  # a late event: the newest pane
    last = jnp.where(
        any_ev, jnp.max(jnp.where(mask, pane, _I32_MIN)), cur0
    )
    return pane, any_ev, cur0, last


def pane_rounds(carry: Dict, mask, pane, any_ev, last, runway: int,
                fold, close):
    """Fold a micro-batch into a pane ring and close every window it
    reached, ``runway`` panes a round: ``fold(c, hi)`` adds the events
    of panes ``c["folded"] < p <= hi``, ``close(q, c)`` closes the
    window that ends where pane ``q`` starts. A gap in the stream is
    skipped in one step once the ring is empty (``c["row_tot"]``)."""

    def round_(c):
        hi = jnp.minimum(last, c["cur"] + runway)
        c = fold(c, hi)
        c = lax.fori_loop(c["cur"] + 1, hi + 1, close, c)
        # a gap in the stream: with the ring empty, skip to the
        # pane before the next event's
        nxt = jnp.min(jnp.where(mask & (pane > hi), pane, _I32_MAX))
        skip = (c["row_tot"].sum() == 0) & (nxt != _I32_MAX)
        cur = jnp.where(skip, nxt - 1, hi)
        return {**c, "cur": cur, "folded": cur}

    return lax.while_loop(
        lambda c: any_ev & (c["folded"] < last), round_, carry
    )


@dataclass
class HopWindowArtifact:
    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    ts_key: str
    size_ms: int
    slide_ms: int
    aggs: List  # window._Agg, every one a count()
    code_key: Optional[str]
    encoder: Optional[GroupEncoder]
    key_cols: List[str]  # tape keys of the group-by columns
    key_types: List[AttributeType]
    proj_fns: List
    having_fn: Optional[Callable]
    # windowMax(x): (env slot, x over the closing window's env)
    window_maxes: List[Tuple[str, Callable]]
    group_slots: int
    # panes beyond the open one that a micro-batch may reach before it
    # has to close windows first (it then takes a second round)
    runway: int = 1
    output_mode: str = "buffered"

    @property
    def panes(self) -> int:
        return self.size_ms // self.slide_ms

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        return EMIT_ROWS

    def cost_info(self) -> Dict:
        info = {
            "name": self.name,
            "kind": "hop_window",
            "amplification": 1,
            "residency_ms": int(self.size_ms),
        }
        if self.encoder is not None:
            info["grows_with"] = "groups"
        return info

    def drain_counters(self, payload) -> Dict[str, int]:
        """What a drain delivered, for the job's counters: the rows, and
        the windows they close (a window's rows share its stamp and
        leave in one step, so in one drain)."""
        ts = getattr(payload, "ts", None)
        if ts is None:  # the row lane: (ts, row) pairs
            ts = [t for t, _row in payload]
        return {
            "hop.rows_emitted": len(ts),
            "hop.windows_closed": len(np.unique(ts)),
        }

    # -- state ---------------------------------------------------------------
    def _G(self) -> int:
        if self.encoder is None:
            return 1
        return _bucket(len(self.encoder), self.group_slots)

    def init_state(self) -> Dict:
        P, G = self.panes + self.runway, self._G()
        st = {
            "enabled": jnp.asarray(True),
            "cnt": jnp.zeros((P, G), jnp.int32),
            # events per ring row: an empty window costs nothing
            "row_tot": jnp.zeros(P, jnp.int32),
            "started": jnp.asarray(False),
            "cur": jnp.asarray(0, jnp.int32),  # the newest pane seen
        }
        for j, t in enumerate(self.key_types):
            st[f"key{j}"] = jnp.zeros(G, t.device_dtype)
        return st

    def grow_state(self, state: Dict) -> Dict:
        G = state["cnt"].shape[1]
        need = self._G()
        if need <= G:
            return state
        out = dict(state)
        for k, v in state.items():
            if k == "cnt" or k.startswith("key"):
                pad = [(0, 0)] * (v.ndim - 1) + [(0, need - G)]
                out[k] = jnp.pad(v, pad)
        return out

    # -- the step ------------------------------------------------------------
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        P, G = state["cnt"].shape
        W, R, V = self.panes, self.runway, EMIT_ROWS
        slide = self.slide_ms

        g = (
            env[self.code_key].astype(jnp.int32)
            if self.code_key is not None
            else jnp.zeros(E, jnp.int32)
        )
        pane, any_ev, cur0, last = pane_clock(
            mask, env[self.ts_key], slide, state
        )
        row = jnp.mod(pane, P)
        # the slot's key, written by the events themselves
        keys = [
            state[f"key{j}"].at[jnp.where(mask, g, G)].set(
                env[k].astype(state[f"key{j}"].dtype), mode="drop"
            )
            for j, k in enumerate(self.key_cols)
        ]

        carry = {
            "cnt": state["cnt"],
            "row_tot": state["row_tot"],
            "cur": cur0,
            "folded": cur0 - 1,
            "out_ts": jnp.zeros(V, jnp.int32),
            "out_cols": tuple(
                jnp.zeros(V, f.atype.device_dtype)
                for f in self.output_schema.fields
            ),
            "n_out": jnp.asarray(0, jnp.int32),  # rows due (may pass V)
        }

        @jax.named_scope("fst.hop_fold")
        def fold(c, hi):
            sel = mask & (pane > c["folded"]) & (pane <= hi)
            flat = jnp.where(sel, row * G + g, P * G)
            one = sel.astype(jnp.int32)
            cnt = c["cnt"].reshape(-1).at[flat].add(
                one, mode="drop"
            ).reshape(P, G)
            row_tot = c["row_tot"].at[jnp.where(sel, row, P)].add(
                one, mode="drop"
            )
            return {**c, "cnt": cnt, "row_tot": row_tot}

        def emit(q, c):
            rows = jnp.mod(q - W + jnp.arange(W, dtype=jnp.int32), P)
            cnt = c["cnt"][rows].sum(0)
            live = cnt > 0
            fenv: ColumnEnv = {
                agg.slot: cnt.astype(agg.out_type.device_dtype)
                for agg in self.aggs
            }
            for k, kv in zip(self.key_cols, keys):
                fenv[k] = kv
            cols = [
                jnp.broadcast_to(jnp.asarray(p(fenv)), (G,))
                for p in self.proj_fns
            ]
            out_mask = live
            if self.having_fn is not None:
                henv = dict(fenv)
                for f, col in zip(self.output_schema.fields, cols):
                    henv[f"@out:{f.name}"] = col
                for slot, fn in self.window_maxes:
                    v = jnp.broadcast_to(jnp.asarray(fn(henv)), (G,))
                    henv[slot] = jnp.max(
                        jnp.where(live, v, _identity("max", v.dtype)))
                out_mask = out_mask & self.having_fn(henv)
            n_q = out_mask.sum().astype(jnp.int32)
            idx = jnp.nonzero(out_mask, size=V, fill_value=G)[0]
            ok = idx < G
            idx = jnp.minimum(idx, G - 1)
            # a window's rows leave in the order of their group keys
            order = jnp.lexsort(
                tuple(kv[idx] for kv in reversed(keys))
                + ((~ok).astype(jnp.int32),)
            )
            idx = idx[order]
            lane = jnp.arange(V, dtype=jnp.int32)
            dest = jnp.where(lane < n_q, c["n_out"] + lane, V)
            stamp = q * slide - 1 - tape.time_off
            return {
                **c,
                "out_ts": c["out_ts"].at[dest].set(stamp, mode="drop"),
                "out_cols": tuple(
                    o.at[dest].set(col[idx].astype(o.dtype), mode="drop")
                    for o, col in zip(c["out_cols"], cols)
                ),
                "n_out": c["n_out"] + n_q,
            }

        def close(q, c):
            """Window q ends where pane q starts: panes q-W .. q-1."""
            rows = jnp.mod(q - W + jnp.arange(W, dtype=jnp.int32), P)
            with jax.named_scope("fst.hop_max"):
                c = lax.cond(
                    c["row_tot"][rows].sum() > 0, emit, lambda _q, x: x,
                    q, c,
                )
            # pane q-W has left its last window: its row takes pane q+R
            gone = jnp.mod(q - W, P)

            def zero(c):
                return {
                    **c,
                    "cnt": c["cnt"].at[gone].set(0),
                    "row_tot": c["row_tot"].at[gone].set(0),
                }

            return lax.cond(c["row_tot"][gone] > 0, zero, lambda x: x, c)

        carry = pane_rounds(carry, mask, pane, any_ev, last, R, fold, close)

        new_state = dict(state)
        new_state["cnt"] = carry["cnt"]
        new_state["row_tot"] = carry["row_tot"]
        new_state["started"] = state["started"] | any_ev
        new_state["cur"] = carry["cur"]
        for j, kv in enumerate(keys):
            new_state[f"key{j}"] = kv
        return new_state, (
            carry["n_out"], carry["out_ts"], carry["out_cols"]
        )


def _lift_window_max(expr, found: List):
    """``windowMax(x)`` -> a slot reference; the calls' arguments, with
    their slots, go to ``found``."""
    if isinstance(expr, ast.Call) and expr.name.lower() == "windowmax":
        if len(expr.args) != 1 or expr.namespace is not None:
            raise SiddhiQLError("windowMax() takes exactly one argument")
        slot = f"@wmax{len(found)}"
        found.append((slot, expr.args[0]))
        return ast.Attr(slot)
    if isinstance(expr, ast.Unary):
        return ast.Unary(expr.op, _lift_window_max(expr.operand, found))
    if isinstance(expr, ast.Binary):
        return ast.Binary(
            expr.op,
            _lift_window_max(expr.left, found),
            _lift_window_max(expr.right, found),
        )
    if isinstance(expr, ast.Call):
        return ast.Call(
            expr.name,
            tuple(_lift_window_max(a, found) for a in expr.args),
            expr.namespace,
        )
    return expr


def compile_hop_window(
    q: ast.Query, name, window, resolver, stream_code, extensions, config,
    filter_fns, items, host_filters,
):
    """``window`` is ``('hop', (tsAttr, size_ms, slide_ms))``."""

    ts_attr, size_ms, slide_ms = window[1]
    if slide_ms <= 0 or size_ms <= 0 or size_ms % slide_ms:
        raise SiddhiQLError(
            "#window.hop(ts, size, slide): the size has to be a positive "
            "multiple of the slide"
        )
    if DAY_MS % slide_ms:
        raise SiddhiQLError(
            "#window.hop(ts, size, slide): the slide has to divide a day, "
            "so that windows end at multiples of it on the epoch's clock"
        )
    ts_res = resolver.resolve(ts_attr)
    if ts_res.atype != AttributeType.LONG:
        raise SiddhiQLError(
            "#window.hop needs a long (epoch ms) time attribute"
        )
    ts_key = time_key(ts_res.key)
    if q.partition_with:
        raise SiddhiQLError(
            "#window.hop inside 'partition with' is not supported"
        )

    collector = _AggCollector(resolver, extensions)
    rewritten = [
        ast.SelectItem(collector.rewrite(i.expr), i.alias) for i in items
    ]
    lifted: List = []
    having_re = None
    if q.selector.having is not None:
        having_re = _lift_window_max(
            collector.rewrite(q.selector.having), lifted
        )
        lifted = [(s, collector.rewrite(e)) for s, e in lifted]
    for agg in collector.aggs:
        if agg.kind != "count":
            raise SiddhiQLError(
                f"#window.hop counts its panes: {agg.kind}() is not "
                "supported over it (count() is)"
            )
    if not collector.aggs:
        raise SiddhiQLError("#window.hop needs an aggregating select")

    group_resolved = [
        resolver.resolve(ast.split_group_key(n))
        for n in q.selector.group_by
    ]
    key_cols = [r.key for r in group_resolved]
    for item in rewritten:
        for attr in ast.iter_attrs(item.expr):
            if attr.name.startswith("@"):
                continue
            if resolver.resolve(attr).key not in key_cols:
                raise SiddhiQLError(
                    f"#window.hop: {attr.name!r} is neither aggregated nor "
                    "a group-by key (a window's row belongs to a group, "
                    "not to an event)"
                )

    slot_types = {a.slot: a.out_type for a in collector.aggs}
    slot_resolver = _SlotResolver(resolver, slot_types)
    proj_fns, out_fields = [], []
    for item in rewritten:
        ce = compile_expr(item.expr, slot_resolver, extensions)
        proj_fns.append(ce.fn)
        out_fields.append(OutputField(item.output_name(), ce.atype, ce.table))

    having_fn, window_maxes = None, []
    if having_re is not None:
        alias_slots = {f.name: f.atype for f in out_fields}
        wmax_types: Dict[str, AttributeType] = {}

        class _HavingResolver:
            def resolve(self, attr: ast.Attr) -> ResolvedAttr:
                if attr.qualifier is None and attr.index is None:
                    for types in (slot_types, wmax_types):
                        if attr.name in types:
                            return ResolvedAttr(
                                attr.name, types[attr.name], None
                            )
                    if attr.name in alias_slots:
                        return ResolvedAttr(
                            f"@out:{attr.name}", alias_slots[attr.name], None
                        )
                r = resolver.resolve(attr)
                if r.key not in key_cols:
                    raise SiddhiQLError(
                        f"#window.hop: having reads {attr.name!r}, which "
                        "is neither aggregated nor a group-by key"
                    )
                return r

        for slot, inner in lifted:
            ce = compile_expr(inner, _HavingResolver(), extensions)
            if not ce.atype.is_numeric:
                raise SiddhiQLError("windowMax() needs a numeric argument")
            wmax_types[slot] = ce.atype
            window_maxes.append((slot, ce.fn))
        ce = compile_expr(having_re, _HavingResolver(), extensions)
        if ce.atype != AttributeType.BOOL:
            raise SiddhiQLError("having clause must be boolean")
        having_fn = ce.fn

    panes = size_ms // slide_ms
    code_key, encoder, encoded = _group_encoding(
        name, group_resolved, stream_code, filter_fns,
        encoder=GroupEncoder(retain_ticks=panes),
        host_filters=host_filters,
    )
    if encoded:
        import dataclasses

        encoded = (
            dataclasses.replace(
                encoded[0], tick_key=ts_key, tick_ms=slide_ms
            ),
        )
    art = HopWindowArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        stream_code=stream_code,
        filter_fns=filter_fns,
        ts_key=ts_key,
        size_ms=size_ms,
        slide_ms=slide_ms,
        aggs=collector.aggs,
        code_key=code_key,
        encoder=encoder,
        key_cols=key_cols,
        key_types=[r.atype for r in group_resolved],
        proj_fns=proj_fns,
        having_fn=having_fn,
        window_maxes=window_maxes,
        group_slots=int(config.hop_group_slots),
    )
    art.encoded_columns = encoded
    art.time_columns = (ts_res.key,)
    return art
