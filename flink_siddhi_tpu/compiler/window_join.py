"""A tumbling-window equi-join keyed on the device (NEXmark Q8's shape):

```
from L[filter]#window.hop(<ts>, <size>, <size>) as l
join R[filter]#window.hop(<ts>, <size>, <size>) as r
on l.<key> == r.<key>
select l.<key> as k, count() as n
group by l.<key>
insert into Out
```

Both sides fall into the same tumbling windows of an event-time
attribute (a hop whose slide is its size: ``docs/window_join.md``), and
the ``on`` equality is a **key**: every window keeps, per key, how many
left and how many right events it has seen. A window closes when an
event of either side arrives at or after its end, and emits one row per
key that both sides touched, in key order, stamped with the window's
last millisecond: the key and ``count()``, the key's joined pairs (left
events x right events; the right events where a key comes once on the
left, as a person registers once). Whichever side came first; a pair
split by a window's end does not match; a window without a match emits
nothing; nothing is flushed at the end of the stream.

The cost is the events a batch holds, not a pair grid: the host interns
both sides' keys into **one** slot table (``schema/encoders.py``
``intern_sources``: the left key under the left filter, the right key
under the right), one code column goes up with the tape, and the step
folds both sides with one scatter-add into ``cnt[side, ring row, slot]``
(``fst.join_fold``). The close (``fst.join_close``) sorts the slots that
both sides touched by key, once, with their counts riding along, and
writes them as one block: **nothing is dropped**, the step's emission
block holds every row a step can owe (``emit_rows``). Slots expire one
window after their last event (``GroupEncoder(retain_ticks=1)``), so the
table is the keys two windows hold, sized by
``EngineConfig.hop_group_slots``. A slot's key is written on the device
by the left side's events: only a slot the left side touched emits.

``compiler/join.py`` keeps every other join (``length`` and ``time``
sides, pairs out); ``is_window_join`` tells the plan which is which.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..query import ast
from ..query.lexer import SiddhiQLError
from ..runtime.tape import DAY_MS, EncodedColumn, KeySource, time_key
from ..schema.encoders import GroupEncoder
from ..schema.types import AttributeType
from .expr import ColumnEnv, ExprResolver, compile_expr
from .hop_window import pane_clock, pane_rounds
from .join import _JoinResolver
from .output import OutputField, OutputSchema
from .window import _bucket, _select_fn, _window_of, host_filter_fns

_KEY_TYPES = (AttributeType.INT, AttributeType.LONG)


def is_window_join(inp) -> bool:
    """A stream join with ``#window.hop`` on a side takes this path
    (and is refused here, with the reason, where it does not fit)."""
    return isinstance(inp, ast.JoinInput) and any(
        w.name.split(".")[-1].lower() == "hop"
        for si in (inp.left, inp.right)
        for w in si.windows
    )


@dataclass
class _Side:
    stream_code: int
    filter_fns: List[Callable]
    ts_key: str  # the window's time attribute on the job's clock
    key_col: str  # tape key of the side's ``on`` attribute


@dataclass
class WindowJoinArtifact:
    name: str
    output_schema: OutputSchema
    left: _Side
    right: _Side
    size_ms: int
    code_key: str
    encoder: GroupEncoder
    key_type: AttributeType
    # per output field: True the key, False ``count()``
    out_is_key: Tuple[bool, ...]
    group_slots: int
    # panes beyond the open one that a micro-batch may reach before it
    # has to close windows first (it then takes a second round)
    runway: int = 1
    output_mode: str = "buffered"

    def emit_rows(self, tape_capacity: int, slots: int) -> int:
        """Rows one step can owe: every key of the window that was open
        before it (as many as there are slots), and for each window the
        batch itself opened and closed a row per two of its events."""
        return slots + tape_capacity // 2

    def safe_cycles(self, tape_capacity: int, state: Dict, cap: int) -> int:
        """Cycles the accumulator of ``cap`` rows holds without a swap.
        A step's block is wide because one closing may fill it, not
        because every step does: over ``k`` cycles the rows are at most
        the open window's keys and a row per two events, so cycle
        ``k + 1`` still finds room for its block while
        ``slots + k * tape_capacity / 2 + block <= cap``."""
        slots = state["cnt"].shape[-1]
        block = self.emit_rows(tape_capacity, slots)
        return (cap // 2 - slots - block) // max(tape_capacity // 2, 1)

    def cost_info(self) -> Dict:
        return {
            "name": self.name,
            "kind": "window_join",
            "amplification": 1,
            "residency_ms": int(self.size_ms),
            "grows_with": "groups",
        }

    def drain_counters(self, payload) -> Dict[str, int]:
        """What a drain delivered: the rows, and the windows they close
        (a window's rows share its stamp and leave in one step)."""
        ts = getattr(payload, "ts", None)
        if ts is None:  # the row lane: (ts, row) pairs
            ts = [t for t, _row in payload]
        return {
            "join.rows_emitted": len(ts),
            "join.windows_closed": len(np.unique(ts)),
        }

    # -- state ---------------------------------------------------------------
    def _G(self) -> int:
        return _bucket(len(self.encoder), self.group_slots)

    def init_state(self) -> Dict:
        P, G = 1 + self.runway, self._G()
        return {
            "enabled": jnp.asarray(True),
            "cnt": jnp.zeros((2, P, G), jnp.int32),  # side, ring row, slot
            # events per ring row: an empty window costs nothing
            "row_tot": jnp.zeros(P, jnp.int32),
            "started": jnp.asarray(False),
            "cur": jnp.asarray(0, jnp.int32),  # the newest pane seen
            "key": jnp.zeros(G, self.key_type.device_dtype),
        }

    def grow_state(self, state: Dict) -> Dict:
        G, need = state["cnt"].shape[-1], self._G()
        if need <= G:
            return state
        out = dict(state)
        for k in ("cnt", "key"):
            v = state[k]
            out[k] = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, need - G)])
        return out

    # -- the step ------------------------------------------------------------
    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        masks = []
        for side in (self.left, self.right):
            m = tape.valid & (tape.stream == side.stream_code)
            for f in side.filter_fns:
                m = m & f(env)
            masks.append(m & state["enabled"])
        ml, mr = masks
        mask = ml | mr
        _S, P, G = state["cnt"].shape
        R = self.runway
        V = self.emit_rows(tape.capacity, G)
        ts = env[self.left.ts_key]
        if self.right.ts_key != self.left.ts_key:
            ts = jnp.where(mr, env[self.right.ts_key], ts)
        pane, any_ev, cur0, last = pane_clock(mask, ts, self.size_ms, state)
        row = jnp.mod(pane, P)
        g = env[self.code_key].astype(jnp.int32)
        # the slot's key, written by the left side's events: a slot
        # emits only where that side touched it
        key = state["key"].at[jnp.where(ml, g, G)].set(
            env[self.left.key_col].astype(state["key"].dtype), mode="drop"
        )
        fields = self.output_schema.fields

        carry = {
            "cnt": state["cnt"],
            "row_tot": state["row_tot"],
            "cur": cur0,
            "folded": cur0 - 1,
            "out_ts": jnp.zeros(V, jnp.int32),
            "out_cols": tuple(
                jnp.zeros(V, f.atype.device_dtype) for f in fields
            ),
            "n_out": jnp.asarray(0, jnp.int32),
        }

        @jax.named_scope("fst.join_fold")
        def fold(c, hi):
            sel = mask & (pane > c["folded"]) & (pane <= hi)
            # the sides are disjoint: one scatter-add folds both
            flat = jnp.where(
                sel, (mr.astype(jnp.int32) * P + row) * G + g, 2 * P * G
            )
            cnt = c["cnt"].reshape(-1).at[flat].add(
                sel.astype(jnp.int32), mode="drop"
            ).reshape(2, P, G)
            row_tot = c["row_tot"] + jnp.stack([
                (sel & (row == r)).sum().astype(jnp.int32)
                for r in range(P)
            ])
            return {**c, "cnt": cnt, "row_tot": row_tot}

        def emit(q, c):
            """The rows of the window that ends where pane q starts: its
            slots sorted once, those that both sides touched first and
            in key order, the counts riding along; they land behind the
            rows this step already owes."""
            r = jnp.mod(q - 1, P)
            lc, rc = c["cnt"][0, r], c["cnt"][1, r]
            both = (lc > 0) & (rc > 0)
            _miss, skey, pairs = lax.sort(
                ((~both).astype(jnp.int32), key, lc * rc), num_keys=2
            )
            n_q = both.sum().astype(jnp.int32)
            lane = jnp.arange(V, dtype=jnp.int32)
            mine = (lane >= c["n_out"]) & (lane < c["n_out"] + n_q)

            def put(out, col):
                wide = jnp.pad(col.astype(out.dtype), (0, V - G))
                return jnp.where(mine, jnp.roll(wide, c["n_out"]), out)

            stamp = q * self.size_ms - 1 - tape.time_off
            return {
                **c,
                "out_ts": jnp.where(mine, stamp, c["out_ts"]),
                "out_cols": tuple(
                    put(o, skey if is_key else pairs)
                    for o, is_key in zip(c["out_cols"], self.out_is_key)
                ),
                "n_out": c["n_out"] + n_q,
            }

        def close(q, c):
            gone = jnp.mod(q - 1, P)  # its row takes pane q + R

            def emit_and_clear(c):
                with jax.named_scope("fst.join_close"):
                    c = emit(q, c)
                return {
                    **c,
                    "cnt": c["cnt"].at[:, gone].set(0),
                    "row_tot": c["row_tot"].at[gone].set(0),
                }

            return lax.cond(
                c["row_tot"][gone] > 0, emit_and_clear, lambda x: x, c
            )

        carry = pane_rounds(carry, mask, pane, any_ev, last, R, fold, close)

        new_state = dict(state)
        new_state["cnt"] = carry["cnt"]
        new_state["row_tot"] = carry["row_tot"]
        new_state["started"] = state["started"] | any_ev
        new_state["cur"] = carry["cur"]
        new_state["key"] = key
        return new_state, (
            carry["n_out"], carry["out_ts"], carry["out_cols"]
        )


def _conjuncts(e):
    if isinstance(e, ast.Binary) and e.op == "and":
        yield from _conjuncts(e.left)
        yield from _conjuncts(e.right)
    else:
        yield e


def _pinned(si: ast.StreamInput) -> Dict[str, object]:
    """attribute -> constant, for every ``attr == constant`` that a
    side's filters demand."""
    out = {}
    for f in si.filters:
        for e in _conjuncts(f):
            if isinstance(e, ast.Binary) and e.op == "==":
                for a, b in ((e.left, e.right), (e.right, e.left)):
                    if isinstance(a, ast.Attr) and isinstance(b, ast.Literal):
                        out[a.name] = b.value
    return out


def compile_window_join(
    q: ast.Query, name: str, schemas, stream_codes: Dict[str, int],
    extensions, config,
):
    inp = q.input
    sel = q.selector

    def refuse(why: str):
        return SiddhiQLError(f"window join {name!r}: {why}")

    if inp.join_type != "join":
        raise refuse(
            f"'{inp.join_type}' is not supported: a window's row belongs "
            "to a key that both sides touched (inner joins only)"
        )
    if inp.within is not None:
        raise refuse("'within' is not supported: the window bounds a pair")

    # -- the window: the same tumble on both sides ---------------------------
    resolver = _JoinResolver(inp.left, inp.right, schemas)
    windows = []
    for si in (inp.left, inp.right):
        w = _window_of(si)
        if w is None or w[0] != "hop":
            raise refuse(
                f"side {si.ref_name!r} has "
                f"{'no window' if w is None else '#window.' + w[0]}: both "
                "sides need the same #window.hop(ts, size, size)"
            )
        ts_attr, size_ms, slide_ms = w[1]
        if size_ms != slide_ms:
            raise refuse(
                f"side {si.ref_name!r} hops ({size_ms} ms by {slide_ms} "
                "ms): only tumbling windows join (size has to equal slide)"
            )
        sres = ExprResolver(
            {si.ref_name: (si.stream_id, schemas[si.stream_id])},
            default_scope=si.ref_name,
        )
        ts_res = sres.resolve(ts_attr)
        if ts_res.atype != AttributeType.LONG:
            raise refuse(
                f"#window.hop on side {si.ref_name!r} needs a long "
                "(epoch ms) time attribute"
            )
        windows.append((size_ms, ts_res, sres))
    size_ms = windows[0][0]
    if windows[1][0] != size_ms:
        raise refuse(
            f"the sides' windows differ ({size_ms} ms and "
            f"{windows[1][0]} ms): both sides need the same window"
        )
    if size_ms <= 0 or DAY_MS % size_ms:
        raise refuse(
            "the window has to divide a day, so that windows end at "
            "multiples of it on the epoch's clock"
        )

    # -- the key: the one equality of ``on`` ---------------------------------
    on = inp.on
    keys = {}
    if (
        isinstance(on, ast.Binary) and on.op == "=="
        and isinstance(on.left, ast.Attr) and isinstance(on.right, ast.Attr)
    ):
        for a in (on.left, on.right):
            r = resolver.resolve(a)
            keys[resolver.used[r.key][0]] = r
    if set(keys) != {"l", "r"}:
        raise refuse(
            "'on' has to be one equality between an attribute of each "
            "side (it is the key both sides fold under)"
        )
    if any(r.atype not in _KEY_TYPES for r in keys.values()):
        raise refuse("the key attributes have to be int or long")
    key_cols = {t: resolver.used[r.key][1] for t, r in keys.items()}

    # -- the sides have to be told apart -------------------------------------
    if inp.left.stream_id == inp.right.stream_id:
        pl, pr = _pinned(inp.left), _pinned(inp.right)
        if not any(a in pr and pr[a] != v for a, v in pl.items()):
            raise refuse(
                "both sides read the same stream: their filters have to "
                "compare one attribute with two different constants "
                "([event_type == 0] and [event_type == 1]), so that no "
                "event is on both sides"
            )

    # -- the select: the key and count() -------------------------------------
    if q.partition_with:
        raise refuse("'partition with' is not supported")
    if sel.is_star:
        raise refuse("select * is not supported: select the key and count()")
    if sel.having is not None:
        raise refuse("'having' is not supported")
    out_fields, out_is_key = [], []
    for item in sel.items:
        e = item.expr
        if isinstance(e, ast.Call) and ast.is_aggregate_call(e):
            if e.name.lower() != "count" or e.args:
                raise refuse(
                    f"{e.name}() is not supported: a window join counts "
                    "its pairs (count() is)"
                )
            out_fields.append(
                OutputField(item.output_name(), AttributeType.LONG, None))
            out_is_key.append(False)
            continue
        if not (
            isinstance(e, ast.Attr)
            and resolver.resolve(e).key in (keys["l"].key, keys["r"].key)
        ):
            raise refuse(
                f"select item {item.output_name()!r} is neither the key nor "
                "count(): a window's row belongs to a key, not to an event"
            )
        out_fields.append(
            OutputField(item.output_name(), keys["l"].atype, None))
        out_is_key.append(True)
    grouped = [
        resolver.resolve(ast.split_group_key(g)).key for g in sel.group_by
    ]
    if len(grouped) != 1 or grouped[0] not in (keys["l"].key, keys["r"].key):
        raise refuse(
            "it emits one row per key: 'group by' has to name the key "
            "of 'on' and nothing else"
        )

    # -- the sides -----------------------------------------------------------
    sides, sources = [], []
    for tag, si, (_size, ts_res, sres), counter in (
        ("l", inp.left, windows[0], "join.left_events"),
        ("r", inp.right, windows[1], "join.right_events"),
    ):
        fns = []
        for f in si.filters:
            ce = compile_expr(f, sres, extensions)
            if ce.atype != AttributeType.BOOL:
                raise SiddhiQLError("stream filter must be boolean")
            fns.append(ce.fn)
        host = host_filter_fns(si.filters, sres)
        code = stream_codes[si.stream_id]
        sides.append(_Side(code, fns, time_key(ts_res.key), key_cols[tag]))
        sources.append(KeySource(
            in_key=key_cols[tag], stream_code=code,
            select_fn=_select_fn(host if host is not None else fns),
            tick_key=time_key(ts_res.key), counter=counter,
        ))
    code_key = f"@group:{name}"
    encoder = GroupEncoder(retain_ticks=1)
    art = WindowJoinArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        left=sides[0],
        right=sides[1],
        size_ms=size_ms,
        code_key=code_key,
        encoder=encoder,
        key_type=keys["l"].atype,
        out_is_key=tuple(out_is_key),
        group_slots=int(config.hop_group_slots),
    )
    art.encoded_columns = (EncodedColumn(
        out_key=code_key, in_keys=(key_cols["l"], key_cols["r"]),
        stream_code=sides[0].stream_code, encoder=encoder,
        tick_ms=size_ms, sources=tuple(sources),
    ),)
    art.time_columns = tuple(sorted({w[1].key for w in windows}))
    # the right key is interned on the host and never read on the
    # device (the left side writes a slot's key): where no filter reads
    # it either, it stays off the wire
    read = {
        a.name for si in (inp.left, inp.right) for f in si.filters
        for a in ast.iter_attrs(f)
    }
    art.host_only_columns = (
        (key_cols["r"],)
        if key_cols["r"] != key_cols["l"]
        and key_cols["r"].split(".", 1)[1] not in read
        else ()
    )
    return art
