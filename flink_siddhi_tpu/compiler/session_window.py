"""``#window.session``: per-key sessions that close on the stream's
clock (``docs/session_window.md`` states the rule).

Two spellings, one artifact: ``#window.session(gap[, key])`` reads the
event's own timestamp, ``#window.session(tsAttribute, gap, key)`` a
``long`` event-time attribute that rides the job's clock
(``runtime.tape.time_key``), as ``#window.hop`` names it. ``partition
with`` keys the session by the partition attribute.

The **clock** is the largest time of any event that passed the filters;
an older event counts at the clock. A key's events less than ``gap``
apart are one session. A session **closes when the clock reaches
``last + gap``**, whether or not its key ever returns, and emits one
row then, stamped with its last millisecond ``last + gap - 1``; at the
end of the stream ``flush`` closes what is open. Nothing is capped or
dropped: the step's block holds every row a step can owe.

The step is vectorised over the micro-batch. Per slot of a host-interned
table (``schema/encoders.py``; the slots **expire**, so the table is
the keys a gap holds) the device keeps ``open``, ``first``, ``last``,
``cnt``, the slot's key (written by the events themselves) and the
other aggregates' rows.

* **fold** (``fst.session_fold``): the batch's events of a span under
  the gap fold with scatter-add / -min / -max over their slot codes; a
  slot's events then join its open session or, at a distance of the gap
  or more, replace it. A batch that spans the gap or more takes more
  such rounds, each closed before the next is folded, so several
  sessions of one key in one batch come out right.
* **close** (``fst.session_close``), once a round, after the round's
  clock is known: ``open & (clock - last >= gap)`` over the table,
  compacted to the rows that close. The compaction neither sorts nor
  scatters the table: a prefix count per tile of 128 slots (a matrix
  product), the tiles' starts laid out by one scatter of a value per
  tile, then gathers of the closing rows alone, ``_LANES`` at a time.

Rows leave in stamp order and within a stamp in key order: a step's
stamps lie after the step's before, and ``decode_packed`` orders the
rows of a drain.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, FrozenSet, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..query import ast
from ..query.lexer import SiddhiQLError
from ..runtime.tape import EncodedColumn, time_key
from ..schema.encoders import GroupEncoder
from ..schema.types import AttributeType
from .compact import to_word
from .expr import ColumnEnv
from .output import ColumnBatch, OutputField, OutputSchema
from .window import _Agg, _bucket, _identity, _select_fn

_I32_MIN = -(2 ** 31)
_TILE = 128  # slots a tile: the compaction's prefix count runs per tile
# rows the close gathers at a time; a step that closes more takes
# another turn of the loop
_LANES = 16384
# block rows beyond the stamp and the select items: a closed session's
# events and how it closed (``cnt * 2 + by_clock``), and the sessions
# opened since the last row left (on a step's first row)
_EXTRA_ROWS = 2
# the state's leaves that are not rows of the session table's rounds
_OUTSIDE_ROUNDS = ("enabled", "key", "clock", "started")


def expiry_ticks(gap_ms: int) -> Tuple[int, int]:
    """(tick_ms, retain_ticks) of a session table's slots: a tick is a
    tenth of the gap, and a slot is freed once the gap and two ticks
    have passed the batch that last touched it. The host frees a slot
    from the ticks of batches it has already staged, which the device
    steps before the batch that reuses it: by then the clock is at
    least ``gap + tick_ms`` past the slot's last event, and the session
    is closed and emitted."""
    tick_ms = max(1, gap_ms // 10)
    return tick_ms, -(-gap_ms // tick_ms) + 2


class _Rows(list):
    """A decoded row list that carries its drain's counters."""

    counters: Dict[str, int] = {}


def _tile_prefix(mask):
    """(within, count, start) of a mask over slots, per tile: the
    inclusive count of set slots inside each tile (a product with a
    triangle of ones: exact, a tile holds 128), each tile's total and
    the number set in the tiles before it."""
    G = mask.shape[0]
    T = min(_TILE, G)
    tri = (
        jnp.arange(T)[:, None] <= jnp.arange(T)[None, :]
    ).astype(jnp.float32)
    within = jnp.dot(
        mask.reshape(G // T, T).astype(jnp.float32), tri,
        preferred_element_type=jnp.float32,
    ).astype(jnp.int32)
    count = within[:, -1]
    return within, count, jnp.cumsum(count) - count


def _pick(within, count, start, base, lanes: int):
    """Slots of the set bits ranked ``base <= r < base + lanes``, in
    slot order (the slot of a rank past the last is arbitrary). Each
    tile that holds one of them writes its number where its first rank
    falls; a running maximum spreads it over the tile's ranks."""
    R, T = within.shape
    lane = jnp.arange(lanes, dtype=jnp.int32)
    holds = (count > 0) & (start < base + lanes) & (start + count > base)
    at = jnp.where(holds, jnp.maximum(start - base, 0), lanes)
    tile = lax.cummax(
        jnp.zeros(lanes, jnp.int32).at[at].max(
            jnp.arange(R, dtype=jnp.int32), mode="drop"
        )
    )
    rank = base + lane - start[tile]
    col = (within[tile] <= rank[:, None]).sum(1).astype(jnp.int32)
    return tile * T + jnp.minimum(col, T - 1)


@dataclass
class SessionWindowArtifact:
    name: str
    output_schema: OutputSchema
    stream_code: int
    filter_fns: List
    gap_ms: int
    # the window's time attribute on the job's clock, or None: the
    # event's own timestamp
    ts_key: Optional[str]
    code_key: Optional[str]
    encoder: Optional[GroupEncoder]
    key_col: Optional[str]  # tape key of the session key
    key_type: Optional[AttributeType]
    aggs: List[_Agg]
    arg_fns: List[Callable]
    arg_types: List[AttributeType]
    # arguments that are the window's time attribute: their min and max
    # are the session's first and last time, exact on the clock
    clock_args: FrozenSet[int]
    proj_map: List  # per select item: ('key',) | ('agg', slot)
    group_slots: int
    output_mode: str = "packed"

    @property
    def acc_rows(self) -> int:
        return 1 + len(self.output_schema.fields) + _EXTRA_ROWS

    # -- what the executor and admission ask ---------------------------------
    def emit_rows(self, tape_capacity: int, slots: int) -> int:
        """Rows one step can owe: every session that was open before it
        (as many as there are slots) and every session the batch itself
        opened and closed (one needs an event)."""
        return slots + tape_capacity

    def emit_block_width(self, tape_capacity: int, state: Dict) -> int:
        rows = self.emit_rows(tape_capacity, state["open"].shape[0])
        return rows + min(_LANES, rows)  # the last turn's whole width

    def safe_cycles(self, tape_capacity: int, state: Dict, cap: int) -> int:
        """Cycles the accumulator of ``cap`` rows holds without a swap.
        The block is wide because one step may close every open session,
        not because every step does: ``k`` cycles emit at most the
        sessions open before them (``slots``) and one per event, so the
        next cycle still finds room for its block while ``slots + k *
        tape_capacity + block <= cap``. That bound is the worst case
        itself, so it takes the whole accumulator; rounded down to a
        power of two, a swap falls on a segment's end."""
        slots = state["open"].shape[0]
        block = self.emit_block_width(tape_capacity, state)
        k = (cap - slots - block) // max(tape_capacity, 1)
        return 1 << (max(k, 1).bit_length() - 1)

    def cost_info(self) -> Dict:
        """Admission-cost descriptor: per-key aggregates, no event
        retained; a session's state lives for the gap past its last
        event, one row out when it closes. The table grows with the
        keys a gap holds (its slots expire and are reused)."""
        info = {
            "name": self.name,
            "kind": "session_window",
            "amplification": 1,
            "residency_ms": int(self.gap_ms),
        }
        if self.encoder is not None:
            info["grows_with"] = "keys"
        return info

    def drain_counters(self, payload) -> Dict[str, int]:
        """What a drain delivered, as ``decode_packed`` counted it from
        the rows' own record: the sessions closed, their events, how
        many the clock closed (not their key's return, not the flush),
        and the sessions opened meanwhile."""
        return dict(getattr(payload, "counters", None) or {})

    # -- state ---------------------------------------------------------------
    def _G(self) -> int:
        if self.encoder is None:
            return 1
        return _bucket(len(self.encoder), self.group_slots)

    def _stat_of(self, agg: _Agg) -> Tuple[str, int, object]:
        """(kind, argument, dtype) of the per-slot row an aggregate
        reads, ``<kind><argument>`` in the state: ``sum`` in the
        argument's own type (an integer sum stays an integer), ``min``,
        ``max``; the mean of an integer argument sums in float32
        (``fsum``)."""
        j = agg.arg_idx
        dt = self.arg_types[j].device_dtype
        if agg.kind != "avg":
            return agg.kind, j, dt
        if jnp.issubdtype(dt, jnp.floating):
            return "sum", j, dt
        return "fsum", j, jnp.float32

    def _stats(self) -> List[Tuple[str, int, object]]:
        """The per-slot rows the select's aggregates need beyond
        ``cnt``, ``first`` and ``last``, each once."""
        out = []
        for agg in self.aggs:
            if agg.kind == "count" or agg.arg_idx in self.clock_args:
                continue
            stat = self._stat_of(agg)
            if stat not in out:
                out.append(stat)
        return out

    def init_state(self) -> Dict:
        G = self._G()
        st = {
            "enabled": jnp.asarray(True),
            "open": jnp.zeros(G, bool),
            "first": jnp.zeros(G, jnp.int32),
            "last": jnp.zeros(G, jnp.int32),
            "cnt": jnp.zeros(G, jnp.int32),
            "clock": jnp.asarray(0, jnp.int32),
            "started": jnp.asarray(False),
            # sessions opened that no row has reported yet
            "opened": jnp.asarray(0, jnp.int32),
        }
        if self.key_type is not None:
            st["key"] = jnp.zeros(G, self.key_type.device_dtype)
        for kind, j, dt in self._stats():
            st[f"{kind}{j}"] = jnp.full(G, _identity(kind, dt), dt)
        return st

    def grow_state(self, state: Dict) -> Dict:
        G, need = state["open"].shape[0], self._G()
        if need <= G:
            return state
        fresh = self.init_state()
        return {
            k: v if v.ndim == 0
            else jnp.concatenate([v, fresh[k][G:]])
            for k, v in state.items()
        }

    # -- the rows ------------------------------------------------------------
    def _words(self, st: Dict, key, by_clock):
        """The block's rows but the last, per slot, as int32 words:
        stamp, the select items, ``cnt * 2 + by_clock``."""
        gap = jnp.int32(self.gap_ms)
        cnt = st["cnt"]
        slots: Dict[str, object] = {}
        for agg in self.aggs:
            j = agg.arg_idx
            if agg.kind == "count":
                v = cnt
            elif j in self.clock_args:
                # on the job's clock; the emission tail adds the epoch
                v = st["first"] if agg.kind == "min" else st["last"]
            else:
                kind, _j, _dt = self._stat_of(agg)
                v = st[f"{kind}{j}"]
                if agg.kind == "avg":
                    v = v.astype(jnp.float32) / (
                        jnp.maximum(cnt, 1).astype(jnp.float32))
            slots[agg.slot] = v.astype(agg.out_type.device_dtype)
        rows = [st["last"] + gap - 1]
        for kind in self.proj_map:
            rows.append(to_word(key if kind[0] == "key" else slots[kind[1]]))
        rows.append(cnt * 2 + by_clock.astype(jnp.int32))
        return rows

    @staticmethod
    def _emit(out, n_out, ends, words, K: int):
        """Append the rows of the slots in ``ends`` to the block, ``K``
        a turn."""
        within, count, start = _tile_prefix(ends)
        n = count.sum()

        def turn(c):
            k, out = c
            idx = _pick(within, count, start, k * K, K)
            ok = k * K + jnp.arange(K, dtype=jnp.int32) < n
            vals = jnp.stack([jnp.where(ok, w[idx], 0) for w in words])
            return k + 1, lax.dynamic_update_slice(
                out, vals, (0, n_out + k * K))

        _k, out = lax.while_loop(
            lambda c: c[0] * K < n, turn, (jnp.int32(0), out))
        return out, n_out + n

    # fst:hotpath device=state,tape
    def step(self, state: Dict, tape) -> Tuple[Dict, Tuple]:
        env: ColumnEnv = dict(tape.cols)
        mask = tape.valid & (tape.stream == self.stream_code)
        for f in self.filter_fns:
            mask = mask & f(env)
        mask = mask & state["enabled"]
        E = tape.capacity
        G = state["open"].shape[0]
        gap = jnp.int32(self.gap_ms)
        # times are ms since the job's epoch, as the rows' stamps
        t_raw = (
            env[self.ts_key].astype(jnp.int32) - tape.time_off
            if self.ts_key is not None else tape.ts
        )
        g = (
            env[self.code_key].astype(jnp.int32)
            if self.code_key is not None
            else jnp.zeros(E, jnp.int32)
        )
        # an older event counts at the clock: the running maximum
        clock0 = jnp.where(state["started"], state["clock"], _I32_MIN)
        t = lax.cummax(jnp.maximum(jnp.where(mask, t_raw, _I32_MIN), clock0))
        any_ev = mask.any()
        stats = self._stats()
        args = {
            j: jnp.broadcast_to(jnp.asarray(self.arg_fns[j](env)), (E,))
            for j in {j for _k, j, _dt in stats}
        }
        K = min(_LANES, self.emit_rows(E, G))
        V = self.emit_rows(E, G) + K
        width = self.acc_rows

        @jax.named_scope("fst.session_fold")
        def fold(st, key, sel):
            """The round's events into the table; (the table, the
            slots whose open session is over, of them those whose key
            did not return)."""
            idx = jnp.where(sel, g, G)

            def scatter(kind, vals, dt):
                init = jnp.full(G, _identity(kind, dt), dt)
                at = init.at[idx]
                vals = vals.astype(dt)
                if kind in ("min", "max"):
                    return (at.min if kind == "min" else at.max)(
                        vals, mode="drop")
                return at.add(vals, mode="drop")

            bcnt = scatter("sum", sel, jnp.int32)
            bfirst = scatter("min", t, jnp.int32)
            blast = scatter("max", t, jnp.int32)
            if key is not None:
                # the slot's key, written by the events themselves
                key = key.at[idx].set(
                    env[self.key_col].astype(key.dtype), mode="drop")
            touched = bcnt > 0
            clock = jnp.max(jnp.where(sel, t, _I32_MIN))
            joins = st["open"] & touched & (bfirst - st["last"] < gap)
            over = st["open"] & ~joins & (clock - st["last"] >= gap)
            fresh = touched & ~joins
            new = dict(st)
            new["open"] = (st["open"] & ~over) | touched
            new["first"] = jnp.where(fresh, bfirst, st["first"])
            new["last"] = jnp.where(touched, blast, st["last"])
            new["cnt"] = jnp.where(fresh, 0, st["cnt"]) + bcnt
            for kind, j, dt in stats:
                name = f"{kind}{j}"
                b = scatter(kind, args[j], dt)
                old = jnp.where(fresh, _identity(kind, dt), st[name])
                new[name] = (
                    jnp.minimum(old, b) if kind == "min"
                    else jnp.maximum(old, b) if kind == "max"
                    else old + b
                )
            new["opened"] = st["opened"] + fresh.sum().astype(jnp.int32)
            return new, key, over, over & ~touched

        def round_(c):
            st, key, out, n_out, lo = c
            sel = mask & (t >= lo) & (t - lo < gap)
            old = st
            st, key, over, by_clock = fold(st, key, sel)
            with jax.named_scope("fst.session_close"):
                out, n_out = self._emit(
                    out, n_out, over, self._words(old, key, by_clock), K)
            nxt = jnp.min(jnp.where(
                mask & (t >= lo) & (t - lo >= gap), t, jnp.int32(2 ** 31 - 1)
            ))
            return st, key, out, n_out, nxt

        st0 = {
            k: v for k, v in state.items()
            if k not in _OUTSIDE_ROUNDS
        }
        first_t = jnp.min(jnp.where(mask, t, jnp.int32(2 ** 31 - 1)))
        last_t = jnp.max(jnp.where(mask, t, _I32_MIN))
        st, key, out, n_out, _lo = lax.while_loop(
            lambda c: any_ev & (c[4] <= last_t),
            round_,
            (st0, state.get("key"), jnp.zeros((width, V), jnp.int32),
             jnp.int32(0), first_t),
        )
        st, out = self._report_opened(st, out, n_out)
        new_state = dict(state)
        new_state.update(st)
        if key is not None:
            new_state["key"] = key
        new_state["clock"] = jnp.where(any_ev, last_t, state["clock"])
        new_state["started"] = state["started"] | any_ev
        return new_state, (n_out, out)

    @staticmethod
    def _report_opened(st: Dict, out, n_out):
        """The block's last row, first lane: the sessions opened since a
        row last left (they wait in ``opened`` while no row does)."""
        sent = n_out > 0
        out = out.at[-1, 0].set(jnp.where(sent, st["opened"], 0))
        return {**st, "opened": jnp.where(sent, 0, st["opened"])}, out

    @property
    def flush_is_noop(self) -> bool:
        return False

    def flush(self, state: Dict) -> Tuple[Dict, Tuple]:
        """End of stream: every open session closes."""
        G = state["open"].shape[0]
        st = {
            k: v for k, v in state.items()
            if k not in _OUTSIDE_ROUNDS
        }
        K = min(_LANES, G)
        out, n_out = self._emit(
            jnp.zeros((self.acc_rows, G + K), jnp.int32), jnp.int32(0),
            state["open"],
            self._words(st, state.get("key"), jnp.zeros(G, bool)), K,
        )
        st, out = self._report_opened(st, out, n_out)
        new_state = dict(state)
        new_state.update(st)
        new_state["open"] = jnp.zeros(G, bool)
        return new_state, (n_out, out)

    # -- decode --------------------------------------------------------------
    def _ordered(self, n: int, block):
        """(stamps, one raw column per select item, counters) of a
        drain's rows, in stamp order and within a stamp in key order."""
        block = np.asarray(block)[:, :n]
        cols = []
        for c, f in enumerate(self.output_schema.fields):
            raw = block[1 + c]
            if np.dtype(f.atype.device_dtype) == np.dtype(np.float32):
                raw = raw.view(np.float32)
            cols.append(raw)
        keys = [c for c, kind in zip(cols, self.proj_map)
                if kind[0] == "key"]
        order = (
            np.lexsort((keys[0], block[0])) if keys
            else np.argsort(block[0], kind="stable")
        )
        meta = block[-2].astype(np.int64)
        counters = {
            "session.closed": int(n),
            "session.events": int((meta >> 1).sum()),
            "session.closed_by_clock": int((meta & 1).sum()),
            "session.opened": int(block[-1].astype(np.int64).sum()),
        }
        return (block[0][order].astype(np.int64),
                [c[order] for c in cols], counters)

    def decode_packed(self, n: int, block):
        ts, cols, counters = self._ordered(n, block)
        schema = self.output_schema
        col_lists = [
            f.decode_column(c) for f, c in zip(schema.fields, cols)
        ]
        rows = _Rows(zip(ts.tolist(), map(tuple, zip(*col_lists))))
        rows.counters = counters
        return [(schema, rows)]

    def decode_packed_columns(self, n: int, block, lookup_np=None):
        ts, cols, counters = self._ordered(n, block)
        schema = self.output_schema
        cb = ColumnBatch(ts, {
            f.name: f.decode_column_np(c)
            for f, c in zip(schema.fields, cols)
        })
        cb.counters = counters
        return [(schema, cb)]


def compile_session_window(
    q: ast.Query, name, args, resolver, stream_code, extensions, config,
    filter_fns, rewritten, collector, having_re, host_filters,
    part_attr=None,
):
    """``args`` is ``(gap_ms, keyAttr or None, tsAttr or None)``."""
    gap_ms, key_attr, ts_attr = args
    if gap_ms <= 0:
        raise SiddhiQLError("#window.session needs a positive gap")
    if part_attr is not None:
        # 'partition with' sessions: the partition key IS the session
        # key (each partition instance tracks its own gap)
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
    ts_key, ts_res = None, None
    if ts_attr is not None:
        ts_res = resolver.resolve(ts_attr)
        if ts_res.atype != AttributeType.LONG:
            raise SiddhiQLError(
                "#window.session(tsAttribute, gap, key) needs a long "
                "(epoch ms) time attribute"
            )
        ts_key = time_key(ts_res.key)
    gb = tuple(ast.bare_group_key(g) for g in q.selector.group_by)
    if gb and (key_attr is None or gb != (key_attr.name,)):
        raise SiddhiQLError(
            "group by on #window.session must be the session key"
        )
    if not collector.aggs:
        raise SiddhiQLError(
            "#window.session without aggregation emits nothing; "
            "aggregate the session (e.g. count())"
        )
    clock_args = frozenset(
        j for j, e in enumerate(collector.arg_exprs)
        if ts_res is not None and isinstance(e, ast.Attr)
        and resolver.resolve(e).key == ts_res.key
    )
    for a in collector.aggs:
        if a.kind not in ("count", "sum", "avg", "min", "max"):
            raise SiddhiQLError(
                f"{a.kind}() is not supported over #window.session"
            )
        if a.arg_idx in clock_args and a.kind not in ("min", "max"):
            raise SiddhiQLError(
                f"{a.kind}() of the window's time attribute is not "
                "supported over #window.session (min() and max() are: a "
                "session's start and its last event)"
            )
    slot_names = {a.slot: a for a in collector.aggs}
    key_res = resolver.resolve(key_attr) if key_attr is not None else None
    proj_map: List = []
    out_fields: List[OutputField] = []
    for item in rewritten:
        e = item.expr
        if isinstance(e, ast.Attr) and e.name in slot_names:
            agg = slot_names[e.name]
            proj_map.append(("agg", e.name))
            out_fields.append(OutputField(
                item.output_name(), agg.out_type, None,
                on_clock=agg.arg_idx in clock_args,
            ))
        elif (
            isinstance(e, ast.Attr)
            and key_res is not None
            and resolver.resolve(e).key == key_res.key
        ):
            proj_map.append(("key",))
            out_fields.append(
                OutputField(item.output_name(), key_res.atype, key_res.table)
            )
        else:
            raise SiddhiQLError(
                "#window.session select items must be the session key "
                "or aggregations (a closed session has no single "
                "current event to read other attributes from)"
            )
    code_key, encoder, encoded = None, None, ()
    if key_res is not None:
        tick_ms, retain = expiry_ticks(int(gap_ms))
        code_key = f"@group:{name}"
        encoder = GroupEncoder(retain_ticks=retain)
        # interning selects its rows with the numpy filters, where they
        # compile to numpy: the device's cost the host a round trip
        encoded = (EncodedColumn(
            out_key=code_key, in_keys=(key_res.key,),
            stream_code=stream_code, encoder=encoder,
            select_fn=_select_fn(
                host_filters if host_filters is not None else filter_fns),
            tick_key=ts_key or "@ts", tick_ms=tick_ms,
        ),)
    art = SessionWindowArtifact(
        name=name,
        output_schema=OutputSchema(q.output_stream, tuple(out_fields)),
        stream_code=stream_code,
        filter_fns=filter_fns,
        gap_ms=int(gap_ms),
        ts_key=ts_key,
        code_key=code_key,
        encoder=encoder,
        key_col=key_res.key if key_res is not None else None,
        key_type=key_res.atype if key_res is not None else None,
        aggs=collector.aggs,
        arg_fns=collector.arg_fns,
        arg_types=collector.arg_types,
        clock_args=clock_args,
        proj_map=proj_map,
        group_slots=int(config.hop_group_slots),
    )
    art.encoded_columns = encoded
    art.time_columns = (ts_res.key,) if ts_res is not None else ()
    return art
