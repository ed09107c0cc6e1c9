"""Sharded execution: one shard_map-ed device step over a mesh of shards.

The multi-device analog of the reference's N parallel operator subtasks, each
hosting a full copy of every execution plan (AbstractSiddhiOperator.java:
301-313): plan state is stacked along a leading ``shards`` axis and laid out
with a ``NamedSharding`` so each device owns its shard; the jitted step is a
``jax.shard_map`` that advances every shard's plan in ONE SPMD program. Events
reach shards through the host Router (key-hash / round-robin / broadcast —
the DynamicPartitioner contract) as one selection of a cycle's rows, built
once into tapes of a common bucketed capacity, one row of each leaf a shard.

On a real TPU slice the ``shards`` axis rides ICI; in tests it is an 8-device
virtual CPU mesh (the MiniCluster analog, SURVEY.md §4).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import heapq
import logging
import threading
import time

from ..compiler import pallas_ops
from ..compiler.output import ColumnBatch
from ..compiler.plan import CompiledPlan
from ..runtime.executor import Job, _PlanRuntime, _staging_allow
from ..runtime.tape import build_tape, bucket_size
from ..schema.batch import EventBatch
from ..telemetry import LatencyHistogram, MetricsRegistry
from .mesh import SHARD_AXIS, make_cep_mesh
from .router import Router

_LOG = logging.getLogger(__name__)


def _tree_stack(trees: Sequence):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _tree_index(tree, i: int):
    """Index the leading (shard) axis of a host tree."""
    return jax.tree.map(lambda x: np.asarray(x)[i], tree)


def _shapes(tree) -> List[Tuple]:
    return [np.shape(leaf) for leaf in jax.tree.leaves(tree)]


def make_sharded_step_acc(
    plan: CompiledPlan, mesh, jitted: bool = True
) -> callable:
    """jit(shard_map(plan.step_acc)): each shard appends its emissions to
    its own on-device accumulator — the hot loop never fetches (same
    contract as the single-device executor). ``jitted=False`` returns
    the bare shard_map'd callable for callers that embed it in a larger
    program (the sharded bounded-replay scan).

    Inside the shard body every leaf carries a leading local shard dim of 1,
    stripped before the single-shard step and restored after, so the
    single-device compile path and the sharded path share all kernels.
    """

    # host-side, before the trace: where Pallas applies the sharded step
    # uses the same fused kernel as the single-device step, and a kernel
    # that does not survive the shard_map lowering raises here
    pallas_ops.warmup_shard()

    def local(states, acc, tape):
        states = jax.tree.map(lambda x: x[0], states)
        acc = jax.tree.map(lambda x: x[0], acc)
        tape = jax.tree.map(lambda x: x[0], tape)
        new_states, new_acc = plan.step_acc(
            states, acc, tape, SHARD_AXIS
        )
        expand = lambda t: jax.tree.map(lambda x: jnp.asarray(x)[None], t)
        return expand(new_states), expand(new_acc)

    smapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        # no collectives in the per-shard body; vma checking would also
        # reject the pallas kernel's un-annotated out_shape
        check_vma=False,
    )
    if not jitted:
        return smapped
    return jax.jit(smapped, donate_argnums=(0, 1))


class ShardedJob(Job):
    """A Job whose plans run sharded over a device mesh.

    Semantics parity with reference parallelism (SURVEY.md §2.7): group-by
    streams are key-partitioned so every group's state lives on exactly one
    shard (exact results); shuffle streams are round-robined so stateful
    cross-event queries (patterns without keys) match within a shard, exactly
    as the reference's random channel selection does for partitionKey −1.
    """

    def __init__(
        self,
        plans: Sequence[CompiledPlan],
        sources,
        mesh=None,
        n_shards: Optional[int] = None,
        **kwargs,
    ) -> None:
        self.mesh = mesh if mesh is not None else make_cep_mesh(n_shards)
        self.n_shards = self.mesh.devices.size
        self._routers: Dict[str, Router] = {}
        self._state_sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        super().__init__(plans, sources, **kwargs)

    # -- plan management -----------------------------------------------------
    def add_plan(self, plan: CompiledPlan, dynamic: bool = False) -> None:
        # dynamic-group folding is a single-device optimization; sharded
        # adds keep one runtime per plan (dynamic flag accepted for API
        # parity)
        # artifact-declared host columns (e.g. #window.cron's window
        # ids) are PURE functions of event data — safe to evaluate
        # per shard — unlike the pushdown preds the guard below strips
        art_keys = {
            hc.out_key
            for a in plan.artifacts
            for hc in getattr(a, "host_columns", ())
        }
        if any(getattr(a, "lazy_pairs", ()) for a in plan.artifacts) or any(
            hp.out_key not in art_keys for hp in plan.spec.host_preds
        ):
            # lazy projection / predicate pushdown are single-device
            # (the ordinal ring and the host mask evaluation live on one
            # ingest host): auto-recompile without them instead of
            # refusing
            _LOG.warning(
                "%s: lazy projection / predicate pushdown are "
                "single-device; recompiling the plan without them for "
                "the sharded mesh",
                plan.plan_id,
            )
            plan = plan.recompiled(
                lazy_projection=False, pred_pushdown=False
            )
        parts = plan.partitions
        if plan.chained:
            # chained consumers keep per-shard state and the producer's
            # partitioning never propagates through the intermediate
            # stream: pin the whole plan to one owner shard (exact,
            # unscaled) rather than emit per-shard partial aggregates
            _LOG.warning(
                "%s: chained queries run owner-pinned on a sharded mesh "
                "(exact results; intermediate streams are shard-local)",
                plan.plan_id,
            )
            from ..query.planner import StreamPartition

            parts = {
                sid: StreamPartition("broadcast") for sid in parts
            }
        stacked = _tree_stack([plan.init_state()] * self.n_shards)
        stacked = jax.device_put(stacked, self._state_sharding)
        init_acc = jax.jit(
            lambda: _tree_stack(
                [plan.init_acc()] * self.n_shards
            ),
            out_shardings=self._state_sharding,
        )
        self._plans[plan.plan_id] = _PlanRuntime(
            plan=plan,
            states=stacked,
            jitted_acc=make_sharded_step_acc(plan, self.mesh),
            jitted_init_acc=init_acc,
            acc=init_acc(),
        )
        self._routers[plan.plan_id] = Router(self.n_shards, parts)
        # per-plan emission attribution (Job._attr_scope reads the
        # stamp on the drain-decode path)
        self._stamp_attribution(plan)

    def remove_plan(self, plan_id: str) -> None:
        super().remove_plan(plan_id)
        self._routers.pop(plan_id, None)

    # -- sharded hot path ----------------------------------------------------
    def _grow_stacked(self, plan: CompiledPlan, stacked):
        """Group tables grow when host interning discovers new keys; growth
        is detected abstractly (shape metadata only — no device transfer in
        the common case) and, when needed, applied per shard and restacked."""
        probe = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x)[1:], x.dtype), stacked
        )
        grown = jax.eval_shape(plan.grow_state, probe)
        if _shapes(grown) == _shapes(probe):
            return stacked
        host = jax.device_get(stacked)
        shards = [
            plan.grow_state(_tree_index(host, s))
            for s in range(self.n_shards)
        ]
        return jax.device_put(_tree_stack(shards), self._state_sharding)

    def _step_plan(self, rt: _PlanRuntime, ready: List[EventBatch]) -> None:
        plan = rt.plan
        tel = self.telemetry
        involved = [
            b for b in ready if b.stream_id in plan.spec.stream_codes
        ]
        if not involved:
            return
        router = self._routers[plan.plan_id]
        with tel.span("route"):
            rows = router.select(involved)
        # per-shard placement visibility: a skewed key distribution
        # shows up here long before it shows up as one hot shard
        tel.gauge(
            f"route.per_shard_events.{plan.plan_id}",
            [int(r) for r in router.routed],
        )
        # sticky capacity: pad the end-of-stream tail up to the compiled
        # shape instead of bucketing down into a fresh XLA executable
        rt.tape_capacity = max(
            rt.tape_capacity,
            bucket_size(int(rows.counts.max()) or 1),
        )
        with tel.span("tape_build"):
            stacked_tape = self._stage_tapes(rt, involved, rows)
        # host-driven re-bucketing after group growth is staging-class
        # work (device_get + per-shard rebuild + explicit device_put)
        with _staging_allow():
            rt.states = self._grow_stacked(plan, rt.states)
        # per-shard on-device accumulation; no fetch in the hot loop
        # (drained in bulk through Job's drain queue and fetch thread).
        # The tape is committed, one row per chip: the call moves nothing
        with tel.span("dispatch"):
            self._issue_step()
            rt.states, rt.acc = rt.jitted_acc(
                rt.states, rt.acc, stacked_tape
            )
            clock = self._starve_clock()
            if clock is not None:
                # a ticket for the starvation clock alone (no ticket
                # window, no segment record on a mesh): the step's own
                # count prefix, ready when every shard has finished the
                # step. The next step donates it, after the clock has
                # dropped it for that step's (StarveClock.watch)
                clock.watch(rt.acc["meta"])
            rt.acc_dirty = True
            if rt.dirty_since is None:
                rt.dirty_since = time.monotonic()
        tel.inc("shard.cycles")
        self._count_merges(rt)
        # shared no-overflow contract (Job._update_drain_hint); strip the
        # leading shard axis via shape metadata only
        self._update_drain_hint(
            plan,
            rt.tape_capacity,
            lambda name: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x)[1:], x.dtype
                ),
                rt.states.get(name),
            ),
        )

    def _stage_tapes(self, rt: _PlanRuntime, involved, rows):
        """One cycle's tapes from one ``build_tape`` call with the
        router's selection: each leaf comes out ``[n_shards,
        capacity]``, row ``s`` filled in place with the tape of the
        rows shard ``s`` receives, and is uploaded in ONE explicit
        sharded put: row ``s`` goes straight to the chip that steps shard
        ``s``, and no eager device program runs per leaf."""
        tel = self.telemetry
        with tel.span("shard_build"):
            stacked = build_tape(
                rt.plan.spec, involved, self._epoch_ms, rt.tape_capacity,
                want_prov=False, rows=rows,
            )[0]
        tel.inc("shard.tape_builds")
        with tel.span("shard_put"):
            stacked = jax.device_put(stacked, self._state_sharding)
        tel.inc("shard.tape_puts")
        return stacked

    # -- drain: Job's queue and fetch thread; two seams know the rank ------
    # ShardedJob inherits the whole drain (request, readiness, FIFO
    # poll, backlog wait, the blocking barrier, prewarm_drains) and
    # supplies only what knows that the accumulator is stacked
    # ``[shards, ...]``: the slice program of a fetch width and the
    # fetch-thread body.

    # smallest fetch bucket of a sharded drain. A sharded slice program
    # is under the persistent cache's threshold and compiles every run,
    # so prewarm_drains pays for each width from here to the capacity:
    # the floor keeps that to ten programs at the default budget, and a
    # sparse drain's fetch to 16,384 slots a shard
    MIN_FETCH_WIDTH = 1 << 14

    @staticmethod
    def _pack_data(rt: _PlanRuntime, acc: Dict, width: int):
        """Job._pack_data over the stacked accumulator: one device array
        holding ``buf[:, :, :width]``, each shard's slice cut on its own
        chip, jitted once a width."""
        jits = getattr(rt, "pack_jits", None)
        if jits is None:
            # fst:threadsafe lazy idempotent init, GIL-atomic dict ops (as Job._pack_data): prewarm (run loop) and the fetch thread may race the first width; the loser's entry is identical
            jits = rt.pack_jits = {}
        fn = jits.get(width)
        if fn is None:
            # fst:hotpath
            def pack(a, _w=width):
                shards, rows, _ = a["buf"].shape
                return jax.lax.slice(
                    a["buf"], (0, 0, 0), (shards, rows, _w)
                )

            fn = jits[width] = jax.jit(pack)
        return fn(acc)

    @staticmethod
    # fst:thread-root name=drain-fetch
    def _fetch_acc(rt: _PlanRuntime, acc: Dict, want: bool,
                   columnar: frozenset,
                   stages: Dict, tel: MetricsRegistry, drain: int):
        """Job._fetch_acc over the stacked accumulator, on the same one
        fetch thread: the count prefix of every shard in one fetch
        (``meta`` is ``(shards, 4, A)``), one data fetch at the width
        bucketed from the fullest shard, ``drain_decode`` per shard,
        and the cross-shard merge — so the run loop receives what
        Job's does, ``{artifact: [(schema, payload)]}`` with counts and
        overflow summed over shards, and only emits.

        The lane is per stream, by Job's rule (``columnar``, resolved
        at request time): a columnar stream decodes to one ColumnBatch
        a shard and merges with one argsort (ColumnBatch.merge_by_ts);
        every other stream decodes to tuples and merges with
        heapq.merge — the oracle the tests hold the columnar lane to.
        Both orders are the same: by timestamp, ties to the lower
        shard.

        Booked here as each ends: ``drain.fetch``, ``drain.decode``
        (the shards' decodes, summed; each shard's own into its
        histogram on the runtime, folded by metrics()), ``drain.merge``;
        ``stages["merge_s"]`` is the half of ``drain.emit`` this thread
        did and ``stages["off_loop"]`` whether it was the fetch thread
        (_drain_poll_inner books both)."""
        with tel.annotate("fst.drain.fetch", drain=drain):
            stages["t_fetch0"] = time.monotonic()
            meta = np.asarray(acc["meta"])  # phase one, every shard's
            counts, overflow = meta[:, 0], meta[:, 1]
            Job._book_prefix(tel, rt.plan, meta[:, 2:].sum(axis=0))
            max_n = int(counts.max()) if counts.size else 0
            stages["t_meta"] = time.monotonic()
            data = None
            if want and max_n:
                width = min(
                    bucket_size(
                        max_n, minimum=ShardedJob.MIN_FETCH_WIDTH
                    ),
                    rt.plan.acc_capacity(),
                )
                data = np.asarray(
                    ShardedJob._pack_data(rt, acc, width)
                )[:, :, :max_n]
            stages["t_dec0"] = time.monotonic()
        tel.record_seconds(
            "drain.fetch", stages["t_dec0"] - stages["t_fetch0"]
        )
        # stream id -> (artifact that first wrote it, schema, the
        # shards' payloads in shard order): artifacts that write one
        # stream merge into one emission
        streams: Dict[str, Tuple] = {}
        with tel.annotate("fst.drain.decode", drain=drain):
            if data is not None:
                shard_hists = getattr(rt, "_shard_decode_hists", None)
                if shard_hists is None:
                    shard_hists = rt._shard_decode_hists = [
                        LatencyHistogram() for _ in range(len(counts))
                    ]
                for s, hist in enumerate(shard_hists):
                    t0 = time.perf_counter()
                    shard = rt.plan.drain_decode(
                        counts[s], data[s], columnar_streams=columnar
                    )
                    hist.record_seconds(time.perf_counter() - t0)
                    for a in rt.plan.artifacts:
                        for schema, payload in shard.get(a.name) or []:
                            streams.setdefault(
                                schema.stream_id, (a.name, schema, [])
                            )[2].append(payload)
            t_merge0 = time.monotonic()
        tel.record_seconds("drain.decode", t_merge0 - stages["t_dec0"])
        decoded = None
        if data is not None:
            decoded = {a.name: [] for a in rt.plan.artifacts}
            n_rows = n_columnar = 0  # handed to the emit tails
            with tel.annotate("fst.drain.merge", drain=drain):
                for name, schema, parts in streams.values():
                    n = sum(len(p) for p in parts)
                    n_rows += n
                    if all(isinstance(p, ColumnBatch) for p in parts):
                        n_columnar += n
                        merged = ColumnBatch.merge_by_ts(parts)
                    else:
                        # a stream that decoded rows anywhere (a stacked
                        # group writes rows into a stream a plain
                        # artifact writes columns into) stays whole on
                        # the row lane
                        merged = list(heapq.merge(
                            *(
                                p.rows() if isinstance(p, ColumnBatch)
                                else p
                                for p in parts
                            ),
                            key=lambda r: r[0],
                        ))
                    decoded[name].append((schema, merged))
            stages["merge_s"] = time.monotonic() - t_merge0
            tel.record_seconds("drain.merge", stages["merge_s"])
            # how often the columnar lane engages: rows this drain
            # hands to _emit_columns over all rows it hands on
            tel.inc("drain.rows", n_rows)
            tel.inc("drain.rows_columnar", n_columnar)
        stages["t_fetch1"] = time.monotonic()
        # for drains.fetched_off_loop: where all of the above ran
        stages["off_loop"] = threading.current_thread().name.startswith(
            "fst-fetch"
        )
        return counts.sum(axis=0), overflow.sum(axis=0), decoded

    def _drain_poll_inner(
        self, rt: _PlanRuntime, block: bool = False, limit: int = 0
    ) -> None:
        """Job's poll, one drain a call, so that each completed drain
        books its own ``drain.emit`` (merge + emit + sinks): the
        merge's seconds from the fetch thread plus the emission's here,
        which starts when the poll has the fetch's result — at entry,
        or when the fetch thread finished if the poll waited for it.
        ``drains.fetched_off_loop`` is bumped here too, in the poll
        that bumps ``drains.completed``, so that the two agree over
        any window: a drain fetched, decoded and merged anywhere but on
        the fetch thread is missing from it."""
        tel = self.telemetry
        done = 0
        while rt.drain_q and not (limit and done >= limit):
            head = rt.drain_q[0]
            t0 = time.monotonic()
            super()._drain_poll_inner(rt, block, 1)
            if rt.drain_q and rt.drain_q[0] is head:
                return  # not fetched yet, and not asked to wait
            done += 1
            stages = head.get("stages") or {}
            if stages.get("off_loop"):
                tel.inc("drains.fetched_off_loop")
            if tel.enabled and "merge_s" in stages:
                tel.record_seconds(
                    "drain.emit",
                    stages["merge_s"] + time.monotonic()
                    - max(t0, stages["t_fetch1"]),
                )

    def flush(self) -> None:
        for rt in self._plans.values():
            self._drain_plan(rt)
            if not rt.plan.has_flush:
                continue
            with self.telemetry.span("flush"):
                host = jax.device_get(rt.states)
                new_shards = []
                for s in range(self.n_shards):
                    st, outputs = rt.plan.flush(_tree_index(host, s))
                    new_shards.append(st)
                    if outputs:
                        self._decode_outputs(
                            rt.plan, outputs, only=set(outputs)
                        )
                rt.states = jax.device_put(
                    _tree_stack(new_shards), self._state_sharding
                )

    # -- observability -------------------------------------------------------
    def metrics(self, drain: bool = False):
        """Adds the cross-shard view: every shard's decode-time
        histogram folded into one (``LatencyHistogram.merge`` — the
        associative shard-aggregation primitive) plus the router's
        per-shard placement counts."""
        m = super().metrics(drain)
        if not self.telemetry.enabled:
            return m
        merged = LatencyHistogram()
        for rt in list(self._plans.values()):
            for h in getattr(rt, "_shard_decode_hists", ()):
                merged.merge(h)
        m["telemetry"]["histograms"]["drain.shard_decode"] = (
            merged.snapshot()
        )
        m["telemetry"]["gauges"]["route.cumulative_per_shard"] = {
            pid: [int(x) for x in r.routed]
            for pid, r in list(self._routers.items())
        }
        return m

    # -- results: merge shard-interleaved output back to time order ---------
    def results_with_ts(self, output_stream: str):
        self.drain_outputs()
        rows = list(self.collected.get(output_stream, []))
        rows.sort(key=lambda p: p[0])
        return rows

    def results(self, output_stream: str):
        return [row for _, row in self.results_with_ts(output_stream)]
