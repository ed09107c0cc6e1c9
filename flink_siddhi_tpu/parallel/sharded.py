"""Sharded execution: one shard_map-ed device step over a mesh of shards.

The multi-device analog of the reference's N parallel operator subtasks, each
hosting a full copy of every execution plan (AbstractSiddhiOperator.java:
301-313): plan state is stacked along a leading ``shards`` axis and laid out
with a ``NamedSharding`` so each device owns its shard; the jitted step is a
``jax.shard_map`` that advances every shard's plan in ONE SPMD program. Events
reach shards through the host Router (key-hash / round-robin / broadcast —
the DynamicPartitioner contract) as per-shard tapes stacked to a common
bucketed capacity.

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

import time

from ..compiler import pallas_ops
from ..compiler.output import ColumnBatch
from ..compiler.plan import CompiledPlan
from ..runtime.executor import Job, _PlanRuntime, _staging_allow
from ..runtime.tape import build_tape, bucket_size
from ..schema.batch import EventBatch
from ..telemetry import LatencyHistogram
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


def make_sharded_step(plan: CompiledPlan, mesh) -> callable:
    """jit(shard_map(plan.step)) over the ``shards`` mesh axis.

    Inside the shard body every leaf carries a leading local shard dim of 1,
    stripped before the single-shard step and restored after, so the
    single-device compile path and the sharded path share all kernels.
    """

    # host-side, before the trace: where Pallas applies the sharded step
    # uses the same fused kernel as the single-device step, and a kernel
    # that does not survive the shard_map lowering raises here
    pallas_ops.warmup_shard()

    def local(states, tape):
        states = jax.tree.map(lambda x: x[0], states)
        tape = jax.tree.map(lambda x: x[0], tape)
        new_states, outputs = plan.step(states, tape, SHARD_AXIS)
        expand = lambda t: jax.tree.map(lambda x: jnp.asarray(x)[None], t)
        return expand(new_states), expand(outputs)

    smapped = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
        # no collectives in the per-shard body; vma checking would also
        # reject the pallas kernel's un-annotated out_shape
        check_vma=False,
    )
    return jax.jit(smapped)


def make_sharded_step_acc(
    plan: CompiledPlan, mesh, jitted: bool = True
) -> callable:
    """jit(shard_map(plan.step_acc)): each shard appends its emissions to
    its own on-device accumulator — the hot loop never fetches (same
    contract as the single-device executor). ``jitted=False`` returns
    the bare shard_map'd callable for callers that embed it in a larger
    program (the sharded bounded-replay scan)."""

    pallas_ops.warmup_shard()  # as make_sharded_step

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
            jitted=make_sharded_step(plan, self.mesh),
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
            shards = router.route_all(involved)
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
            bucket_size(max(sum(len(b) for b in sh) for sh in shards) or 1),
        )
        with tel.span("tape_build"):
            stacked_tape = self._stage_tapes(rt, shards)
        # host-driven re-bucketing after group growth is staging-class
        # work (device_get + per-shard rebuild + explicit device_put)
        with _staging_allow():
            rt.states = self._grow_stacked(plan, rt.states)
        # per-shard on-device accumulation; no fetch in the hot loop
        # (drained in bulk by _drain_plan, same as the single-device Job).
        # The tape is committed, one row per chip: the call moves nothing
        with tel.span("dispatch"):
            rt.states, rt.acc = rt.jitted_acc(
                rt.states, rt.acc, stacked_tape
            )
            rt.acc_dirty = True
            if rt.dirty_since is None:
                rt.dirty_since = time.monotonic()
        tel.inc("shard.cycles")
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

    def _stage_tapes(self, rt: _PlanRuntime, shards):
        """One cycle's per-shard tapes, stacked leaf by leaf in host
        memory to ``[n_shards, capacity]`` and uploaded in ONE explicit
        sharded put: row ``s`` goes straight to the chip that steps
        shard ``s``, and no eager device program runs per leaf."""
        tel = self.telemetry
        tapes = [
            build_tape(
                rt.plan.spec, sh, self._epoch_ms, rt.tape_capacity,
                want_prov=False,
            )[0]
            for sh in shards
        ]
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *tapes)
        with tel.span("shard_put"):
            stacked = jax.device_put(stacked, self._state_sharding)
        tel.inc("shard.tape_puts")
        return stacked

    def prewarm_drains(self, widths=None) -> None:
        # no-op: Job's packed-drain programs (jit_pack, one per fetch
        # width) serve its fetch thread; a sharded drain still slices
        # the stacked accumulator directly, on the run loop
        return

    def drain_outputs(self, wait: bool = True) -> None:
        # still synchronous, on the run loop: request, two blocking
        # fetches, decode, merge and emit before the next cycle (Job's
        # wait=False fetch thread and ticketed readiness are not wired
        # here). What IS shared with Job is the host side after the
        # fetch: drain_decode's columnar lane, ColumnBatch and
        # _emit_columns (see _drain_plan_body)
        for rt in self._plans.values():
            self._drain_plan(rt)

    def _interval_drain(self) -> None:
        for rt in self._plans.values():
            if self._has_consumers(rt):
                self._drain_plan(rt)

    def _drain_plan(self, rt: _PlanRuntime) -> None:
        # the drain IS the engine's intended device->host boundary:
        # gathering the sharded accumulator to host (and the scalar
        # ops the cross-shard gather stages) is the design's own
        # transfer, so the hot-loop guard must not trip on it
        with _staging_allow():
            with self.telemetry.span("drain"):
                self._drain_plan_body(rt)

    def _drain_plan_body(self, rt: _PlanRuntime) -> None:
        if rt.acc is None or not rt.plan.artifacts:
            return
        # footprint meter poll (same drain-boundary contract as Job):
        # leaf nbytes sums whole stacked shards — metadata only
        self._update_footprint(rt)
        t_dirty = rt.dirty_since
        rt.acc_dirty = False
        rt.dirty_since = None
        tel = self.telemetry
        t_req = time.monotonic()
        # the drain's legs, each a profiler annotation and a histogram:
        # drain.fetch (request -> both fetches done), drain.decode (the
        # per-shard decodes, summed), drain.emit (merge, emit, sinks).
        # The lane is picked per stream from the sinks, by Job's own
        # rule: a stream in ``columnar`` (retention off, every sink has
        # accept_columns, no snapshot limiter) decodes to one
        # ColumnBatch a shard, merges with one argsort and leaves
        # through _emit_columns — no Python row exists between the
        # chips and the sink. Every other stream takes the row lane
        # below (decode to tuples, heapq.merge, _emit_rows), which is
        # also the oracle the tests hold the columnar lane to.
        columnar = self._columnar_streams(rt)
        with tel.annotate("fst.drain.fetch"):
            meta = np.asarray(rt.acc["meta"])  # (shards, 2, A) — one fetch
            counts, overflow = meta[:, 0], meta[:, 1]
            seen = getattr(rt, "_overflow_seen", None)
            already = 0 if seen is None else int(np.sum(seen))
            total = int(overflow.sum())
            if total > already:  # log new drops once, not per check
                _LOG.warning(
                    "%s: %d emissions dropped across shards (accumulator "
                    "full; raise EngineConfig.acc_budget_bytes or drain "
                    "more often)", rt.plan.plan_id, total - already,
                )
                tel.inc("faults.emissions_dropped", total - already)
            rt._overflow_seen = overflow
            max_n = int(counts.max()) if counts.size else 0
            if max_n == 0:
                return
            # bucketed fetch width: stable slice shapes (see
            # Job._drain_plan)
            fetch_n = min(bucket_size(max_n, minimum=1024),
                          rt.plan.acc_capacity())
            data = np.asarray(
                rt.acc["buf"][:, :, :fetch_n]
            )[:, :, :max_n]  # fetch two
        tel.record_seconds("drain.fetch", time.monotonic() - t_req)
        rt.acc = rt.jitted_init_acc()
        rt._overflow_seen = None  # counters reset with the accumulator
        # per-shard decode-time histograms, kept PER SHARD on the
        # runtime and folded into the job registry after the sweep —
        # the mergeable-across-shards histogram contract in production
        # use (tests assert merge associativity)
        shard_hists = getattr(rt, "_shard_decode_hists", None)
        if shard_hists is None and tel.enabled:
            shard_hists = rt._shard_decode_hists = [
                LatencyHistogram() for _ in range(self.n_shards)
            ]
        # per-event traces complete PER SHARD into per-shard histograms
        # (merged by metrics() — the same cross-shard fold as the decode
        # hists). Rate-limited streams are excluded: their rows may be
        # thinned at emission, and a thinned row must not stop the
        # clock — those complete post-limiter in _emit_rows or
        # _emit_columns instead (into the base trace.e2e, without
        # per-shard attribution).
        shard_trace = getattr(rt, "_shard_trace_hists", None)
        if shard_trace is None and self.tracer.enabled:
            shard_trace = rt._shard_trace_hists = [
                LatencyHistogram() for _ in range(self.n_shards)
            ]
        # merge each output's per-shard (already time-ordered) payloads
        # by timestamp so sinks observe near-monotonic time across shards
        per_schema = {}
        decode_s = 0.0
        epoch = self._epoch_ms or 0
        for s in range(self.n_shards):
            with tel.annotate("fst.drain.decode", shard=s):
                t0 = time.perf_counter()
                decoded = rt.plan.drain_decode(
                    counts[s], data[s], columnar_streams=columnar
                )
                dt = time.perf_counter() - t0
            decode_s += dt
            if shard_hists is not None:
                shard_hists[s].record_seconds(dt)
            for a in rt.plan.artifacts:
                # a payload is a ColumnBatch (columnar lane) or a list
                # of (ts, row) pairs; len() counts rows of either
                for schema, payload in decoded.get(a.name) or []:
                    if (
                        shard_trace is not None
                        and schema.stream_id not in self._rate_limiters
                    ):
                        if isinstance(payload, ColumnBatch):
                            self.tracer.complete_ts(
                                epoch, payload.ts, hist=shard_trace[s]
                            )
                        else:
                            self.tracer.complete_rows(
                                epoch, payload, hist=shard_trace[s]
                            )
                    if tel.enabled:
                        # pre-rate-limit match attribution, summed
                        # across shards (same scope the single-device
                        # drain records into — the merged cross-shard
                        # view falls out of one registry)
                        sc = self._attr_scope(schema)
                        if sc is not None:
                            sc.inc("matches", len(payload))
                    per_schema.setdefault(
                        schema.stream_id, (schema, [])
                    )[1].append(payload)
        tel.record_seconds("drain.decode", decode_s)
        t_emit = time.monotonic()
        n_rows = n_columnar = 0  # handed to the emit tails, pre-limiter
        with tel.annotate("fst.drain.emit"):
            for schema, parts in per_schema.values():
                n = sum(len(p) for p in parts)
                n_rows += n
                if all(isinstance(p, ColumnBatch) for p in parts):
                    # heapq.merge's order exactly, ties included (equal
                    # timestamps: the lower shard first). Traces of an
                    # unlimited stream completed per shard above, so
                    # _emit_columns' own completion finds none pending;
                    # a rate-limited one completes there, post-limiter
                    n_columnar += n
                    self._emit_columns(
                        schema, ColumnBatch.merge_by_ts(parts)
                    )
                    continue
                # a stream that decoded rows anywhere (a stacked group
                # writes rows into a stream a plain artifact writes
                # columns into) stays whole on the row lane
                shard_rows = [
                    p.rows() if isinstance(p, ColumnBatch) else p
                    for p in parts
                ]
                if self._sinks.get(schema.stream_id):
                    # sinks observe emission order: merge shards by
                    # timestamp
                    rows = list(
                        heapq.merge(*shard_rows, key=lambda p: p[0])
                    )
                else:
                    # collectors re-sort on read; skip the per-row merge
                    rows = [r for sh in shard_rows for r in sh]
                # traces already completed per shard above, except for
                # rate-limited streams (completed post-limiter here)
                self._emit_rows(
                    schema, rows,
                    trace=schema.stream_id in self._rate_limiters,
                )
        if tel.enabled:
            # same semantics as Job's drain.total: meta check -> rows
            # emitted (the timestamp merge and sink delivery included),
            # so the metric is comparable across job kinds
            now = time.monotonic()
            tel.record_seconds("drain.emit", now - t_emit)
            tel.record_seconds("drain.total", now - t_req)
            stale = None
            if t_dirty is not None and self._has_consumers(rt):
                # same contract as Job: age of the oldest undrained
                # match when its drain completed — consumer-visible
                # drains only (capacity swaps of unobserved plans are
                # not the scheduler's report card)
                stale = now - t_dirty
                tel.record_seconds("drain.staleness", stale)
            tel.inc("drains.completed")
            # how often the columnar lane engages: rows this drain
            # handed to _emit_columns over all rows it handed on
            tel.inc("drain.rows", n_rows)
            tel.inc("drain.rows_columnar", n_columnar)
            self._scoped_drain_record(rt, now - t_req, stale)

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
        # fold per-shard trace histograms into the trace view's e2e
        m["telemetry"]["trace"] = self.tracer.snapshot(
            extra_hists=[
                h
                for rt in list(self._plans.values())
                for h in getattr(rt, "_shard_trace_hists", ())
            ]
        )
        return m

    # -- results: merge shard-interleaved output back to time order ---------
    def results_with_ts(self, output_stream: str):
        self.drain_outputs()
        rows = list(self.collected.get(output_stream, []))
        rows.sort(key=lambda p: p[0])
        return rows

    def results(self, output_stream: str):
        return [row for _, row in self.results_with_ts(output_stream)]
