"""Per-plan engine capacities.

Every data-dependent structure in the engine is bounded (fixed-capacity
device arrays with counted overflow — SURVEY.md §7 hard parts 1-2).
These bounds were module constants in round 1; they are now a per-plan
configuration passed to ``compile_plan(..., config=...)``, the analog of
the config surface the reference delegates to Flink's ExecutionConfig
(SiddhiOperatorContext.java:43-48).

Raising a capacity changes state shapes, so two plans with different
configs never share executables — set them at compile time, not per
batch.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class EngineConfig:
    # chain matcher: carried partial matches per query
    pattern_pool: int = 1024
    # slot NFA: concurrent partial-match slots per query
    pattern_slots: int = 64
    # max events concurrently inside a time join's side, an
    # #window.externalTime and a #window.time under min / max /
    # distinctCount: paths whose step costs by this capacity (an
    # (events x capacity) matrix: refused over 65,536)
    time_window_capacity: int = 512
    # slots of the ring a #window.time with count / sum / avg / stddev
    # keeps its members in (compiler/time_window.py): the events the
    # span holds at the stream's peak rate, with headroom. It costs
    # memory alone (12 bytes a slot and 4 an argument more): the step
    # reads and writes a tape's width of it. A member lost because the
    # ring was full is a wrong answer, counted (state leaf `overflow`,
    # counter window.ring_evicted)
    time_ring_capacity: int = 512
    # max distinct timeBatch windows touched per micro-batch
    time_batch_slots: int = 64
    # #window.hop, the window join, #window.session and the per-key
    # length window of a partition ('partition with' + #window.length):
    # the group slots their state starts with. Slots expire and are
    # reused (the per-key window's under @purge), so the table needs the
    # keys a window (a session's gap, a partition's idle.period) holds,
    # not the keys ever seen: set it from the window and the rate at
    # which keys open, and the step never re-buckets (a recompile)
    # inside a steady stream
    hop_group_slots: int = 64
    # join ring slots per side (time/unbounded windows)
    join_window_capacity: int = 128
    # join output buffer capacity = factor * tape capacity
    join_out_factor: int = 4
    # rows per event table
    table_capacity: int = 1024
    # device output accumulator budget per plan
    acc_budget_bytes: int = 256 * 1024 * 1024
    # pre-padded query slots per dynamic chain group
    dyn_query_slots: int = 8
    # compile-window cap (None = auto): oversized micro-batches step in
    # chunks of this tape capacity instead of compiling one huge program
    # — XLA compile time scales with tape width, catastrophically so for
    # wide multi-query stacks
    max_tape_capacity: Optional[int] = None
    # late materialization for single-chain plans: projection-only
    # columns never ship to the device — the matcher emits event
    # ordinals and decode resolves them against host-retained batches.
    # Single-device jobs only (ShardedJob rejects lazy plans); carried
    # partial matches older than the host ring's byte budget (or a
    # checkpoint/restore) decode their lazy columns as None.
    lazy_projection: bool = False
    # host retention budget for lazy-projected columns (the ordinal ring)
    lazy_ring_budget_bytes: int = 256 * 1024 * 1024
    # wire predicate pushdown: host-evaluable predicates (single-chain /
    # single-select plans) are computed on the ingest host with numpy and
    # ship as ONE BIT per event, dropping their raw columns off the wire
    # — fewer bytes over the host->device link per event. Host
    # predicates see f64 where the device sees f32
    # (strictly closer to the reference's double semantics). Opt-in like
    # lazy_projection: a pushed plan keeps its own runtime (it cannot
    # fold into a recompile-free dynamic chain group, whose tape carries
    # the raw columns).
    pred_pushdown: bool = False
    # compiled-plan verification (analysis/plancheck.py): validate the
    # emitted artifact stack's invariants — schema agreement, slot-NFA
    # table well-formedness, padded-stack consistency, donation safety
    # — at compile() time. One extra trace per compile, no device
    # allocation. Off by default so bench hot paths never pay it; the
    # test lane turns it on globally via FST_VERIFY_PLANS=1
    # (tests/conftest.py), and FST_VERIFY_PLANS=0 force-disables even
    # an explicit True (bench escape hatch).
    verify_plans: bool = False
    # admission-time resource budgets (analysis/admit.py
    # AdmissionBudgets): when set, every compile is analyzed for
    # worst-case state footprint / output amplification / residency
    # and REJECTED (AdmissionError) on any ADM finding — the control
    # plane's per-tenant envelope. None = report-only tiers still run
    # under FST_VERIFY_PLANS (static hook validation on =1, full
    # footprint+signature on =full), but no budget verdicts.
    admission_budgets: Optional[object] = None


DEFAULT_CONFIG = EngineConfig()
