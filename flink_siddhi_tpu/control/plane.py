"""The dynamic query control plane: admission gate + runtime facade.

ROADMAP direction #1 made real: the reference's L3 control plane
(``MetadataControlEvent`` / ``OperationControlEvent`` add, disable and
re-route SiddhiQL queries in a running Flink job — PAPER.md §L3,
``AddRouteOperator``) re-shaped for this engine's epoch-boundary
execution model. Three pieces live here:

* :class:`AdmissionGate` — the *before it touches the running stack*
  check: compile the candidate, run ``analysis/plancheck.verify_plan``
  (PLC-series structural findings) AND ``analysis/admit.admit_plan``
  (ADM-series resource verdicts against :class:`AdmissionBudgets`), and
  either return the JSON-safe admission summary a control event carries
  or raise :class:`ControlRejected` with the exact rule ids. The REST
  service calls this at the boundary (fail fast, 4xx with rule ids);
  the executor re-checks the carried verdict at apply time (defense in
  depth against events injected past the service).
* :class:`ControlPlane` — the programmatic facade over a running
  ``Job`` + ``ControlQueueSource``: ``admit`` / ``retire`` /
  ``set_enabled`` / ``status``. Mutations ride control events and take
  effect at epoch boundaries (micro-batch in streaming, on a dispatched
  segment; replay-epoch in resident mode — docs/control_plane.md has the
  exact contract per mode).
* re-exports of the AOT executable cache (``aotcache.py``) the
  ``Job`` uses so a shape class's first-compile cost is paid once.

What the reference's ``DynamicPartitioner`` does that this plane does
not yet: re-ROUTING — moving a live query between parallel operator
instances with its state. Queries here are re-routed only between
group slots on one device; cross-shard query migration remains open
(docs/control_plane.md states this honestly).
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, List, Optional

from .aotcache import AOTExecutableCache, CachedExecutables, cache_key
from .events import MetadataControlEvent, OperationControlEvent

_LOG = logging.getLogger(__name__)

__all__ = [
    "AOTExecutableCache",
    "AdmissionGate",
    "CachedExecutables",
    "ControlPlane",
    "ControlRejected",
    "cache_key",
]


class ControlRejected(Exception):
    """A candidate query refused by the admission gate. ``rules`` holds
    the exact PLC/ADM rule ids; ``findings`` the rendered messages."""

    def __init__(self, rules: List[str], findings: List[str], summary=None):
        self.rules = list(rules)
        self.findings = list(findings)
        self.summary = summary  # AdmissionReport.summary() when available
        super().__init__(
            "query admission rejected ["
            + ", ".join(self.rules)
            + "]:\n"
            + "\n".join(f"  {f}" for f in self.findings)
        )


class AdmissionGate:
    """Compile + statically verify + admission-analyze one CQL string.

    ``compile_fn(cql, plan_id) -> CompiledPlan`` is the caller's
    compiler (the same one the job's ``plan_compiler`` uses, so the
    gate judges exactly what would run). ``budgets`` is the tenant
    resource envelope (``analysis/admit.AdmissionBudgets``); None runs
    the report-only tiers (footprint + signature still computed — the
    summary is the AOT cache key carrier)."""

    def __init__(
        self,
        compile_fn: Callable,
        budgets=None,
        capacity: int = 128,
    ) -> None:
        self.compile_fn = compile_fn
        self.budgets = budgets
        self.capacity = capacity

    def __call__(self, cql: str, plan_id: str = "candidate") -> dict:
        """Returns the JSON-safe admission summary
        (``AdmissionReport.summary()`` + the PLC tier's implicit pass),
        or raises :class:`ControlRejected` / the compiler's own
        ``SiddhiQLError`` for unparsable input."""
        from ..analysis.admit import AdmissionError, analyze_plan
        from ..analysis.plancheck import PlanCheckError, verify_plan

        try:
            plan = self.compile_fn(cql, plan_id)
        except PlanCheckError as e:
            raise ControlRejected(
                [i.rule for i in e.issues],
                [i.render() for i in e.issues],
            ) from e
        except AdmissionError as e:
            raise ControlRejected(
                [i.rule for i in e.issues],
                [i.render() for i in e.issues],
                summary=e.report.summary() if e.report else None,
            ) from e
        # compile_plan may have verified already under FST_VERIFY_PLANS;
        # running the static+trace tiers again here is cheap (one
        # eval_shape, no XLA compile) and makes the gate self-contained
        # in production where the env var is absent
        plc = verify_plan(plan, trace=True, raise_on_error=False)
        if plc:
            raise ControlRejected(
                [i.rule for i in plc], [i.render() for i in plc]
            )
        report = analyze_plan(
            plan, budgets=self.budgets, capacity=self.capacity, deep=True
        )
        if report.findings:
            raise ControlRejected(
                [i.rule for i in report.findings],
                [i.render() for i in report.findings],
                summary=report.summary(),
            )
        return report.summary()


class ControlPlane:
    """Programmatic admit/retire/status over a running job.

    The plane never mutates the job directly: every mutation is a
    control event pushed onto ``control`` (a
    ``app.service.ControlQueueSource`` the job was constructed with),
    so it applies at the next epoch boundary on the run-loop thread —
    the same path REST calls and a real control topic take, and the
    reason a mutation can never tear a compiled segment (the executor
    force-dispatches the pending fused segment before applying, the
    PR 8 contract)."""

    def __init__(
        self,
        job,
        control,
        gate: Optional[AdmissionGate] = None,
    ) -> None:
        self.job = job
        self.control = control
        self.gate = gate

    # -- mutations (epoch-boundary, via control events) -----------------
    def admit(
        self,
        cql: str,
        plan_id: Optional[str] = None,
        tenant: Optional[str] = None,
        timestamp_ms: Optional[int] = None,
    ) -> str:
        """Gate (when configured) + push the add event. Returns the
        plan id; raises :class:`ControlRejected` when the gate refuses
        — a refused query never reaches the control stream at all."""
        b = MetadataControlEvent.builder()
        pid = plan_id or MetadataControlEvent.new_plan_id()
        summary = None
        if self.gate is not None:
            summary = self.gate(cql, plan_id=pid)
        b.add_execution_plan(cql, admission=summary, plan_id=pid)
        ev = b.build()
        ev.tenant = tenant
        self.control.push(ev, timestamp_ms=timestamp_ms)
        return pid

    def retire(
        self, plan_id: str, timestamp_ms: Optional[int] = None
    ) -> None:
        b = MetadataControlEvent.builder()
        b.remove_execution_plan(plan_id)
        self.control.push(b.build(), timestamp_ms=timestamp_ms)

    def set_enabled(
        self,
        plan_id: str,
        enabled: bool,
        timestamp_ms: Optional[int] = None,
    ) -> None:
        ev = (
            OperationControlEvent.enable_query(plan_id)
            if enabled
            else OperationControlEvent.disable_query(plan_id)
        )
        self.control.push(ev, timestamp_ms=timestamp_ms)

    # -- observation ----------------------------------------------------
    def status(self) -> Dict:
        """Control-plane view of the job: live plans (with fold
        host/slot), counters, AOT cache stats, and the recent-rejection
        ring — everything a tenant needs to see a refused add without
        log-diving."""
        job = self.job
        plans = {}
        for pid, rt in list(job._plans.items()):
            if pid.startswith(("@dyn:", "@shr:")):
                continue
            plans[pid] = {"enabled": rt.enabled, "folded": None}
        for pid, skey in list(job._shared_member.items()):
            e = job._shared.get(skey)
            if e is not None and pid in plans:
                plans[pid]["shared"] = {
                    "host": e["host_id"],
                    "members": len(e["members"]),
                }
        for pid, (host, slot) in list(job._folded.items()):
            plans[pid] = {
                "enabled": job._folded_enabled.get(pid, True),
                "folded": {"host": host, "slot": slot},
            }
        out = dict(job.control_status())
        out["plans"] = plans
        return out
