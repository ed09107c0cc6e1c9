"""REST query-management service.

The reference sketched this API and left every route unimplemented
(CEPService.scala:43-95: ``/api/v1/queries`` CRUD, all bodies ``???``).
This is the working version: a small stdlib HTTP server that translates
REST calls into control-plane events (control/events.py) pushed onto a
``ControlQueueSource`` that a running Job consumes at micro-batch
boundaries — the same path a control stream takes (§3.4 of the
reference: MetadataControlEvent / OperationControlEvent).

Routes (JSON in/out):
    GET    /api/v1/metrics               -> Job.metrics() snapshot
    GET    /api/v1/metrics/prometheus    -> the same snapshot rendered
                                           as Prometheus text format
                                           (plan/tenant labels on the
                                           scoped series; telemetry/
                                           openmetrics.py)
    GET    /api/v1/traces                -> per-event trace sampling view
    GET    /api/v1/flightrecorder        -> the job's flight-recorder
                                           journal (telemetry/
                                           flightrec.py), filterable:
                                           ?kind=control&plan=q1&
                                           tenant=t0&since_seq=42&
                                           limit=100
    GET    /api/v1/slo                   -> SLO watchdog snapshot
                                           (telemetry/slo.py):
                                           per-tenant compliance, burn
                                           rates, journal-reconciled
                                           violation account
    GET    /api/v1/health                -> supervisor liveness: alive +
                                           last-checkpoint age + restart
                                           count (Supervisor.health();
                                           503 once the restart budget
                                           is exhausted) + the control-
                                           plane counters/cache/refusal
                                           block (job.control_status())
    GET    /api/v1/queries               -> {"queries": [{id, tenant,
                                           enabled, folded}]} — the
                                           whole fleet in ONE poll
    GET    /api/v1/queries/<id>          -> per-query status: enabled,
                                           tenant, fold host/slot and
                                           live scoped metrics, or the
                                           recorded refusal (rule ids)
    POST   /api/v1/queries   {"cql": s,
                              "tenant"?} -> {"id": plan_id,
                                            "admission": summary}
    PUT    /api/v1/queries/<id> {"cql"}  -> {"id": id}
    DELETE /api/v1/queries/<id>          -> {"id": id}
    POST   /api/v1/queries/<id>/enable   -> {"id": id}
    POST   /api/v1/queries/<id>/disable  -> {"id": id}

Admission (docs/control_plane.md): construct the service with
``admission=control.plane.AdmissionGate(compile_fn, budgets)`` and every
POST/PUT body is compiled + plancheck-verified + admission-analyzed
BEFORE an event is pushed — a hostile or over-budget query is refused
at the boundary with HTTP 422 and the exact PLC/ADM rule ids in the
body, and the verdict summary rides the control event so the executor
re-checks it at apply time (defense in depth)."""

from __future__ import annotations

import json
import logging
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional, Tuple

import numpy as np

from ..control.events import (
    MetadataControlEvent,
    OperationControlEvent,
)

_LOG = logging.getLogger(__name__)


def _json_safe(obj):
    """Recursively convert a metrics snapshot to JSON-serializable
    primitives: numpy scalars/arrays (watermarks, routed-event gauges)
    become Python ints/floats/lists at the REST boundary."""
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return [_json_safe(v) for v in obj.tolist()]
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, np.floating):
        return float(obj)
    if obj is None or isinstance(obj, (bool, int, float, str)):
        return obj
    return str(obj)


class ControlQueueSource:
    """Push-style control source: the service enqueues events, the job's
    executor drains them at micro-batch boundaries. Stays open until
    ``close()`` (a pipeline with a live control service never finishes on
    its own)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._pending: List[Tuple[int, object]] = []
        self._clock_ms = 0
        self._closed = False

    def push(self, event, timestamp_ms: Optional[int] = None) -> None:
        with self._lock:
            if self._closed:
                raise RuntimeError("control source closed")
            ts = (
                int(timestamp_ms)
                if timestamp_ms is not None
                else int(event.created_ms)
            )
            self._pending.append((ts, event))

    def close(self) -> None:
        with self._lock:
            self._closed = True

    def poll(self, max_events: int):
        with self._lock:
            take = self._pending[:max_events]
            self._pending = self._pending[max_events:]
            done = self._closed and not self._pending
            # a live (empty) control queue must not hold back the data
            # watermark: control applies at the next batch boundary anyway
            wm = np.iinfo(np.int64).max if (done or not self._pending) else (
                take[-1][0] if take else None
            )
            return take, wm, done


class QueryControlService:
    """HTTP facade over a ControlQueueSource (optionally mirroring a live
    Job for GET /queries)."""

    def __init__(
        self,
        control: ControlQueueSource,
        job=None,
        host: str = "127.0.0.1",
        port: int = 0,
        validate=None,  # callable(cql) raising on bad queries
        supervisor=None,  # runtime.supervisor.Supervisor for /health
        admission=None,  # AdmissionGate: (cql, plan_id) -> summary
        fleet_ops=None,  # {"drain": fn} hooks a replica process wires
    ) -> None:
        self.control = control
        self.job = job
        self.validate = validate
        self.supervisor = supervisor
        self.admission = admission
        self.fleet_ops = fleet_ops
        service = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _reply(self, code: int, payload: dict) -> None:
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _reply_text(
                self, code: int, text: str, content_type: str
            ) -> None:
                body = text.encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _body(self) -> dict:
                n = int(self.headers.get("Content-Length") or 0)
                if not n:
                    return {}
                try:
                    return json.loads(self.rfile.read(n))
                except ValueError:
                    return {}

            def _route(self):
                parts = [p for p in self.path.split("/") if p]
                # expect ['api', 'v1', 'queries', <id>?, <action>?]
                if parts[:3] != ["api", "v1", "queries"]:
                    return None
                return parts[3:]

            # fst:thread-root name=service
            def do_GET(self):
                from urllib.parse import parse_qs, urlsplit

                url = urlsplit(self.path)
                parts = [p for p in url.path.split("/") if p]
                if parts == ["api", "v1", "flightrecorder"]:
                    # the flight-recorder journal (telemetry/
                    # flightrec.py), filterable by kind / plan /
                    # tenant / since-seq — the black-box poll a
                    # post-incident investigation starts from.
                    # Lock-guarded snapshot: safe off the run-loop
                    # thread.
                    job = service._live_job()
                    fr = getattr(job, "flightrec", None)
                    if fr is None:
                        return self._reply(
                            200, {"seq": 0, "events": []}
                        )
                    q = parse_qs(url.query)

                    def _one(name):
                        v = q.get(name)
                        return v[0] if v else None

                    # seq BEFORE events(): the two reads are separate
                    # lock acquisitions, and an event recorded between
                    # them must not be skipped by a cursor client —
                    # reading seq first means it can only UNDERstate,
                    # so such an event re-delivers on the next poll
                    # (at-least-once, never lost)
                    seq = fr.seq
                    try:
                        since = _one("since_seq")
                        limit = _one("limit")
                        events = fr.events(
                            kind=_one("kind"),
                            plan=_one("plan"),
                            tenant=_one("tenant"),
                            since_seq=(
                                int(since) if since is not None else None
                            ),
                            limit=(
                                int(limit) if limit is not None else 512
                            ),
                        )
                    except ValueError:
                        return self._reply(
                            400,
                            {"error": "since_seq/limit must be ints"},
                        )
                    return self._reply(
                        200,
                        {"seq": seq, "events": _json_safe(events)},
                    )
                if parts == ["api", "v1", "health"]:
                    # liveness + checkpoint freshness + restart count.
                    # 200 while supervised-and-alive (or merely
                    # unsupervised); 503 once the restart budget is
                    # exhausted — a probe can alert on status alone.
                    sup = service.supervisor
                    if sup is not None:
                        payload = _json_safe(sup.health())
                        return self._reply(
                            200 if payload.get("alive") else 503,
                            payload,
                        )
                    if service.job is not None:
                        return self._reply(200, {
                            "alive": True,
                            "supervised": False,
                            "finished": bool(service.job.finished),
                            "processed_events": int(
                                service.job.processed_events
                            ),
                            # event-time robustness: silent sources and
                            # late-row drops are alertable from /health
                            "idle_sources": (
                                service.job.idle_source_ids()
                            ),
                            "late_dropped": int(
                                service.job.late_dropped
                            ),
                            # control-plane observability: admitted /
                            # retired / refused counters, AOT cache
                            # hit/miss/evict, and the refusal ring — a
                            # refused tenant add is alertable from
                            # /health alone
                            "control": _json_safe(
                                service.job.control_status()
                            ),
                            # SLO watchdog compact view (telemetry/
                            # slo.py): worst-burning tenant + active
                            # violation count, same block the
                            # supervised payload carries
                            "slo": _json_safe(
                                service.job.slo.health_summary()
                                if getattr(
                                    service.job, "slo", None
                                )
                                else None
                            ),
                            # serving-fleet block (fleet/,
                            # docs/fleet.md): replica id/role, warm-
                            # store counters, last handoff — None
                            # outside a fleet (the supervised payload
                            # carries the same block via
                            # Supervisor.health())
                            "fleet": _json_safe(
                                service.job.fleet_status()
                                if hasattr(
                                    service.job, "fleet_status"
                                )
                                else None
                            ),
                        })
                    return self._reply(
                        200, {"alive": True, "supervised": False}
                    )
                if parts == ["api", "v1", "slo"]:
                    # the SLO watchdog's full snapshot (telemetry/
                    # slo.py): per-tenant compliance, burn rates, and
                    # the journal-reconciled violation account
                    job = service._live_job()
                    slo = getattr(job, "slo", None)
                    if slo is None:
                        return self._reply(200, {})
                    return self._reply(200, _json_safe(slo.snapshot()))
                if parts == ["api", "v1", "metrics", "prometheus"]:
                    # OpenMetrics exposition (docs/observability.md):
                    # the scraping story without a bespoke JSON client.
                    # Same host-side snapshot as /metrics below.
                    from ..telemetry.openmetrics import CONTENT_TYPE

                    job = service._live_job()
                    if job is None:
                        return self._reply_text(
                            200, "# no job attached\n", CONTENT_TYPE
                        )
                    return self._reply_text(
                        200, job.openmetrics(), CONTENT_TYPE
                    )
                if parts == ["api", "v1", "metrics"]:
                    job = service._live_job()
                    if job is None:
                        return self._reply(200, {})
                    # metrics(drain=False): host-side registry snapshot
                    # only — never touches the device from this thread
                    # (response schema: docs/observability.md)
                    return self._reply(
                        200, _json_safe(job.metrics())
                    )
                if parts == ["api", "v1", "traces"]:
                    # per-event trace sampling view (telemetry/tracing):
                    # sample rate, counters, the end-to-end histogram,
                    # and the ring of recently-completed traces
                    job = service._live_job()
                    tracer = getattr(job, "tracer", None)
                    if tracer is None:
                        return self._reply(200, {})
                    return self._reply(
                        200, _json_safe(tracer.snapshot())
                    )
                tail = self._route()
                if tail is None:
                    return self._reply(404, {"error": "not found"})
                if len(tail) == 1:
                    # per-query status: live state, fold host/slot, or
                    # the recorded refusal (by rule id) for a plan the
                    # gate turned away
                    return self._reply(
                        *service._query_status(tail[0])
                    )
                if tail:
                    return self._reply(404, {"error": "not found"})
                # one poll shows the whole fleet: id + tenant + enabled
                # + fold host/slot per entry (previously bare ids, so
                # fleet state took N+1 requests)
                job = service._live_job()
                listing = (
                    job.query_listing() if job is not None else []
                )
                self._reply(200, {"queries": _json_safe(listing)})

            # fst:thread-root name=service
            def do_POST(self):
                parts = [p for p in self.path.split("/") if p]
                if parts == ["api", "v1", "fleet", "drain"]:
                    # rolling-restart handoff (docs/fleet.md): ask the
                    # replica to finish at the next checkpoint
                    # boundary — final checkpoint + warm-store persist
                    # + commit-log epoch land before the process exits
                    fn = (service.fleet_ops or {}).get("drain")
                    if fn is None:
                        return self._reply(
                            404, {"error": "not a fleet replica"}
                        )
                    return self._reply(
                        202, _json_safe(fn() or {"draining": True})
                    )
                tail = self._route()
                if tail is None:
                    return self._reply(404, {"error": "not found"})
                if not tail:  # add query
                    body = self._body()
                    cql = body.get("cql")
                    if not cql:
                        return self._reply(400, {"error": "missing cql"})
                    err = service._check(cql)
                    if err:
                        return self._reply(400, {"error": err})
                    # a client may supply the plan id (fleet router
                    # fan-out: every replica must admit the SAME query
                    # under the SAME id or per-replica status/retire
                    # would diverge); otherwise the service mints one
                    plan_id = body.get("id")
                    if plan_id is not None and (
                        not isinstance(plan_id, str)
                        or not re.fullmatch(r"[\w.:-]{1,128}", plan_id)
                    ):
                        return self._reply(
                            400, {"error": "invalid id"}
                        )
                    if plan_id is None:
                        plan_id = MetadataControlEvent.new_plan_id()
                    summary, reject = service._admit(
                        cql, plan_id, tenant=body.get("tenant")
                    )
                    if reject is not None:
                        return self._reply(422, reject)
                    b = MetadataControlEvent.builder()
                    b.add_execution_plan(
                        cql, admission=summary, plan_id=plan_id
                    )
                    ev = b.build()
                    ev.tenant = body.get("tenant")
                    service.control.push(ev)
                    return self._reply(
                        201, {"id": plan_id, "admission": summary}
                    )
                if len(tail) == 2 and tail[1] in ("enable", "disable"):
                    ev = (
                        OperationControlEvent.enable_query(tail[0])
                        if tail[1] == "enable"
                        else OperationControlEvent.disable_query(tail[0])
                    )
                    service.control.push(ev)
                    return self._reply(200, {"id": tail[0]})
                self._reply(404, {"error": "not found"})

            # fst:thread-root name=service
            def do_PUT(self):
                tail = self._route()
                if tail is None or len(tail) != 1:
                    return self._reply(404, {"error": "not found"})
                body = self._body()
                cql = body.get("cql")
                if not cql:
                    return self._reply(400, {"error": "missing cql"})
                err = service._check(cql)
                if err:
                    return self._reply(400, {"error": err})
                summary, reject = service._admit(
                    cql, tail[0], tenant=body.get("tenant")
                )
                if reject is not None:
                    return self._reply(422, reject)
                b = MetadataControlEvent.builder()
                b.update_execution_plan(tail[0], cql)
                if summary is not None:
                    b.with_admission(tail[0], summary)
                ev = b.build()
                ev.tenant = body.get("tenant")
                service.control.push(ev)
                self._reply(200, {"id": tail[0], "admission": summary})

            # fst:thread-root name=service
            def do_DELETE(self):
                tail = self._route()
                if tail is None or len(tail) != 1:
                    return self._reply(404, {"error": "not found"})
                b = MetadataControlEvent.builder()
                b.remove_execution_plan(tail[0])
                service.control.push(b.build())
                self._reply(200, {"id": tail[0]})

        self._server = ThreadingHTTPServer((host, port), Handler)
        self._thread: Optional[threading.Thread] = None

    def _live_job(self):
        """The job every GET route reads: the explicitly-attached one,
        else the supervised pipeline's CURRENT job (``Supervisor.job``
        is a GIL-atomic read; None mid-restart). The fallback makes the
        whole observability surface — metrics, prometheus, traces,
        queries, flight recorder, SLO — scrapeable on a supervised
        pipeline without re-wiring the service at every restart."""
        job = self.job
        if job is None and self.supervisor is not None:
            job = self.supervisor.job
        return job

    def _admit(self, cql: str, plan_id: str, tenant=None):
        """Run the admission gate at the REST boundary. Returns
        ``(summary, None)`` on pass (summary None when no gate is
        configured) or ``(None, reject_payload)`` carrying the exact
        PLC/ADM rule ids — the 422 body. A refusal is also recorded in
        the attached job's rejection ring (source ``"service"``), so a
        tenant add turned away at the boundary shows up in
        ``GET /health`` and ``GET /queries/<id>`` like an apply-time
        one — not only in the 422 response the caller may have
        dropped."""
        if self.admission is None:
            return None, None
        from ..control.plane import ControlRejected
        from ..query.lexer import SiddhiQLError

        try:
            return self.admission(cql, plan_id), None
        except ControlRejected as e:
            rules, findings = e.rules, e.findings
        except SiddhiQLError as e:
            rules, findings = ["CQL000"], [f"{type(e).__name__}: {e}"]
        except Exception as e:  # noqa: BLE001 — recorded, never hidden
            # a compiler or device error is not a bad query: its own
            # rule id (as Job._compile_admitted), traceback at ERROR
            _LOG.exception("admission gate: engine error on %s", plan_id)
            rules, findings = ["ENG000"], [f"{type(e).__name__}: {e}"]
        job = self._live_job()
        if job is not None:
            job._record_rejection(
                plan_id, rules, findings, tenant, source="service"
            )
        return None, {
            "error": "admission rejected",
            "id": plan_id,
            "rules": rules,
            "findings": findings,
        }

    def _query_status(self, plan_id: str):
        """(code, payload) for GET /api/v1/queries/<id>."""
        job = self._live_job()
        if job is None:
            return 404, {"error": "no job attached"}
        folded = job._folded.get(plan_id)
        if folded is not None:
            host, slot = folded
            return 200, {
                "id": plan_id,
                "state": "live",
                "tenant": job.tenant_of(plan_id),
                "enabled": bool(
                    job._folded_enabled.get(plan_id, True)
                ),
                "folded": {"host": host, "slot": int(slot)},
                # live scoped metrics: rows/matches/drain legs and the
                # shared host's footprint (docs/observability.md)
                "metrics": _json_safe(job.plan_metrics(plan_id)),
            }
        rt = job._plans.get(plan_id)
        if rt is not None:
            return 200, {
                "id": plan_id,
                "state": "live",
                "tenant": job.tenant_of(plan_id),
                "enabled": bool(rt.enabled),
                "folded": None,
                "metrics": _json_safe(job.plan_metrics(plan_id)),
            }
        rej = job.control_rejections.get(plan_id)
        if rej is not None:
            return 200, {
                "id": plan_id,
                "state": "rejected",
                **_json_safe(rej),
            }
        return 404, {"error": f"unknown query {plan_id!r}"}

    def _check(self, cql: str) -> Optional[str]:
        """Fail-fast validation at the REST boundary (parity with the
        reference's graph-build-time validateSiddhiApp,
        AbstractSiddhiOperator.java:291-299). Returns an error string or
        None."""
        if self.validate is None:
            return None
        try:
            self.validate(cql)
            return None
        except Exception as e:
            return str(e)

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def start(self) -> "QueryControlService":
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
