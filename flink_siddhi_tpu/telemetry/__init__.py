"""Telemetry subsystem: stage-attributed tracing, latency histograms,
and a per-Job metrics registry.

The role of Flink's operator metric groups + latency markers (Carbone
et al. 2015; PAPERS.md) for this TPU-native runtime: all host wall-clock
is attributed to a named stage via ``MetricsRegistry.span``, latency
distributions are log-bucketed HDR-style histograms (mergeable across
shards, bounded memory), and ``Job.metrics()`` / ``GET /api/v1/metrics``
snapshot the whole registry atomically.

Instrumentation stays OFF the jitted device path: spans and histogram
records happen at micro-batch / drain boundaries on the host only, so
the measured overhead on headline replay throughput is <2%
(docs/observability.md).
"""

from .flightrec import FlightRecorder
from .histogram import LatencyHistogram
from .openmetrics import render_openmetrics
from .registry import Counter, MetricsRegistry
from .slo import SLOPolicy, SLOWatchdog
from .spans import NULL_SPAN, StageTimes
from .tracing import TraceSampler

# Stage names that partition the RUN-LOOP thread's wall-clock (spans
# opened while another span is active on the same thread accrue under
# "nested.<name>" instead — see spans.StageTimes). Summing exactly
# these against an elapsed wall clock is how attribution.py's
# ``coverage`` is computed, and the benchmark's
# ``runloop_unattributed_share`` reads the same spans. Fetch-thread work
# (d2h + decode) intentionally overlaps this lane and is reported via
# the drain.* histograms instead.
TOP_LEVEL_STAGES = (
    # a caller's own set-up, booked with add_time
    "input_gen",
    "plan_compile",
    "job_init",
    "prewarm",
    # streaming micro-batch cycle (runtime/executor.py)
    "ingest",
    "reorder",
    "route",
    "tape_build",
    # fused streaming dispatch: the stacked segment's single async
    # H2D device_put, issued while the previous segment computes
    # (host-side enqueue time only — the transfer itself overlaps
    # the device)
    "stage.h2d_overlap",
    "dispatch",
    "backpressure_wait",
    "drain",
    # bounded-replay staging (runtime/replay.py)
    "stage.source_pull",
    "stage.h2d",
    "stage.compile",
    "stage.warm",
    "stage.prewarm",
    # bounded-replay execution
    "replay.dispatch",
    "replay.drain",
    "replay.reset",
    # end of stream
    "flush",
)

__all__ = [
    "Counter",
    "FlightRecorder",
    "LatencyHistogram",
    "MetricsRegistry",
    "NULL_SPAN",
    "SLOPolicy",
    "SLOWatchdog",
    "StageTimes",
    "TOP_LEVEL_STAGES",
    "TraceSampler",
    "render_openmetrics",
]
