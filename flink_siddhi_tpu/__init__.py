"""flink_siddhi_tpu — a TPU-native streaming complex-event-processing framework.

A ground-up JAX/XLA re-design of the capability surface of ``tammypi/flink-siddhi``
(reference layout: core/src/main/java/org/apache/flink/streaming/siddhi/): SiddhiQL
continuous queries — filters, projections, windows, joins, aggregations,
group-by/having, pattern (``every A -> B``) and sequence (``A+, B?`` with ``within``)
matching, event tables, user extensions — over unbounded event streams, with typed
stream registration, a dynamic query control plane, key/broadcast/shuffle routing,
event-time ordering with watermarks, and checkpoint/restore of *all* engine state.

Instead of embedding a per-event JVM interpreter inside a stream operator
(reference: AbstractSiddhiOperator.java:209-233 driving siddhi-core's InputHandler
per event), queries compile ahead-of-time into dense artifacts — predicate kernels,
NFA transition tables, segment-reduce window plans — that a ``jax.jit``-ed
``lax.scan`` advances over micro-batched columnar events, ``vmap``-ed across a query
axis and sharded across a key axis with ``shard_map`` over a ``jax.sharding.Mesh``.
"""

from .api.cep import SiddhiCEP, CEPEnvironment
from .api.stream import ExecutionStream, Row
from .compiler.output import ColumnBatch
from .runtime.executor import ColumnarSink, late_stream
from .runtime.sources import (
    BoundedDisorderWatermark,
    PunctuatedWatermark,
    WatermarkStrategy,
    WatermarkedSource,
    with_watermarks,
)
from .runtime.supervisor import RestartBudgetExceeded, Supervisor
from .schema.types import AttributeType
from .schema.stream_schema import StreamSchema
from .schema.batch import EventBatch
from .control.events import (
    ControlEvent,
    MetadataControlEvent,
    OperationControlEvent,
    CONTROL_STREAM,
)
from .control.plane import AdmissionGate, ControlPlane, ControlRejected

__version__ = "0.1.0"

__all__ = [
    "SiddhiCEP",
    "CEPEnvironment",
    "ColumnBatch",
    "ColumnarSink",
    "ExecutionStream",
    "Row",
    "AttributeType",
    "StreamSchema",
    "EventBatch",
    "AdmissionGate",
    "ControlEvent",
    "ControlPlane",
    "ControlRejected",
    "MetadataControlEvent",
    "OperationControlEvent",
    "CONTROL_STREAM",
    "RestartBudgetExceeded",
    "Supervisor",
    "BoundedDisorderWatermark",
    "PunctuatedWatermark",
    "WatermarkStrategy",
    "WatermarkedSource",
    "late_stream",
    "with_watermarks",
]
