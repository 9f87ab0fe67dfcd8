"""repro.obs — tracing + metrics for the multi-tenant serving stack.

One recorder serves both runtimes (virtual-clock simulation and real
kernel dispatchers) because every clock in the stack is a caller-supplied
float.  See ``trace.TraceRecorder`` for the hook surface and
``histogram.LogHistogram`` for the fixed-memory aggregation primitive, and
``spans.span`` for the trainer's and the data plane's program spans.
"""
from repro.obs.config import (
    FEDERATED_STAGES,
    LIFECYCLE_STAGES,
    RECOVERY_STAGES,
    ObservabilityConfig,
)
from repro.obs.histogram import LogHistogram
from repro.obs.spans import span
from repro.obs.trace import (
    OUTCOMES,
    STAGE_METRICS,
    CircuitTrace,
    RoundEvent,
    TraceBuffer,
    TraceRecorder,
    WorkerSpan,
    WorkerTimeline,
    validate_trace,
)

__all__ = [
    "FEDERATED_STAGES",
    "LIFECYCLE_STAGES",
    "OUTCOMES",
    "RECOVERY_STAGES",
    "STAGE_METRICS",
    "CircuitTrace",
    "LogHistogram",
    "ObservabilityConfig",
    "RoundEvent",
    "TraceBuffer",
    "TraceRecorder",
    "WorkerSpan",
    "WorkerTimeline",
    "span",
    "validate_trace",
]
