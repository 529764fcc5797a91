from repro.runtime.errors import (  # noqa: F401
    FALLBACK_LEVELS, ExecutionReport, FaultInjector, LaunchError,
    NonFiniteStateError, PlanInvariantError, PlanRejected, QueueFull,
    RequestTimeout, ServingFault)
from repro.runtime.ft import FTConfig, StragglerWatchdog, TrainLoop  # noqa: F401
from repro.runtime.obs import (  # noqa: F401
    Counter, Histogram, MetricsRegistry, NULL_TRACER, NullTracer, Span,
    Tracer, as_tracer, fence, measure_us, monotonic_s, slot_signature)
