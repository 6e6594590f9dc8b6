"""Batched parameterized-circuit execution engine.

Layered on ``repro.core``: templates split circuits into static structure +
parameter vector, plans compile each structure once per backend, the batch
executor vmaps plans over parameter sweeps, and the scheduler batches
heterogeneous request traffic by plan key.
"""
from repro.engine.template import (  # noqa: F401
    CircuitTemplate, TemplateOp, fixed_op, template_of,
    qaoa_template, hea_template, PARAM_KINDS,
)
from repro.engine.plan import (  # noqa: F401
    CompiledPlan, PlanCache, PlanItem, CacheStats, compile_plan,
    resolve_diag_f, PARAM_OP_CLASS, GLOBAL_PLAN_CACHE,
)
from repro.engine.telemetry import (  # noqa: F401
    Histogram, MetricsRegistry, NULL_TRACER, ServedActivity, Span,
    SpanTracer, VectorizationProfile, device_scope, engine_registry,
    host_span, vectorization_profile,
)
from repro.engine.results import (  # noqa: F401
    MODE_EXPECTATION, MODE_NOISY, MODE_SHOTS, MODE_STATEVECTOR, NoiseChannel,
    ResultSpec, amplitude_damping, bit_flip, depolarizing, phase_flip,
)
from repro.engine.shapeclass import (  # noqa: F401
    ClassDispatch, ClassExecutable, class_row_tensors, class_slot_shapes,
    shape_class_key,
)
from repro.engine.batch import BatchExecutor  # noqa: F401
from repro.engine.scheduler import (  # noqa: F401
    BatchScheduler, InFlightBatch, Request, RequestState, SchedulerStats,
)
from repro.engine.ingest import (  # noqa: F401
    IngestClosed, IngestHandle, IngestRejected, IngestServer,
)
from repro.engine.resilience import (  # noqa: F401
    DeadlineExceeded, FaultInjector, InjectedFault, PlanBreaker, RequestRecord,
    RetryPolicy, ServingCheckpoint, replay_records, snapshot_records,
)
