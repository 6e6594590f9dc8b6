"""Async streaming request scheduler with a reliable request lifecycle.

The serving analogue of the paper's fixed-cost amortization: requests whose
templates share a structure hash (and therefore a compiled plan) are grouped
into batches up to ``max_batch``, padded to the next power of two so only
O(log max_batch) distinct batched programs ever compile, and dispatched as
one vmapped execution.

Dispatch is *streamed*: ``submit`` returns a future-like :class:`Request`
handle, and batches are launched through the executor's non-blocking
``dispatch_batch`` path.  Up to ``inflight`` launched batches stay unwaited,
so batch *k+1* is grouped, padded, and its params staged on the host while
batch *k* executes on the device — the latency-hiding discipline the paper
applies to fixed costs, applied to host/device overlap.  ``drain`` is the
synchronous path (each batch blocks before the next launches); ``drain_async``
keeps the in-flight window open and ``sync`` retires it.

Every request moves through an explicit lifecycle::

    QUEUED -> DISPATCHED -> DONE | FAILED
               |    ^
               v    | (redispatch after backoff)
             RETRYING -> FAILED | SHED

and no path drops a request: a batch that raises (at plan compile, dispatch,
or device execution) affects exactly its own requests, and every other
batch still runs.  Without a retry policy a batch failure is terminal
``FAILED`` with the exception recorded on ``Request.error``; with
``retry=`` (a :class:`~repro.engine.resilience.RetryPolicy`) transient
failures re-enqueue the failed chunk — intact, so its padded batch size
and therefore its bitwise results are preserved — onto a backoff queue,
and only a request whose retry budget is exhausted (or whose error is not
transient) finalizes ``FAILED``.  Requests may carry a deadline
(``submit(deadline_ms=...)``): a past-deadline request is ``SHED`` (a
distinct terminal state, error :class:`DeadlineExceeded`) *before* its
chunk wastes a dispatch.  Latencies are recorded only after device
results are ready — an idle scheduler reports no latency at all rather
than a fake 0.0 ms.

The scheduler is safe under concurrent producers: the queue, the in-flight
window, and every counter are guarded (``SchedulerStats`` carries its own
lock; batches retire idempotently under a per-batch lock), and drain loops
never busy-spin: :meth:`BatchScheduler.poll` is the non-blocking step
(launch full/aged groups, retire only batches whose device results are
already available), while :meth:`BatchScheduler.wait_for_work` /
``drain_async(wait_ms=)`` give scheduler-level loops a condition wait on
submissions.  (The ingest front end pairs ``poll`` with its *own* intake
condition, which also covers its producer lanes.)  Time is
injectable (``clock=``) so concurrency tests can step aging triggers and
latencies deterministically (:class:`repro.testing.FakeClock`).
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Callable, Sequence

import jax
import numpy as np

from repro.core import statevec as SV
from repro.core.circuits import Circuit
from repro.engine.batch import BatchExecutor
from repro.engine.resilience import DeadlineExceeded, SITE_FINALIZE
from repro.engine.results import MODE_STATEVECTOR, ResultSpec
from repro.engine.telemetry import (Histogram, NULL_TRACER, STAGE_DEVICE_READY,
                                    STAGE_DISPATCH, STAGE_DONE, STAGE_FAILED,
                                    STAGE_RETRYING, STAGE_SHED, STAGE_SUBMIT,
                                    host_span)
from repro.engine.template import CircuitTemplate, template_of

# retained latency samples for percentile estimates; totals stay exact
# (Histogram keeps count/sum/min/max over every sample forever)
LATENCY_WINDOW = 4096


class RequestState:
    """Lifecycle states of a scheduled request.

    Transitions follow an explicit legal-transition table
    (``_LEGAL_TRANSITIONS``): the fault-free path is strictly forward —
    ``QUEUED -> DISPATCHED -> DONE | FAILED`` — and every submitted
    request reaches a terminal state.  Under a retry policy a transient
    batch failure moves its requests to ``RETRYING`` (from ``QUEUED`` for
    a dispatch-time failure, from ``DISPATCHED`` for a device-side one)
    and back to ``DISPATCHED`` on redispatch — the one sanctioned cycle;
    a past-deadline request is ``SHED`` instead of dispatched.  No path
    re-queues a terminal request or drops one.  ``Request.done`` /
    ``Request.ok`` are the terminal-state predicates; ``Request.wait()``
    blocks on a ``DISPATCHED`` request's in-flight batch.
    """

    QUEUED = "QUEUED"          # submitted, waiting in the scheduler queue
    DISPATCHED = "DISPATCHED"  # launched on device, result not yet retired
    RETRYING = "RETRYING"      # transient failure; awaiting backoff redispatch
    DONE = "DONE"              # result available on Request.result
    FAILED = "FAILED"          # execution raised; Request.error holds why
    SHED = "SHED"              # deadline exceeded before dispatch


_TERMINAL_STATES = frozenset(
    {RequestState.DONE, RequestState.FAILED, RequestState.SHED})

# the full legal lifecycle: forward-only plus the one sanctioned retry
# cycle (RETRYING -> DISPATCHED).  RETRYING -> RETRYING is a redispatch
# that failed again before reaching the device (dispatch-time fault).
_LEGAL_TRANSITIONS = frozenset({
    (RequestState.QUEUED, RequestState.DISPATCHED),
    (RequestState.QUEUED, RequestState.RETRYING),
    (RequestState.QUEUED, RequestState.FAILED),
    (RequestState.QUEUED, RequestState.SHED),
    (RequestState.DISPATCHED, RequestState.DONE),
    (RequestState.DISPATCHED, RequestState.FAILED),
    (RequestState.DISPATCHED, RequestState.RETRYING),
    (RequestState.RETRYING, RequestState.DISPATCHED),
    (RequestState.RETRYING, RequestState.RETRYING),
    (RequestState.RETRYING, RequestState.FAILED),
    (RequestState.RETRYING, RequestState.SHED),
})


@dataclasses.dataclass
class Request:
    """One circuit execution moving through the scheduler lifecycle."""

    req_id: int
    template: CircuitTemplate
    params: np.ndarray               # [P]
    submitted: float
    state: str = RequestState.QUEUED
    # statevector mode resolves to a State; shots to int32[k] basis-state
    # samples; expectation/noisy to f32[num_observables] — never the state
    result: "SV.State | np.ndarray | None" = None
    latency: float | None = None     # seconds, submit -> result ready
    error: Exception | None = None
    history: list = dataclasses.field(default_factory=list)
    retries: int = 0                 # completed retry re-enqueues so far
    deadline: float | None = None    # absolute (scheduler-clock) deadline
    result_spec: ResultSpec | None = None   # None = statevector mode
    _batch: "InFlightBatch | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    _key: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)
    # the key this request was actually grouped under: its shape-class key
    # when class-routed, else its exact plan key (== _key)
    _gkey: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def __post_init__(self):
        if not self.history:
            self.history.append(self.state)

    def _transition(self, new: str) -> None:
        """Legal-table state change; raises on any unsanctioned move.

        Enforced (not just documented) so a concurrency bug that double-
        retires or re-queues a request fails loudly in the stress suite
        instead of silently corrupting the lifecycle history.  The table
        admits exactly one cycle — ``RETRYING -> DISPATCHED`` — so a
        terminal state still can never be left and a request can never be
        dispatched twice without an intervening RETRYING.
        """
        if (self.state, new) not in _LEGAL_TRANSITIONS:
            raise RuntimeError(
                f"request {self.req_id}: illegal lifecycle transition "
                f"{self.state} -> {new} (history: {self.history})")
        self.state = new
        self.history.append(new)

    @property
    def done(self) -> bool:
        """Terminal: the request ended DONE, FAILED, or SHED."""
        return self.state in _TERMINAL_STATES

    @property
    def ok(self) -> bool:
        return self.state == RequestState.DONE

    def wait(self) -> "Request":
        """Block until this request is terminal (requires it be dispatched)."""
        if self.done:
            return self
        if self._batch is None:
            raise RuntimeError(
                f"request {self.req_id} is {self.state}; call drain() / "
                f"drain_async() to dispatch it before waiting")
        self._batch.finalize()
        return self


def validate_params(template: CircuitTemplate | Circuit,
                    params) -> tuple[CircuitTemplate, np.ndarray]:
    """Canonical submission validation: Circuit -> template conversion and
    parameter-vector coercion/shape check.  Shared by the scheduler and the
    ingest front end so the two entry points can never drift."""
    if isinstance(template, Circuit):
        template = template_of(template)
    p = (np.zeros(template.num_params, np.float32) if params is None
         else np.asarray(params, np.float32).reshape(-1))
    if p.shape[0] != template.num_params:
        raise ValueError(f"{template.name}: expected "
                         f"{template.num_params} params, got {p.shape[0]}")
    return template, p


def validate_sweep(template: CircuitTemplate, params_matrix) -> np.ndarray:
    """Canonical ``[B, P]`` sweep-matrix coercion: a 1-D array is B separate
    bindings when the template takes one parameter, a single P-parameter
    binding otherwise."""
    arr = np.asarray(params_matrix, np.float32)
    if arr.ndim == 1:
        arr = (arr.reshape(-1, 1) if template.num_params == 1
               else arr.reshape(1, -1))
    if arr.ndim != 2 or arr.shape[1] != template.num_params:
        raise ValueError(
            f"{template.name}: params matrix must be "
            f"[B, {template.num_params}], got {tuple(arr.shape)}")
    return arr


def _pad_size(b: int, max_batch: int) -> int:
    """Next power of two >= b, capped at max_batch."""
    p = 1
    while p < b:
        p <<= 1
    return min(p, max_batch)


# (seed, trajectory) stamped on padding rows.  The trajectory half makes
# the pair unreachable by real traffic: served rows index trajectories
# 0..unravelings-1, never 2**32 - 1, so a filler row's PRNG stream is
# never a replay of a request's sampling epilogue
_FILLER_ROWKEY = 0xFFFFFFFF


def _stage(chunk: list[Request], rows: list[int] | None, klass: bool,
           padded: int) -> tuple:
    """``(params matrix, rowkeys, templates)`` of one chunk, padded to
    ``padded`` device rows.

    A noisy request's ``rows[i]`` trajectories are each stamped with
    (request key, trajectory index): randomness never depends on batch
    position.  Filler rows are inert: zero params and a dead rowkey — a
    padded slot must never re-execute a real request's sampling epilogue
    (replicating the last row would re-run its full unraveling, and its
    payload would differ from the real row's only by being discarded —
    wasted flops and a misleading trace).  ``templates`` (per row) only
    for a shape-class chunk."""
    if rows is None:
        pm = np.stack([r.params for r in chunk])
        rowkeys = None
        templates = [r.template for r in chunk] if klass else None
    else:
        pm = np.concatenate([np.repeat(r.params[None, :], k, axis=0)
                             for r, k in zip(chunk, rows)])
        rowkeys = np.concatenate([
            np.stack([np.full(k, r.result_spec.key, np.uint32),
                      np.arange(k, dtype=np.uint32)], axis=1)
            for r, k in zip(chunk, rows)])
        templates = ([r.template for r, k in zip(chunk, rows)
                      for _ in range(k)] if klass else None)
    b = pm.shape[0]
    if padded > b:
        pm = np.concatenate(
            [pm, np.zeros((padded - b, pm.shape[1]), np.float32)])
        if rowkeys is not None:
            rowkeys = np.concatenate(
                [rowkeys, np.full((padded - b, 2), _FILLER_ROWKEY,
                                  np.uint32)])
    return pm, rowkeys, templates


@dataclasses.dataclass
class SchedulerStats:
    """Aggregate serving counters, safe under concurrent submitters.

    Every mutation goes through a method that holds the internal lock, so
    8 producer threads hammering ``submit`` while a drain loop retires
    batches never lose an increment; ``summary()`` snapshots under the same
    lock.  (The lock lives outside the dataclass fields so equality/repr
    semantics are unchanged.)

    ``latencies`` is a bounded :class:`~repro.engine.telemetry.Histogram`
    (carrying its own lock): a long-running serve holds fixed memory —
    count and mean stay exact over every request ever served, while the
    p50/p99 estimates cover the most recent ``LATENCY_WINDOW`` samples.
    ``len(stats.latencies)`` is still the total recorded count.
    """

    requests: int = 0       #: guarded-by: _lock
    batches: int = 0        #: guarded-by: _lock
    batch_rows: int = 0     #: guarded-by: _lock
    padded_slots: int = 0   #: guarded-by: _lock
    failed: int = 0         #: guarded-by: _lock
    retried: int = 0        #: guarded-by: _lock
    shed: int = 0           #: guarded-by: _lock
    # shape-class routing counters (zero / absent from summaries unless the
    # scheduler actually class-routes)
    class_routed: int = 0   #: guarded-by: _lock
    class_batches: int = 0  #: guarded-by: _lock
    overflow_spills: int = 0  #: guarded-by: _lock
    # per-class routed request counts, keyed by the short class label
    #: guarded-by: _lock
    class_groups: dict = dataclasses.field(default_factory=dict)
    # per-result-mode request counts (statevector/shots/expectation/noisy)
    #: guarded-by: _lock
    modes: dict = dataclasses.field(default_factory=dict)
    # (not guarded-by _lock: the Histogram carries its own internal lock)
    latencies: Histogram = dataclasses.field(
        default_factory=lambda: Histogram(LATENCY_WINDOW, name="latency"))

    def __post_init__(self):
        self._lock = threading.Lock()

    def add_request(self, mode: str = MODE_STATEVECTOR) -> None:
        with self._lock:
            self.requests += 1
            self.modes[mode] = self.modes.get(mode, 0) + 1

    def add_batch(self, rows: int, padded_slots: int,
                  klass: bool = False) -> None:
        """Count one dispatched batch: ``rows`` real rows, ``padded_slots``
        filler rows, ``klass`` when it ran the shape-class program."""
        with self._lock:
            self.batches += 1
            self.batch_rows += rows
            self.padded_slots += padded_slots
            if klass:
                self.class_batches += 1

    def add_class_routed(self, label: str) -> None:
        """Count one request routed into the shape-class group ``label``."""
        with self._lock:
            self.class_routed += 1
            self.class_groups[label] = self.class_groups.get(label, 0) + 1

    def add_spill(self) -> None:
        """Count one capacity overflow: a request whose shape-class group
        was already at capacity, spilled to exact-key grouping."""
        with self._lock:
            self.overflow_spills += 1

    def add_failure(self) -> None:
        with self._lock:
            self.failed += 1

    def add_retried(self, k: int = 1) -> None:
        """Count ``k`` retry re-enqueues (one per request per attempt)."""
        with self._lock:
            self.retried += k

    def add_shed(self) -> None:
        with self._lock:
            self.shed += 1

    def add_latency(self, seconds: float) -> None:
        self.latencies.record(seconds)

    def summary(self) -> dict:
        with self._lock:
            out = {
                "requests": self.requests,
                "batches": self.batches,
                "padded_slots": self.padded_slots,
                "failed": self.failed,
                "retried": self.retried,
                "shed": self.shed,
            }
            # batch fill: real rows / device rows — the serving analogue of
            # vector-lane occupancy.  Absent until a batch has dispatched
            # (an idle scheduler reports no fabricated 100%)
            device_rows = self.batch_rows + self.padded_slots
            if device_rows:
                out["fill_rate"] = self.batch_rows / device_rows
            # routing counters only when class routing actually happened —
            # a per-key-only scheduler's summary is unchanged
            if self.class_routed or self.overflow_spills:
                out["class_routed"] = self.class_routed
                out["class_batches"] = self.class_batches
                out["overflow_spills"] = self.overflow_spills
                out["shape_classes"] = len(self.class_groups)
            # one counter per served result mode, only for modes actually
            # seen — an idle mode never fabricates a zero row
            out.update({f"mode_{m}": c
                        for m, c in sorted(self.modes.items())})
        # no latency keys at all for an idle scheduler — a fabricated 0.0 ms
        # percentile is indistinguishable from a genuinely fast one
        lat = self.latencies.summary()
        if lat:
            out.update({
                "latency_mean_ms": lat["mean"] * 1e3,
                "latency_p50_ms": lat["p50"] * 1e3,
                "latency_p99_ms": lat["p99"] * 1e3,
            })
        return out

    def routing_summary(self) -> dict:
        """Shape-class routing counters for the telemetry registry: fill
        rate, routed/spilled request counts, batches served by class
        programs, and per-class routed counts.  Empty before any batch
        dispatches so an idle source contributes no fabricated rows."""
        with self._lock:
            device_rows = self.batch_rows + self.padded_slots
            if not device_rows:
                return {}
            out = {
                "fill_rate": self.batch_rows / device_rows,
                "batch_rows": self.batch_rows,
                "class_routed": self.class_routed,
                "class_batches": self.class_batches,
                "overflow_spills": self.overflow_spills,
                "shape_classes": len(self.class_groups),
            }
            out.update({f"class_{label}": c
                        for label, c in sorted(self.class_groups.items())})
        return out


class InFlightBatch:
    """One launched batch whose device results have not been retired yet.

    ``scheduler`` (when given) routes device-side failures through the
    scheduler's retry path and feeds batch outcomes to the executor's
    plan breaker; without it a failure is terminal (the pre-resilience
    behavior, kept for direct construction in tests).  ``injector`` is
    the chaos hook for the ``finalize`` site (transient device loss at
    retire), and ``straggler`` — set by the scheduler from the injector's
    schedule — makes :attr:`ready` report not-ready for that many extra
    polls, modeling a retire hang without any wall-clock sleep.
    """

    def __init__(self, plan, requests: list[Request], raw,
                 stats: SchedulerStats,
                 clock: Callable[[], float] = time.perf_counter,
                 tracer=NULL_TRACER, scheduler=None, injector=None,
                 rows: list[int] | None = None, padded: int = 0):
        self.plan = plan
        self.requests = requests
        self.rows = rows                 # per-request row counts (result mode)
        self.padded = padded             # device rows, filler included
        self.raw = raw                   # unwaited device array [padded, ...]
        self.stats = stats
        self.clock = clock
        self.tracer = tracer
        self.scheduler = scheduler
        self.injector = injector
        self.straggler = 0               # extra not-ready polls (chaos runs)
        self.finalized = False           #: guarded-by: _flock
        self._flock = threading.Lock()   # finalize is idempotent *and* racy-
                                         # safe: wait() callers vs drain loop

    @property
    def ready(self) -> bool:
        """True when device results can be retired without blocking."""
        # lint-ok: EL001 racy-read by design: finalized only ever flips
        # False->True, so a stale read merely reports not-ready one poll
        # early; taking _flock here would serialize polls behind finalize
        if self.finalized:
            return True
        if self.straggler > 0:
            # injected straggler: only the single-dispatcher poll path reads
            # ready, so this unguarded countdown stays deterministic
            self.straggler -= 1
            return False
        try:
            return bool(self.raw.is_ready())
        except AttributeError:  # non-jax raw (test doubles): treat as ready
            return True

    def finalize(self) -> None:
        """Wait for device results and retire every request (idempotent);
        the profiler span ``repro.sched.finalize``."""
        with self._flock:
            if self.finalized:
                return
            self.finalized = True
            rows = len(self.requests) if self.rows is None else sum(self.rows)
            with host_span("repro.sched.finalize", rows=rows,
                           padded=self.padded,
                           req=self.requests[0].req_id):
                self._retire()

    def _retire(self) -> None:
        """Wait for device results and retire every request.  Caller holds
        ``_flock``."""
        try:
            if self.injector is not None:
                self.injector.fire(SITE_FINALIZE)
            jax.block_until_ready(self.raw)
        except Exception as e:  # noqa: BLE001 — device-side failure
            self.raw = None
            if self.scheduler is not None:
                # retry-aware path: transient faults re-enqueue the
                # whole chunk; budget-exhausted requests finalize FAILED
                self.scheduler._resolve_batch_failure(self.requests, e)
            else:
                _fail(self.requests, e, self.stats, self.clock(),
                      tracer=self.tracer)
            return
        now = self.clock()
        if self.plan.result is not None:
            # non-statevector payloads: collapse row expansion (noisy
            # trajectories average) back to one payload per request
            results = _reduce_result_rows(
                np.asarray(self.raw),
                self.rows if self.rows is not None
                else [1] * len(self.requests))
        else:
            results = self.plan.wrap_batch(self.raw,
                                           count=len(self.requests))
        for req, res in zip(self.requests, results):
            req.result = res
            req.latency = now - req.submitted
            req._transition(RequestState.DONE)
            self.stats.add_latency(req.latency)
        self.raw = None
        if self.scheduler is not None:
            # a success resets the plan breaker's consecutive-failure
            # count for this chunk's key
            self.scheduler._note_outcome(self.requests, ok=True)
        if self.tracer.enabled:
            # device retire at ``now`` (the latency stamp), finalize —
            # host-side wrap + lifecycle transitions — ends here
            end = self.clock()
            for req in self.requests:
                self.tracer.record(req.req_id, STAGE_DEVICE_READY, now)
                self.tracer.record(req.req_id, STAGE_DONE, end)


def _reduce_result_rows(arr: np.ndarray, rows: list[int]) -> list[np.ndarray]:
    """Collapse a row-expanded payload stack to one payload per request.

    ``arr`` is the stacked ``run_batch_result_raw`` output (padding rows
    past ``sum(rows)`` are discarded); a request occupying ``k > 1`` rows
    is a noisy unraveling whose trajectory expectations average (float64
    accumulation, so wide unravelings don't lose precision in fp32).
    """
    out: list[np.ndarray] = []
    off = 0
    for k in rows:
        seg = arr[off:off + k]
        off += k
        out.append(seg[0] if k == 1
                   else seg.mean(axis=0, dtype=np.float64)
                   .astype(np.float32))
    return out


def _fail(requests: list[Request], error: Exception,
          stats: SchedulerStats, now: float, tracer=NULL_TRACER) -> None:
    """Terminal FAILED transition: record error + latency, never re-raise.

    Failure latencies stay on the Request only — mixing time-to-failure into
    the aggregate percentiles would skew p50/p99 of the served traffic.
    """
    for req in requests:
        req.error = error
        req.latency = now - req.submitted
        req._transition(RequestState.FAILED)
        stats.add_failure()
        if tracer.enabled:
            tracer.record(req.req_id, STAGE_FAILED, now,
                          error=type(error).__name__)


@dataclasses.dataclass
class _Group:
    """One open queue group: its requests, row total, and open stamp.

    ``opened`` is the aging anchor — the *earliest* moment work for this
    grouping key started waiting, not merely the head request's submit
    stamp.  When a key re-opens while older co-batchable requests sit in
    the retry backlog, the open stamp inherits their wait start, so the
    aging trigger is monotone across re-opens (a key's effective age never
    jumps backwards just because a force-flush emptied its group).

    ``rows`` is the device-row total (a noisy request occupies its
    unraveling count), the quantity both the fullness trigger and the
    shape-class capacity check meter — request counts under-measure noisy
    traffic.
    """

    reqs: list = dataclasses.field(default_factory=list)
    opened: float = 0.0
    rows: int = 0


class BatchScheduler:
    """Groups queued requests by plan key and executes them batched.

    ``inflight`` bounds the window of launched-but-unretired batches
    (double-buffering at the default of 2).  ``max_wait_ms`` enables
    streaming dispatch from ``submit`` itself: a plan group launches as soon
    as it reaches ``max_batch`` requests, or once its oldest request has
    waited longer than ``max_wait_ms``; with the default ``None`` nothing
    launches until ``drain`` / ``drain_async`` / ``poll``.

    Safe under concurrent producers: the grouped queue and window are
    lock-guarded, and submissions notify a condition variable so drain
    loops (:class:`repro.engine.ingest.IngestServer`) block on
    :meth:`wait_for_work` instead of busy-spinning.  ``clock`` injects the
    time source used for submit stamps, aging triggers, and latencies
    (default ``time.perf_counter``; tests pass a fake).  ``tracer`` is a
    :class:`~repro.engine.telemetry.SpanTracer` recording per-request
    lifecycle events (submit → dispatch → device retire → finalize) off the
    same clock; the default :data:`~repro.engine.telemetry.NULL_TRACER` is
    disabled and every instrumentation site is gated on ``tracer.enabled``,
    so an untraced scheduler does zero telemetry work.
    """

    def __init__(self, executor: BatchExecutor | None = None,
                 max_batch: int = 64, pad_to_pow2: bool = True,
                 inflight: int = 2, max_wait_ms: float | None = None,
                 clock: Callable[[], float] | None = None,
                 tracer=None, retry=None, class_routing: bool = False,
                 capacity_factor: float = 2.0):
        if inflight < 0:
            raise ValueError(f"inflight must be >= 0, got {inflight}")
        if capacity_factor < 1.0:
            raise ValueError(
                f"capacity_factor must be >= 1.0, got {capacity_factor}")
        self.executor = executor if executor is not None else BatchExecutor()
        self.max_batch = max_batch
        self.pad_to_pow2 = pad_to_pow2
        self.inflight = inflight
        self.max_wait_ms = max_wait_ms
        # shape-class routing (repro.engine.shapeclass): group requests by
        # canonical item-sequence shape instead of exact plan key, so a
        # long-tailed template mix fills batches.  ``capacity_factor`` is
        # the MoE-style expert capacity — an *open* class group holds at
        # most capacity_factor * max_batch rows; a request that would
        # overflow it spills to its exact plan key (never dropped, never
        # unboundedly padded)
        self.class_routing = class_routing
        self.capacity_factor = capacity_factor
        self._class_labels: dict = {}    #: guarded-by: _lock
        # retry policy (repro.engine.resilience.RetryPolicy); None keeps the
        # pre-resilience semantics: any batch failure is terminal FAILED
        self.retry = retry
        self.stats = SchedulerStats()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self._clock = clock if clock is not None else time.perf_counter
        self._ids = itertools.count()
        self._batch_ids = itertools.count()
        # one lock guards the queue + window; the condition variable is
        # signalled on every submit so drain loops can sleep between bursts
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)
        self._window: collections.deque[InFlightBatch] = collections.deque()  #: guarded-by: _lock, _work
        # the queue, grouped by plan key (or shape-class key under class
        # routing), maintained incrementally so the streaming trigger check
        # in submit() stays O(group count)
        self._groups: dict[tuple, _Group] = {}  #: guarded-by: _lock, _work
        # failed chunks awaiting backoff redispatch: (not_before, chunk).
        # Chunks are re-enqueued *intact* — never merged with new arrivals —
        # so a retried batch keeps its padded size and its results stay
        # bitwise-equal to a fault-free run of the same traffic
        self._retries: list[tuple[float, list[Request]]] = []  #: guarded-by: _lock, _work

    @property
    def clock(self) -> Callable[[], float]:
        return self._clock

    @property
    def pending(self) -> list[Request]:
        """Queued (not yet dispatched) requests, in submit order per group,
        plus any failed chunks awaiting their retry backoff."""
        with self._lock:
            out = [r for g in self._groups.values() for r in g.reqs]
            out += [r for _, reqs in self._retries for r in reqs]
        return out

    @property
    def backoff_pending(self) -> bool:
        """True while any failed chunk awaits its retry backoff — drain
        loops must keep ticking (timed sleeps) rather than wait untimed."""
        with self._lock:
            return bool(self._retries)

    def outstanding(self) -> list[Request]:
        """Every non-terminal request — queued, awaiting retry backoff, or
        in the un-retired in-flight window — ordered by request id.  This
        is the checkpoint snapshot set
        (:func:`repro.engine.resilience.snapshot_records`)."""
        with self._lock:
            seen: dict[int, Request] = {}
            for g in self._groups.values():
                for r in g.reqs:
                    seen[r.req_id] = r
            for _, reqs in self._retries:
                for r in reqs:
                    seen[r.req_id] = r
            for batch in self._window:
                for r in batch.requests:
                    if not r.done:
                        seen[r.req_id] = r
        return [seen[k] for k in sorted(seen)]

    # -- queueing -------------------------------------------------------------
    def submit(self, template: CircuitTemplate | Circuit,
               params: Sequence[float] | None = None, *,
               deadline_ms: float | None = None,
               deadline_at: float | None = None,
               result: ResultSpec | None = None) -> Request:
        """Enqueue one request; returns a future-like handle immediately.

        ``deadline_ms`` arms a deadline that many milliseconds after the
        submit stamp; ``deadline_at`` sets an absolute (scheduler-clock)
        deadline instead, for callers that started the clock earlier (the
        ingest front end stamps at producer-side enqueue).  A request past
        its deadline at dispatch time is SHED, never dispatched.

        ``result`` selects the request's result mode
        (:class:`~repro.engine.results.ResultSpec`): shots, expectation
        sweep, or noisy unraveling.  The default (or an explicit
        statevector spec) keeps the engine's historical behavior —
        ``Request.result`` is the full :class:`~repro.core.statevec.State`.
        """
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if result is not None:
            if not isinstance(result, ResultSpec):
                raise TypeError(f"result must be a ResultSpec, "
                                f"got {type(result).__name__}")
            if result.mode == MODE_STATEVECTOR:
                result = None        # byte-identical plans to a spec-less run
        template, p = validate_params(template, params)
        if result is not None:
            result.validate_for(template)
        # key resolution runs OUTSIDE the scheduler lock: the class key
        # compiles the plan (canonical form is a property of the lowering),
        # and producers must never block behind an XLA compile
        exact, ckey = self._route_keys(template, result)
        with self._lock:
            req = Request(req_id=next(self._ids), template=template, params=p,
                          submitted=self._clock(), result_spec=result)
            req._key = exact
            if deadline_at is not None:
                req.deadline = float(deadline_at)
            elif deadline_ms is not None:
                req.deadline = req.submitted + deadline_ms / 1e3
            self._enqueue_locked(req, ckey)
            self._work.notify_all()
        if self.tracer.enabled:
            # the submit stamp doubles as the span start: no extra clock read
            self.tracer.record(req.req_id, STAGE_SUBMIT, req.submitted,
                               template=template.name)
        self.stats.add_request(result.mode if result is not None
                               else MODE_STATEVECTOR)
        if self.max_wait_ms is not None:
            self._dispatch_groups(self._take_triggered())
        return req

    def submit_sweep(self, template: CircuitTemplate,
                     params_matrix, *,
                     deadline_ms: float | None = None,
                     result: ResultSpec | None = None) -> list[Request]:
        """Submit one request per row of a ``[B, P]`` parameter matrix.

        A 1-D array is B separate bindings when the template takes one
        parameter, and a single P-parameter binding otherwise.  ``result``
        applies the same result mode to every row.
        """
        return [self.submit(template, row, deadline_ms=deadline_ms,
                            result=result)
                for row in validate_sweep(template, params_matrix)]

    def wait_for_work(self, timeout: float | None = None) -> bool:
        """Block until submissions are queued (condition variable, no spin).

        Returns True if work is queued (including failed chunks awaiting
        retry), False on timeout.  This is the drain-loop primitive that
        replaces polling ``pending`` in a busy loop: producers signal the
        condition on every ``submit`` (and the failure resolver on every
        retry re-enqueue).
        """
        with self._work:
            if self._groups or self._retries:
                return True
            self._work.wait(timeout)
            return bool(self._groups or self._retries)

    # -- grouping -------------------------------------------------------------
    def _plan_key(self, req: Request) -> tuple:
        """Grouping key = the executor's plan-cache key (mesh-shape-aware:
        the same structure headed for a different mesh never co-batches)."""
        if req._key is None:
            req._key = self.executor.plan_key(req.template,
                                              result=req.result_spec)
        return req._key

    def _route_keys(self, template: CircuitTemplate,
                    result: ResultSpec | None) -> tuple[tuple, tuple | None]:
        """``(exact plan key, shape-class key or None)`` for a submission.

        The class key is best-effort: resolving it lowers the plan, and a
        template whose compile fails must still enqueue normally so the
        failure surfaces at dispatch with the batch-failure semantics
        (retry/FAILED), not as a submit-time raise.
        """
        exact = self.executor.plan_key(template, result=result)
        if not self.class_routing:
            return exact, None
        try:
            return exact, self.executor.class_key(template, result=result)
        except Exception:  # noqa: BLE001 — broken plan: exact-key fallback
            return exact, None

    def _enqueue_locked(self, req: Request, ckey: tuple | None) -> None:
        """Append ``req`` to its queue group, choosing class vs exact key.

        Caller holds ``_lock``.  A class group at capacity
        (``capacity_factor * max_batch`` device rows, MoE expert-capacity
        style) spills the request to its exact plan key instead —
        streaming schedulers launch full groups from ``submit`` long
        before capacity binds, so spills measure genuine overload.
        """
        rows = req.result_spec.rows if req.result_spec is not None else 1
        gkey = req._key
        if ckey is not None:
            cap = max(int(self.capacity_factor * self.max_batch),
                      self.max_batch)
            g = self._groups.get(ckey)
            if g is not None and g.rows + rows > cap:
                self.stats.add_spill()
            else:
                gkey = ckey
                self.stats.add_class_routed(self._class_label(ckey))
        g = self._groups.get(gkey)
        if g is None:
            # aging anchor: inherit the wait start of any co-batchable
            # request still in the retry backlog, so re-opening a key does
            # not reset its age (see _Group)
            opened = req.submitted
            for _, chunk in self._retries:
                for r in chunk:
                    if r._gkey == gkey:
                        opened = min(opened, r.submitted)
            g = _Group(opened=opened)
            self._groups[gkey] = g
        else:
            g.opened = min(g.opened, req.submitted)
        g.reqs.append(req)
        g.rows += rows
        req._gkey = gkey

    def _class_label(self, ckey: tuple) -> str:
        """Memoized short digest of a class key (stats/report readability).
        Caller holds ``_lock`` (the memo dict rides the scheduler lock)."""
        label = self._class_labels.get(ckey)
        if label is None:
            from repro.engine.shapeclass import class_label
            label = class_label(ckey)
            self._class_labels[ckey] = label
        return label

    def _take_groups(self) -> list[list[Request]]:
        """Dequeue all pending requests, grouped by plan key in FIFO order."""
        with self._lock:
            groups = [g.reqs for g in self._groups.values()]
            # dequeue before executing: a failing chunk must not leave its (or
            # other groups') requests queued for a silent re-run on the next
            # drain
            self._groups = {}
        return groups

    def _take_triggered(self, force: bool = False) -> list[list[Request]]:
        """Dequeue every group that is full or has aged out (all if force).

        Fullness is metered in device *rows* (a noisy request counts its
        unraveling expansion), and age runs from the group's ``opened``
        stamp — monotone across re-opens — not the current head request.
        """
        with self._lock:
            now = self._clock()
            fired = []
            for key, g in list(self._groups.items()):
                full = g.rows >= self.max_batch
                aged = (self.max_wait_ms is not None and
                        (now - g.opened) * 1e3 >= self.max_wait_ms)
                if force or full or aged:
                    del self._groups[key]
                    fired.append(g.reqs)
        return fired

    def _take_retries(self, force: bool = False) -> list[list[Request]]:
        """Dequeue retry chunks whose backoff has elapsed (all when force —
        explicit flush points override backoff delays)."""
        with self._lock:
            if not self._retries:
                return []
            now = self._clock()
            due, later = [], []
            for entry in self._retries:
                (due if force or now >= entry[0] else later).append(entry)
            self._retries = later
        return [chunk for _, chunk in due]

    # -- failure resolution ---------------------------------------------------
    def _note_outcome(self, chunk: list[Request], ok: bool) -> None:
        """Feed one batch outcome to the executor's plan breaker (if any)."""
        breaker = getattr(self.executor, "breaker", None)
        if breaker is None:
            return
        key = chunk[0]._key
        if key is None:
            return
        if ok:
            breaker.record_success(key)
        else:
            breaker.record_failure(key)

    def _resolve_batch_failure(self, chunk: list[Request],
                               error: Exception) -> None:
        """Route one failed batch: retry transient faults, fail the rest.

        Satisfies the no-drop contract under faults: every request in the
        chunk either re-enqueues as one intact retry chunk (state
        RETRYING, backoff per the policy) or finalizes FAILED (budget
        exhausted, non-transient error, no policy, or past deadline).
        Called from ``_dispatch_chunk`` (dispatch-time failure, requests
        still QUEUED/RETRYING) and from ``InFlightBatch.finalize`` under
        its idempotent-finalize lock (device-side failure, DISPATCHED).
        """
        now = self._clock()
        self._note_outcome(chunk, ok=False)
        to_retry: list[Request] = []
        to_fail: list[Request] = []
        for req in chunk:
            in_deadline = req.deadline is None or now < req.deadline
            if (self.retry is not None and in_deadline
                    and self.retry.should_retry(error, req.retries + 1)):
                to_retry.append(req)
            else:
                to_fail.append(req)
        if to_fail:
            _fail(to_fail, error, self.stats, now, tracer=self.tracer)
        if not to_retry:
            return
        for req in to_retry:
            req.retries += 1
            req._batch = None
            req._transition(RequestState.RETRYING)
        self.stats.add_retried(len(to_retry))
        attempt = max(r.retries for r in to_retry)
        delay = self.retry.backoff_s(attempt, token=to_retry[0].req_id)
        if self.tracer.enabled:
            for req in to_retry:
                self.tracer.record(req.req_id, STAGE_RETRYING, now,
                                   attempt=req.retries,
                                   error=type(error).__name__,
                                   backoff_ms=round(delay * 1e3, 3))
        with self._lock:
            self._retries.append((now + delay, to_retry))
            self._work.notify_all()

    def _shed(self, requests: list[Request], now: float) -> None:
        """Terminal SHED: past-deadline requests never waste a dispatch."""
        for req in requests:
            req.error = DeadlineExceeded(
                f"request {req.req_id}: deadline exceeded "
                f"{(now - req.deadline) * 1e3:.3f} ms before dispatch")
            req.latency = now - req.submitted
            req._transition(RequestState.SHED)
            self.stats.add_shed()
            if self.tracer.enabled:
                self.tracer.record(req.req_id, STAGE_SHED, now)

    def _dispatch_groups(self, groups: list[list[Request]]) -> list[Request]:
        out: list[Request] = []
        for reqs in groups:
            self._dispatch_group(reqs)
            out += reqs
        return out

    # -- dispatch -------------------------------------------------------------
    def _row_chunks(self, reqs: list[Request]) -> list[list[Request]]:
        """Split a group into dispatch chunks of at most ``max_batch``
        device *rows* (and at most ``max_batch`` requests).

        Row-aware chunking caps unraveling expansion at grouping time: a
        group of noisy requests splits *before* dispatch instead of
        producing ever-larger expanded batches whose unbounded distinct
        padded sizes thrash the per-plan batched-program LRU.  The one
        irreducible case — a single request whose own unraveling exceeds
        ``max_batch`` — dispatches alone (its rows can never split across
        batches: a batch finalizes all its trajectories together).
        """
        chunks: list[list[Request]] = []
        cur: list[Request] = []
        cur_rows = 0
        for r in reqs:
            k = r.result_spec.rows if r.result_spec is not None else 1
            if cur and (cur_rows + k > self.max_batch
                        or len(cur) >= self.max_batch):
                chunks.append(cur)
                cur, cur_rows = [], 0
            cur.append(r)
            cur_rows += k
        if cur:
            chunks.append(cur)
        return chunks

    def _dispatch_group(self, reqs: list[Request],
                        finalize_each: bool = False) -> list[InFlightBatch]:
        launched = []
        for chunk in self._row_chunks(reqs):
            batch = self._dispatch_chunk(chunk)
            if batch is not None:
                if finalize_each:
                    batch.finalize()
                launched.append(batch)
        return launched

    def _dispatch_chunk(self, chunk: list[Request]) -> InFlightBatch | None:
        """Launch one chunk non-blocking; FAILED (never raised) on error.

        The slow part — plan resolution and program dispatch — runs outside
        the scheduler lock (the executor serializes compiles itself), so
        producers are never blocked behind an XLA compile; only the window
        and lifecycle mutations are guarded.
        """
        if any(r.deadline is not None for r in chunk):
            now = self._clock()
            expired = [r for r in chunk
                       if r.deadline is not None and now >= r.deadline]
            if expired:
                self._shed(expired, now)
                chunk = [r for r in chunk if not r.done]
                if not chunk:
                    return None
        template = chunk[0].template
        spec = chunk[0].result_spec     # chunks group by plan or class key;
                                        # either way the structural spec
                                        # component is chunk-uniform
        # a chunk whose requests resolve to different exact plan keys came
        # from a shape-class group and must run the class program; a
        # key-uniform chunk always takes the exact path (identical results,
        # and the per-plan program is already the hot one)
        klass = len({r._key for r in chunk}) > 1
        # a noisy request occupies ``unravelings`` rows of the vmapped batch
        # axis (row expansion)
        rows = (None if spec is None
                else [r.result_spec.rows for r in chunk])
        b = len(chunk) if rows is None else sum(rows)
        if not self.pad_to_pow2:
            padded = b
        elif b <= self.max_batch:
            padded = _pad_size(b, self.max_batch)
        else:
            # a single request whose unraveling exceeds max_batch (row-aware
            # chunking dispatches it alone): pad to the next power of two so
            # oversized traffic still compiles O(log) distinct batch sizes
            padded = 1 << (b - 1).bit_length()
        span = {"rows": b, "padded": padded, "req": chunk[0].req_id}
        with host_span("repro.sched.stage", **span):
            pm, rowkeys, templates = _stage(chunk, rows, klass, padded)
        try:
            with host_span("repro.sched.dispatch", **span):
                if klass:
                    plan, raw = self.executor.dispatch_class_batch(
                        templates, pm, result=spec, rowkeys=rowkeys)
                else:
                    plan, raw = self.executor.dispatch_batch(
                        template, pm, result=spec, rowkeys=rowkeys)
        except Exception as e:  # noqa: BLE001 — compile/trace/launch failure
            self._resolve_batch_failure(chunk, e)
            return None
        self.stats.add_batch(b, padded - b, klass=klass)
        if self.tracer.enabled:
            bid = next(self._batch_ids)
            now = self._clock()
            for req in chunk:
                self.tracer.record(req.req_id, STAGE_DISPATCH, now,
                                   batch=bid, rows=b, padded=padded)
        injector = getattr(self.executor, "injector", None)
        batch = InFlightBatch(plan, chunk, raw, self.stats, clock=self._clock,
                              tracer=self.tracer, scheduler=self,
                              injector=injector, rows=rows, padded=padded)
        if injector is not None:
            batch.straggler = injector.draw_straggler()
        overflow: list[InFlightBatch] = []
        with self._lock:
            for req in chunk:
                req._transition(RequestState.DISPATCHED)
                req._batch = batch
            self._window.append(batch)
            while len(self._window) > self.inflight:
                overflow.append(self._window.popleft())
        for old in overflow:
            old.finalize()
        return batch

    def poll(self, force: bool = False) -> list[InFlightBatch]:
        """One non-blocking drain step (the ingest drain-loop primitive).

        Launches every plan group that is full or (under ``max_wait_ms``)
        has aged out — all queued groups when ``force`` — then retires any
        in-flight batch whose device results are already available
        (``InFlightBatch.ready``), oldest first.  Never blocks on the
        device: a batch still executing stays in the window.  Returns the
        newly launched batches.  The call is the profiler span
        ``repro.sched.poll``; the batches it stages, dispatches and
        finalizes are spans inside it.
        """
        launched: list[InFlightBatch] = []
        with host_span("repro.sched.poll"):
            for reqs in self._take_retries(force):
                launched += self._dispatch_group(reqs)
            for reqs in self._take_triggered(force):
                launched += self._dispatch_group(reqs)
            while True:
                with self._lock:
                    if not (self._window and self._window[0].ready):
                        break
                    batch = self._window.popleft()
                batch.finalize()
        return launched

    def retire_one(self) -> bool:
        """Finalize the oldest in-flight batch, blocking until its device
        results land; False if the window is empty.  Drain loops call this
        when there is nothing left to launch — it converts idle host time
        into result delivery instead of a spin."""
        with self._lock:
            if not self._window:
                return False
            batch = self._window.popleft()
        batch.finalize()
        return True

    def drain(self) -> list[Request]:
        """Synchronously flush the queue: every returned request is terminal.

        Each batch is retired (host blocks on device results) before the next
        one launches — the blocking baseline that ``drain_async`` pipelines.
        Loops until the queue, retry backlog, and window are all empty, so a
        request that faults and re-enqueues mid-drain is still terminal on
        return (deduplicated by id: a retried request counts once).
        """
        completed: dict[int, Request] = {}
        while True:
            groups = self._take_retries(force=True) + self._take_groups()
            if not groups:
                with self._lock:
                    window_empty = not self._window
                if window_empty:
                    break
                self.sync()
                continue
            for reqs in groups:
                self._dispatch_group(reqs, finalize_each=True)
                for req in reqs:
                    completed[req.req_id] = req
        return list(completed.values())

    def drain_async(self, wait_ms: float | None = None) -> list[Request]:
        """Launch everything queued without retiring the in-flight window.

        Returned requests are ``DISPATCHED`` (or already terminal); host-side
        grouping/padding/staging of each batch overlaps device execution of
        the previous ones.  Retire with ``sync()`` or per-request ``wait()``.

        ``wait_ms`` bounds a condition-variable wait for submissions when
        the queue is empty (a drain loop calling ``drain_async`` in a loop
        must never busy-spin while requests are merely in flight); ``None``
        returns immediately.
        """
        if wait_ms is not None:
            with self._lock:
                empty = not self._groups and not self._retries
            if empty:
                self.wait_for_work(wait_ms / 1e3)
        for reqs in self._take_retries():
            self._dispatch_group(reqs)
        return self._dispatch_groups(self._take_groups())

    def sync(self) -> None:
        """Retire every in-flight batch (oldest first), then flush any retry
        backlog to terminal — a flush point overrides backoff delays, so a
        caller observing ``sync()`` return knows nothing is still pending."""
        while True:
            with self._lock:
                if self._window:
                    batch = self._window.popleft()
                else:
                    batch = None
            if batch is not None:
                batch.finalize()
                continue
            chunks = self._take_retries(force=True)
            if not chunks:
                return
            for chunk in chunks:
                self._dispatch_group(chunk)

    # -- reporting ------------------------------------------------------------
    def report(self) -> dict:
        out = self.stats.summary()
        with self._lock:
            out["inflight"] = len([b for b in self._window if not b.finalized])
        out.update({f"cache_{k}": v
                    for k, v in self.executor.stats.as_dict().items()})
        # compile-time attribution: total/percentile seconds spent compiling
        # plans for this traffic (absent until the first compile)
        out.update({f"compile_{k}": v
                    for k, v in self.executor.stats.compile_summary().items()})
        # per-class fused-gate counts of the plans serving this traffic, so
        # specialization coverage is trackable alongside throughput
        out.update({f"gates_{cls}": c
                    for cls, c in self.executor.class_counts().items()})
        return out
