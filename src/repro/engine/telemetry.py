"""End-to-end engine telemetry: metrics registry, span tracing, activity,
and the names the profiler sees.

The paper's methodological contribution beyond raw speedups is *measurement*:
it defines PMU-derived metrics (AVL, IRR — §VII-A) to quantify vectorization
activity and uses them to explain performance across machines.  This module
is the serving-side analogue, four instruments:

* **Metrics registry** — :class:`MetricsRegistry` unifies the engine's
  scattered stats objects (``SchedulerStats``, ``CacheStats``, the ingest
  counters, served vectorization activity) behind one ``snapshot()`` /
  ``write_json()`` API via *sources* — callables polled at snapshot time,
  so the existing lock-carrying stats objects stay the single writers of
  their counters (exactness under the 8-producer hammer is theirs; the
  registry never copies a counter it could race).  :class:`Histogram` is
  the bounded sample record those stats objects keep (fixed memory: exact
  count/sum/min/max forever, percentiles over a fixed-capacity window of
  the most recent samples).

* **Span tracing** — :class:`SpanTracer` records per-request lifecycle
  events (ingest lane enqueue → scheduler submit → dispatch → device
  retire → finalize) stamped off the scheduler's injectable clock, and
  exports Chrome-trace/Perfetto JSON (``write_chrome_trace``) plus a JSONL
  structured event log (``write_jsonl``).  ``span_trees()`` validates the
  record: exactly one well-formed tree per request, no orphans, no
  duplicate stages, non-decreasing timestamps.  When tracing is off the
  engine holds :data:`NULL_TRACER`, whose ``enabled`` flag gates every
  call site — a disabled run does no telemetry work at all and is bitwise
  identical to an untraced one.

* **Vectorization activity** — :class:`VectorizationProfile` is computed
  once per compiled plan (from :mod:`repro.core.metrics`): ALO (average
  lane occupancy, the AVL analogue), ORR (op-reduction ratio, the IRR
  analogue), structural arithmetic intensity, and the fraction of
  amplitude traffic taking the diagonal/permutation fast path.
  :class:`ServedActivity` aggregates those profiles over *served* rows per
  plan key, so a running server can report "what fraction of served
  amplitudes took the diagonal fast path, at what lane occupancy" — the
  serving-side analogue of the paper's Table IV.

* **Profiler names** — :func:`device_scope` and :func:`host_span`
  (defined in :mod:`repro.core.scopes`, so that ``core`` and the kernels
  can use them without importing the engine).  Every operation of a
  compiled plan program carries ``repro_*`` device attributes (plan item,
  kind, width, exchange or apply, epilogue term), which reach a TPU
  profile; the program's host stages are ``repro.*`` spans on the
  profiler's own clock, where ``SpanTracer``'s scheduler-clock stamps
  cannot go.  The attributes leave the compiled program as it was; a
  span costs about a microsecond of host time when no profile is being
  taken (``docs/OBSERVABILITY.md``, "Device attributes and profiler
  spans").
"""
from __future__ import annotations

import dataclasses
import json
import threading
from typing import Callable, Sequence

import numpy as np

from repro.core.metrics import circuit_cost
from repro.core.scopes import device_scope, host_span

__all__ = [
    "Histogram", "MetricsRegistry", "device_scope", "host_span",
    "Span", "SpanTracer", "NULL_TRACER",
    "STAGE_ENQUEUE", "STAGE_SUBMIT", "STAGE_DISPATCH",
    "STAGE_DEVICE_READY", "STAGE_DONE", "STAGE_FAILED",
    "STAGE_RETRYING", "STAGE_SHED",
    "VectorizationProfile", "vectorization_profile", "ServedActivity",
    "engine_registry",
]


# -- instruments ---------------------------------------------------------------

class Histogram:
    """Bounded-memory sample histogram with exact totals.

    ``count``/``sum``/``min``/``max`` are exact over every recorded sample;
    percentiles are computed over a fixed-capacity ring of the most recent
    ``capacity`` samples, so a long-running serve holds O(capacity) memory
    no matter how many latencies it records (the fix for the unbounded
    ``SchedulerStats.latencies`` list).  Thread-safe: one lock guards the
    ring and the totals, so concurrent recorders never lose a sample count.
    """

    __slots__ = ("name", "capacity", "_ring", "_count", "_sum", "_min",
                 "_max", "_lock")

    def __init__(self, capacity: int = 4096, name: str = ""):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.name = name
        self.capacity = capacity
        self._ring = np.empty(capacity, np.float64)  #: guarded-by: _lock
        self._count = 0                              #: guarded-by: _lock
        self._sum = 0.0                              #: guarded-by: _lock
        self._min = np.inf                           #: guarded-by: _lock
        self._max = -np.inf                          #: guarded-by: _lock
        self._lock = threading.Lock()

    def record(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self._ring[self._count % self.capacity] = v
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    def __len__(self) -> int:
        """Total samples ever recorded (NOT the retained window size)."""
        with self._lock:
            return self._count

    @property
    def count(self) -> int:
        return len(self)

    def window(self) -> np.ndarray:
        """Copy of the retained samples (at most ``capacity``, newest last
        wrap order — order is irrelevant for percentiles)."""
        with self._lock:
            return self._ring[:min(self._count, self.capacity)].copy()

    def percentile(self, q: float) -> float:
        w = self.window()
        if not len(w):
            raise ValueError(f"histogram {self.name!r} is empty")
        return float(np.percentile(w, q))

    def summary(self) -> dict:
        """count/mean/p50/p95/p99/max in the recorded unit; empty dict when
        no samples (callers decide how to report idleness — fabricating a
        0.0 percentile is the bug the scheduler already fixed once)."""
        with self._lock:
            n = self._count
            if not n:
                return {}
            w = self._ring[:min(n, self.capacity)].copy()
            total, mx = self._sum, self._max
        p50, p95, p99 = np.percentile(w, [50, 95, 99])
        return {"count": n, "mean": total / n, "p50": float(p50),
                "p95": float(p95), "p99": float(p99), "max": float(mx)}

    def __repr__(self) -> str:
        return (f"Histogram({self.name or 'unnamed'}, count={self.count}, "
                f"capacity={self.capacity})")


class MetricsRegistry:
    """Pollable snapshot sources behind one export API.

    *Sources* are callables returning dicts, polled at :meth:`snapshot`
    time and merged under a prefix; they let the engine's existing
    lock-carrying stats objects (``SchedulerStats``, ``CacheStats``,
    ingest counters, served activity) publish through one export API
    without a second copy of their state.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._sources: list[tuple[str, Callable[[], dict]]] = []  #: guarded-by: _lock

    def register_source(self, prefix: str, fn: Callable[[], dict]) -> None:
        """Attach a dict-returning callable; its keys appear in snapshots
        as ``<prefix>_<key>``.  Sources are polled outside the registry
        lock — they carry their own locks."""
        with self._lock:
            self._sources.append((prefix, fn))

    def snapshot(self) -> dict:
        """One flat dict over every source."""
        with self._lock:
            sources = list(self._sources)
        out: dict = {}
        for prefix, fn in sources:
            for k, v in fn().items():
                out[f"{prefix}_{k}"] = v
        return out

    def write_json(self, path: str) -> dict:
        """Write the snapshot as pretty JSON; returns the snapshot."""
        snap = self.snapshot()
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(snap, fh, indent=2, sort_keys=True, default=str)
            fh.write("\n")
        return snap


def engine_registry(*, scheduler=None, executor=None,
                    server=None) -> MetricsRegistry:
    """The one snapshot/export API over the engine's stats objects.

    Wires a :class:`MetricsRegistry` with sources for whichever pieces are
    given: ``scheduler_*`` / ``routing_*``
    (:class:`~repro.engine.scheduler.SchedulerStats` summary and its
    shape-class routing counters),
    ``cache_*`` / ``compile_*`` (:class:`~repro.engine.plan.CacheStats`
    counters and compile-time percentiles), ``served_*``
    (:class:`ServedActivity`), and ``ingest_*`` (the
    :class:`~repro.engine.ingest.IngestServer` front-end counters).
    Passing ``server=`` implies its scheduler and executor.
    """
    reg = MetricsRegistry()
    if server is not None:
        reg.register_source("ingest", server.ingest_counters)
        scheduler = scheduler if scheduler is not None else server.scheduler
    if scheduler is not None:
        reg.register_source("scheduler", scheduler.stats.summary)
        # shape-class routing source: batch fill + per-class routed counts
        # (empty until a batch dispatches, so idle schedulers add no keys)
        reg.register_source("routing", scheduler.stats.routing_summary)
        executor = executor if executor is not None else scheduler.executor
    if executor is not None:
        reg.register_source("cache", executor.stats.as_dict)
        reg.register_source("compile", executor.stats.compile_summary)
        reg.register_source("served", executor.activity.summary)
        # resilience instruments ride along when installed (duck-typed so
        # telemetry never imports the resilience layer)
        injector = getattr(executor, "injector", None)
        if injector is not None:
            reg.register_source("faults", injector.counters)
        breaker = getattr(executor, "breaker", None)
        if breaker is not None:
            reg.register_source("breaker", breaker.counters)
    return reg


# -- span tracing --------------------------------------------------------------

STAGE_ENQUEUE = "ingest_enqueue"      # producer lane append (ingest only)
STAGE_SUBMIT = "submit"               # scheduler submit (ticket merged)
STAGE_DISPATCH = "dispatch"           # batch launched on device
STAGE_DEVICE_READY = "device_ready"   # device results available
STAGE_RETRYING = "retrying"           # transient fault; re-enqueued for retry
STAGE_DONE = "done"                   # result delivered on the request
STAGE_FAILED = "failed"               # terminal failure
STAGE_SHED = "shed"                   # terminal: deadline exceeded pre-dispatch

# display/sort rank only — lifecycle validation is the append-order state
# machine in ``_build_tree`` (retries legally revisit dispatch, so a global
# forward-only rank cannot express the record any more)
_STAGE_RANK = {STAGE_ENQUEUE: 0, STAGE_SUBMIT: 1, STAGE_DISPATCH: 2,
               STAGE_DEVICE_READY: 3, STAGE_RETRYING: 4,
               STAGE_DONE: 5, STAGE_FAILED: 5, STAGE_SHED: 5}
_TERMINALS = (STAGE_DONE, STAGE_FAILED, STAGE_SHED)

# child-span names derived from consecutive stage events
SPAN_INGEST_WAIT = "ingest.wait"      # lane enqueue -> scheduler submit
SPAN_QUEUE = "sched.queue"            # submit -> dispatch (grouping + aging)
SPAN_EXECUTE = "device.execute"       # dispatch -> device results ready
SPAN_FINALIZE = "finalize"            # device ready -> request terminal
SPAN_RETRY = "retry.backoff"          # retrying -> next dispatch (or terminal)

# child-span name keyed by the *leading* stage of a consecutive event pair
_CHILD_NAME = {STAGE_ENQUEUE: SPAN_INGEST_WAIT, STAGE_SUBMIT: SPAN_QUEUE,
               STAGE_DISPATCH: SPAN_EXECUTE,
               STAGE_DEVICE_READY: SPAN_FINALIZE, STAGE_RETRYING: SPAN_RETRY}


@dataclasses.dataclass
class Span:
    """One named interval; a request's root span carries stage children."""

    name: str
    start: float
    end: float
    args: dict = dataclasses.field(default_factory=dict)
    children: list = dataclasses.field(default_factory=list)

    @property
    def duration(self) -> float:
        return self.end - self.start


class _NullTracer:
    """Tracing disabled: ``enabled`` gates every instrumentation site, so a
    disabled engine does zero telemetry work (no clock reads, no appends)
    and behaves bit-for-bit like an untraced one."""

    __slots__ = ()
    enabled = False

    def record(self, req_id: int, stage: str, ts: float, **attrs) -> None:
        """No-op (kept callable so mis-gated sites fail soft, not loud)."""


NULL_TRACER = _NullTracer()


class SpanTracer:
    """Collects per-request lifecycle events and exports span trees.

    Events are appended under one lock (``record`` is called from producer
    threads, the drain loop, and finalizing waiters concurrently); each
    event is ``(stage, timestamp, attrs)`` keyed by scheduler ``req_id``.
    Timestamps come from whatever clock the scheduler was built with, so
    fake-clock tests get exact, reproducible spans.
    """

    enabled = True

    def __init__(self):
        self._lock = threading.Lock()
        self._events: dict[int, list] = {}  #: guarded-by: _lock

    # -- recording (hot path) -------------------------------------------------
    def record(self, req_id: int, stage: str, ts: float, **attrs) -> None:
        ev = {"stage": stage, "ts": float(ts)}
        if attrs:
            ev.update(attrs)
        with self._lock:
            self._events.setdefault(req_id, []).append(ev)

    # -- inspection -----------------------------------------------------------
    def events(self) -> dict[int, list]:
        """Snapshot of raw events per request id."""
        with self._lock:
            return {rid: list(evs) for rid, evs in self._events.items()}

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def span_trees(self) -> list[Span]:
        """Validated span trees, one per request, ordered by request id.

        Raises ``ValueError`` on any malformed record: a missing/duplicate
        ``submit`` or terminal stage, a duplicated intermediate stage, a
        stage after the terminal, or timestamps that decrease along the
        stage order — the span-integrity contract the concurrency suite
        pins under the 8-producer hammer.
        """
        trees = []
        for rid, evs in sorted(self.events().items()):
            trees.append(self._build_tree(rid, evs))
        return trees

    @staticmethod
    def _build_tree(rid: int, evs: list) -> Span:
        """Validate one request's append-ordered event list into a span tree.

        Lifecycle is checked as a state machine over append order rather
        than a global stage rank, because retries legally revisit stages:
        each ``retrying`` event re-arms exactly one more ``dispatch`` /
        ``device_ready`` pair, so a retried request still yields exactly
        one well-formed tree with its re-dispatch intervals nested as
        children (never a second orphan tree).
        """
        for ev in evs:
            if ev["stage"] not in _STAGE_RANK:
                raise ValueError(
                    f"request {rid}: unknown stage {ev['stage']!r}")
        enq = [ev for ev in evs if ev["stage"] == STAGE_ENQUEUE]
        if len(enq) > 1:
            raise ValueError(
                f"request {rid}: duplicate {STAGE_ENQUEUE!r} event")
        rest = [ev for ev in evs if ev["stage"] != STAGE_ENQUEUE]
        if not any(ev["stage"] == STAGE_SUBMIT for ev in rest):
            raise ValueError(f"request {rid}: no submit event (orphan)")
        if rest[0]["stage"] != STAGE_SUBMIT:
            raise ValueError(
                f"request {rid}: {rest[0]['stage']!r} recorded before submit")
        if sum(1 for ev in rest if ev["stage"] == STAGE_SUBMIT) > 1:
            raise ValueError(
                f"request {rid}: duplicate {STAGE_SUBMIT!r} event")
        terminal = [ev["stage"] for ev in rest if ev["stage"] in _TERMINALS]
        if len(terminal) != 1:
            raise ValueError(
                f"request {rid}: expected exactly one terminal stage, "
                f"got {terminal or 'none'}")
        if rest[-1]["stage"] not in _TERMINALS:
            raise ValueError(
                f"request {rid}: {rest[-1]['stage']!r} recorded after the "
                f"terminal stage")
        dispatched = ready_seen = False
        last_dispatch = None
        retries = 0
        for ev in rest[1:-1]:
            stage = ev["stage"]
            if stage == STAGE_DISPATCH:
                if dispatched:
                    raise ValueError(
                        f"request {rid}: duplicate {STAGE_DISPATCH!r} event "
                        f"(no intervening retry)")
                dispatched, ready_seen = True, False
                last_dispatch = ev
            elif stage == STAGE_DEVICE_READY:
                if not dispatched:
                    raise ValueError(
                        f"request {rid}: {STAGE_DEVICE_READY!r} before "
                        f"{STAGE_DISPATCH!r}")
                if ready_seen:
                    raise ValueError(
                        f"request {rid}: duplicate "
                        f"{STAGE_DEVICE_READY!r} event")
                ready_seen = True
            elif stage == STAGE_RETRYING:
                dispatched = ready_seen = False
                retries += 1
        ordered = enq + rest
        for a, b in zip(ordered, ordered[1:]):
            if b["ts"] < a["ts"]:
                raise ValueError(
                    f"request {rid}: timestamps decrease "
                    f"{a['stage']}@{a['ts']} -> {b['stage']}@{b['ts']}")
        end_ev = rest[-1]

        def attrs(ev):
            return {k: v for k, v in ev.items() if k not in ("stage", "ts")}

        args = {"req_id": rid, "status": end_ev["stage"],
                **attrs(rest[0]),
                **attrs(last_dispatch or {}),
                **attrs(end_ev)}
        if retries:
            args["retries"] = retries
        root = Span("request", ordered[0]["ts"], end_ev["ts"], args=args)
        for a, b in zip(ordered, ordered[1:]):
            root.children.append(
                Span(_CHILD_NAME[a["stage"]], a["ts"], b["ts"],
                     args=attrs(a) if a["stage"] == STAGE_ENQUEUE else {}))
        return root

    # -- export ---------------------------------------------------------------
    def chrome_trace(self) -> dict:
        """Chrome-trace/Perfetto JSON object: one thread row per request,
        complete ("X") events for the root and each stage span, timestamps
        in microseconds relative to the earliest event."""
        trees = self.span_trees()
        t0 = min((s.start for s in trees), default=0.0)
        events: list = [{"ph": "M", "pid": 1, "tid": 0,
                         "name": "process_name",
                         "args": {"name": "repro-engine"}}]

        def emit(span: Span, tid: int):
            events.append({
                "name": span.name, "cat": "engine", "ph": "X",
                "ts": (span.start - t0) * 1e6,
                "dur": max(span.duration, 0.0) * 1e6,
                "pid": 1, "tid": tid, "args": span.args,
            })
            for child in span.children:
                emit(child, tid)

        for tree in trees:
            emit(tree, tree.args["req_id"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path: str) -> int:
        """Write the Chrome-trace JSON file; returns the span-tree count."""
        trace = self.chrome_trace()
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(trace, fh, default=str)
            fh.write("\n")
        return len(self)

    def write_jsonl(self, path: str) -> int:
        """Structured event log: one JSON object per line, time-ordered;
        returns the number of events written."""
        rows = [{"req_id": rid, **ev}
                for rid, evs in self.events().items() for ev in evs]
        rows.sort(key=lambda r: (r["ts"], r["req_id"],
                                 _STAGE_RANK.get(r["stage"], 9)))
        with open(path, "w", encoding="utf-8") as fh:
            for row in rows:
                fh.write(json.dumps(row, default=str))
                fh.write("\n")
        return len(rows)


# -- vectorization-activity observability --------------------------------------

@dataclasses.dataclass(frozen=True)
class VectorizationProfile:
    """Structural vectorization profile of one compiled plan.

    Computed once at plan-compile time from :mod:`repro.core.metrics` —
    the serving-side analogues of the paper's PMU metrics (§VII-A):
    ``alo`` mirrors AVL (average active vector length), ``orr`` mirrors
    IRR (instruction reduction ratio), ``ai`` is the structural arithmetic
    intensity, and ``fast_amp_frac`` is the fraction of amplitude traffic
    (item applications weighted by touched amplitudes) taking the
    diagonal/permutation matmul-free fast path.
    """

    alo: float                    # average active lanes per vector op
    lanes: int                    # the target's vector lanes (ALO ceiling)
    orr: float                    # naive scalar ops / VLA vector ops
    ai: float                     # structural flops per HBM byte
    flops_per_amp_actual: float
    flops_per_amp_generic: float
    flops_saved_frac: float
    fast_amp_frac: float

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def vectorization_profile(plan, gates: Sequence,
                          target) -> VectorizationProfile:
    """Profile one compiled plan: costs from the paper's structural model
    (:func:`repro.core.metrics.circuit_cost` over the original gate list)
    plus per-item fast-path coverage from the plan's lowered items."""
    n = plan.n
    cost_gen = circuit_cost(gates, n, target, specialized=False)
    cost = circuit_cost(gates, n, target, specialized=plan.specialize)
    fl = plan.flops_per_amp()
    total = fast = 0.0
    for item in plan.items:
        if item.kind == "result":
            continue   # reduction epilogue, not gate amplitude traffic
        amps = float(1 << n) / (1 << len(item.controls))
        total += amps
        if item.kind in ("diag", "perm"):
            fast += amps
    return VectorizationProfile(
        alo=float(cost.active_lanes),
        lanes=int(target.lanes),
        orr=(cost_gen.flops / 2.0) / max(cost.vector_ops, 1.0),
        ai=float(cost.ai),
        flops_per_amp_actual=fl["flops_per_amp_actual"],
        flops_per_amp_generic=fl["flops_per_amp_generic"],
        flops_saved_frac=fl["flops_saved_frac"],
        fast_amp_frac=fast / total if total else 0.0,
    )


class ServedActivity:
    """Served vectorization activity, aggregated per plan key.

    The executor calls :meth:`record` once per dispatch (rows include any
    padding the scheduler added — this measures what the device actually
    ran).  Per-plan aggregates weight each plan's static profile by the
    amplitudes it served, so ``summary()`` answers the serving-side
    Table-IV question: over everything this engine executed, what lane
    occupancy ran and what fraction of amplitude traffic took the
    diagonal/permutation fast path.  For result plans it also counts the
    observables each row asked for, split by route: ``z_pass_term_frac``
    is the share that the epilogue's shared Z-string pass served, the rest
    took the per-term fallback (0.0 when no observable was served).
    """

    _ZERO = {"rows": 0, "batches": 0, "amps": 0.0, "alo_w": 0.0,
             "orr_w": 0.0, "ai_w": 0.0, "fast_w": 0.0, "saved_w": 0.0,
             "z_terms": 0, "fallback_terms": 0}

    def __init__(self):
        self._lock = threading.Lock()
        self._per_key: dict[str, dict] = {}  #: guarded-by: _lock

    @staticmethod
    def plan_label(plan) -> str:
        """Stable per-plan aggregation key: template name + structure hash
        prefix + the lowering knobs that make plans distinct artifacts."""
        return (f"{plan.template.name}:"
                f"{plan.template.structure_key()[:6]}|{plan.backend}"
                f"|f{plan.f}|sb{plan.state_bits}"
                f"{'' if plan.specialize else '|generic'}")

    def record(self, plan, rows: int) -> None:
        if rows <= 0:
            return
        prof = plan.profile
        amps = float(rows) * (1 << plan.n)
        key = self.plan_label(plan)
        with self._lock:
            e = self._per_key.get(key)
            if e is None:
                e = self._per_key[key] = dict(self._ZERO)
            e["rows"] += int(rows)
            e["batches"] += 1
            e["amps"] += amps
            e["z_terms"] += plan.z_pass_terms * int(rows)
            e["fallback_terms"] += plan.fallback_terms * int(rows)
            if prof is not None:
                e["alo_w"] += prof.alo * amps
                e["orr_w"] += prof.orr * amps
                e["ai_w"] += prof.ai * amps
                e["fast_w"] += prof.fast_amp_frac * amps
                e["saved_w"] += prof.flops_saved_frac * amps

    @staticmethod
    def _finish(e: dict) -> dict:
        amps = max(e["amps"], 1.0)
        terms = max(e["z_terms"] + e["fallback_terms"], 1)
        return {"rows": e["rows"], "batches": e["batches"],
                "amps": e["amps"],
                "alo": e["alo_w"] / amps, "orr": e["orr_w"] / amps,
                "ai": e["ai_w"] / amps,
                "fast_amp_frac": e["fast_w"] / amps,
                "flops_saved_frac": e["saved_w"] / amps,
                "z_pass_term_frac": e["z_terms"] / terms}

    def per_plan(self) -> dict[str, dict]:
        """Amps-weighted activity per plan key (rows, amps, ALO, ORR, AI,
        fast-path and flops-saved fractions) and the rows-weighted share of
        observables served by the shared Z-string pass."""
        with self._lock:
            items = {k: dict(v) for k, v in self._per_key.items()}
        return {k: self._finish(e) for k, e in sorted(items.items())}

    def summary(self) -> dict:
        """Aggregate served activity over every plan key."""
        with self._lock:
            entries = [dict(v) for v in self._per_key.values()]
        agg = dict(self._ZERO)
        for e in entries:
            for k in agg:
                agg[k] += e[k]
        out = self._finish(agg)
        out["plans"] = len(entries)
        return out
