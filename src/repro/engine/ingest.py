"""Concurrent ingest front end over the streaming scheduler.

The paper keeps the vector units saturated no matter how work arrives; the
serving analogue is keeping batches full under real concurrent load.
:class:`IngestServer` is that front end: many producer threads (or asyncio
tasks) submit circuit requests, a single background drain loop merges them
into :class:`~repro.engine.scheduler.BatchScheduler`'s streaming triggers,
and every submission gets a future/awaitable :class:`IngestHandle`.

Design:

* **Lock-free-ish submission path.**  Each producer thread owns a private
  lane (a ``deque`` — appends are atomic under the GIL), so the hot path
  costs one backpressure-semaphore acquire, one sequence ticket, one lane
  append, and one condition notify; producers never contend on the
  scheduler lock or wait behind an XLA compile.  The drain loop merges the
  lanes by ticket order, so cross-producer FIFO fairness holds.
* **One dispatcher.**  Only the drain loop touches ``scheduler.submit`` /
  ``poll``, which keeps batch formation single-writer: groups fill to
  ``max_batch`` or age out after ``max_wait_ms``, the non-blocking
  :meth:`BatchScheduler.poll` step launches them, and ready batches retire
  opportunistically.  The loop sleeps on a condition variable between
  bursts — no busy spin while requests are merely in flight.
* **Backpressure.**  ``max_pending`` bounds submitted-but-unresolved
  requests with two policies: ``"block"`` (producers wait for a slot —
  the default) and ``"reject"`` (raise :class:`IngestRejected` so callers
  can shed load).
* **Graceful shutdown.**  ``close()`` stops intake, flushes every queued
  lane item and in-flight batch, resolves every handle, and joins the
  loop; requests racing past intake during shutdown are still executed by
  a final sweep, so no handle is ever dropped.
* **Deterministic testing.**  ``autostart=False`` plus an injected
  ``clock`` (:class:`repro.testing.FakeClock`) turns the server into a
  hand-cranked machine: tests call :meth:`IngestServer.step` — exactly one
  drain iteration — and advance the fake clock between steps, making race
  windows and aging triggers reproducible under pytest.
"""
from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import itertools
import threading
from typing import Callable, Sequence

import numpy as np

from repro.core.circuits import Circuit
from repro.engine.batch import BatchExecutor
from repro.engine.results import ResultSpec
from repro.engine.scheduler import (BatchScheduler, Request, validate_params,
                                    validate_sweep)
from repro.engine.telemetry import STAGE_ENQUEUE, host_span
from repro.engine.template import CircuitTemplate

BLOCK = "block"      # producers wait for a pending slot (default)
REJECT = "reject"    # submit raises IngestRejected when the window is full

# "not provided" sentinel: None is a *meaningful* max_wait_ms (the
# scheduler's no-aging-trigger mode — underfull groups wait for
# drain()/close()), so it cannot double as the default marker
_UNSET = object()


class IngestClosed(RuntimeError):
    """The server no longer accepts submissions (close() was called)."""


class IngestRejected(RuntimeError):
    """Backpressure: the pending window is full under the reject policy."""


class IngestHandle:
    """Future-like handle for one ingested request.

    Works from threads (``result(timeout)`` / ``exception()`` /
    ``add_done_callback``) and from asyncio (``await handle``).  Once the
    drain loop has ingested the submission, ``request`` exposes the
    underlying scheduler :class:`~repro.engine.scheduler.Request` (req_id,
    lifecycle ``history``, latency).
    """

    __slots__ = ("seq", "template", "params", "request", "enqueue_ts",
                 "deadline_at", "result_spec", "_future")

    def __init__(self, seq: int, template: CircuitTemplate,
                 params: np.ndarray):
        self.seq = seq
        self.template = template
        self.params = params
        self.request: Request | None = None   # set by the drain loop
        self.enqueue_ts: float | None = None  # lane-append stamp (traced runs)
        self.deadline_at: float | None = None  # absolute deadline (clock units)
        self.result_spec: ResultSpec | None = None  # None = statevector mode
        self._future: concurrent.futures.Future = concurrent.futures.Future()

    def done(self) -> bool:
        return self._future.done()

    def result(self, timeout: float | None = None):
        """Block for the resulting state; re-raises the execution error of
        a FAILED request."""
        return self._future.result(timeout)

    def exception(self, timeout: float | None = None):
        return self._future.exception(timeout)

    def add_done_callback(self, fn) -> None:
        self._future.add_done_callback(fn)

    def __await__(self):
        return asyncio.wrap_future(self._future).__await__()

    def __repr__(self) -> str:
        state = (self.request.state if self.request is not None
                 else "SUBMITTED")
        return f"IngestHandle(seq={self.seq}, {self.template.name}, {state})"


class _Lane:
    """One producer thread's private submission queue."""

    __slots__ = ("buf",)

    def __init__(self):
        self.buf: collections.deque[IngestHandle] = collections.deque()


class IngestServer:
    """Thread-safe + asyncio-native submission front end over the scheduler.

    ::

        with IngestServer(executor, max_batch=16, max_wait_ms=2.0) as srv:
            handles = [srv.submit(template, p) for p in params]   # any thread
            states = [h.result() for h in handles]

    or from asyncio::

        h = await srv.submit_async(template, p)
        state = await h

    Parameters mirror the scheduler's; ``max_wait_ms`` is the streaming
    age-out for underfull groups — 2 ms by default when the server builds
    its own scheduler, an explicit ``None`` disables aging (groups dispatch
    on fullness; :meth:`drain`/:meth:`close` flush the rest — the
    deterministic-batching mode) — ``max_pending`` + ``policy`` the
    backpressure window.  With
    a pre-built ``scheduler=``, the scheduler-owned knobs (``max_batch``,
    ``inflight``, ``max_wait_ms``, ``clock``, ``tracer``) must be configured
    on it — passing them here raises rather than silently losing them.
    ``tracer`` (a :class:`~repro.engine.telemetry.SpanTracer`) extends the
    scheduler's request spans back to the producer-side lane append, so a
    trace shows the ingest wait ahead of queueing and dispatch.
    ``autostart=False`` skips the background thread so tests drive
    :meth:`step` deterministically.
    """

    def __init__(self, executor: BatchExecutor | None = None, *,
                 scheduler: BatchScheduler | None = None,
                 max_batch: int | None = None, inflight: int | None = None,
                 max_wait_ms: "float | None" = _UNSET,
                 max_pending: int = 1024,
                 policy: str = BLOCK,
                 clock: Callable[[], float] | None = None,
                 tracer=None,
                 autostart: bool = True):
        if policy not in (BLOCK, REJECT):
            raise ValueError(f"policy must be {BLOCK!r} or {REJECT!r}, "
                             f"got {policy!r}")
        if max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        if scheduler is not None:
            if executor is not None:
                raise ValueError("pass either a scheduler or an executor")
            # never silently ignore (or worse, mutate) knobs the pre-built
            # scheduler owns
            ignored = [name for name, val in (("max_batch", max_batch),
                                              ("inflight", inflight),
                                              ("clock", clock),
                                              ("tracer", tracer))
                       if val is not None]
            if max_wait_ms is not _UNSET:
                ignored.append("max_wait_ms")
            if ignored:
                raise ValueError(
                    f"{', '.join(ignored)} belong to the scheduler; "
                    f"configure them on the BatchScheduler you pass in")
            self.scheduler = scheduler
        else:
            # the scheduler's own streaming trigger stays on: the drain loop
            # is its only submitter, so trigger checks never race across
            # threads
            self.scheduler = BatchScheduler(
                executor,
                max_batch=64 if max_batch is None else max_batch,
                inflight=2 if inflight is None else inflight,
                # default 2ms streaming age-out; an explicit None means
                # dispatch on fullness only (drain()/close() flush the rest)
                max_wait_ms=2.0 if max_wait_ms is _UNSET else max_wait_ms,
                clock=clock, tracer=tracer)
        # the scheduler owns the tracer (one span record per engine); the
        # server only extends its spans back to the producer-side lane append
        self.tracer = self.scheduler.tracer
        # None = the scheduler has no aging trigger: underfull groups wait
        # for drain()/close(); the loop then only ticks for result delivery
        self.max_wait_ms = self.scheduler.max_wait_ms
        self.policy = policy
        self.max_pending = max_pending
        self._slots = threading.BoundedSemaphore(max_pending)
        self._seq = itertools.count()
        self._lanes: dict[int, _Lane] = {}            # thread ident -> lane  #: guarded-by: _mutex, _wake
        self._local = threading.local()
        # _mutex orders intake state (lanes map, seq, closed flag) and backs
        # the drain loop's condition sleep; _done tracks outstanding counts
        # for flush()
        self._mutex = threading.Lock()
        self._wake = threading.Condition(self._mutex)
        self._done = threading.Condition(threading.Lock())
        # serializes every _live/_deliver driver (the loop, step(), and any
        # concurrent close()/flush() pair) so teardown paths can never
        # double-deliver a handle or double-release its pending slot
        self._sweep = threading.RLock()
        self._outstanding = 0                         #: guarded-by: _done
        self._live: dict[int, IngestHandle] = {}      # drain-loop private  #: guarded-by: _sweep
        self._closed = False           #: guarded-by: _mutex, _wake
        self._force = False            # one-shot: dispatch underfull groups  #: guarded-by: _mutex, _wake
        self._loop_error: BaseException | None = None
        self._rejected = 0             #: guarded-by: _mutex, _wake
        self._thread: threading.Thread | None = None
        if autostart:
            self.start()

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> "IngestServer":
        if self._thread is None:
            self._thread = threading.Thread(target=self._drain_loop,
                                            name="ingest-drain", daemon=True)
            self._thread.start()
        return self

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def __enter__(self) -> "IngestServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def close(self) -> None:
        """Stop intake, flush queued + in-flight work, resolve every handle.

        Idempotent.  Safe to call with producers still racing ``submit``:
        anything that made it into a lane is executed by the shutdown sweep
        (here, if the loop thread already exited), never dropped.
        """
        with self._mutex:
            self._closed = True
            self._wake.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._loop_error is not None:
            # the loop crashed: don't re-drive the (possibly broken)
            # dispatch path, just fail any straggler handles
            self._abort(self._loop_error)
            return
        # requests that raced past intake after the loop's final sweep
        self._final_sweep()

    def flush(self, timeout: float | None = None) -> bool:
        """Block until every submission so far is resolved; False on timeout.

        With a running drain loop this only *waits* — batching decisions
        (fullness, age-out, an explicit :meth:`drain`) stay with the loop.
        On a server with no loop (``autostart=False``, or already closed)
        nothing else would make progress, so flush drives one forced sweep
        itself and is then equivalent to :meth:`drain`."""
        if self._thread is None and self._loop_error is None:
            # never after a loop crash — _abort has already resolved
            # everything and the dispatch path may be broken
            self._final_sweep()
        with self._done:
            return self._done.wait_for(lambda: self._outstanding == 0,
                                       timeout)

    def drain(self, timeout: float | None = None) -> bool:
        """Force-dispatch everything queued (underfull groups included) and
        block until resolved — :meth:`flush` without waiting out the
        ``max_wait_ms`` age of a last underfull batch.  The natural call
        once a submission burst is known to be over."""
        if self.running:
            with self._mutex:
                self._force = True
                self._wake.notify_all()
        return self.flush(timeout)

    # -- submission (any thread) ----------------------------------------------
    def _lane(self) -> _Lane:
        lane = getattr(self._local, "lane", None)
        if lane is None:
            ident = threading.get_ident()
            with self._mutex:
                # reuse, never replace: CPython recycles thread idents, and
                # a dead producer's lane may still hold uncollected handles
                # — overwriting it would drop them
                lane = self._lanes.get(ident)
                if lane is None:
                    lane = self._lanes[ident] = _Lane()
            self._local.lane = lane
        return lane

    def submit(self, template: CircuitTemplate | Circuit,
               params: Sequence[float] | None = None, *,
               timeout: float | None = None,
               deadline_ms: float | None = None,
               result: ResultSpec | None = None) -> IngestHandle:
        """Enqueue one request from any thread; returns immediately with a
        future-like handle (modulo backpressure under the block policy).

        ``deadline_ms`` arms a serving deadline counted from *this* call
        (producer-side, so lane wait burns budget too): a request still
        undispatched when it elapses is shed with a terminal
        :class:`~repro.engine.resilience.DeadlineExceeded` instead of
        wasting a dispatch.

        ``result`` selects the result mode
        (:class:`~repro.engine.results.ResultSpec`): ``handle.result()``
        then resolves to int32 shot samples or f32 expectation values
        instead of a state.  Validated here so a bad spec (wrong type,
        out-of-range observable qubit) raises in the submitting thread,
        mirroring the ``validate_params`` contract.  The call is the profiler
        span ``repro.ingest.submit``."""
        with host_span("repro.ingest.submit"):
            return self._submit(template, params, timeout=timeout,
                                deadline_ms=deadline_ms, result=result)

    def _submit(self, template, params, *, timeout, deadline_ms,
                result) -> IngestHandle:
        """:meth:`submit` inside its span."""
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError(f"deadline_ms must be > 0, got {deadline_ms}")
        if result is not None and not isinstance(result, ResultSpec):
            raise TypeError(f"result must be a ResultSpec, "
                            f"got {type(result).__name__}")
        # lint-ok: EL001 unlocked fast-path check only; the authoritative
        # closed-vs-accepted decision is re-made under _mutex below, after
        # backpressure — this read just fails producers early without
        # contending the intake mutex
        if self._closed:
            raise IngestClosed("ingest server is closed")
        # shared with BatchScheduler.submit, so shape errors surface in the
        # submitting thread and the two entry points can never drift
        template, p = validate_params(template, params)
        if result is not None:
            result.validate_for(template)
        blocking = self.policy == BLOCK
        if not self._slots.acquire(blocking=blocking,
                                   timeout=timeout if blocking else None):
            if blocking:
                raise TimeoutError(f"no pending slot within {timeout}s")
            with self._mutex:
                self._rejected += 1    # reject-policy sheds only; a block-
                                       # policy timeout is not a rejection
            raise IngestRejected(f"pending window full ({self.max_pending}); "
                                 f"policy={self.policy!r}")
        handle = IngestHandle(next(self._seq), template, p)
        if result is not None:
            handle.result_spec = result
        if deadline_ms is not None:
            handle.deadline_at = self.scheduler.clock() + deadline_ms / 1e3
        if self.tracer.enabled:
            # producer-side stamp off the scheduler clock; recorded against
            # the req_id once the drain loop merges this ticket
            handle.enqueue_ts = self.scheduler.clock()
        lane = self._lane()
        # counted before the append so flush() can never observe a resolved
        # handle ahead of its own increment
        with self._done:
            self._outstanding += 1
        # append + closed-check are atomic under the intake mutex: close()
        # flips the flag under the same mutex *before* its final sweep, so a
        # handle is either rejected here or guaranteed to be swept — never
        # silently dropped.  (The notify needed this mutex anyway, so the
        # hot path still never touches the scheduler lock or a compile.)
        with self._mutex:
            if self._closed:      # closed while we waited on backpressure
                self._slots.release()
                with self._done:
                    self._outstanding -= 1
                    self._done.notify_all()
                raise IngestClosed("ingest server is closed")
            lane.buf.append(handle)
            self._wake.notify_all()
        return handle

    def submit_sweep(self, template: CircuitTemplate, params_matrix, *,
                     timeout: float | None = None,
                     result: ResultSpec | None = None) -> list[IngestHandle]:
        """Submit one request per row of a ``[B, P]`` parameter matrix
        (1-D rows follow :meth:`BatchScheduler.submit_sweep` semantics);
        ``result`` applies the same result mode to every row."""
        arr = validate_sweep(template, params_matrix)
        handles: list[IngestHandle] = []
        try:
            for row in arr:
                handles.append(self.submit(template, row, timeout=timeout,
                                           result=result))
        except Exception as e:
            # rows already accepted are live and will execute: hand their
            # handles to the caller on the exception so a partial sweep can
            # be awaited / retried without duplicating work
            e.partial_handles = handles
            raise
        return handles

    async def submit_async(self, template: CircuitTemplate | Circuit,
                           params: Sequence[float] | None = None,
                           result: ResultSpec | None = None,
                           ) -> IngestHandle:
        """Asyncio-native submit: never blocks the event loop, even when
        the block policy has to wait for a pending slot."""
        loop = asyncio.get_running_loop()
        return await loop.run_in_executor(
            None, lambda: self.submit(template, params, result=result))

    async def run_async(self, template: CircuitTemplate | Circuit,
                        params: Sequence[float] | None = None,
                        result: ResultSpec | None = None):
        """Submit and await the resulting payload in one call."""
        handle = await self.submit_async(template, params, result=result)
        return await handle

    # -- drain loop (single background thread, or step() from tests) ----------
    def _collect(self) -> list[IngestHandle]:
        """Merge every producer lane, ordered by submission ticket."""
        got: list[IngestHandle] = []
        with self._mutex:
            lanes = list(self._lanes.values())
        for lane in lanes:
            while True:
                try:
                    got.append(lane.buf.popleft())
                except IndexError:
                    break
        got.sort(key=lambda h: h.seq)
        return got

    def _deliver(self) -> int:
        """Resolve futures of terminal requests; frees backpressure slots.
        Caller holds ``_sweep`` (the loop's ``_step_once``, ``_final_sweep``,
        or the ``_abort`` teardown)."""
        resolved = [(seq, h) for seq, h in self._live.items()
                    if h.request is not None and h.request.done]
        for seq, h in resolved:
            del self._live[seq]
            req = h.request
            try:
                if req.ok:
                    h._future.set_result(req.result)
                else:
                    h._future.set_exception(
                        req.error if req.error is not None
                        else RuntimeError(f"request {req.req_id} failed"))
            except concurrent.futures.InvalidStateError:
                # the client cancelled the future (e.g. asyncio.wait_for
                # timeout through wrap_future): the result is simply
                # unwanted — never let one abandoned handle kill the loop
                pass
            self._slots.release()
        if resolved:
            with self._done:
                self._outstanding -= len(resolved)
                self._done.notify_all()
        return len(resolved)

    def _step_once(self, force: bool = False) -> int:
        """Ingest lanes -> poll the scheduler -> deliver results; the
        profiler spans ``repro.ingest.collect``, ``repro.sched.poll`` and
        ``repro.ingest.deliver``."""
        with self._sweep:
            with host_span("repro.ingest.collect"):
                collected = self._collect()
                # register BEFORE submitting: if an ingest raises mid-list,
                # _abort can still fail every collected handle (never a
                # silent drop)
                for h in collected:
                    self._live[h.seq] = h
                for h in collected:
                    h.request = self.scheduler.submit(
                        h.template, h.params, deadline_at=h.deadline_at,
                        result=h.result_spec)
                    if self.tracer.enabled and h.enqueue_ts is not None:
                        self.tracer.record(h.request.req_id, STAGE_ENQUEUE,
                                           h.enqueue_ts, seq=h.seq)
            self.scheduler.poll(force=force)
            with host_span("repro.ingest.deliver"):
                return self._deliver()

    def step(self, force: bool = False) -> int:
        """One deterministic drain iteration (no waiting, no thread).

        Exposed for fake-clock tests: ingest whatever the lanes hold, launch
        full/aged (all, when ``force``) groups, retire device-ready batches,
        resolve handles.  Returns the number of handles resolved.  Only for
        ``autostart=False`` servers — a running drain loop is the sole
        dispatcher otherwise.
        """
        if self.running:
            raise RuntimeError("step() is for autostart=False servers; the "
                               "background drain loop owns dispatch here")
        return self._step_once(force=force)

    def _have_lane_items(self) -> bool:
        with self._mutex:
            lanes = list(self._lanes.values())
        return any(lane.buf for lane in lanes)

    def _final_sweep(self) -> None:
        """Flush everything visible right now: lanes, queued groups
        (underfull included), the in-flight window — then deliver."""
        with self._sweep:
            self._step_once(force=True)
            self.scheduler.sync()
            self._deliver()

    def _drain_loop(self) -> None:
        try:
            self._drain_loop_body()
        except BaseException as e:  # noqa: BLE001 — the loop must not die
            # silently: a dead drain thread would hang every result() call
            # and deadlock block-policy producers on the pending semaphore.
            # Fail every unresolved handle with the cause and close intake.
            self._loop_error = e
            self._abort(e)

    def _abort(self, error: BaseException) -> None:
        """Crash path: resolve what finished, fail everything else."""
        with self._mutex:
            self._closed = True
        with self._sweep:
            self._abort_locked(error)

    def _abort_locked(self, error: BaseException) -> None:
        """Caller holds ``_sweep``."""
        try:
            self._deliver()              # terminal requests resolve normally
        except Exception:  # noqa: BLE001 — best effort during teardown
            pass
        for h in self._collect():
            self._live[h.seq] = h
        pending = list(self._live.values())
        self._live.clear()
        for h in pending:
            try:
                h._future.set_exception(RuntimeError(
                    f"ingest drain loop crashed: {error!r}"))
            except concurrent.futures.InvalidStateError:
                pass                     # already resolved or cancelled
            self._slots.release()
        if pending:
            with self._done:
                self._outstanding -= len(pending)
                self._done.notify_all()

    def _drain_loop_body(self) -> None:
        tick = max(self.max_wait_ms or 0.0, 0.5) / 1e3
        while True:
            with self._mutex:
                force, self._force = self._force, False
            self._step_once(force=force)
            if self._have_lane_items():
                continue                     # a burst landed mid-step
            with self._mutex:
                closed = self._closed
            if closed:
                break
            # nothing to ingest: retire the oldest in-flight batch (blocking
            # converts idle time into result delivery), else sleep on the
            # condition until a submit arrives or the age-out tick elapses —
            # never a busy spin
            # lint-ok: EL001 _live is mutated only by this loop thread while
            # it runs (_step_once/_final_sweep drivers are serialized on
            # _sweep); this unlocked emptiness read only tunes the
            # retire-vs-sleep choice
            if not self._live or not self.scheduler.retire_one():
                with self._wake:
                    # the predicate must cover every wake reason (close,
                    # force-drain, lane items): a drain() landing between
                    # our check and this wait would otherwise be a lost
                    # wakeup costing a full tick
                    if (not self._closed and not self._force and not any(
                            lane.buf for lane in self._lanes.values())):
                        # finite tick only while a group can actually age
                        # toward a max_wait_ms trigger; when idle — or when
                        # the scheduler has no aging trigger at all, so only
                        # a submit/drain/close can create progress — sleep
                        # untimed: zero wakeups, zero lock contention
                        # lint-ok: EL001 same loop-thread-private _live read
                        # as above — only picks timed vs untimed sleep
                        idle = not self._live and not self.scheduler.pending
                        # a retry backlog also ages toward dispatch (its
                        # backoff elapses with no submit to wake us), so it
                        # forces a timed sleep even in no-aging mode
                        timed = not idle and (
                            self.max_wait_ms is not None
                            or self.scheduler.backoff_pending)
                        self._wake.wait(tick if timed else None)
        # shutdown: flush lanes, queued groups, and the in-flight window
        self._final_sweep()

    # -- checkpointing --------------------------------------------------------
    def pending_handles(self) -> list[IngestHandle]:
        """Every submission not yet terminal, ticket-ordered — the in-flight
        state a :func:`~repro.engine.resilience.snapshot_records` checkpoint
        captures: ingested-but-unresolved handles plus anything still
        sitting in a producer lane (not yet seen by the drain loop)."""
        with self._sweep:
            live = [h for h in self._live.values()
                    if h.request is None or not h.request.done]
            with self._mutex:
                lanes = list(self._lanes.values())
            for lane in lanes:
                live.extend(list(lane.buf))
        return sorted(live, key=lambda h: h.seq)

    # -- reporting ------------------------------------------------------------
    def ingest_counters(self) -> dict:
        """The front end's own counters, unprefixed — the registry source
        behind :func:`repro.engine.telemetry.engine_registry`'s
        ``ingest_*`` keys (and this server's :meth:`report`)."""
        with self._mutex:
            out = {
                "producers": len(self._lanes),
                "rejected": self._rejected,
                "max_pending": self.max_pending,
                "policy": self.policy,
            }
        with self._done:
            out["outstanding"] = self._outstanding
        return out

    def report(self) -> dict:
        """Scheduler + cache report extended with ingest-front-end fields."""
        out = self.scheduler.report()
        out.update({f"ingest_{k}": v
                    for k, v in self.ingest_counters().items()})
        return out
