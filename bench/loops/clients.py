"""Closed-loop variational clients through ``IngestServer.submit``.

``traffic["clients"]`` clients each send a request, wait for its handle
and send the next parameters at once (an optimizer's walk, see
:class:`bench.traffic_gen.ClientParams`), with no think time.  The fleet
is a whole number of batches and all of its first requests are in the
server's lanes before it starts, so every batch holds ``max_batch``
requests and a batch completes whenever the count of completions reaches
a multiple of ``max_batch``.

The window starts at the batch completion that ends the warm-up and ends
at the first batch completion after ``--seconds``: ``evals_per_s`` is the
requests completed between the two over the seconds between them, and
``p95_ms`` the nearest-rank 95th percentile of those requests'
submit-to-result latencies.  Once the window has closed, a seeded sample
of its completed requests is compared, term by term, with the
reference's expectation values (``zz_gap``), and every request sent in
the window has to come back (``failed``).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time

import jax.numpy as jnp
import numpy as np

from bench import compare, reference
from bench.harness import (DRAIN_GRACE_S, CompileCounter, Profiler,
                           RunRecord, Spans, delta, fmt, memory_peak_bytes,
                           patched, worse)
from bench.traffic_gen import ClientParams, rng

REF_BATCH = 64          # rows per reference batch
WARMUP_TIMEOUT_S = 900.0


@dataclasses.dataclass
class _Req:
    client: int
    params: np.ndarray
    t_submit: float
    k: int | None = None            # completion index, in completion order
    t_done: float | None = None
    result: np.ndarray | None = None
    error: BaseException | None = None


def run(cfg, traffic, family, *, seed, seconds, trace, devices, log):
    from repro.engine import BatchExecutor, ResultSpec
    from repro.engine.ingest import IngestServer
    from repro.engine.plan import PlanCache
    from repro.engine.telemetry import (STAGE_DISPATCH, STAGE_ENQUEUE,
                                        SpanTracer)

    batch = cfg["max_batch"]
    warm = traffic["warmup_rounds"] * traffic["clients"]
    if traffic["clients"] % batch:
        raise ValueError(f"{traffic['clients']} clients are not whole "
                         f"batches of {batch}")
    span = Spans(trace)
    template = family.program_template(cfg)
    spec = ResultSpec.expectation(family.program_observables(cfg))
    executor = BatchExecutor(backend=traffic["backend"], cache=PlanCache())
    tracer = SpanTracer() if trace else None
    server = IngestServer(executor, max_batch=batch,
                          max_wait_ms=cfg["max_wait_ms"], tracer=tracer,
                          autostart=False)
    gen = ClientParams(seed, template.num_params, traffic)
    reqs: list[_Req] = []
    done_at: list[float] = []       # completion times, in completion order
    done_cv = threading.Condition()
    stop = threading.Event()

    def submit(client, g, params):
        req = _Req(client, params, time.perf_counter())
        with span("submit"):
            handle = server.submit(template, params, result=spec)
        with done_cv:
            reqs.append(req)
        handle.add_done_callback(lambda fut: resolve(req, g, fut))

    def resolve(req, g, fut):
        with span("resolve"):
            req.error = fut.exception()
            if req.error is None:
                req.result = np.asarray(fut.result())
            with done_cv:
                req.t_done = time.perf_counter()
                req.k = len(done_at)
                done_at.append(req.t_done)
                done_cv.notify_all()
            if not stop.is_set():
                submit(req.client, g, gen.step(g, req.params))

    def batch_end_after(deadline):
        """The completion count at the first batch completion at or
        after ``deadline``, or None while there is none yet."""
        for k in range(warm + batch, len(done_at) + 1, batch):
            if done_at[k - 1] >= deadline:
                return k
        return None

    with CompileCounter() as cc:
        for c in range(traffic["clients"]):
            g, p = gen.start(c)
            submit(c, g, p)
        server.start()
        with done_cv:
            if not done_cv.wait_for(lambda: len(done_at) >= warm,
                                    timeout=WARMUP_TIMEOUT_S):
                raise RuntimeError(f"warm-up: {len(done_at)} of {warm} "
                                   f"requests done in {WARMUP_TIMEOUT_S} s")
            t0 = done_at[warm - 1]
        setup = cc.snapshot()
        stats = server.scheduler.stats
        rows0, pad0 = stats.batch_rows, stats.padded_slots
        log(f"setup: clients={traffic['clients']} max_batch={batch} "
            f"warm_requests={warm} {fmt(setup)}")
        window = min(seconds, traffic["trace_seconds"]) if trace else seconds
        prof = Profiler(trace)
        prof.start()
        with span("window"):
            time.sleep(max(0.0, t0 + window - time.perf_counter()))
            with done_cv:
                if not done_cv.wait_for(
                        lambda: batch_end_after(t0 + window) is not None,
                        timeout=DRAIN_GRACE_S):
                    raise RuntimeError(f"no batch completed in the "
                                       f"{DRAIN_GRACE_S} s after the window")
                k1 = batch_end_after(t0 + window)
                t1 = done_at[k1 - 1]
        rows1, pad1 = stats.batch_rows, stats.padded_slots
        stop.set()
        prof.stop()
        in_window = delta(setup, cc.snapshot())
        drained = server.drain(timeout=DRAIN_GRACE_S)
        server.close()
    peak = memory_peak_bytes(devices)
    with done_cv:
        all_reqs = list(reqs)
    sent = [r for r in all_reqs if t0 <= r.t_submit < t1]
    failed = sum(1 for r in sent if r.t_done is None or r.error is not None)
    finished = [r for r in all_reqs if r.k is not None and warm <= r.k < k1]
    good = [r for r in finished if r.error is None]
    lat = [r.t_done - r.t_submit for r in finished]
    log(f"window: seconds={t1 - t0!r} completed={len(finished)} "
        f"sent={len(sent)} failed={failed} drained={drained} "
        f"{fmt(in_window)} memory_peak_bytes={peak}")
    counters = {"batch_rows": rows1 - rows0, "padded_slots": pad1 - pad0}
    if tracer is not None:
        counters["queue_wait_s"] = _queue_waits(
            tracer.events(), STAGE_ENQUEUE, STAGE_DISPATCH, t0, t1)
    del server, executor

    gap = _check(cfg, family, good, seed, traffic["check_requests"], log)
    return RunRecord(
        e2e={"evals_per_s": len(finished) / (t1 - t0),
             "p95_ms": 1e3 * compare.percentile(lat, 95)},
        counters=counters,
        checks=[("zz_gap", gap, cfg["limits"]["zz_gap"]),
                ("failed", failed, 0)],
        attempted=len(sent), failed=failed, memory_peak_bytes=peak,
        window_start=t0, trace_dir=prof.dir)


def _queue_waits(events, enqueue, dispatch, t0, t1) -> list:
    """Lane-append to dispatch, for requests dispatched in [t0, t1)."""
    out = []
    for evs in events.values():
        enq = [e["ts"] for e in evs if e["stage"] == enqueue]
        dis = [e["ts"] for e in evs if e["stage"] == dispatch]
        if enq and dis and t0 <= dis[0] < t1:
            out.append(dis[0] - enq[0])
    return out


def _check(cfg, family, good, seed, k, log) -> float:
    """Widest gap, over a seeded sample of completed requests and every
    term, between a served expectation value and the reference's."""
    if not good:
        return math.inf
    pick = rng(seed, 1, 1).choice(len(good), size=min(k, len(good)),
                                  replace=False)
    sample = [good[j] for j in sorted(pick)]
    gates = family.reference_gates(cfg)
    pairs = family.observables(cfg)
    worst = 0.0
    for s in range(0, len(sample), REF_BATCH):
        chunk = sample[s:s + REF_BATCH]
        pm = np.stack([r.params for r in chunk])
        if len(chunk) < REF_BATCH:     # one batch shape: one compile
            pm = np.concatenate([pm, np.repeat(pm[-1:], REF_BATCH - len(chunk),
                                               axis=0)])
        re, im = reference.run_gates(cfg["n"], gates, pm)
        want = np.sum(np.asarray(reference.zz_expectations(
            re, im, pairs=pairs), np.float64), axis=-1)[:len(chunk)]
        got = np.stack([r.result for r in chunk])
        worst = worse(worst, compare.widest_gap(got, want))
        del re, im
    log(f"check: requests={len(sample)} terms={len(pairs)} zz_gap={worst!r}")
    return worst


def control(cfg, traffic, family):
    """The reference at ``high`` in place of the batched result program."""
    from repro.engine.plan import CompiledPlan
    gates, n = family.reference_gates(cfg), cfg["n"]
    pairs = family.observables(cfg)

    def build(self):
        def fn(data0, pm, rowkeys):
            re, im = reference.run_gates(n, gates, np.asarray(pm), "high")
            blocks = reference.zz_expectations(re, im, pairs=pairs)
            return jnp.sum(blocks, axis=-1)
        return fn
    return patched(CompiledPlan, "_build_batched_result", build)


def _half_batch(cfg, traffic, family):
    from repro.engine.batch import BatchExecutor
    orig = BatchExecutor.dispatch_batch

    def dispatch(self, template, params_matrix, *args, **kw):
        pm = np.array(params_matrix, np.float32, copy=True)
        half = pm.shape[0] // 2
        pm[pm.shape[0] - half:] = pm[:half]
        return orig(self, template, pm, *args, **kw)
    return patched(BatchExecutor, "dispatch_batch", dispatch)


def _epilogue_fault(change):
    def fault(cfg, traffic, family):
        from repro.engine.plan import CompiledPlan
        orig = CompiledPlan._epilogue_step

        def epilogue(self, spec):
            epi = orig(self, spec)
            return lambda data, key: change(epi(data, key))
        return patched(CompiledPlan, "_epilogue_step", epilogue)
    return fault


# faults this loop can have besides bench.control's own: half of a batch
# computed from the other half's parameters; one term of each expectation
# row altered, or made NaN, where the row is produced
FAULTS = {
    "half_batch": _half_batch,
    "altered": _epilogue_fault(lambda r: r.at[0].add(1e-2)),
    "nan": _epilogue_fault(lambda r: r.at[0].set(jnp.nan)),
}
