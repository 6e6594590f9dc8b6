"""Mixed-traffic serving: synchronous drain vs async streaming pipeline.

Heterogeneous request traffic (two QAOA depths + a hardware-efficient
ansatz — three distinct plan structures) is pushed through the request
scheduler twice with warm plan/program caches: once with the blocking
``drain`` (each batch retired before the next launches) and once with
``drain_async`` under a double-buffered in-flight window (host-side
grouping/padding/staging of batch *k+1* overlaps device execution of batch
*k*).  Reports throughput plus p50/p99 request latency for both modes.

CSV: serve_{sync|async}_n<q>_b<B>,us_per_request,circuits_per_s=..;p50_ms=..;
p99_ms=.. and a final speedup row.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.engine import (BatchExecutor, BatchScheduler, PlanCache,
                          hea_template, qaoa_template)

N_QUBITS = 12
MAX_BATCH = 16
REQUESTS = 96
INFLIGHT = 2
ITERS = 3


def make_traffic(n: int, requests: int, seed: int = 0):
    """Random mix over three distinct template structures."""
    templates = (qaoa_template(n, 2), qaoa_template(n, 3),
                 hea_template(n, 2))
    rng = np.random.default_rng(seed)
    return [(t, rng.uniform(-np.pi, np.pi, t.num_params))
            for t in (templates[int(i)]
                      for i in rng.integers(0, len(templates), requests))]


def serve_once(cache: PlanCache, traffic, mode: str, max_batch: int,
               inflight: int) -> tuple[float, dict]:
    """One pass of the traffic through a fresh scheduler on a warm cache."""
    ex = BatchExecutor(backend="planar", cache=cache)
    sched = BatchScheduler(ex, max_batch=max_batch,
                           inflight=inflight if mode == "async" else 0)
    t0 = time.perf_counter()
    for template, params in traffic:
        sched.submit(template, params)
    if mode == "async":
        sched.drain_async()
        sched.sync()
    else:
        sched.drain()
    dt = time.perf_counter() - t0
    rep = sched.report()
    assert rep["failed"] == 0, rep
    return dt, rep


def run(n: int = N_QUBITS, requests: int = REQUESTS,
        max_batch: int = MAX_BATCH, inflight: int = INFLIGHT,
        iters: int = ITERS) -> float:
    """Benchmark both modes; returns the async-over-sync throughput ratio."""
    traffic = make_traffic(n, requests)
    cache = PlanCache()
    serve_once(cache, traffic, "sync", max_batch, inflight)   # warm compiles
    results = {}
    for mode in ("sync", "async"):
        best = None
        for _ in range(iters):
            dt, rep = serve_once(cache, traffic, mode, max_batch, inflight)
            if best is None or dt < best[0]:
                best = (dt, rep)
        dt, rep = best
        results[mode] = dt
        emit(f"serve_{mode}_n{n}_b{max_batch}", dt / requests,
             f"circuits_per_s={requests / dt:.1f};"
             f"p50_ms={rep['latency_p50_ms']:.1f};"
             f"p99_ms={rep['latency_p99_ms']:.1f};"
             f"batches={rep['batches']}")
    speedup = results["sync"] / results["async"]
    emit(f"serve_async_speedup_n{n}_b{max_batch}", results["async"] / requests,
         f"speedup={speedup:.2f}x")
    return speedup


def main() -> None:
    run()


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=N_QUBITS)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    ap.add_argument("--max-batch", type=int, default=MAX_BATCH)
    ap.add_argument("--inflight", type=int, default=INFLIGHT)
    ap.add_argument("--iters", type=int, default=ITERS)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(args.qubits, args.requests, args.max_batch, args.inflight, args.iters)
