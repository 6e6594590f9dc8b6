"""Chaos serving: throughput + p99 under injected dispatch faults.

The same mixed traffic is served twice on warm plan/program caches:

* **fault-free** — the oracle run: submit all, blocking ``drain()``; its
  per-request states are the bitwise reference and its throughput the
  baseline;
* **chaos** — identical traffic through an executor carrying a seeded
  :class:`~repro.engine.FaultInjector` (10% dispatch-fault rate by
  default) and a scheduler with a :class:`~repro.engine.RetryPolicy`.
  Every faulted batch re-enqueues as one intact retry chunk, so the
  retried dispatch reuses the same padded batch size — and therefore the
  same compiled executable — as the fault-free run.

The derived column asserts the resilience contract: ``mismatches=0``
(every retried result bitwise-equal to the fault-free oracle),
``failed=0`` / ``dropped=0`` (no request lost to a transient fault), and
reports the retry volume plus the chaos run's throughput/p99 cost.  The
chaos schedule is a pure function of (seed, rate, traffic), so a failing
run reproduces exactly from the CSV's logged seed.

CSV: ``chaos_faultfree_*`` and ``chaos_f<rate>_*`` rows.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from benchmarks.serve_mixed import make_traffic
from repro.engine import (BatchExecutor, BatchScheduler, FaultInjector,
                          PlanCache, RetryPolicy)

N_QUBITS = 12
MAX_BATCH = 16
REQUESTS = 96
FAULT_RATE = 0.10
SEED = 7
ITERS = 3       # best-of: the 2-core container is jittery


def serve(cache: PlanCache, traffic, max_batch: int,
          injector: FaultInjector | None = None,
          retry: RetryPolicy | None = None):
    """Submit all traffic, blocking drain; returns (dt, report, states)."""
    ex = BatchExecutor(backend="planar", cache=cache,
                       injector=injector)
    sched = BatchScheduler(ex, max_batch=max_batch, inflight=0, retry=retry)
    t0 = time.perf_counter()
    reqs = [sched.submit(t, p) for t, p in traffic]
    sched.drain()
    dt = time.perf_counter() - t0
    rep = sched.report()
    dropped = sum(not r.done for r in reqs)
    assert dropped == 0, f"{dropped} requests never reached a terminal state"
    assert rep["failed"] == 0, rep
    return dt, rep, [np.asarray(r.result.to_dense()) for r in reqs]


def run(n: int = N_QUBITS, requests: int = REQUESTS,
        max_batch: int = MAX_BATCH, rate: float = FAULT_RATE,
        seed: int = SEED, iters: int = ITERS) -> int:
    """Serve with and without chaos; returns the chaos run's retry count."""
    traffic = make_traffic(n, requests)
    cache = PlanCache()
    serve(cache, traffic, max_batch)               # warm plans + programs

    def chaos_run():
        injector = FaultInjector(seed=seed, rates={"dispatch": rate})
        # budget sized so a request surviving the whole run is overwhelmingly
        # likely: P(8 consecutive faults) at 10% is 1e-8
        dt, rep, states = serve(cache, traffic, max_batch,
                                injector=injector,
                                retry=RetryPolicy(max_retries=8))
        return dt, rep, states, injector.counters()

    best_ok = best_chaos = None
    for _ in range(iters):
        dt, rep, ref = serve(cache, traffic, max_batch)
        if best_ok is None or dt < best_ok[0]:
            best_ok = (dt, rep, ref)
        got = chaos_run()
        if best_chaos is None or got[0] < best_chaos[0]:
            best_chaos = got

    ok_dt, ok_rep, ok_states = best_ok
    ch_dt, ch_rep, ch_states, ch_counters = best_chaos
    mismatches = sum(not np.array_equal(a, b)
                     for a, b in zip(ch_states, ok_states))
    emit(f"chaos_faultfree_n{n}_b{max_batch}", ok_dt / requests,
         f"circuits_per_s={requests / ok_dt:.1f};"
         f"p99_ms={ok_rep['latency_p99_ms']:.1f};"
         f"batches={ok_rep['batches']}")
    emit(f"chaos_f{int(rate * 100)}_n{n}_b{max_batch}", ch_dt / requests,
         f"circuits_per_s={requests / ch_dt:.1f};"
         f"p99_ms={ch_rep['latency_p99_ms']:.1f};"
         f"batches={ch_rep['batches']};seed={seed};"
         f"fired={ch_counters['dispatch_fired']};"
         f"retried={ch_rep['retried']};failed={ch_rep['failed']};"
         f"mismatches={mismatches}")
    assert ch_counters["dispatch_fired"] > 0, (
        "chaos run injected no faults — the schedule exercised nothing "
        f"(seed={seed}, rate={rate})")
    assert mismatches == 0, (
        f"{mismatches} chaos-run results differ bitwise from the "
        f"fault-free oracle (seed={seed}, rate={rate})")
    return int(ch_rep["retried"])


def main() -> None:
    run()


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=N_QUBITS)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    ap.add_argument("--max-batch", type=int, default=MAX_BATCH)
    ap.add_argument("--rate", type=float, default=FAULT_RATE)
    ap.add_argument("--seed", type=int, default=SEED)
    ap.add_argument("--iters", type=int, default=ITERS)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(args.qubits, args.requests, args.max_batch, args.rate, args.seed,
        args.iters)
