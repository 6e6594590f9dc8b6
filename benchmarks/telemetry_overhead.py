"""Telemetry overhead: the same ingest burst with tracing off vs on.

The observability layer's contract is that it is free when disabled (the
``NULL_TRACER`` gate: no clock reads, no appends) and cheap when enabled
(per-request span recording is a handful of dict appends under one lock).
This benchmark measures both claims on the concurrent ingest workload:
K barrier-synchronized producers push mixed heterogeneous traffic through
:class:`repro.engine.IngestServer` on warm plan/program caches, once with
the default disabled tracer and once with a live :class:`SpanTracer` +
metrics-registry export — reporting throughput and p99 latency deltas.

Both sides are best-of-``iters`` (the 2-core container is jittery under
threads), and the traced run's span record is validated: exactly one
well-formed span tree per request, or the run fails.

CSV: telemetry_off_* / telemetry_on_* rows and a final
``telemetry_overhead_*`` row whose derived column carries the throughput
overhead percentage (reference < 5% at n=12, batch 16, 4 producers) and
the p99 delta.  ``--trace FILE`` writes the traced run's Chrome-trace JSON
(CI feeds it to ``tools/trace_report.py`` as the export-format check);
``--assert-overhead-pct X`` turns the reference bound into a hard failure.
"""
from __future__ import annotations

import time

from benchmarks.common import emit
from benchmarks.serve_mixed import make_traffic
from repro.engine import (BatchExecutor, IngestServer, PlanCache, SpanTracer,
                          engine_registry)
from repro.testing import run_producers

N_QUBITS = 12
MAX_BATCH = 16
REQUESTS = 96
CLIENTS = 4
ITERS = 5       # best-of: thread scheduling noise dominates single runs
# fullness-only dispatch (no aging): identical batching decisions on both
# sides, so the delta measures telemetry, not trigger timing
MAX_WAIT_MS = None


def serve(cache: PlanCache, traffic, max_batch: int, clients: int,
          tracer: SpanTracer | None = None):
    """One ingest burst; returns (wall seconds, report, server)."""
    ex = BatchExecutor(backend="planar", cache=cache)
    srv = IngestServer(ex, max_batch=max_batch, inflight=2,
                       max_wait_ms=MAX_WAIT_MS, tracer=tracer)
    chunks = [traffic[i::clients] for i in range(clients)]
    starts: list = []

    def client(i: int):
        starts.append(time.perf_counter())    # right after the barrier
        return [srv.submit(t, p) for t, p in chunks[i]]

    run_producers(clients, client, timeout=600)
    assert srv.drain(timeout=600)
    dt = time.perf_counter() - min(starts)
    rep = srv.report()
    srv.close()
    assert rep["failed"] == 0, rep
    return dt, rep, srv


def run(n: int = N_QUBITS, requests: int = REQUESTS,
        max_batch: int = MAX_BATCH, clients: int = CLIENTS,
        iters: int = ITERS, trace: str | None = None,
        assert_overhead_pct: float | None = None) -> float:
    """Benchmark tracing off vs on; returns the throughput overhead pct."""
    traffic = make_traffic(n, requests)
    cache = PlanCache()
    serve(cache, traffic, max_batch, clients)                  # warm programs
    serve(cache, traffic, max_batch, clients, SpanTracer())    # + traced path

    best_off = best_on = None
    for _ in range(iters):
        dt, rep, _ = serve(cache, traffic, max_batch, clients)
        if best_off is None or dt < best_off[0]:
            best_off = (dt, rep)
        dt, rep, srv = serve(cache, traffic, max_batch, clients, SpanTracer())
        if best_on is None or dt < best_on[0]:
            best_on = (dt, rep, srv)

    off_dt, off_rep = best_off
    on_dt, on_rep, on_srv = best_on
    # span integrity of the best traced run: one well-formed tree per
    # request (span_trees raises on orphans / duplicates / bad ordering)
    trees = on_srv.tracer.span_trees()
    assert len(trees) == requests, (
        f"trace covers {len(trees)} of {requests} requests")
    if trace:
        on_srv.tracer.write_chrome_trace(trace)
        reg = engine_registry(server=on_srv)
        reg.write_json(trace + ".metrics.json")

    overhead = on_dt / off_dt - 1.0
    p99_delta = on_rep["latency_p99_ms"] - off_rep["latency_p99_ms"]
    emit(f"telemetry_off_n{n}_b{max_batch}_c{clients}", off_dt / requests,
         f"circuits_per_s={requests / off_dt:.1f};"
         f"p99_ms={off_rep['latency_p99_ms']:.1f};"
         f"batches={off_rep['batches']}")
    emit(f"telemetry_on_n{n}_b{max_batch}_c{clients}", on_dt / requests,
         f"circuits_per_s={requests / on_dt:.1f};"
         f"p99_ms={on_rep['latency_p99_ms']:.1f};"
         f"spans={len(trees)}")
    emit(f"telemetry_overhead_n{n}_b{max_batch}", on_dt / requests,
         f"overhead_pct={overhead * 100:.2f};"
         f"p99_delta_ms={p99_delta:.2f}")
    if assert_overhead_pct is not None:
        assert overhead * 100 < assert_overhead_pct, (
            f"tracing overhead {overhead * 100:.2f}% exceeds the "
            f"{assert_overhead_pct}% bound "
            f"(off={off_dt:.3f}s on={on_dt:.3f}s)")
    return overhead * 100


def main() -> None:
    run()


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=N_QUBITS)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    ap.add_argument("--max-batch", type=int, default=MAX_BATCH)
    ap.add_argument("--clients", type=int, default=CLIENTS)
    ap.add_argument("--iters", type=int, default=ITERS)
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="write the traced run's Chrome-trace JSON here "
                         "(plus FILE.metrics.json, the registry snapshot)")
    ap.add_argument("--assert-overhead-pct", type=float, default=None,
                    help="fail if tracing costs more than this much "
                         "throughput (CI uses 5)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(args.qubits, args.requests, args.max_batch, args.clients, args.iters,
        trace=args.trace, assert_overhead_pct=args.assert_overhead_pct)
