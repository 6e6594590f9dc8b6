"""Batched-serving driver: synthetic request traffic through the engine.

Simulates a serving workload of parameterized-circuit requests (QAOA sweeps,
hardware-efficient-ansatz evaluations, fixed benchmark circuits), pushes them
through the request scheduler — synchronously (``--mode sync``: every batch
blocks before the next launches), as the async streaming pipeline
(``--mode async``: host-side batch formation overlaps device execution under
an ``--inflight``-deep window), or through the concurrent ingest front end
(``--mode ingest``: ``--clients K`` producer threads submit through
``IngestServer`` while its drain loop batches and dispatches) — and reports
throughput, latency percentiles, failure counts, padding overhead, and
plan-cache statistics.

  PYTHONPATH=src python -m repro.launch.serve_sim --qubits 10 --requests 128
  PYTHONPATH=src python -m repro.launch.serve_sim --mode async --inflight 2 \
      --backend pallas --workload qaoa --requests 64 --max-batch 32
  PYTHONPATH=src python -m repro.launch.serve_sim --mode ingest --clients 4 \
      --max-wait-ms 2 --requests 128

The target and the Pallas interpret mode follow the device JAX finds.  The
exit code is non-zero when any request failed.

Telemetry (docs/OBSERVABILITY.md): ``--trace FILE`` records every request's
lifecycle span and writes a Chrome-trace/Perfetto JSON (``--trace-jsonl`` the
raw event log), ``--metrics-json FILE`` exports the unified metrics-registry
snapshot, and ``--stats`` adds the served vectorization-activity report
(ALO/ORR/fast-path coverage per plan key).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import circuits as C
from repro.engine import (BatchExecutor, BatchScheduler, FaultInjector,
                          IngestRejected, IngestServer, PlanBreaker,
                          ResultSpec, RetryPolicy, SpanTracer, depolarizing,
                          engine_registry, hea_template, qaoa_template,
                          template_of)
from repro.launch.compile_cache import enable_compile_cache
from repro.testing import run_producers


def make_traffic(workload: str, n: int, requests: int, seed: int):
    """Yield (template, params) pairs for a synthetic request mix."""
    rng = np.random.default_rng(seed)
    templates = []
    if workload in ("qaoa", "mixed"):
        templates.append(qaoa_template(n, 2))
        templates.append(qaoa_template(n, 3))
    if workload in ("hea", "mixed"):
        templates.append(hea_template(n, 2))
    if workload == "mixed":
        templates.append(template_of(C.ghz(n)))
    out = []
    for _ in range(requests):
        t = templates[int(rng.integers(0, len(templates)))]
        out.append((t, rng.uniform(-np.pi, np.pi, t.num_params)))
    return out


def _make_result_spec(args, n: int) -> ResultSpec | None:
    """Resolve --result-mode (+ its knobs) into the per-request spec."""
    mode = args.result_mode
    if mode == "statevector":
        return None
    if mode == "shots":
        return ResultSpec.sample(args.shots, key=args.seed)
    observables = [{0: "Z"}, {n - 1: "Z"}]
    if mode == "expectation":
        return ResultSpec.expectation(observables)
    channels = [depolarizing(q, args.noise_p) for q in (0, n - 1)]
    return ResultSpec.noisy(channels, observables,
                            unravelings=args.unravelings, key=args.seed)


def _serve(sched: BatchScheduler, traffic, mode: str,
           deadline_ms: float | None = None, result=None) -> float:
    """Push traffic through one scheduler; returns wall seconds."""
    t0 = time.perf_counter()
    for template, params in traffic:
        sched.submit(template, params, deadline_ms=deadline_ms,
                     result=result)
    if mode == "async":
        sched.drain_async()
        sched.sync()
    else:
        sched.drain()
    return time.perf_counter() - t0


def _serve_ingest(sched: BatchScheduler, traffic, clients: int,
                  max_pending: int, policy: str,
                  deadline_ms: float | None = None, result=None,
                  ) -> tuple[float, dict, IngestServer]:
    """K concurrent client threads through the ingest front end; returns
    wall seconds, the server report (scheduler + ingest_* fields), and the
    (closed) server — its counters stay readable for the metrics export."""
    srv = IngestServer(scheduler=sched, max_pending=max_pending,
                       policy=policy)
    chunks = [traffic[i::clients] for i in range(clients)]
    starts: list = []

    def client(i: int) -> None:
        starts.append(time.perf_counter())    # right after the barrier
        for template, params in chunks[i]:
            try:
                srv.submit(template, params, deadline_ms=deadline_ms,
                           result=result)
            except IngestRejected:
                pass    # shed load, keep serving; the server counts these
                        # (ingest_rejected in the report)

    run_producers(clients, client, timeout=600)
    srv.drain()
    dt = time.perf_counter() - min(starts)
    rep = srv.report()
    srv.close()
    return dt, rep, srv


def _print_report(rep: dict, dt: float, label: str, args,
                  cache=None, activity=None) -> None:
    print(f"[{label}] served {rep['requests']} requests in {dt:.3f}s "
          f"({rep['requests'] / dt:.1f} circuits/s) "
          f"in {rep['batches']} batches, backend={args.backend}, "
          f"n={args.qubits}, failed={rep['failed']}")
    if rep.get("retried") or rep.get("shed"):
        print(f"[{label}] resilience: retried={rep.get('retried', 0)} "
              f"shed={rep.get('shed', 0)}")
    if "latency_p50_ms" in rep:
        print(f"[{label}] latency ms: mean={rep['latency_mean_ms']:.1f} "
              f"p50={rep['latency_p50_ms']:.1f} "
              f"p99={rep['latency_p99_ms']:.1f}; "
              f"padded slots={rep['padded_slots']}")
    else:
        print(f"[{label}] no completed requests -> no latency stats")
    modes = {k[len("mode_"):]: v for k, v in rep.items()
             if k.startswith("mode_")}
    if modes:
        print(f"[{label}] result modes: "
              + " ".join(f"{m}={c}" for m, c in sorted(modes.items())))
    print(f"[{label}] plan cache: {rep['cache_compiles']} compiles, "
          f"{rep['cache_hits']} hits, {rep['cache_misses']} misses")
    if "compile_seconds_total" in rep:
        print(f"[{label}] compile time: "
              f"total={rep['compile_seconds_total'] * 1e3:.1f}ms over "
              f"{rep['compile_count']} compiles "
              f"(p50={rep['compile_seconds_p50'] * 1e3:.1f}ms "
              f"max={rep['compile_seconds_max'] * 1e3:.1f}ms)")
    if "ingest_producers" in rep:
        print(f"[{label}] ingest: producers={rep['ingest_producers']} "
              f"rejected={rep['ingest_rejected']} "
              f"outstanding={rep['ingest_outstanding']} "
              f"(policy={rep['ingest_policy']}, "
              f"max_pending={rep['ingest_max_pending']})")
    if getattr(args, "stats", False):
        if "class_routed" in rep:
            # shape-class routing: batch fill plus how much of the traffic
            # actually co-batched across exact plan keys (spills = requests
            # that hit the class group's capacity and fell back to per-key)
            print(f"[{label}] routing: fill={rep['fill_rate'] * 100:.1f}% "
                  f"class_routed={rep['class_routed']} "
                  f"class_batches={rep['class_batches']} "
                  f"spills={rep['overflow_spills']} "
                  f"classes={rep['shape_classes']}")
        elif "fill_rate" in rep:
            print(f"[{label}] routing: fill={rep['fill_rate'] * 100:.1f}% "
                  f"(exact-key grouping; --class-routing to co-batch "
                  f"shape-compatible templates)")
        print(f"[{label}] fused gates by class: "
              f"diagonal={rep.get('gates_diagonal', 0)} "
              f"permutation={rep.get('gates_permutation', 0)} "
              f"general={rep.get('gates_general', 0)}")
        if cache is not None:
            fl = cache.flops_summary()
            print(f"[{label}] est. flops/amp: "
                  f"{fl['flops_per_amp_actual']:.0f} specialized vs "
                  f"{fl['flops_per_amp_generic']:.0f} generic "
                  f"({fl['flops_saved_frac'] * 100:.1f}% saved)")
        if activity is not None:
            # served vectorization activity: what the dispatched traffic
            # actually ran, amplitude-weighted per plan key (the serving-
            # side analogue of the paper's Table IV)
            for key, a in activity.per_plan().items():
                print(f"[{label}] served {key}: rows={a['rows']} "
                      f"batches={a['batches']} alo={a['alo']:.1f} "
                      f"orr={a['orr']:.1f} ai={a['ai']:.2f} "
                      f"fast_amp={a['fast_amp_frac'] * 100:.0f}% "
                      f"flops_saved={a['flops_saved_frac'] * 100:.0f}%")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=10)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--workload", default="mixed",
                    choices=["qaoa", "hea", "mixed"])
    ap.add_argument("--backend", default="planar",
                    choices=["dense", "planar", "pallas"])
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--mode", default="async",
                    choices=["sync", "async", "ingest"],
                    help="sync: drain() blocks per batch; async: streaming "
                         "pipeline with an in-flight window; ingest: "
                         "--clients concurrent producer threads through "
                         "IngestServer's drain loop")
    ap.add_argument("--inflight", type=int, default=2,
                    help="async/ingest: max launched-but-unretired batches")
    ap.add_argument("--clients", type=int, default=4,
                    help="ingest mode: number of concurrent producer threads")
    ap.add_argument("--max-pending", type=int, default=1024,
                    help="ingest mode: backpressure window (submitted but "
                         "unresolved requests)")
    ap.add_argument("--policy", default="block", choices=["block", "reject"],
                    help="ingest mode: producers block for a pending slot, "
                         "or get IngestRejected to shed load")
    ap.add_argument("--max-wait-ms", type=float, default=None,
                    help="streaming dispatch: launch a plan group once its "
                         "oldest request has waited this long (default: "
                         "only drain dispatches)")
    ap.add_argument("--f", type=int, default=None)
    ap.add_argument("--mesh", type=int, default=None,
                    help="execute sharded over this many devices (batch-"
                         "first split; planar backend only; see --max-local-"
                         "qubits for the state-sharding spill)")
    ap.add_argument("--max-local-qubits", type=int, default=None,
                    help="per-device row budget: requests whose n exceeds "
                         "it spill from batch sharding into state sharding")
    ap.add_argument("--specialize", default="on", choices=["on", "off"],
                    help="gate-class-specialized plan lowering (diagonal/"
                         "permutation fast paths)")
    ap.add_argument("--stats", action="store_true",
                    help="report per-class fused-gate counts, the estimated "
                         "flops saved by specialization, and served "
                         "vectorization activity (ALO/ORR) per plan key")
    ap.add_argument("--trace", default=None, metavar="FILE",
                    help="record per-request lifecycle spans and write a "
                         "Chrome-trace/Perfetto JSON file (open in "
                         "https://ui.perfetto.dev or chrome://tracing)")
    ap.add_argument("--trace-jsonl", default=None, metavar="FILE",
                    help="also/instead write the raw span events as a "
                         "JSONL structured log (one event per line)")
    ap.add_argument("--metrics-json", default=None, metavar="FILE",
                    help="export the unified metrics-registry snapshot "
                         "(scheduler/cache/compile/served/ingest) as JSON")
    ap.add_argument("--result-mode", default="statevector",
                    choices=["statevector", "shots", "expectation", "noisy"],
                    help="what every request asks the engine to return: the "
                         "full state, measurement shots, Pauli expectation "
                         "values, or noisy (trajectory-unraveled) "
                         "expectations (docs/ARCHITECTURE.md layer 10)")
    ap.add_argument("--shots", type=int, default=256,
                    help="--result-mode shots: samples per request")
    ap.add_argument("--unravelings", type=int, default=8,
                    help="--result-mode noisy: stochastic trajectories "
                         "averaged per request (each occupies a batch row)")
    ap.add_argument("--noise-p", type=float, default=0.05,
                    help="--result-mode noisy: depolarizing probability of "
                         "the per-edge-qubit channels")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", type=float, default=None, metavar="RATE",
                    help="fault-injection chaos mode: inject dispatch "
                         "failures at this rate (docs/RESILIENCE.md); "
                         "implies a retry policy so faulted batches replay")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-injection schedule seed (a chaos run is a "
                         "pure function of seed + rate + traffic)")
    ap.add_argument("--retries", type=int, default=None,
                    help="per-request retry budget for transient batch "
                         "failures (default: 3 under --chaos, else no "
                         "retry policy — batch failures stay terminal)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request serving deadline: requests still "
                         "undispatched after this long are SHED, never "
                         "dispatched")
    ap.add_argument("--breaker-threshold", type=int, default=None,
                    help="plan-key circuit breaker: quarantine a key to the "
                         "generic lowering after this many consecutive "
                         "batch failures")
    ap.add_argument("--class-routing", action="store_true",
                    help="group requests by shape class (canonical fused-"
                         "item skeleton) instead of exact plan key, so a "
                         "long-tailed template mix still fills batches "
                         "(results stay bitwise-identical)")
    ap.add_argument("--capacity-factor", type=float, default=2.0,
                    help="MoE-style expert capacity under --class-routing: "
                         "an open class group holds at most this many "
                         "max-batches of rows before overflow spills to "
                         "exact-key grouping (default 2.0)")
    ap.add_argument("--verify-plans", action="store_true",
                    help="run the plan-IR verifier on every compiled plan "
                         "and every class dispatch (repro.analysis; CI "
                         "smoke mode)")
    ap.add_argument("--compare-sync", action="store_true",
                    help="also run the same traffic through a fresh "
                         "synchronous scheduler (warm plans) and report the "
                         "async speedup")
    args = ap.parse_args(argv)

    enable_compile_cache()
    injector = None
    if args.chaos is not None:
        injector = FaultInjector(seed=args.chaos_seed,
                                 rates={"dispatch": args.chaos})
    breaker = (PlanBreaker(args.breaker_threshold)
               if args.breaker_threshold is not None else None)
    retries = args.retries
    if retries is None and args.chaos is not None:
        retries = 3            # chaos without a retry policy would just fail
    retry = RetryPolicy(max_retries=retries) if retries is not None else None
    executor = BatchExecutor(backend=args.backend, f=args.f,
                             specialize=args.specialize == "on",
                             mesh=args.mesh,
                             max_local_qubits=args.max_local_qubits,
                             verify=args.verify_plans,
                             injector=injector, breaker=breaker)
    # ingest mode streams by default (2ms age-out) — without a trigger the
    # drain loop would hold every underfull group until the final drain()
    max_wait_ms = args.max_wait_ms
    if max_wait_ms is None and args.mode == "ingest":
        max_wait_ms = 2.0
    # tracing is opt-in: without --trace/--trace-jsonl the scheduler keeps
    # the disabled NULL_TRACER and does zero telemetry work
    tracer = SpanTracer() if (args.trace or args.trace_jsonl) else None
    sched = BatchScheduler(executor, max_batch=args.max_batch,
                           inflight=args.inflight,
                           max_wait_ms=max_wait_ms, tracer=tracer,
                           retry=retry,
                           class_routing=args.class_routing,
                           capacity_factor=args.capacity_factor)
    traffic = make_traffic(args.workload, args.qubits, args.requests,
                            args.seed)
    result = _make_result_spec(args, args.qubits)

    srv = None
    if args.mode == "ingest":
        dt, rep, srv = _serve_ingest(sched, traffic, max(1, args.clients),
                                     args.max_pending, args.policy,
                                     deadline_ms=args.deadline_ms,
                                     result=result)
    else:
        dt = _serve(sched, traffic, args.mode, deadline_ms=args.deadline_ms,
                    result=result)
        rep = sched.report()
    _print_report(rep, dt, args.mode, args, cache=executor.cache,
                  activity=executor.activity)
    if injector is not None:
        fc = injector.counters()
        print(f"[{args.mode}] chaos: seed={args.chaos_seed} "
              f"rate={args.chaos} "
              f"fired={fc['total_fired']}/{fc['dispatch_checks']} "
              f"dispatch checks; retried={rep.get('retried', 0)}")
    if breaker is not None:
        bc = breaker.counters()
        print(f"[{args.mode}] breaker: trips={bc['trips']} "
              f"open_keys={bc['open_keys']} "
              f"fallback_batches={bc['fallback_batches']}")

    if tracer is not None:
        if args.trace:
            count = tracer.write_chrome_trace(args.trace)
            print(f"[trace] wrote {count} request spans -> {args.trace} "
                  f"(summarize: python tools/trace_report.py {args.trace})")
        if args.trace_jsonl:
            n_events = tracer.write_jsonl(args.trace_jsonl)
            print(f"[trace] wrote {n_events} events -> {args.trace_jsonl}")
    if args.metrics_json:
        reg = engine_registry(scheduler=sched, executor=executor, server=srv)
        snap = reg.write_json(args.metrics_json)
        print(f"[metrics] wrote {len(snap)} fields -> {args.metrics_json}")

    if args.compare_sync:
        sync_sched = BatchScheduler(
            BatchExecutor(target=executor.target,
                          backend=args.backend, f=args.f,
                          specialize=args.specialize == "on",
                          mesh=args.mesh,
                          max_local_qubits=args.max_local_qubits,
                          cache=executor.cache),   # warm plans: isolate overlap
            max_batch=args.max_batch,
            class_routing=args.class_routing,
            capacity_factor=args.capacity_factor)
        before = executor.cache.stats.as_dict()   # shared cache: report deltas
        sync_dt = _serve(sync_sched, traffic, "sync", result=result)
        sync_rep = sync_sched.report()
        for k, v in before.items():
            sync_rep[f"cache_{k}"] -= v
        if sync_rep["cache_compiles"] == 0:
            # warm plans by construction: the cumulative compile_* summary
            # belongs to the async phase, not this delta report
            sync_rep = {k: v for k, v in sync_rep.items()
                        if not k.startswith("compile_")}
        _print_report(sync_rep, sync_dt, "sync", args, cache=executor.cache)
        print(f"{args.mode}(cold) vs sync(warm) speedup: "
              f"{sync_dt / dt:.2f}x "
              f"(the {args.mode} time above includes its "
              f"{rep['cache_compiles']} plan compiles; see benchmarks/"
              f"serve_mixed.py for the steady-state comparison)")
        if sync_rep["failed"]:
            return 1
    return 1 if rep["failed"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
