"""Shape-class routing vs exact-key grouping on a long-tailed template mix.

A family of K structurally distinct QAOA templates (per-edge constant tilt
angles baked into the circuit, so every member has its own exact plan key
while all share one fused-item skeleton) is sampled under a Zipf mix — the
long tail leaves most exact-key groups nearly empty.  The same trace is
served twice on warm caches: grouped by exact plan key, then routed by
shape class (structurally different templates co-batched under one vmapped
class program, per-row constants stacked as batch inputs).

Results must agree bitwise — class routing is a scheduling decision, never
a numerical one — and the class-routed pass must fill device batches at
least as well; both are asserted, so CI smoke catches a routing regression.
``--verify-plans`` additionally runs the plan-IR verifier's shape-class
invariants on every compile and every class dispatch.

CSV: route_{exact|class}_n<q>_b<B>,us_per_request,circuits_per_s=..;
fill_pct=..;batches=.. plus a final comparison row.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.core import gates as G
from repro.engine import BatchExecutor, BatchScheduler, PlanCache
from repro.engine.template import CircuitTemplate, TemplateOp, fixed_op

N_QUBITS = 12
MAX_BATCH = 16
REQUESTS = 256
TEMPLATES = 8
ITERS = 3
ZIPF_S = 1.2
MAX_WAIT_MS = 5.0


def tilted_qaoa(n: int, tilts, name: str) -> CircuitTemplate:
    """QAOA ring with constant per-edge tilts baked into the structure."""
    ops = [fixed_op(G.h(q)) for q in range(n)]
    for i in range(n):
        a, b = i, (i + 1) % n
        ops += [fixed_op(G.cnot(a, b)), fixed_op(G.rz(b, tilts[i])),
                TemplateOp("rz", (b,), param=0, scale=2.0, name="rz"),
                fixed_op(G.cnot(a, b))]
    ops += [TemplateOp("rx", (q,), param=1, scale=2.0, name="rx")
            for q in range(n)]
    return CircuitTemplate(n, tuple(ops), num_params=2, name=name)


def make_traffic(n: int, requests: int, templates: int, seed: int = 0):
    """Zipf-weighted request mix over ``templates`` class-sharing members."""
    family = [tilted_qaoa(n, tuple(0.1 + 0.2 * i + 0.05 * j
                                   for j in range(n)), name=f"tilted{i}")
              for i in range(templates)]
    rng = np.random.default_rng(seed)
    w = 1.0 / (1.0 + np.arange(templates)) ** ZIPF_S
    w /= w.sum()
    return [(family[i], rng.uniform(-np.pi, np.pi, 2).astype(np.float32))
            for i in rng.choice(templates, size=requests, p=w)]


def serve_once(cache: PlanCache, traffic, routed: bool, max_batch: int,
               verify: bool = False):
    """One streaming pass on a warm cache; returns (dt, report, payloads)."""
    ex = BatchExecutor(backend="planar", cache=cache,
                       verify=verify)
    sched = BatchScheduler(ex, max_batch=max_batch, max_wait_ms=MAX_WAIT_MS,
                           class_routing=routed)
    t0 = time.perf_counter()
    reqs = [sched.submit(t, p) for t, p in traffic]
    sched.drain()
    dt = time.perf_counter() - t0
    rep = sched.report()
    assert rep["failed"] == 0, rep
    payloads = [np.asarray(r.result.to_dense()) for r in reqs]
    return dt, rep, payloads


def run(n: int = N_QUBITS, requests: int = REQUESTS,
        max_batch: int = MAX_BATCH, templates: int = TEMPLATES,
        iters: int = ITERS, verify: bool = False) -> float:
    """Benchmark both groupings; returns the class-over-exact throughput
    ratio.  Raises if results diverge bitwise or class routing fills worse.
    """
    traffic = make_traffic(n, requests, templates)
    cache = PlanCache()
    for routed in (False, True):                  # warm compiles, both paths
        serve_once(cache, traffic, routed, max_batch, verify=verify)
    results = {}
    for mode, routed in (("exact", False), ("class", True)):
        best = None
        for _ in range(iters):
            dt, rep, payloads = serve_once(cache, traffic, routed, max_batch,
                                           verify=verify)
            if best is None or dt < best[0]:
                best = (dt, rep, payloads)
        results[mode] = best
        dt, rep, _ = best
        emit(f"route_{mode}_n{n}_b{max_batch}", dt / requests,
             f"circuits_per_s={requests / dt:.1f};"
             f"fill_pct={rep['fill_rate'] * 100:.1f};"
             f"batches={rep['batches']}")
    mism = sum(not np.array_equal(a, b)
               for a, b in zip(results["exact"][2], results["class"][2]))
    assert mism == 0, f"{mism} requests diverged between routing modes"
    fill_exact = results["exact"][1]["fill_rate"]
    fill_class = results["class"][1]["fill_rate"]
    assert fill_class > fill_exact, (
        f"class routing must out-fill exact-key grouping on a long-tailed "
        f"mix: {fill_class:.3f} vs {fill_exact:.3f}")
    speedup = results["exact"][0] / results["class"][0]
    emit(f"route_class_gain_n{n}_b{max_batch}",
         results["class"][0] / requests,
         f"speedup={speedup:.2f}x;mismatches={mism};"
         f"fill_gain_pts={(fill_class - fill_exact) * 100:.1f}")
    return speedup


def main() -> None:
    run()


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=N_QUBITS)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    ap.add_argument("--max-batch", type=int, default=MAX_BATCH)
    ap.add_argument("--templates", type=int, default=TEMPLATES)
    ap.add_argument("--iters", type=int, default=ITERS)
    ap.add_argument("--verify-plans", action="store_true")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run(args.qubits, args.requests, args.max_batch, args.templates,
        args.iters, verify=args.verify_plans)
