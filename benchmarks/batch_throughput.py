"""Batched-engine throughput: circuits/sec vs batch size.

One QAOA template structure, B parameter bindings per batch.  The sequential
baseline runs the same bindings one dispatch at a time through the *same*
compiled plan (warm cache), so the measured speedup isolates the batching
win — compile amortization comes on top for cold traffic.

CSV: batch_<backend>_n<q>_b<B>,us_per_call,circuits_per_s=..,speedup=..x
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, time_fn
from repro.engine import BatchExecutor, qaoa_template

N_QUBITS = 12
LAYERS = 2
BATCHES = (1, 4, 16, 64)


def run_backend(backend: str, n: int = N_QUBITS,
                batches: tuple[int, ...] = BATCHES,
                verify: bool = False) -> None:
    ex = BatchExecutor(backend=backend, verify=verify)
    template = qaoa_template(n, LAYERS)
    plan = ex.plan_for(template)
    rng = np.random.default_rng(0)

    def seq_all(pm):
        out = None
        for row in pm:
            out = plan.run(params=row).data
        return out

    pm_base = rng.uniform(-np.pi, np.pi,
                          (max(batches), template.num_params)).astype(np.float32)
    seq_sec = time_fn(seq_all, pm_base[:1])           # per-circuit dispatch
    seq_per_circuit = seq_sec
    emit(f"batch_{backend}_n{n}_seq", seq_per_circuit,
         f"circuits_per_s={1.0 / seq_per_circuit:.1f}")

    for b in batches:
        pm = pm_base[:b]
        sec = time_fn(plan.run_batch_raw, pm)
        per_circuit = sec / b
        speedup = seq_per_circuit / per_circuit
        emit(f"batch_{backend}_n{n}_b{b}", per_circuit,
             f"circuits_per_s={1.0 / per_circuit:.1f};speedup={speedup:.2f}x")
    assert ex.stats.compiles == 1, ex.stats


def main() -> None:
    run_backend("planar")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=N_QUBITS)
    ap.add_argument("--batches", default=",".join(map(str, BATCHES)),
                    help="comma-separated batch sizes")
    ap.add_argument("--backend", default="planar",
                    choices=["dense", "planar", "pallas"])
    ap.add_argument("--verify-plans", action="store_true",
                    help="run the plan-IR verifier on every compile "
                         "(repro.analysis; CI smoke mode)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    run_backend(args.backend, n=args.qubits,
                batches=tuple(int(b) for b in args.batches.split(",")),
                verify=args.verify_plans)
