"""Gate-class-specialized lowering: specialized vs generic throughput.

Two workloads whose hot loops are dominated by non-general gate classes:

* ``qaoa_cost`` — a QAOA ansatz with a heavy cost stack (CNOT·RZ·CNOT per
  ring edge, several cost layers per mixer).  Specialized lowering composes
  each cost stack into a few wide *phase vectors* (diagonal clusters, 6
  flops/amp) instead of many ``8·2**f``-flop dense matvecs.
* ``grover`` — Grover search: a no-regression guard for workloads whose
  classes interleave.  Its X layers ride or downgrade into the adjacent H
  clusters (cluster_gates' free-rider/downgrade rules), so the specialized
  plan intentionally matches the generic clustering — the row documents
  that specialization costs ~nothing when there is nothing to win.

Each row compares one backend (planar / pallas-interpret) with
specialization on vs off on the *same* circuit structure — same fusion
pass, same jit pipeline, only the per-class lowering differs.

CSV: classes_<workload>_<backend>_n<q>_<spec|generic>,us_per_call,
     circuits_per_s=..;diag=..;perm=..;general=..;flops_saved=..[;speedup=..x]
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit, time_fn
from repro.core import circuits as C
from repro.core import gates as G
from repro.engine import BatchExecutor, PlanCache, template_of
from repro.engine.template import CircuitTemplate, TemplateOp, fixed_op

N_QUBITS = 12
COST_LAYERS = 6
BATCH = 16
BACKENDS = ("planar", "pallas")


def qaoa_cost_heavy(n: int, cost_layers: int) -> CircuitTemplate:
    """QAOA-cost-layer-heavy ansatz: one H layer, ``cost_layers`` ring-edge
    ZZ stacks (CNOT · RZ(2*gamma_l) · CNOT), one RX mixer layer."""
    edges = [(i, (i + 1) % n) for i in range(n)] if n > 2 else [(0, 1)]
    ops: list[TemplateOp] = [fixed_op(G.h(q)) for q in range(n)]
    for layer in range(cost_layers):
        for a, b in edges:
            ops.append(fixed_op(G.cnot(a, b)))
            ops.append(TemplateOp("rz", (b,), param=layer, scale=2.0,
                                  name="rz"))
            ops.append(fixed_op(G.cnot(a, b)))
    for q in range(n):
        ops.append(TemplateOp("rx", (q,), param=cost_layers, scale=2.0,
                              name="rx"))
    return CircuitTemplate(n, tuple(ops), num_params=cost_layers + 1,
                           name=f"qaoacost{n}x{cost_layers}")


def _workloads(n: int, cost_layers: int):
    return (
        ("qaoa_cost", qaoa_cost_heavy(n, cost_layers)),
        ("grover", template_of(C.grover(n, iterations=2))),
    )


def run_workload(name: str, template: CircuitTemplate, backend: str,
                 n: int, batch: int = BATCH, iters: int = 5,
                 specialize_modes=(True, False),
                 verify: bool = False) -> dict[bool, float]:
    """Time one workload on one backend for each specialization mode
    (batched throughput through one compiled plan — the engine's native
    execution mode); returns seconds per circuit keyed by mode."""
    rng = np.random.default_rng(0)
    pm = rng.uniform(-np.pi, np.pi,
                     (batch, template.num_params)).astype(np.float32)
    secs: dict[bool, float] = {}
    for spec in specialize_modes:
        ex = BatchExecutor(backend=backend, specialize=spec,
                           cache=PlanCache(), verify=verify)
        plan = ex.plan_for(template)
        secs[spec] = time_fn(plan.run_batch_raw, pm, iters=iters) / batch
        counts = plan.class_counts()
        fl = plan.flops_per_amp()
        label = "spec" if spec else "generic"
        derived = (f"circuits_per_s={1.0 / secs[spec]:.1f};"
                   f"diag={counts['diagonal']};perm={counts['permutation']};"
                   f"general={counts['general']};"
                   f"flops_saved={fl['flops_saved_frac'] * 100:.1f}%")
        if not spec and True in secs:
            derived += f";speedup={secs[False] / secs[True]:.2f}x"
        emit(f"classes_{name}_{backend}_n{n}_b{batch}_{label}",
             secs[spec], derived)
    return secs


def main(n: int = N_QUBITS, cost_layers: int = COST_LAYERS,
         backends=BACKENDS, batch: int = BATCH, verify: bool = False) -> None:
    for name, template in _workloads(n, cost_layers):
        for backend in backends:
            run_workload(name, template, backend, n, batch=batch,
                         verify=verify)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=N_QUBITS)
    ap.add_argument("--cost-layers", type=int, default=COST_LAYERS)
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--backend", default=None, choices=list(BACKENDS),
                    help="restrict to one backend (default: both)")
    ap.add_argument("--verify-plans", action="store_true",
                    help="run the plan-IR verifier on every compile "
                         "(repro.analysis; CI smoke mode)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(args.qubits, args.cost_layers,
         (args.backend,) if args.backend else BACKENDS, batch=args.batch,
         verify=args.verify_plans)
