"""Sharded batch execution: single-device vs 2/4/8-way device meshes.

On a chip every device count runs in this process over ``jax.devices()``
(a child could not get a chip this process holds).  On the CPU each count
runs in a child process with that many forced host devices (XLA's host
device count must be forced before jax initializes; a CPU backend holds no
device, so the parent's own JAX use does not get in the way).  Each pushes
QAOA and Grover batches through ``BatchExecutor(mesh=D)`` in two layouts:

* ``batch``  — the default batch-first policy: whole states stay local,
  the parameter sweep splits over the mesh (embarrassingly parallel).
* ``state``  — forced state sharding (``max_local_qubits = n - log2 D``):
  each state's rows shard over the mesh and plans execute with qubit-block
  swap collectives; the ``swaps=`` field counts the traced ``all_to_all``s
  (diagonal items are communication-free, so QAOA pays only for its
  mixer layers).

On the single-core CPU container the mesh devices are simulated, so rows
measure *overhead* of the sharded lowering rather than real scaling; on a
multi-core host or a TPU slice the same rows show the scaling the paper
gets from state-group parallelism (§IV).

CSV: sharded_<workload>_n<q>_b<batch>_d<D>_<layout>,us_per_circuit,
     circuits_per_s=..;speedup=..x;swaps=..;state_bits=..
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys

N_QUBITS = (12,)
DEVICES = (1, 2, 4, 8)
BATCH = 16
ITERS = 3


def _inner(devices: int, qubits: list[int], batch: int, iters: int,
           verify: bool = False) -> None:
    """Runs inside the subprocess with the forced device count."""
    import jax
    import numpy as np

    from benchmarks.common import emit, time_fn
    from repro.core import circuits as C
    from repro.engine import BatchExecutor, PlanCache, qaoa_template, \
        template_of

    for n in qubits:
        workloads = [("qaoa", qaoa_template(n, 2)),
                     ("grover", template_of(C.grover(n, iterations=1)))]
        for name, t in workloads:
            rng = np.random.default_rng(0)
            pm = rng.uniform(-np.pi, np.pi,
                             (batch, t.num_params)).astype(np.float32)

            def bench(ex):
                def run():
                    plan, raw = ex.dispatch_batch(t, pm)
                    jax.block_until_ready(raw)
                    return plan
                plan = run()
                return time_fn(lambda: run(), iters=iters) / batch, plan

            base_s, _ = bench(BatchExecutor(backend="planar",
                                            cache=PlanCache(),
                                            verify=verify))
            layouts = [("batch", None)]
            if devices > 1:
                layouts.append(("state", n - (devices.bit_length() - 1)))
            for layout, max_local in layouts:
                if devices == 1 and layout == "batch":
                    secs, plan = base_s, None
                else:
                    ex = BatchExecutor(backend="planar",
                                       cache=PlanCache(), mesh=devices,
                                       max_local_qubits=max_local,
                                       verify=verify)
                    secs, plan = bench(ex)
                derived = (f"circuits_per_s={1.0 / secs:.1f};"
                           f"speedup={base_s / secs:.2f}x")
                if plan is not None:
                    derived += (f";swaps={plan.sharded_swaps}"
                                f";state_bits={plan.state_bits}")
                emit(f"sharded_{name}_n{n}_b{batch}_d{devices}_{layout}",
                     secs, derived)


def main(qubits=N_QUBITS, devices=DEVICES, batch: int = BATCH,
         iters: int = ITERS, verify: bool = False) -> None:
    """Run each device count: in-process on a chip, else one child per
    count with forced host devices, streaming its CSV rows."""
    import jax
    if jax.devices()[0].platform != "cpu":
        for d in devices:
            if d <= len(jax.devices()):
                _inner(d, list(qubits), batch, iters, verify=verify)
        return
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    root = os.path.join(os.path.dirname(__file__), "..")
    for d in devices:
        env = dict(os.environ)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={d}"
        env["PYTHONPATH"] = os.pathsep.join(
            [src, root] + env.get("PYTHONPATH", "").split(os.pathsep))
        out = subprocess.run(
            [sys.executable, "-m", "benchmarks.sharded_batch", "--inner",
             "--devices", str(d),
             "--qubits", ",".join(str(q) for q in qubits),
             "--batch", str(batch), "--iters", str(iters)]
            + (["--verify-plans"] if verify else []),
            env=env, cwd=root, capture_output=True, text=True, timeout=1800)
        if out.returncode != 0:
            raise RuntimeError(
                f"sharded benchmark subprocess (d={d}) failed:\n"
                f"{out.stdout}\n{out.stderr}")
        for line in out.stdout.splitlines():
            if line.startswith("sharded_"):
                print(line, flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true",
                    help="internal: run the measurement in-process (the "
                         "parent already forced the device count)")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated device counts (outer) or the "
                         "single forced count (--inner)")
    ap.add_argument("--qubits", default=None,
                    help=f"comma-separated qubit counts "
                         f"(default {','.join(map(str, N_QUBITS))}; the "
                         f"paper-style sweep is 12-16)")
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--iters", type=int, default=ITERS)
    ap.add_argument("--verify-plans", action="store_true",
                    help="run the plan-IR verifier on every compile "
                         "(repro.analysis; CI smoke mode)")
    args = ap.parse_args()
    qs = ([int(q) for q in args.qubits.split(",")] if args.qubits
          else list(N_QUBITS))
    if args.inner:
        _inner(int(args.devices), qs, args.batch, args.iters,
               verify=args.verify_plans)
    else:
        print("name,us_per_call,derived")
        main(qs, [int(d) for d in args.devices.split(",")],
             batch=args.batch, iters=args.iters, verify=args.verify_plans)
