"""Result-mode serving: shots / expectation epilogues vs full-state returns.

The same QAOA request batch is served three times through the scheduler —
returning the full statevector, ``--shots`` measurement samples, and a
Pauli-Z expectation sweep — with warm plan caches, so the rows isolate what
the fused result epilogue costs and what it saves: a shots/expectation
response is a few bytes where the statevector response materializes all
``2**n`` amplitudes (the paper's ExpectationValue/Sampling motivation —
never store states you only reduce).

Correctness is asserted inline, which makes this the CI smoke for the
result-mode serving path:

* shots are **bitwise identical** when the same request is re-served in a
  different batch composition (per-request PRNG keys, not batch-position
  randomness);
* every served expectation value matches the dense gate-by-gate oracle to
  ``ORACLE_ATOL``.

CSV: result_{sv|shots|expect}_n<q>_b<B>,us_per_request,
circuits_per_s=..;resp_bytes=..  (+ per-mode assertions in derived).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.core import apply as A
from repro.core import gates as G
from repro.engine import (BatchExecutor, BatchScheduler, PlanCache,
                          ResultSpec, qaoa_template)

N_QUBITS = 12
MAX_BATCH = 16
REQUESTS = 16
SHOTS = 256
ORACLE_ATOL = 1e-5


def _params_list(template, requests: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.uniform(-np.pi, np.pi, template.num_params)
            .astype(np.float32) for _ in range(requests)]


def _serve(cache: PlanCache, template, params_list, spec, max_batch: int,
           verify: bool = False):
    """One scheduler pass on a warm cache; returns (wall s, results)."""
    ex = BatchExecutor(backend="planar", cache=cache,
                      verify=verify)
    sched = BatchScheduler(ex, max_batch=max_batch)
    t0 = time.perf_counter()
    reqs = [sched.submit(template, p, result=spec) for p in params_list]
    sched.drain()
    dt = time.perf_counter() - t0
    rep = sched.report()
    assert rep["failed"] == 0, rep
    return dt, [r.result for r in reqs]


def _oracle_expectations(template, params, observables):
    """Dense gate-by-gate <P> oracle (apply P, then inner product)."""
    import jax.numpy as jnp
    n = template.n
    psi = jnp.zeros(1 << n, jnp.complex64).at[0].set(1.0)
    for g in template.bind(params).gates:
        psi = A.apply_gate_dense(psi, n, g.qubits, g.matrix, g.controls)
    mats = {"X": G.X_M, "Y": G.Y_M, "Z": G.Z_M}
    out = []
    for obs in observables:
        phi = psi
        for q, p in obs.items():
            phi = A.apply_gate_dense(phi, n, (q,), mats[p])
        out.append(float(np.real(np.vdot(np.asarray(psi),
                                         np.asarray(phi)))))
    return np.asarray(out, np.float32)


def run(n: int = N_QUBITS, requests: int = REQUESTS,
        max_batch: int = MAX_BATCH, shots: int = SHOTS,
        verify: bool = False, seed: int = 0) -> None:
    template = qaoa_template(n, 2)
    params_list = _params_list(template, requests, seed)
    observables = [{0: "Z"}, {n // 2: "Z"}, {n - 1: "Z"}]
    sv_bytes = (1 << n) * 8          # complex64 amplitudes per response

    specs = {
        "sv": None,
        "shots": ResultSpec.sample(shots, key=seed),
        "expect": ResultSpec.expectation(observables),
    }
    cache = PlanCache()
    for spec in specs.values():       # warm the plan/program caches
        _serve(cache, template, params_list, spec, max_batch, verify=verify)

    outputs = {}
    for name, spec in specs.items():
        dt, results = _serve(cache, template, params_list, spec, max_batch)
        outputs[name] = results
        if name == "sv":
            resp = sv_bytes
            extra = ""
        elif name == "shots":
            resp = shots * 4
            # bitwise reproducibility across batch compositions: re-serve a
            # prefix of the traffic (different padding/grouping) and demand
            # identical samples per request
            _, again = _serve(cache, template, params_list[:3], spec,
                              max_batch)
            for a, b in zip(again, results):
                assert np.array_equal(np.asarray(a), np.asarray(b)), \
                    "shots changed with batch composition"
            extra = ";repro=bitwise"
        else:
            resp = len(observables) * 4
            err = max(float(np.abs(np.asarray(got)
                                   - _oracle_expectations(template, p,
                                                          observables)).max())
                      for got, p in zip(results, params_list))
            assert err <= ORACLE_ATOL, \
                f"expectation error {err:.2e} > {ORACLE_ATOL}"
            extra = f";max_err={err:.1e}"
        emit(f"result_{name}_n{n}_b{max_batch}", dt / requests,
             f"circuits_per_s={requests / dt:.1f};resp_bytes={resp};"
             f"state_bytes_saved={1.0 - resp / sv_bytes:.4f}" + extra)


def main(n: int = N_QUBITS, requests: int = REQUESTS,
         max_batch: int = MAX_BATCH, shots: int = SHOTS,
         verify: bool = False) -> None:
    run(n, requests, max_batch, shots, verify=verify)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--qubits", type=int, default=N_QUBITS)
    ap.add_argument("--requests", type=int, default=REQUESTS)
    ap.add_argument("--max-batch", type=int, default=MAX_BATCH)
    ap.add_argument("--shots", type=int, default=SHOTS)
    ap.add_argument("--verify-plans", action="store_true",
                    help="run the plan-IR verifier on every compile "
                         "(repro.analysis; CI smoke mode)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    main(args.qubits, args.requests, args.max_batch, args.shots,
         verify=args.verify_plans)
