"""QAOA for MaxCut on a ring (Farhi, Goldstone, Gutmann, 2014).

Hadamards on every qubit, then ``p`` layers of the cost unitary
exp(-i gamma_l sum Z_a Z_b) over the ring's edges and the mixer RX(2
beta_l) on every qubit.  Parameters: ``[gamma_0 .. gamma_(p-1), beta_0 ..
beta_(p-1)]``.  The observables are the cost's terms <Z_a Z_b>, one per
edge.  The program builds the circuit with its own
``repro.engine.template.qaoa_template``; the reference from the
definition above.
"""
from __future__ import annotations

import math

import numpy as np


def _ring(n: int) -> tuple:
    return tuple((i, (i + 1) % n) for i in range(n))


def num_params(cfg: dict) -> int:
    return 2 * cfg["p"]


def instance(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """One random point of the parameter space: uniform in [-pi, pi)."""
    return rng.uniform(-math.pi, math.pi, num_params(cfg))


def reference_gates(cfg: dict) -> list:
    """The circuit as :func:`bench.reference.run_gates` takes it."""
    n, p = cfg["n"], cfg["p"]
    gates: list = [("h", q) for q in range(n)]
    for layer in range(p):
        # CNOT . RZ(2 gamma) . CNOT on every ring edge is
        # exp(-i gamma Z_a Z_b); the mixer is RX(2 beta) on every qubit
        gates.append(("zz", _ring(n), layer, 1.0))
        gates += [("rx", q, p + layer, 2.0) for q in range(n)]
    return gates


def observables(cfg: dict) -> tuple:
    """The cost Hamiltonian's terms as qubit pairs."""
    return _ring(cfg["n"])


def program_template(cfg: dict):
    from repro.engine.template import qaoa_template
    return qaoa_template(cfg["n"], cfg["p"])


def program_observables(cfg: dict) -> list:
    """The cost terms as the program's Pauli-string dicts."""
    return [{a: "Z", b: "Z"} for a, b in observables(cfg)]
