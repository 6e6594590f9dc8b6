"""Random circuits on a 2-D grid of qubits, after Boixo et al. (2018).

Qubit ``q = r * cols + c`` sits at row ``r``, column ``c``.  Cycle 0 puts
a Hadamard on every qubit.  Each later cycle ``t`` (1 to ``depth``) places
the CZ pattern ``(t - 1) % 8`` and then a single-qubit gate on every qubit
that a CZ held in cycle ``t - 1`` and none holds in cycle ``t``:

- the first such gate on a qubit is T;
- every later one is drawn from {X^1/2, Y^1/2, T}, never the gate the
  qubit had last.

The eight patterns alternate between horizontal and vertical: pattern
``2 s`` couples ``(r, c)`` with ``(r, c + 1)`` where ``(c - 2 r) % 4 == s``,
and pattern ``2 s + 1`` couples ``(r, c)`` with ``(r + 1, c)`` where
``(r - 2 c) % 4 == s``.  Over eight cycles every edge of the grid carries
one CZ, and no qubit is in two CZs of one cycle.

Where the gates go follows from these rules alone; which gate a drawn
position gets is the instance.  A drawn gate is written as
``rz(alpha) ry(beta) rz(gamma)`` with three parameters, so every instance
runs through one compiled program:

- X^1/2: ``(gamma, beta, alpha) = (pi/2, pi/2, -pi/2)``, which is rx(pi/2);
- Y^1/2: ``(0, pi/2, 0)``, which is ry(pi/2);
- T: ``(pi/4, 0, 0)``, which is rz(pi/4).

Each equals the named gate up to a global phase.  Program and reference
apply the same rotations, so their states agree phase and all.
"""
from __future__ import annotations

import math

import numpy as np

HALF = math.pi / 2
DRAWN = {"x_half": (HALF, HALF, -HALF), "y_half": (0.0, HALF, 0.0),
         "t": (math.pi / 4, 0.0, 0.0)}
T_MATRIX = np.array([[1, 0], [0, np.exp(0.25j * math.pi)]], np.complex128)


def cz_pattern(rows: int, cols: int, index: int) -> tuple:
    """The CZ pairs of pattern ``index`` (0 to 7) on a grid."""
    s = index // 2
    pairs = []
    if index % 2 == 0:
        for r in range(rows):
            for c in range(cols - 1):
                if (c - 2 * r) % 4 == s:
                    pairs.append((r * cols + c, r * cols + c + 1))
    else:
        for r in range(rows - 1):
            for c in range(cols):
                if (r - 2 * c) % 4 == s:
                    pairs.append((r * cols + c, (r + 1) * cols + c))
    return tuple(pairs)


def _cycles(cfg: dict):
    """Per cycle: ``(cz pairs, [(qubit, slot)])``, where a slot is ``"t"``
    for a qubit's first gate and the index of a drawn gate otherwise."""
    rows, cols, depth = cfg["rows"], cfg["cols"], cfg["depth"]
    first = [True] * (rows * cols)
    busy_before: set = set()
    drawn = 0
    out = []
    for t in range(1, depth + 1):
        pairs = cz_pattern(rows, cols, (t - 1) % 8)
        busy = {q for pair in pairs for q in pair}
        slots = []
        for q in sorted(busy_before - busy):
            if first[q]:
                slots.append((q, "t"))
                first[q] = False
            else:
                slots.append((q, drawn))
                drawn += 1
        out.append((pairs, slots))
        busy_before = busy
    return out


def _check(cfg: dict) -> None:
    if cfg["rows"] * cfg["cols"] != cfg["n"]:
        raise ValueError(f"a {cfg['rows']} x {cfg['cols']} grid is not "
                         f"n = {cfg['n']} qubits")


def num_params(cfg: dict) -> int:
    _check(cfg)
    return 3 * sum(1 for _, slots in _cycles(cfg)
                   for _, s in slots if s != "t")


def instance(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """One random circuit of the family: the parameters of its drawn
    gates, each uniform over the gates its qubit did not have last."""
    last = {}
    params = []
    for _, slots in _cycles(cfg):
        for q, s in slots:
            if s == "t":
                last[q] = "t"
                continue
            options = [g for g in DRAWN if g != last[q]]
            g = options[int(rng.integers(0, len(options)))]
            last[q] = g
            params.extend(DRAWN[g])
    return np.asarray(params, np.float64)


def reference_gates(cfg: dict) -> list:
    """The circuit as :func:`bench.reference.run_gates` takes it."""
    _check(cfg)
    gates: list = [("h", q) for q in range(cfg["n"])]
    for pairs, slots in _cycles(cfg):
        gates.append(("cz", pairs))
        for q, s in slots:
            if s == "t":
                gates.append(("u", q, T_MATRIX))
            else:
                gates += [("rz", q, 3 * s, 1.0), ("ry", q, 3 * s + 1, 1.0),
                          ("rz", q, 3 * s + 2, 1.0)]
    return gates


def observables(cfg: dict) -> tuple:
    return ()


def program_template(cfg: dict):
    """The same circuit as the program's ``CircuitTemplate``."""
    from repro.core import gates as G
    from repro.engine.template import CircuitTemplate, TemplateOp, fixed_op
    _check(cfg)
    ops = [fixed_op(G.h(q)) for q in range(cfg["n"])]
    for pairs, slots in _cycles(cfg):
        ops += [fixed_op(G.cz(a, b)) for a, b in pairs]
        for q, s in slots:
            if s == "t":
                ops.append(fixed_op(G.t(q)))
            else:
                ops += [TemplateOp(k, (q,), param=3 * s + j, name=k)
                        for j, k in enumerate(("rz", "ry", "rz"))]
    return CircuitTemplate(cfg["n"], tuple(ops), num_params=num_params(cfg),
                           name=cfg["name"])


def program_observables(cfg: dict) -> list:
    return []
