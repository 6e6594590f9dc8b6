"""Table III analogue: gate operations per qubit, low vs high qubits.

The paper's point: gates on qubits below log2(numVals) hit the irregular
(lane/predicated) path; the table counts how many ops land there per
circuit.  We count the same split for the TPU lane width.
"""
from __future__ import annotations

from benchmarks.common import emit
from repro.core import circuits as C


def run(n: int = 12, num_vals: int = 8):
    v = num_vals.bit_length() - 1
    for name in ("qft", "ghz", "grover", "qrc", "qv"):
        kw = {"depth": 8} if name == "qrc" else {}
        circ = C.build(name, n, **kw)
        low = sum(1 for g in circ.gates if any(q < v for q in g.qubits))
        high = circ.num_gates - low
        emit(f"tab3/{name}{n}", 0.0,
             f"low_qubit_ops={low},high_qubit_ops={high},"
             f"total={circ.num_gates}")


def main():
    run()


if __name__ == "__main__":
    main()
