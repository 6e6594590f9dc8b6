"""Table IV analogue: vectorization-activity metrics.

AVL -> ALO (average lane occupancy), IRR -> ORR (op-reduction ratio),
plus measured AI (flops / bytes accessed from XLA cost analysis) for the
naive and VLA programs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core import apply as A
from repro.core import circuits as C
from repro.core import metrics as MET
from repro.core import statevec as SV
from repro.core.simulator import Simulator
from repro.core.target import TPU_V5E, device_target


def run(n: int = 12):
    for name in ("qft", "ghz", "grover", "qrc", "qv"):
        kw = {"depth": 8} if name == "qrc" else {}
        circ = C.build(name, n, **kw)
        sim = Simulator(TPU_V5E, backend="planar")
        fused = sim.prepare(circ)
        cost_naive = MET.circuit_cost(circ.gates, n, TPU_V5E)
        cost_vla = MET.circuit_cost(fused, n, TPU_V5E)
        orr = MET.op_reduction_ratio(circ.gates, fused, n, TPU_V5E)
        alo = cost_vla.active_lanes
        emit(f"tab4/{name}{n}", 0.0,
             f"ALO={alo:.1f}/{TPU_V5E.lanes},ORR={orr:.1f},"
             f"AI_naive={cost_naive.ai:.2f},AI_vla={cost_vla.ai:.2f},"
             f"fused={len(fused)}/{circ.num_gates}")

    # measured AI of one fused-gate application (XLA cost analysis)
    st = SV.random_state(n, device_target(), seed=0)
    g = sim.prepare(C.qft(n))[0]
    ur, ui = A.gate_arrays(g)
    ai = MET.measured_ai(
        lambda d: A.apply_gate_planar(d, n, g.qubits, ur, ui, g.controls),
        st.data)
    emit(f"tab4/measured_ai_fused{g.k}", 0.0, f"AI={ai:.2f}")


def main():
    run()


if __name__ == "__main__":
    main()
