"""Quickstart: simulate the paper's circuits with the VLA simulator.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import build_circuit, Simulator
from repro.core import circuits as C
from repro.core.fusion import fusion_stats
from repro.core.target import CPU_TEST, TPU_V5E


def main():
    # 1. GHZ: maximally entangled state, checked analytically
    sim = Simulator(backend="planar")
    state = sim.run(C.ghz(10))
    probs = np.asarray(sim.probabilities(state))
    print(f"GHZ(10): P(|0..0>)={probs[0]:.3f}  P(|1..1>)={probs[-1]:.3f}")
    assert abs(probs[0] - 0.5) < 1e-5 and abs(probs[-1] - 0.5) < 1e-5

    # 2. Grover: amplify a marked item
    circ = C.grover(8, marked=123, iterations=3)
    state = Simulator(backend="planar").run(circ)
    probs = np.asarray(Simulator().probabilities(state))
    print(f"Grover(8): argmax={probs.argmax()} (marked=123), "
          f"P={probs[123]:.3f}")
    assert probs.argmax() == 123

    # 3. Gate fusion adapts to the machine balance (paper §IV-D)
    circ = C.qft(16)
    for target in (CPU_TEST, TPU_V5E):
        sim = Simulator(target, backend="planar")
        fused = sim.prepare(circ)
        s = fusion_stats(circ.gates, fused)
        print(f"QFT(16) on {target.name:9s}: f={sim.f} "
              f"{s['gates_before']} gates -> {s['gates_after']} fused "
              f"({s['reduction']:.1f}x fewer state sweeps)")

    # 4. Pallas kernel backend (interpret mode on CPU, compiled on TPU)
    state_k = Simulator(backend="pallas", f=3).run(C.qft(8))
    state_r = Simulator(backend="dense").run(C.qft(8))
    err = np.abs(np.asarray(state_k.to_dense())
                 - np.asarray(state_r.to_dense())).max()
    print(f"Pallas kernel vs dense oracle: max |diff| = {err:.2e}")
    assert err < 1e-5
    print("quickstart OK")


if __name__ == "__main__":
    main()
