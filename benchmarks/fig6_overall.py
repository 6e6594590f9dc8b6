"""Fig 2 + Fig 6 analogue: five circuits, naive baseline vs VLA design.

Paper: auto-vectorized Qsim (interleaved complex, no explicit vectorization)
vs the SVE-optimized single source.  Here: ``dense`` backend (complex64 =
XLA's interleaved storage, gate-at-a-time) vs ``planar`` backend
(lane-tiled fp32 planes + machine-balance gate fusion).  Wall times are
CPU-container times; the structural speedup (fewer state sweeps x
unit-stride access) is the paper's effect being measured.
"""
from __future__ import annotations

from benchmarks.common import emit, time_fn
from repro.core import circuits as C
from repro.core.simulator import Simulator


def run(n: int = 16):
    for name in ("qft", "ghz", "grover", "qrc", "qv"):
        kw = {"depth": 8} if name == "qrc" else {}
        circ = C.build(name, n, **kw)
        base = Simulator(backend="dense", fuse=False)
        vla = Simulator(backend="planar")

        t_base = time_fn(lambda: base.run(circ).data, iters=2)
        t_vla = time_fn(lambda: vla.run(circ).data, iters=2)
        speedup = t_base / t_vla
        emit(f"fig6/{name}{n}/naive", t_base, f"gates={circ.num_gates}")
        emit(f"fig6/{name}{n}/vla", t_vla,
             f"speedup={speedup:.2f}x,f={vla.f}")


def main():
    run()


if __name__ == "__main__":
    main()
