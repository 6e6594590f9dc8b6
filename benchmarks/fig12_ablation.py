"""Fig 12 analogue: ablation of the optimization techniques.

Paper ablates: SVE vectorization, temporary load buffer, gate fusion.
Here: planar layout (VLA vectorization analogue), gate fusion, and the
Pallas VMEM-staged kernel (load-buffer analogue, interpret-mode timing is
reported structurally via its fused-gate count rather than wall time).
"""
from __future__ import annotations

from benchmarks.common import emit, time_fn
from repro.core import circuits as C
from repro.core.simulator import Simulator


def run(n: int = 13):
    for name in ("qft", "qrc"):
        kw = {"depth": 6} if name == "qrc" else {}
        circ = C.build(name, n, **kw)
        variants = {
            "full": Simulator(backend="planar"),
            "no_fusion": Simulator(backend="planar", fuse=False),
            "no_layout": Simulator(backend="dense", fuse=False),
        }
        times = {}
        for vname, sim in variants.items():
            t = time_fn(lambda s=sim: s.run(circ).data, iters=2)
            times[vname] = t
            emit(f"fig12/{name}{n}/{vname}", t, "")
        emit(f"fig12/{name}{n}/summary", times["full"],
             f"fusion_gain={times['no_fusion']/times['full']:.2f}x,"
             f"layout_gain={times['no_layout']/times['no_fusion']:.2f}x")


def main():
    run()


if __name__ == "__main__":
    main()
