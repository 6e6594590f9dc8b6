"""Fig 10 analogue: sensitivity of runtime to the fusion degree f."""
from __future__ import annotations

from benchmarks.common import emit, time_fn
from repro.core import circuits as C
from repro.core.simulator import Simulator


def run(n: int = 13, fs=(2, 3, 4, 5)):
    for name in ("qft", "qrc", "qv"):
        kw = {"depth": 6} if name == "qrc" else {}
        circ = C.build(name, n, **kw)
        best = None
        for f in fs:
            sim = Simulator(backend="planar", f=f)
            fused = sim.prepare(circ)
            t = time_fn(lambda: sim.run(circ).data, iters=2)
            emit(f"fig10/{name}{n}/f{f}", t, f"fused_gates={len(fused)}")
            if best is None or t < best[1]:
                best = (f, t)
        emit(f"fig10/{name}{n}/best", best[1], f"best_f={best[0]}")


def main():
    run()


if __name__ == "__main__":
    main()
