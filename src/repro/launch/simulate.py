"""Quantum-simulation driver (the paper's workload).

  PYTHONPATH=src python -m repro.launch.simulate --circuit qft --qubits 20 \
      --backend planar --f 4
  PYTHONPATH=src python -m repro.launch.simulate --circuit ghz --qubits 16 \
      --backend pallas --verify

The target (lane width, fusion caps) and the Pallas interpret mode follow
the device JAX finds.  Exits non-zero when ``--verify`` finds a mismatch.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import circuits as C
from repro.core.fusion import fuse_circuit, fusion_stats
from repro.core.simulator import Simulator
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--circuit", default="qft",
                    choices=list(C.BUILDERS))
    ap.add_argument("--qubits", type=int, default=16)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--backend", default="planar",
                    choices=["dense", "planar", "pallas"])
    ap.add_argument("--f", type=int, default=None)
    ap.add_argument("--no-fuse", action="store_true")
    ap.add_argument("--verify", action="store_true")
    args = ap.parse_args(argv)

    enable_compile_cache()
    kw = {"depth": args.depth} if args.circuit == "qrc" else {}
    circ = C.build(args.circuit, args.qubits, **kw)
    sim = Simulator(backend=args.backend, f=args.f, fuse=not args.no_fuse)
    fused = sim.prepare(circ)
    print(f"{circ.name}: {circ.num_gates} gates -> {len(fused)} fused "
          f"(f={sim.f}) backend={args.backend} target={sim.target.name}")
    t0 = time.time()
    state = sim.run(circ)
    state.data.block_until_ready()
    dt = time.time() - t0
    print(f"simulated in {dt:.3f}s "
          f"({circ.num_gates / dt:.1f} gates/s), norm^2="
          f"{float(state.norm_sq()):.9f}")
    if args.verify:
        ref = Simulator(sim.target, backend="dense").run(circ)
        err = float(np.abs(np.asarray(state.to_dense())
                           - np.asarray(ref.to_dense())).max())
        print(f"max |amp - ref| = {err:.2e}")
        if not err < 1e-5:
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
