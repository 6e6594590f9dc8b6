#!/usr/bin/env python3
"""Runs of a cell with the timed path replaced or broken, to show that
the correctness check fails them.

  python3 bench/control.py --workload <cell> --seed <n> --seconds <s> \
      [--fault <name>]

Without ``--fault`` it runs the control: the plain reference put in the
program's place and computed one precision below the configuration's
(``bench.reference`` at ``precision="high"``: three bfloat16 passes per
product, where the program states float32 at ``Precision.HIGHEST``).
With ``--fault`` it plants one of the faults the cell can have in the
program: those in :data:`FAULTS`, and those in its loop's ``FAULTS``.  The
benchmark's own runs never do either; this file is for setting a limit
on the chip and for ``tests/bench``.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _unchanged(cfg, traffic, family):
    """The plan's first gate item returns its state unchanged."""
    from bench.harness import patched
    from repro.engine.plan import CompiledPlan
    orig = CompiledPlan._step

    def step(self, item):
        if item is self._gate_items()[0]:
            return lambda state, params: state
        return orig(self, item)
    return patched(CompiledPlan, "_step", step)


# faults every loop can have; a loop adds its own in its FAULTS
FAULTS = {"unchanged": _unchanged}


def faults(loop) -> dict:
    """Every fault a cell run under ``loop`` (a loop module) can have."""
    return {**FAULTS, **loop.FAULTS}


def main(argv=None, *, root=ROOT, src=None, require_chip=True) -> int:
    ap = argparse.ArgumentParser(description="control and fault runs")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(root))
    sys.path.insert(0, str(src if src is not None else root / "src"))
    from bench import run
    from bench.spec import Benchmark
    bm = Benchmark(root)
    cell = bm.cell(args.workload)
    cfg, traffic = bm.config(cell["config"]), bm.traffic(cell["traffic"])
    family, loop = bm.family(cfg["circuit"]), bm.loop(traffic["loop"])
    if args.fault is None:
        ctx = loop.control(cfg, traffic, family)
    else:
        known = faults(loop)
        if args.fault not in known:
            ap.error(f"{args.workload} can have the faults {sorted(known)}")
        ctx = known[args.fault](cfg, traffic, family)
    with ctx:
        return run.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds),
                         "--trace", "0"], root=root, src=src,
                        require_chip=require_chip)


if __name__ == "__main__":
    sys.exit(main())
