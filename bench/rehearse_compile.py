#!/usr/bin/env python3
"""Compile each cell's program for a described TPU v5e, without the chip.

  JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/rehearse_compile.py [cell ...]

For every cell of ``BENCHMARK.json`` (or those named), the program its
window drives is lowered at the cell's real size for one chip of a
described ``v5e:2x2`` topology and compiled: the serial cells' whole-circuit
program (``CompiledPlan._program``, state donated), the client cells'
batched expectation program at ``max_batch`` rows.  It prints the compile
seconds and the temporaries.  Nothing runs, so it gives no device time.
"""
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(names) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.spec import Benchmark
    from repro.core.target import TPU_V5E
    from repro.engine import ResultSpec
    from repro.engine.plan import compile_plan

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bm = Benchmark(ROOT)
    cells = names or [w["name"] for w in bm.doc["workloads"]]
    v = TPU_V5E.lane_qubits
    for name in cells:
        cell = bm.cell(name)
        cfg = bm.config(cell["config"])
        traffic = bm.traffic(cell["traffic"])
        family = bm.family(cfg["circuit"])
        n = cfg["n"]
        template = family.program_template(cfg)
        p = template.num_params
        state = jax.ShapeDtypeStruct((2, 1 << (n - v), 1 << v), jnp.float32,
                                     sharding=chip)
        t0 = time.perf_counter()
        if traffic["loop"] == "serial":
            plan = compile_plan(template, backend=traffic["backend"],
                                target=TPU_V5E, interpret=False)
            fn = jax.jit(plan._program(), donate_argnums=(0,))
            args = (state, jax.ShapeDtypeStruct((p,), jnp.float32,
                                                sharding=chip))
        else:
            spec = ResultSpec.expectation(family.program_observables(cfg))
            plan = compile_plan(template, backend=traffic["backend"],
                                target=TPU_V5E, interpret=False, result=spec)
            b = cfg["max_batch"]
            fn = plan._build_batched_result()
            args = (state,
                    jax.ShapeDtypeStruct((b, p), jnp.float32, sharding=chip),
                    jax.ShapeDtypeStruct((b, 2), jnp.uint32, sharding=chip))
        compiled = fn.lower(*args).compile()
        sec = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        state_bytes = (8 << n) * (1 if traffic["loop"] == "serial"
                                  else cfg["max_batch"])
        print(f"{name}: n={n} items={len(plan.items)} compile_s={sec:.1f} "
              f"temp_bytes={mem.temp_size_in_bytes} "
              f"temp_over_states={mem.temp_size_in_bytes / state_bytes:.2f} "
              f"argument_bytes={mem.argument_size_in_bytes} "
              f"output_bytes={mem.output_size_in_bytes}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
