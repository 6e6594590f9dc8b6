"""Fig 13 analogue: strong scaling of the distributed simulator.

The container's fake devices share one CPU core, so wall time cannot show
parallel speedup; what scales (and is reported) is the structure: state
bytes per device halve with each doubling while the collective volume per
device stays bounded — the same property that gave the paper near-linear
scaling to 288 threads.  On a chip every device count compiles in this
process over ``jax.devices()``; on the CPU each count runs in a child with
that many forced host devices (the device count is fixed at jax init).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

_ROOT = os.path.join(os.path.dirname(__file__), "..")


def measure(devices: int, n: int) -> dict:
    """Compile the distributed QRC step over the first ``devices`` devices
    and report its per-device structure."""
    import jax
    from repro.core import circuits as C
    from repro.core.distributed import DistributedSimulator
    from repro.core.target import device_target
    from repro.launch.hlo_analysis import analyze_hlo
    mesh = jax.make_mesh((devices,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:devices])
    circ = C.qrc(n, depth=4)
    ds = DistributedSimulator(n, mesh, device_target(), f=3)
    fn, planes, sc, _ = ds.build_step(circ)
    lowered = fn.lower(ds.global_state_shape(),
                       *[jax.ShapeDtypeStruct(p.shape, p.dtype)
                         for p in planes])
    co = lowered.compile()
    hlo = analyze_hlo(co.as_text())
    mem = co.memory_analysis()
    return {"devices": devices, "swaps": sc["swaps"],
            "flops_per_dev": hlo.flops,
            "coll_bytes_per_dev": hlo.collective_bytes,
            "state_bytes_per_dev": mem.argument_size_in_bytes}


def _probe_child(devices: int, n: int) -> dict:
    """:func:`measure` in a child process with forced host devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), _ROOT]
        + env.get("PYTHONPATH", "").split(os.pathsep))
    script = ("import json; from benchmarks.fig13_scaling import measure; "
              f"print(json.dumps(measure({devices}, {n})))")
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=_ROOT,
                         capture_output=True, text=True, timeout=480)
    if out.returncode != 0:
        raise RuntimeError(out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def run(n: int = 14):
    import jax
    on_chip = jax.devices()[0].platform != "cpu"
    base = None
    for d in (1, 2, 4, 8):
        if on_chip and d > len(jax.devices()):
            break
        r = measure(d, n) if on_chip else _probe_child(d, n)
        if base is None:
            base = r
        emit(f"fig13/qrc{n}/dev{d}", 0.0,
             f"flops_per_dev={r['flops_per_dev']:.3g},"
             f"state_bytes_per_dev={r['state_bytes_per_dev']},"
             f"swaps={r['swaps']},"
             f"coll_bytes_per_dev={r['coll_bytes_per_dev']:.3g},"
             f"parallel_eff={base['flops_per_dev']/(r['flops_per_dev']*d):.2f}")


def main():
    run()


if __name__ == "__main__":
    main()
