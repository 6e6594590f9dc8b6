"""Distributed state-vector simulation across a device mesh.

Shards a 14-qubit state over 8 (host-platform) devices, runs QFT with
qubit-swap collectives, and verifies against the single-device oracle.
On a real pod the same code shards 36+ qubits over 256-512 chips
(see repro.launch.dryrun --quantum).

    PYTHONPATH=src python examples/distributed_sim.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import circuits as C  # noqa: E402
from repro.core.distributed import DistributedSimulator  # noqa: E402
from repro.core.simulator import Simulator  # noqa: E402
from repro.core.target import device_target  # noqa: E402


def main():
    n = 14
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    circ = C.qft(n)
    ds = DistributedSimulator(n, mesh, device_target(), f=4)
    out, perm, counters = ds.run(circ)
    psi = np.asarray(ds.to_dense(out, perm))
    ref = np.asarray(Simulator(backend="dense").run(circ)
                     .to_dense())
    err = np.abs(psi - ref).max()
    print(f"QFT({n}) on {mesh.devices.size} devices: "
          f"{circ.num_gates} gates, {counters['swaps']} qubit-block swaps "
          f"(all_to_all), final perm {'identity' if perm == list(range(n)) else 'lazy'}")
    print(f"max |amp - oracle| = {err:.2e}")
    assert err < 1e-5
    print("distributed_sim OK")


if __name__ == "__main__":
    main()
