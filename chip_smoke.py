#!/usr/bin/env python3
"""Smoke run of the simulator and the serving engine on one TPU v5e.

  python3 chip_smoke.py                # one chip: circuit + serving phases
  python3 chip_smoke.py --four-chips   # four chips: the state-sharded phase

Single circuit: ``Simulator`` runs a seeded depth-8 QRC at n = 28 (a 2 GiB
planar state) on the ``planar`` and ``pallas`` backends and checks each
against the ``dense`` backend on the same chip (fidelity and norm within
1e-4; at n = 28 amplitudes are ~6e-5, so a per-amplitude bound would say
nothing).  Serving: ``BatchScheduler`` in async mode serves 256 seeded
mixed QAOA/HEA/GHZ requests at n = 20 with ``max_batch=64``, first for
state vectors and then for <Z> expectations; no request may fail and 8
seeded requests must match the dense reference.  ``--four-chips`` runs only
the state-sharded path: the same QRC sharded 4 ways (``mesh=4``,
``max_local_qubits=26``) against the single-chip planar result.

Target and Pallas mode come from the device.  Every phase prints its
numbers; the last line is ``{"ok": true, "device": {...}}`` only when every
check passed.  Without an accelerator, or without the repository's
``src/`` next to this file, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
N_CIRCUIT = 28
N_SERVE = 20
MAX_LOCAL = 26           # per-chip qubits of the 4-way sharded phase
TOL = 1e-4


FAILURES: list[str] = []


def fail(msg: str) -> None:
    """Record a failed check; the run goes on so every phase reports."""
    print(f"FAIL: {msg}", flush=True)
    FAILURES.append(msg)


def run_phase(name: str, fn, *args) -> None:
    """Run one phase; an exception fails the phase, not the whole run."""
    t0 = time.perf_counter()
    try:
        fn(*args)
    except Exception:
        traceback.print_exc()
        sys.stderr.flush()
        fail(f"{name}: raised (traceback on stderr)")
    print(f"phase {name}: seconds={time.perf_counter() - t0!r}", flush=True)


def overlap(a, b):
    """(fidelity, norm_a, norm_b) of two planar states' data arrays.

    The device sums blocks of at most 2**14 amplitudes in f32 and the host
    adds the block sums in float64: one f32 sum over 2**28 amplitudes could
    alone be off by about 1e-5.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def f(a, b):
        size = a.size // 2
        a = a.reshape(2, -1, min(size, 1 << 14))
        b = b.reshape(2, -1, min(size, 1 << 14))
        return (jnp.sum(a[0] * b[0] + a[1] * b[1], axis=-1),
                jnp.sum(a[0] * b[1] - a[1] * b[0], axis=-1),
                jnp.sum(a * a, axis=(0, 2)), jnp.sum(b * b, axis=(0, 2)))

    re, im, na, nb = (float(np.sum(np.asarray(x, np.float64)))
                      for x in f(a, b))
    return (re * re + im * im) / (na * nb), na, nb


def check_pair(label: str, ref, got) -> None:
    fid, n_ref, n_got = overlap(ref, got)
    print(f"{label}: fidelity={fid!r} norm={n_got!r} ref_norm={n_ref!r}",
          flush=True)
    if not (fid >= 1 - TOL and abs(n_got - 1) <= TOL
            and abs(n_ref - 1) <= TOL):
        fail(f"{label}: fidelity {fid} / norm {n_got} outside {TOL}")


def peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def timed_run(sim, circ):
    """Run twice: (state, first-call seconds, steady seconds)."""
    t0 = time.perf_counter()
    st = sim.run(circ)
    st.data.block_until_ready()
    first = time.perf_counter() - t0
    del st
    t0 = time.perf_counter()
    st = sim.run(circ)
    st.data.block_until_ready()
    return st, first, time.perf_counter() - t0


def phase_circuit(dev) -> None:
    from repro.core import circuits as C
    from repro.core.simulator import Simulator

    circ = C.qrc(N_CIRCUIT, depth=8)
    dense = Simulator(backend="dense")
    t0 = time.perf_counter()
    ref = dense.run(circ)
    ref.data.block_until_ready()
    print(f"circuit dense: n={N_CIRCUIT} gates={circ.num_gates} "
          f"seconds={time.perf_counter() - t0!r} "
          f"peak_bytes={peak_bytes(dev)}", flush=True)
    for backend in ("planar", "pallas"):
        sim = Simulator(backend=backend)
        st, first, steady = timed_run(sim, circ)
        plan = sim.plan_for(circ)
        print(f"circuit {backend}: n={N_CIRCUIT} items={len(plan.items)} "
              f"f={plan.f} target={sim.target.name} "
              f"compile_s={first - steady!r} run_s={steady!r} "
              f"peak_bytes={peak_bytes(dev)}", flush=True)
        check_pair(f"circuit {backend} vs dense", ref.data, st.data)
        del st


def phase_serving() -> None:
    import numpy as np

    from repro.core.simulator import Simulator
    from repro.engine import BatchExecutor, BatchScheduler, ResultSpec
    from repro.launch.serve_sim import make_traffic

    traffic = make_traffic("mixed", N_SERVE, 256, seed=0)
    picks = np.random.default_rng(0).choice(len(traffic), 8, replace=False)
    executor = BatchExecutor(backend="planar")
    dense = Simulator(backend="dense")
    refs = [dense.run(traffic[i][0].bind(traffic[i][1])) for i in picks]
    observables = [{0: "Z"}, {N_SERVE - 1: "Z"}]
    for label, spec in (("statevector", None),
                        ("expectation", ResultSpec.expectation(observables))):
        sched = BatchScheduler(executor, max_batch=64)
        t0 = time.perf_counter()
        reqs = [sched.submit(t, p, result=spec) for t, p in traffic]
        sched.drain_async()
        sched.sync()
        dt = time.perf_counter() - t0
        rep = sched.report()
        print(f"serving {label}: n={N_SERVE} requests={rep['requests']} "
              f"batches={rep['batches']} failed={rep['failed']} "
              f"seconds={dt!r} compiles={rep['cache_compiles']}", flush=True)
        if rep["failed"] or not all(r.ok for r in reqs):
            fail(f"serving {label}: {rep['failed']} requests failed")
        for i, ref in zip(picks, refs):
            got = reqs[i].result
            if spec is None:
                check_pair(f"serving {label} request {i}", ref.data, got.data)
                continue
            probs = np.asarray(ref.probabilities(), np.float64)
            idx = np.arange(probs.size)
            want = [float(np.sum(np.where((idx >> q) & 1, -probs, probs)))
                    for q in (0, N_SERVE - 1)]
            err = float(np.max(np.abs(np.asarray(got) - want)))
            print(f"serving {label} request {i}: <Z>={np.asarray(got)!r} "
                  f"ref={want!r} err={err!r}", flush=True)
            if not err <= TOL:
                fail(f"serving {label} request {i}: error {err}")


def phase_sharded(devices) -> None:
    from repro.core import circuits as C
    from repro.core.simulator import Simulator

    import jax

    circ = C.qrc(N_CIRCUIT, depth=8)
    single = Simulator(backend="planar").run(circ)
    single.data.block_until_ready()
    sim = Simulator(backend="planar", mesh=4, max_local_qubits=MAX_LOCAL)
    st, first, steady = timed_run(sim, circ)
    shards = st.data.addressable_shards
    total = st.data.nbytes
    sizes = [s.data.nbytes for s in shards]
    owners = sorted({s.device.id for s in shards})
    print(f"sharded: n={N_CIRCUIT} shards={len(shards)} devices={owners} "
          f"shard_bytes={sizes} total_bytes={total} "
          f"compile_s={first - steady!r} run_s={steady!r}", flush=True)
    if len(owners) != 4 or any(b * 4 != total for b in sizes):
        fail("sharded: the state is not split in quarters over 4 chips")
    check_pair("sharded vs single-chip planar", single.data,
               jax.device_put(st.data, devices[0]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-way state-sharded phase")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"FAIL: no accelerator: JAX found {dev.platform!r}")
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"FAIL: repository sources not found next to this script "
                 f"({src})")
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
          f"compile_cache={enable_compile_cache()}", flush=True)
    if args.four_chips:
        if len(devices) < 4:
            sys.exit(f"FAIL: --four-chips needs 4 devices, found "
                     f"{len(devices)}")
        run_phase("sharded", phase_sharded, devices)
    else:
        run_phase("circuit", phase_circuit, dev)
        run_phase("serving", phase_serving)
    if FAILURES:
        sys.exit(f"FAIL: {len(FAILURES)} check(s) failed: "
                 + "; ".join(FAILURES))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
