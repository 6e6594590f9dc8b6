"""One client runs one whole circuit after another (closed loop, batch 1),
each state sharded over the cell's chips.

Each circuit goes through ``Simulator(backend=traffic["backend"],
mesh=<the cell's devices>, max_local_qubits=traffic["max_local_qubits"])
.run(template, params=...)`` and ends in ``block_until_ready``; circuit
``i`` is the family's random instance drawn from ``--seed`` and ``i``.
The window and ``circuit_s`` are those of :mod:`bench.loops.serial`.  Once
the window has closed and the program is dropped, a seeded sample of its
final states is compared with :mod:`bench.reference_sharded`, block by
block on the chip that holds each block (``state_err``): no chip holds the
whole state.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, reference, reference_sharded
from bench.harness import (CompileCounter, Profiler, RunRecord, Spans, delta,
                           fmt, memory_peak_bytes, patched, worse)
from bench.traffic_gen import Instances, Reservoir


def blocks_of(data):
    """``[(device, rows)]`` of a state's row shards, in row order: block
    ``b`` of the state, the amplitudes whose top bits are ``b``."""
    shards = sorted(data.addressable_shards,
                    key=lambda sh: sh.index[1].start or 0)
    rows = data.shape[1] // len(shards)
    if any(sh.data.shape[1] != rows for sh in shards):
        raise ValueError(f"the state's rows are not split evenly over "
                         f"{len(shards)} shards")
    return [(sh.device, sh.data) for sh in shards]


def state_error(data, ref) -> float:
    """||psi - ref|| / ||ref|| for a row-sharded planar state ``data``
    against the blocked reference planes ``ref``, each block compared on
    its own shard's device; the blocks' float32 sums add up on the host in
    float64."""
    err = norm = 0.0
    for (dev, shard), (_, re, im) in zip(blocks_of(data),
                                         reference_sharded.blocks(ref),
                                         strict=True):
        re, im = jax.device_put((re, im), dev)
        rows = min(re.size, compare.BLOCK) // compare.LANES
        e, m = compare._err_blocks(shard, re, im, rows=rows)
        err += float(np.sum(np.asarray(e, np.float64)))
        norm += float(np.sum(np.asarray(m, np.float64)))
    return float(np.sqrt(err / norm))


def _simulator(traffic, devices):
    from jax.sharding import Mesh
    from repro.core.simulator import Simulator
    from repro.engine.plan import PlanCache
    mesh = Mesh(np.asarray(devices[:traffic["mesh"]]), ("chips",))
    return Simulator(backend=traffic["backend"], mesh=mesh,
                     max_local_qubits=traffic["max_local_qubits"],
                     plan_cache=PlanCache())


def _check_program():
    """Refuse, before anything compiles, a program whose sharded plan
    lacks the lane-window victim rule
    (``repro.engine.plan.sharded_windows_kept``).  Without it the swaps
    leave some items' lane bits no free block of row bits, their views
    pad the lanes, and at n = 30 the chip's compiler asks for 32 GiB a
    chip, refusing it only after many minutes (compile rehearsal for a
    v5e)."""
    from repro.engine import plan
    if not callable(getattr(plan, "sharded_windows_kept", None)):
        raise RuntimeError(
            "this program's sharded plan has no lane-window victim rule "
            "(repro.engine.plan.sharded_windows_kept): its program for "
            "this cell needs more memory than a chip has")


def run(cfg, traffic, family, *, seed, seconds, trace, devices, log):
    _check_program()
    span = Spans(trace)
    template = family.program_template(cfg)
    sim = _simulator(traffic, devices)
    params = Instances(seed, cfg, family)
    i = 0
    with CompileCounter() as cc:
        for _ in range(traffic["warmup_circuits"]):
            st = sim.run(template, params=params(i))
            st.data.block_until_ready()
            del st
            i += 1
        plan = sim.plan_for(template)
        bits = traffic["mesh"].bit_length() - 1
        if plan.state_bits != bits:
            raise RuntimeError(f"the plan shards the state {plan.state_bits} "
                               f"bits, the cell {bits}")
        items = [(it.kind, len(it.qubits), len(it.controls))
                 for it in plan.items]
        swaps = plan.sharded_swaps
        setup = cc.snapshot()
        log(f"setup: plan_items={len(items)} f={plan.f} "
            f"backend={traffic['backend']} state_bits={plan.state_bits} "
            f"swaps={swaps} {fmt(setup)}")
        window = min(seconds, traffic["trace_seconds"]) if trace else seconds
        keep = Reservoir(traffic["check_circuits"], seed)
        prof = Profiler(trace)
        prof.start()
        t0 = t1 = time.perf_counter()
        done = 0
        durations = []
        with span("window"):
            while True:
                with span("bind"):
                    p = params(i)
                with span("run"):
                    st = sim.run(template, params=p)
                with span("block"):
                    st.data.block_until_ready()
                t_prev, t1 = t1, time.perf_counter()
                durations.append(t1 - t_prev)
                keep.offer((i, p, st))
                del st
                i += 1
                done += 1
                if t1 - t0 >= window:
                    break
        prof.stop()
        in_window = delta(setup, cc.snapshot())
    peak = memory_peak_bytes(devices)
    slowest = max(range(done), key=durations.__getitem__)
    log(f"window: circuits={done} seconds={t1 - t0!r} {fmt(in_window)} "
        f"memory_peak_bytes={peak}")
    log(f"window: slowest_circuit_s={durations[slowest]!r} at={slowest} "
        f"median_circuit_s={sorted(durations)[done // 2]!r}")
    del sim, plan

    worst = 0.0
    gates = family.reference_gates(cfg)
    for idx, p, st in sorted(keep.items, key=lambda it: it[0]):
        ref = reference_sharded.run_gates(
            cfg["n"], gates, p, [dev for dev, _ in blocks_of(st.data)])
        err = state_error(st.data, ref)
        log(f"check circuit {idx}: state_err={err!r}")
        worst = worse(worst, err)
        del ref
    keep.items.clear()
    return RunRecord(
        e2e={"circuit_s": (t1 - t0) / done},
        counters={"plan_items": items, "circuits": done, "n": cfg["n"],
                  "state_bits": bits,
                  "local_state_bytes": 8 << (cfg["n"] - bits),
                  "swaps": swaps, "device_kind": devices[0].device_kind},
        checks=[("state_err", worst, cfg["limits"]["state_err"])],
        attempted=done, failed=0, memory_peak_bytes=peak,
        window_start=t0, trace_dir=prof.dir)


def control(cfg, traffic, family):
    """The blocked reference at ``high`` in place of ``Simulator.run``,
    its blocks put together as one state sharded over the cell's
    devices."""
    from repro.core import statevec as SV
    from repro.core.simulator import Simulator
    gates, n = family.reference_gates(cfg), cfg["n"]
    devices = jax.devices()[:traffic["mesh"]]

    def run_reference(self, circuit, initial=None, params=None):
        ref = reference_sharded.run_gates(n, gates, params, devices, "high")
        return SV.State(data=reference_sharded.planar(ref), n=n,
                        v=reference.LANE_BITS)
    return patched(Simulator, "run", run_reference)


def _unchanged(cfg, traffic, family):
    """The plan's first gate item returns its block unchanged (the sharded
    form of :mod:`bench.control`'s fault of that name)."""
    from repro.engine.plan import CompiledPlan
    orig = CompiledPlan._sharded_item_step

    def step(self, item, *args):
        if item is self._gate_items()[0]:
            return lambda data, params: data
        return orig(self, item, *args)
    return patched(CompiledPlan, "_sharded_item_step", step)


def _final_state_fault(change):
    def fault(cfg, traffic, family):
        from repro.core.simulator import Simulator
        orig = Simulator.run

        def run_changed(self, *a, **kw):
            st = orig(self, *a, **kw)
            st.data = jax.jit(change, out_shardings=st.data.sharding)(st.data)
            return st
        return patched(Simulator, "run", run_changed)
    return fault


# faults this loop can have: bench.control's ``unchanged`` in the sharded
# item steps, and one amplitude of each final state altered or made NaN
# where the state is produced
FAULTS = {
    "unchanged": _unchanged,
    "altered": _final_state_fault(lambda d: d.at[0, 0, 0].add(0.1)),
    "nan": _final_state_fault(lambda d: d.at[0, 0, 0].set(jnp.nan)),
}
