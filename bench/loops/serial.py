"""One client runs one whole circuit after another (closed loop, batch 1).

Each circuit goes through ``Simulator(backend=traffic["backend"]).run(
template, params=...)`` and ends in ``block_until_ready``; circuit ``i``
is the family's random instance drawn from ``--seed`` and ``i``.  The
window ends at the first completion after ``--seconds``, and
``circuit_s`` is the window over the circuits completed in it.  Once the
window has closed, a seeded sample of its final states is compared with
:mod:`bench.reference` (``state_err``).
"""
from __future__ import annotations

import time

import jax.numpy as jnp

from bench import compare, reference
from bench.harness import (CompileCounter, Profiler, RunRecord, Spans, delta,
                           fmt, memory_peak_bytes, patched, worse)
from bench.traffic_gen import Instances, Reservoir


def run(cfg, traffic, family, *, seed, seconds, trace, devices, log):
    from repro.core.simulator import Simulator
    from repro.engine.plan import PlanCache

    span = Spans(trace)
    template = family.program_template(cfg)
    sim = Simulator(backend=traffic["backend"], plan_cache=PlanCache())
    params = Instances(seed, cfg, family)
    i = 0
    with CompileCounter() as cc:
        for _ in range(traffic["warmup_circuits"]):
            st = sim.run(template, params=params(i))
            st.data.block_until_ready()
            del st
            i += 1
        plan = sim.plan_for(template)
        items = [(it.kind, len(it.qubits), len(it.controls))
                 for it in plan.items]
        setup = cc.snapshot()
        log(f"setup: plan_items={len(items)} f={plan.f} "
            f"backend={traffic['backend']} {fmt(setup)}")
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
        re, im = reference.run_gates(cfg["n"], gates, p)
        err = compare.state_error(st.data, re, im)
        log(f"check circuit {idx}: state_err={err!r}")
        worst = worse(worst, err)
        del re, im
    keep.items.clear()
    return RunRecord(
        e2e={"circuit_s": (t1 - t0) / done},
        counters={"plan_items": items, "circuits": done,
                  "state_bytes": 8 << cfg["n"], "n": cfg["n"]},
        checks=[("state_err", worst, cfg["limits"]["state_err"])],
        attempted=done, failed=0, memory_peak_bytes=peak,
        window_start=t0, trace_dir=prof.dir)


def control(cfg, traffic, family):
    """The reference at ``high`` in place of ``Simulator.run``."""
    from repro.core import statevec as SV
    from repro.core.simulator import Simulator
    gates, n = family.reference_gates(cfg), cfg["n"]

    def run_reference(self, circuit, initial=None, params=None):
        re, im = reference.run_gates(n, gates, params, "high")
        return SV.State(data=jnp.stack([re, im]), n=n, v=reference.LANE_BITS)
    return patched(Simulator, "run", run_reference)


def _final_state_fault(change):
    def fault(cfg, traffic, family):
        from repro.core.simulator import Simulator
        orig = Simulator.run

        def run_changed(self, *a, **kw):
            st = orig(self, *a, **kw)
            st.data = change(st.data)
            return st
        return patched(Simulator, "run", run_changed)
    return fault


# faults this loop can have besides bench.control's own: one amplitude of
# each final state altered, or made NaN, where the state is produced
FAULTS = {
    "altered": _final_state_fault(lambda d: d.at[0, 0, 0].add(0.1)),
    "nan": _final_state_fault(lambda d: d.at[0, 0, 0].set(jnp.nan)),
}
