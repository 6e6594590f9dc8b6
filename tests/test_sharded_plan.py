"""Sharded plan execution: layout policy, mesh-aware cache keys, and
equivalence of the shard_map-lowered plan path against the single-device
plan path / dense oracle on multi-device host meshes."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import distributed as D
from repro.core.target import CPU_TEST, row_budget
from repro.engine import BatchExecutor, PlanCache, qaoa_template
from repro.engine.plan import (_local_perm_map, _relabel_special_item,
                               PlanItem, resolve_diag_f, resolve_f)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# -- row budget: one canonical rule -------------------------------------------

def test_row_budget_is_the_canonical_cap():
    assert row_budget(12, CPU_TEST) == 12 - CPU_TEST.lane_qubits
    assert row_budget(4, CPU_TEST) == 2          # floor keeps 2q gates fusable
    # resolve_f / resolve_diag_f derive their caps from it
    assert resolve_f(99, CPU_TEST, 12, True, "planar") == row_budget(
        12, CPU_TEST)
    assert resolve_diag_f(2, CPU_TEST, 12) == row_budget(12, CPU_TEST)
    # the sharded path applies the same rule to the local sub-state, plus
    # the victim-block reserve
    n, s = 12, 2
    local = row_budget(n - s, CPU_TEST)
    assert resolve_diag_f(2, CPU_TEST, n, state_bits=s) == min(
        local, (n - s) - s)
    assert resolve_f(99, CPU_TEST, n, True, "planar", state_bits=s) <= local


# -- batch-first layout policy ------------------------------------------------

def test_plan_shard_layout_batch_first():
    # n under the budget: all devices to the batch axis
    assert D.plan_shard_layout(12, 16, 4, CPU_TEST) == D.ShardSpec(4, 0)
    # small sweeps don't pad across the whole mesh
    assert D.plan_shard_layout(12, 2, 4, CPU_TEST) == D.ShardSpec(2, 0)
    assert D.plan_shard_layout(12, 3, 8, CPU_TEST) == D.ShardSpec(4, 0)
    # n over the budget: spill exactly the excess into state sharding
    spec = D.plan_shard_layout(30, 16, 4, CPU_TEST, max_local_qubits=28)
    assert spec == D.ShardSpec(1, 2)
    spec = D.plan_shard_layout(29, 16, 8, CPU_TEST, max_local_qubits=28)
    assert spec == D.ShardSpec(4, 1)


def test_plan_shard_layout_single_circuit_goes_state_first():
    # batch=None (Simulator.run): no batch axis exists, whole mesh -> state
    assert D.plan_shard_layout(12, None, 4, CPU_TEST) == D.ShardSpec(1, 2)
    # ... unless the spill knob is explicitly set and the state fits
    assert D.plan_shard_layout(12, None, 4, CPU_TEST,
                               max_local_qubits=30) == D.ShardSpec(1, 0)
    assert D.plan_shard_layout(12, None, 4, CPU_TEST,
                               max_local_qubits=11) == D.ShardSpec(1, 1)
    # clamped so a victim block + width-2 clusters always fit locally
    cap = D.max_state_bits(6, CPU_TEST)
    assert cap == 1
    assert D.plan_shard_layout(6, None, 8, CPU_TEST) == D.ShardSpec(1, 1)


def test_plan_shard_layout_rejects_non_pow2():
    with pytest.raises(ValueError):
        D.plan_shard_layout(12, 16, 3, CPU_TEST)


# -- mesh-shape-aware plan cache keys -----------------------------------------

def test_plan_cache_keys_mesh_shape_separately():
    cache = PlanCache()
    t = qaoa_template(10, 2)
    kw = dict(backend="planar", target=CPU_TEST, f=None, fuse=True,
              interpret=True)
    k1 = cache.plan_key(t, **kw)
    k2 = cache.plan_key(t, **kw, state_bits=1)
    k4 = cache.plan_key(t, **kw, state_bits=2)
    assert len({k1, k2, k4}) == 3
    p1 = cache.get_or_compile(t, **kw)
    p2 = cache.get_or_compile(t, **kw, state_bits=1)
    p4 = cache.get_or_compile(t, **kw, state_bits=2)
    assert len(cache) == 3 and cache.stats.compiles == 3
    assert p1 is not p2 and p2 is not p4
    assert p4.state_bits == 2 and p2.state_bits == 1
    assert cache.get_or_compile(t, **kw) is p1          # hit, not recompile
    assert cache.stats.hits == 1
    # batch-only sharding (state_bits=0) deliberately REUSES the
    # single-device lowering: same artifact, no duplicate compile
    assert cache.get_or_compile(t, **kw, state_bits=0) is p1
    assert cache.stats.compiles == 3


def test_sharded_requires_planar_backend():
    with pytest.raises(ValueError, match="planar"):
        BatchExecutor(backend="pallas", mesh=1)


def test_single_device_mesh_degenerates_to_plain_path():
    # mesh=1 on the single test device: policy yields (1, 0) and execution
    # takes the ordinary vmapped path
    ex = BatchExecutor(target=CPU_TEST, backend="planar", cache=PlanCache(),
                       mesh=1)
    t = qaoa_template(8, 1)
    pm = np.random.default_rng(0).uniform(-1, 1, (3, t.num_params))
    ref = BatchExecutor(target=CPU_TEST, backend="planar", cache=PlanCache())
    outs = [np.asarray(s.to_dense()) for s in ex.run_batch(t, pm)]
    refs = [np.asarray(s.to_dense()) for s in ref.run_batch(t, pm)]
    for a, b in zip(outs, refs):
        np.testing.assert_allclose(a, b, atol=1e-6)


# -- trace-time relabeling helpers --------------------------------------------

def test_local_perm_map_roundtrip():
    rng = np.random.default_rng(5)
    for _ in range(5):
        n = 6
        rho = tuple(rng.permutation(n).tolist())
        m = _local_perm_map(rho)
        psi = rng.standard_normal(1 << n)
        out = psi[m]
        # content of bit p moved to bit rho[p]
        for x in range(1 << n):
            y = 0
            for p in range(n):
                y |= ((x >> p) & 1) << rho[p]
            assert out[y] == psi[x]


def test_relabel_special_item_matches_manual_phase():
    # diag item on qubits (0, 2); physical positions reversed (4, 1)
    phase = np.exp(1j * np.arange(4)).astype(np.complex64)
    item = PlanItem(qubits=(0, 2), controls=(), kind="diag",
                    phases=(("const", phase),))
    rel = _relabel_special_item(item, (4, 1))
    assert rel.qubits == (1, 4)
    # new bit 0 <-> position 1 <-> old cluster bit 1 (qubit 2);
    # new bit 1 <-> position 4 <-> old cluster bit 0 (qubit 0)
    expect = phase[[0, 2, 1, 3]]
    np.testing.assert_allclose(np.asarray(rel.phases[0][1]), expect)


# -- multi-device equivalence (subprocess: needs forced host devices) ---------

def _run(devices: int, body: str, timeout: int = 480) -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        import sys; sys.path.insert(0, {SRC!r})
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.mark.slow
def test_sharded_plan_matches_single_device():
    """Property-style: random diag/perm/mixed circuits through 2- and
    4-device meshes (batch and forced-state sharding) match the
    single-device plan path to 1e-6."""
    _run(4, """
        import numpy as np
        from repro.core import circuits as C
        from repro.core import gates as G
        from repro.core.target import CPU_TEST
        from repro.engine import BatchExecutor, PlanCache, template_of

        def rand_circuit(n, depth, seed, pool):
            r = np.random.default_rng(seed)
            gs = []
            for _ in range(depth):
                q = int(r.integers(0, n))
                q2 = int((q + 1 + r.integers(0, n - 1)) % n)
                gs.append(pool(r, q, q2))
            return C.Circuit(n, gs, name=f"rand{seed}")

        diag = lambda r, q, q2: [G.z(q), G.s(q), G.t(q),
                                 G.rz(q, float(r.uniform(-3, 3))),
                                 G.cz(q2, q)][int(r.integers(0, 5))]
        perm = lambda r, q, q2: [G.x(q), G.cnot(q2, q),
                                 G.swap(q, q2)][int(r.integers(0, 3))]
        mixed = lambda r, q, q2: [G.h(q), G.x(q), G.z(q),
                                  G.rz(q, float(r.uniform(-3, 3))),
                                  G.rx(q, float(r.uniform(-3, 3))),
                                  G.cnot(q2, q), G.cz(q2, q),
                                  G.swap(q, q2)][int(r.integers(0, 8))]

        n = 9
        circs = ([rand_circuit(n, 24, s, diag) for s in range(2)]
                 + [rand_circuit(n, 24, 10 + s, perm) for s in range(2)]
                 + [rand_circuit(n, 30, 20 + s, mixed) for s in range(3)])
        ref_ex = BatchExecutor(target=CPU_TEST, backend="planar",
                               cache=PlanCache())
        for circ in circs:
            t = template_of(circ)
            ref = np.asarray(ref_ex.run(t).to_dense())
            for devs in (2, 4):
                for max_local in (None, n - 2):   # batch / forced state
                    ex = BatchExecutor(target=CPU_TEST, backend="planar",
                                       cache=PlanCache(), mesh=devs,
                                       max_local_qubits=max_local)
                    plan, raw = ex.dispatch_batch(t, np.zeros((2, 0)))
                    for st in plan.wrap_batch(raw):
                        err = np.abs(np.asarray(st.to_dense()) - ref).max()
                        assert err < 1e-6, (circ.name, devs, max_local, err)
        print("OK")
    """, timeout=560)


@pytest.mark.slow
def test_sharded_scheduler_and_swap_amortization():
    """End-to-end scheduler traffic on a mesh (all requests DONE, results
    match) + lazy unswapping: a run of general items on the same
    formerly-global qubits pays one item-driven collective."""
    _run(4, """
        import numpy as np
        from repro.core import circuits as C
        from repro.core import gates as G
        from repro.core.target import CPU_TEST
        from repro.engine import (BatchExecutor, BatchScheduler, PlanCache,
                                  qaoa_template, template_of)

        n = 9
        t = qaoa_template(n, 2)
        rng = np.random.default_rng(0)
        pm = rng.uniform(-np.pi, np.pi, (6, t.num_params))
        ref_ex = BatchExecutor(target=CPU_TEST, backend="planar",
                               cache=PlanCache())
        refs = [np.asarray(s.to_dense())
                for s in ref_ex.run_batch(t, pm)]

        ex = BatchExecutor(target=CPU_TEST, backend="planar",
                           cache=PlanCache(), mesh=4,
                           max_local_qubits=n - 2)
        sched = BatchScheduler(ex, max_batch=4)
        reqs = sched.submit_sweep(t, pm)
        sched.drain()
        assert all(r.ok for r in reqs), [r.state for r in reqs]
        for r, ref in zip(reqs, refs):
            err = np.abs(np.asarray(r.result.to_dense()) - ref).max()
            assert err < 1e-6, err

        # executor.run (batch of one) takes the same sharded path
        one = np.asarray(ex.run(t, pm[0]).to_dense())
        assert np.abs(one - refs[0]).max() < 1e-6

        # non-power-of-two mesh requests are rejected, not truncated
        try:
            BatchExecutor(backend="planar", mesh=3)
        except ValueError as e:
            assert "power of two" in str(e)
        else:
            raise AssertionError("mesh=3 should be rejected")

        # swap amortization: three f=2 clusters alternating between the
        # global pair {7,8} and {6,7} — lazy unswapping pays ONE
        # item-driven swap (plus <=2 restore swaps), not one per item
        r = np.random.default_rng(1)
        circ = C.Circuit(n, [G.su4(7, 8, r), G.su4(6, 7, r),
                             G.su4(7, 8, r)])
        ex2 = BatchExecutor(target=CPU_TEST, backend="planar", f=2,
                            cache=PlanCache(), mesh=4,
                            max_local_qubits=n - 2)
        tpl = template_of(circ)
        plan, raw = ex2.dispatch_batch(tpl, np.zeros((1, 0)))
        out = np.asarray(plan.wrap_batch(raw)[0].to_dense())
        ref = np.asarray(ref_ex.run(tpl).to_dense())
        assert np.abs(out - ref).max() < 1e-6
        assert plan.num_fused_gates >= 3
        assert 1 <= plan.sharded_swaps <= 3, plan.sharded_swaps
        print("OK")
    """, timeout=560)


def test_tiled_target_sharded_run_matches_single_device():
    """On a tiled-memory target the sharded program keeps swap victims above
    the vector tile and undoes its exchanges in reverse, with no local
    gather; the state still matches the single-device planar run."""
    _run(4, """
        import numpy as np
        from repro.core import circuits as C
        from repro.core.simulator import Simulator
        from repro.core.target import TPU_V5E
        from repro.engine import PlanCache

        n = 16
        circ = C.qrc(n, depth=3)
        single = Simulator(TPU_V5E, backend="planar", plan_cache=PlanCache())
        sharded = Simulator(TPU_V5E, backend="planar", mesh=4,
                            max_local_qubits=n - 2, plan_cache=PlanCache())
        st = sharded.run(circ)
        assert len(st.data.addressable_shards) == 4
        plan = sharded.plan_for(circ)
        assert plan.state_bits == 2 and plan.sharded_swaps >= 2
        want = np.asarray(single.run(circ).to_dense())
        got = np.asarray(st.to_dense())
        assert np.abs(got - want).max() < 1e-6, np.abs(got - want).max()
        print("OK")
    """)
