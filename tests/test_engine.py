"""Engine tests: template IR, plan cache, batched execution, scheduler."""
import numpy as np
import pytest

from repro.core import circuits as C
from repro.core.simulator import Simulator
from repro.core.target import CPU_TEST
from repro.engine import (BatchExecutor, BatchScheduler, PlanCache,
                          hea_template, qaoa_template, template_of)

BACKENDS = ("dense", "planar", "pallas")


def _dense(state) -> np.ndarray:
    return np.asarray(state.to_dense())


# -- template IR ---------------------------------------------------------------

def test_template_bind_matches_concrete_qaoa():
    t = qaoa_template(6, 2)
    params = np.array([0.3, -0.7, 0.9, 0.2])
    bound = t.bind(params)
    concrete = C.qaoa(6, gammas=params[:2], betas=params[2:])
    assert [g.qubits for g in bound.gates] == [g.qubits for g in concrete.gates]
    assert [g.controls for g in bound.gates] == [g.controls
                                                 for g in concrete.gates]
    for a, b in zip(bound.gates, concrete.gates):
        np.testing.assert_allclose(a.matrix, b.matrix, atol=1e-7)


def test_template_bind_matches_concrete_hea():
    t = hea_template(4, 2)
    params = np.linspace(-1.0, 1.0, t.num_params)
    bound = t.bind(params)
    concrete = C.hardware_efficient(4, params)
    assert len(bound.gates) == len(concrete.gates)
    for a, b in zip(bound.gates, concrete.gates):
        assert a.qubits == b.qubits and a.controls == b.controls
        np.testing.assert_allclose(a.matrix, b.matrix, atol=1e-7)


def test_structure_key_param_invariant():
    t = qaoa_template(5, 2)
    assert t.structure_key() == qaoa_template(5, 2).structure_key()
    assert t.structure_key() != qaoa_template(5, 3).structure_key()
    assert t.structure_key() != qaoa_template(6, 2).structure_key()
    # concrete circuits with different angles are different structures ...
    k1 = template_of(t.bind([0.1, 0.2, 0.3, 0.4])).structure_key()
    k2 = template_of(t.bind([0.5, 0.6, 0.7, 0.8])).structure_key()
    assert k1 != k2
    # ... but the template itself is angle-agnostic
    assert t.structure_key() == qaoa_template(5, 2).structure_key()


def test_bind_validates_param_count():
    t = qaoa_template(4, 1)
    with pytest.raises(ValueError):
        t.bind([0.1])


# -- plan cache ----------------------------------------------------------------

def test_plan_cache_same_structure_one_compile():
    cache = PlanCache()
    t = qaoa_template(5, 2)
    for params in ([0.1] * 4, [0.9] * 4, [-2.0] * 4):
        plan = cache.get_or_compile(t, backend="planar", target=CPU_TEST)
        plan.run(params=params)
    assert cache.stats.compiles == 1
    assert cache.stats.misses == 1
    assert cache.stats.hits == 2


def test_plan_cache_different_structure_misses():
    cache = PlanCache()
    cache.get_or_compile(qaoa_template(5, 2), backend="planar",
                         target=CPU_TEST)
    cache.get_or_compile(qaoa_template(5, 3), backend="planar",
                         target=CPU_TEST)
    cache.get_or_compile(hea_template(5, 1), backend="planar",
                         target=CPU_TEST)
    assert cache.stats.compiles == 3
    assert cache.stats.hits == 0
    # same structure, different backend -> its own plan
    cache.get_or_compile(qaoa_template(5, 2), backend="dense",
                         target=CPU_TEST)
    assert cache.stats.compiles == 4


def test_plan_fuses_structure():
    cache = PlanCache()
    t = qaoa_template(6, 2)
    plan = cache.get_or_compile(t, backend="planar", target=CPU_TEST)
    assert plan.num_fused_gates < t.num_ops


# -- batched execution ---------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_batch_matches_sequential_qaoa(backend):
    t = qaoa_template(6, 2)
    rng = np.random.default_rng(7)
    pm = rng.uniform(-np.pi, np.pi, (8, t.num_params)).astype(np.float32)
    ex = BatchExecutor(backend=backend, cache=PlanCache())
    states = ex.run_batch(t, pm)
    assert ex.stats.compiles == 1
    sim = Simulator(CPU_TEST, backend=backend, plan_cache=ex.cache)
    for b in range(pm.shape[0]):
        ref = sim.run(t, params=pm[b])
        np.testing.assert_allclose(_dense(states[b]), _dense(ref), atol=1e-5)
    # independent oracle: unfused dense per-circuit runs of the bound circuit
    oracle = Simulator(CPU_TEST, backend="dense", plan_cache=PlanCache())
    for b in (0, 5):
        ref = oracle.run(t.bind(pm[b]))
        np.testing.assert_allclose(_dense(states[b]), _dense(ref), atol=1e-5)


@pytest.mark.parametrize("name,n", [("qft", 6), ("ghz", 7)])
@pytest.mark.parametrize("backend", BACKENDS)
def test_batch_matches_sequential_fixed_circuits(backend, name, n):
    """Zero-parameter templates batch too (shot-style replication)."""
    circ = C.build(name, n)
    t = template_of(circ)
    ex = BatchExecutor(backend=backend, cache=PlanCache())
    states = ex.run_batch(t, np.zeros((3, 0), np.float32))
    ref = Simulator(CPU_TEST, backend=backend,
                    plan_cache=PlanCache()).run(circ)
    for s in states:
        np.testing.assert_allclose(_dense(s), _dense(ref), atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_sweep_64_single_compile(backend):
    """Acceptance: 64-way QAOA sweep, one plan compile, matches per-circuit
    Simulator.run."""
    t = qaoa_template(6, 2)
    rng = np.random.default_rng(11)
    pm = rng.uniform(-np.pi, np.pi, (64, t.num_params)).astype(np.float32)
    ex = BatchExecutor(backend=backend, cache=PlanCache())
    states = ex.run_batch(t, pm)
    assert ex.stats.compiles == 1, ex.stats
    sim = Simulator(CPU_TEST, backend=backend, plan_cache=ex.cache)
    for b in range(64):
        ref = sim.run(t, params=pm[b])
        np.testing.assert_allclose(_dense(states[b]), _dense(ref), atol=1e-5)
    assert ex.stats.compiles == 1, ex.stats


def test_shot_batch_over_initial_states():
    from repro.core import statevec as SV
    t = template_of(C.qft(5))
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    initials = [SV.random_state(5, CPU_TEST, seed=s) for s in range(4)]
    states = ex.run_states(t, initials)
    sim = Simulator(CPU_TEST, backend="planar", plan_cache=PlanCache())
    for seed, out in enumerate(states):
        ref = sim.run(C.qft(5),
                      initial=SV.random_state(5, CPU_TEST, seed=seed))
        np.testing.assert_allclose(_dense(out), _dense(ref), atol=1e-5)


# -- scheduler -----------------------------------------------------------------

def test_scheduler_batches_by_structure():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=8)
    t1, t2 = qaoa_template(5, 2), hea_template(5, 1)
    rng = np.random.default_rng(3)
    reqs = [sched.submit(t1, rng.uniform(-1, 1, t1.num_params))
            for _ in range(5)]
    reqs += [sched.submit(t2, rng.uniform(-1, 1, t2.num_params))
             for _ in range(3)]
    done = sched.drain()
    assert len(done) == 8 and not sched.pending
    assert all(r.done and r.latency is not None for r in done)
    # two structures -> two plans, two batches; 5->8 and 3->4 padding
    assert ex.stats.compiles == 2
    assert sched.stats.batches == 2
    assert sched.stats.padded_slots == (8 - 5) + (4 - 3)
    # results match direct execution
    sim = Simulator(CPU_TEST, backend="planar", plan_cache=ex.cache)
    for r in reqs:
        ref = sim.run(r.template, params=r.params)
        np.testing.assert_allclose(_dense(r.result), _dense(ref), atol=1e-5)


def test_scheduler_splits_oversized_groups():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=4)
    t = qaoa_template(4, 1)
    for i in range(10):
        sched.submit(t, [0.1 * i, 0.2 * i])
    done = sched.drain()
    assert len(done) == 10
    assert sched.stats.batches == 3          # 4 + 4 + 2(padded to 4)
    assert ex.stats.compiles == 1
    rep = sched.report()
    assert rep["requests"] == 10 and rep["cache_compiles"] == 1


# -- probabilities regression (satellite) --------------------------------------

@pytest.mark.parametrize("backend", ("planar", "pallas"))
def test_probabilities_dense_basis_order(backend):
    """Planar-layout probabilities must come back in dense basis order."""
    circ = C.qft(6)
    sim = Simulator(CPU_TEST, backend=backend, plan_cache=PlanCache())
    state = sim.run(circ)
    probs = np.asarray(sim.probabilities(state))
    ref_state = Simulator(CPU_TEST, backend="dense",
                          plan_cache=PlanCache()).run(circ)
    ref = np.abs(_dense(ref_state)) ** 2
    np.testing.assert_allclose(probs, ref, atol=1e-5)
    # State.probabilities agrees with |to_dense()|^2 of the same state
    np.testing.assert_allclose(probs, np.abs(_dense(state)) ** 2, atol=1e-6)
    assert probs.shape == (1 << circ.n,)


def test_batched_build_raises_instead_of_falling_back(monkeypatch):
    """A program with no batching rule is an error, not a silent switch to a
    sequential lax.map (which the parent fell back to)."""
    import jax
    import jax.numpy as jnp
    ex = BatchExecutor(target=CPU_TEST, backend="planar", cache=PlanCache())
    plan = ex.plan_for(qaoa_template(4, 1))

    def unbatchable():
        def program(state, params):
            zero = jax.pure_callback(lambda p: np.float32(0),
                                     jax.ShapeDtypeStruct((), jnp.float32),
                                     params[0])
            return state + zero
        return program

    monkeypatch.setattr(plan, "_program", unbatchable)
    with pytest.raises(NotImplementedError, match="vmap"):
        plan.run_batch_raw(np.zeros((2, plan.num_params), np.float32))


@pytest.mark.parametrize("backend,n", [("planar", 16), ("pallas", 20)])
def test_tiled_target_plans_keep_vector_tiles_whole(backend, n):
    """On a tiled-memory target every non-diagonal item can move its tile
    bits out of the vector tile, and the plan still matches dense."""
    from repro.core.apply import lane_window
    from repro.core.target import TPU_V5E
    from repro.kernels.apply_gate.ops import tile_swaps
    v = TPU_V5E.lane_qubits
    circ = C.qrc(n, depth=3)
    sim = Simulator(TPU_V5E, backend=backend, plan_cache=PlanCache())
    plan = sim.plan_for(circ)
    for it in plan.items:
        bits = it.qubits + it.controls
        if it.kind == "diag" or min(bits) >= (v if backend == "planar"
                                              else v + 3):
            continue
        if backend == "planar":
            assert lane_window(n, v, bits) is not None, bits
        else:
            assert tile_swaps(n, v, bits), bits
    ref = Simulator(TPU_V5E, backend="dense", plan_cache=PlanCache())
    np.testing.assert_allclose(_dense(sim.run(circ)), _dense(ref.run(circ)),
                               atol=2e-6)



def x_layer_circuit(n):
    """X layers after CZ ladders: the planar plan folds them into XOR-mask
    permutation items, some with lane bits."""
    from repro.core import gates as G
    gs = [G.h(q) for q in range(n)]
    gs += [G.cz(q, q + 1) for q in range(n - 1)]
    gs += [G.x(q) for q in range(0, n, 3)]
    gs += [G.cz(q, q + 1) for q in range(n - 1)]
    gs += [G.x(q) for q in range(n)]
    return C.Circuit(n, gs, name="xlayers")


def test_xor_permutations_lower_to_flips():
    """X layers on lane and row bits of a tiled target lower to axis
    reversals: the planar program holds no gather and matches dense."""
    import jax
    import jax.numpy as jnp
    from repro.core.target import TPU_V5E
    n = 16
    circ = x_layer_circuit(n)
    sim = Simulator(TPU_V5E, backend="planar", plan_cache=PlanCache())
    plan = sim.plan_for(circ)
    perms = [it for it in plan.items if it.kind == "perm"]
    assert any(min(it.qubits) < TPU_V5E.lane_qubits for it in perms)
    state = jax.ShapeDtypeStruct((2, 1 << (n - 7), 1 << 7), jnp.float32)
    params = jax.ShapeDtypeStruct((plan.num_params,), jnp.float32)
    hlo = jax.jit(plan._program()).lower(state, params).as_text()
    assert "gather" not in hlo
    ref = Simulator(TPU_V5E, backend="dense", plan_cache=PlanCache())
    np.testing.assert_allclose(_dense(sim.run(circ)), _dense(ref.run(circ)),
                               atol=2e-6)


def _dot_precisions(jaxpr, out):
    """Precision of every dot_general in ``jaxpr`` and its sub-programs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _dot_precisions(sub, out)
    return out


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("expectation", (False, True))
def test_program_dots_use_highest_precision(backend, expectation):
    """A TPU runs a default-precision f32/c64 dot as bf16 passes, so every
    dot a plan traces (parameterized cluster unitaries, gate matvecs,
    observable inner products) asks for HIGHEST."""
    import jax
    import jax.numpy as jnp
    from repro.engine import ResultSpec
    from repro.engine.shapeclass import ClassExecutable, class_row_tensors
    spec = (ResultSpec.expectation([{0: "Z"}, {3: "X"}]) if expectation
            else None)
    plan = PlanCache().get_or_compile(hea_template(8, 2), backend=backend,
                                      target=CPU_TEST, result=spec)
    state = plan._initial_data(None)
    params = jnp.zeros(plan.num_params, jnp.float32)
    if expectation:
        programs = [jax.make_jaxpr(plan._result_program())(
            state, params, jnp.zeros(2, jnp.uint32))]
    else:
        programs = [jax.make_jaxpr(plan._program())(state, params)]
    if backend == "planar" and not expectation:
        consts = [jnp.asarray(c) for c in class_row_tensors(plan)]
        programs.append(jax.make_jaxpr(
            ClassExecutable(plan)._program(False))(state, params, consts))
    for jp in programs:
        precisions = _dot_precisions(jp.jaxpr, [])
        assert precisions or (backend == "dense" and not expectation)
        hi = jax.lax.Precision.HIGHEST
        assert all(p == (hi, hi) for p in precisions), precisions
