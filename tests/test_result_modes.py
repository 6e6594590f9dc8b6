"""Result-mode serving: shots / expectation sweeps / noise channels.

Every mode is exercised end-to-end (``ResultSpec`` -> ``IngestServer`` /
``BatchScheduler`` -> ``compile_plan`` epilogue -> reduced response) and
checked against the dense gate-by-gate oracle:

* **shots** — empirical distributions match dense probabilities, and the
  same request is *bitwise identical* under any batch composition (the
  per-request-key PRNG discipline);
* **expectation** — every served value matches the dense
  apply-then-inner-product oracle, on all three backends (every
  Z-string of a request shares one pass over |amp|^2);
* **noisy** — trajectory unraveling averages to the exact density-matrix
  (Kraus-sum) expectation within a statistical bound, and is exact for the
  deterministic channels (p=0, gamma=1).

Plus: ``ResultSpec``/``NoiseChannel`` validation, co-batching plan-key
rules, scheduler row expansion + reduction, per-mode stats counters, and
seed-logged hypothesis property suites for each mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import apply as A
from repro.core import gates as G
from repro.core.target import CPU_TEST
from repro.engine import (BatchExecutor, BatchScheduler, IngestServer,
                          NoiseChannel, PlanCache, ResultSpec,
                          amplitude_damping, bit_flip, depolarizing,
                          phase_flip, qaoa_template)
from repro.engine.plan import compile_plan
from repro.engine.scheduler import _reduce_result_rows
from repro.engine.template import hea_template

_PAULI = {"X": G.X_M, "Y": G.Y_M, "Z": G.Z_M}


# -- oracles ------------------------------------------------------------------

def _dense_state(template, params):
    n = template.n
    psi = jnp.zeros(1 << n, jnp.complex64).at[0].set(1.0)
    for g in template.bind(params).gates:
        psi = A.apply_gate_dense(psi, n, g.qubits, g.matrix, g.controls)
    return np.asarray(psi)


def _oracle_expectation(template, params, obs):
    psi = jnp.asarray(_dense_state(template, params))
    phi = psi
    for q, p in dict(obs).items():
        phi = A.apply_gate_dense(phi, template.n, (q,), _PAULI[p])
    return float(np.real(np.vdot(np.asarray(psi), np.asarray(phi))))


def _embed(n, qubits, mat):
    """Full 2**n operator for ``mat`` on ``qubits`` (column-wise apply)."""
    cols = []
    for b in range(1 << n):
        e = jnp.zeros(1 << n, jnp.complex64).at[b].set(1.0)
        cols.append(np.asarray(A.apply_gate_dense(e, n, qubits, mat)))
    return np.stack(cols, axis=1)


def _oracle_noisy_expectation(template, params, channels, obs):
    """Exact density-matrix Kraus-sum oracle (no sampling)."""
    n = template.n
    psi = _dense_state(template, params)
    rho = np.outer(psi, psi.conj())
    for ch in channels:
        ks = [_embed(n, ch.qubits, k) for k in ch.kraus]
        rho = sum(k @ rho @ k.conj().T for k in ks)
    p_full = np.eye(1 << n, dtype=np.complex64)
    for q, p in dict(obs).items():
        p_full = _embed(n, (q,), _PAULI[p]) @ p_full
    return float(np.real(np.trace(p_full @ rho)))


def _make_sched(backend="planar", max_batch=8, **kw):
    ex = BatchExecutor(target=CPU_TEST, backend=backend, cache=PlanCache())
    return BatchScheduler(ex, max_batch=max_batch, **kw)


@pytest.fixture(scope="module")
def t5():
    return qaoa_template(5, 1)


@pytest.fixture(scope="module")
def p5():
    return np.array([0.7, 0.4], np.float32)


# -- ResultSpec validation ----------------------------------------------------

def test_spec_statevector_default():
    spec = ResultSpec.statevector()
    assert spec.mode == "statevector"
    assert spec.rows == 1 and not spec.needs_key
    assert spec.plan_key() is None


def test_spec_shots_requires_positive_count():
    with pytest.raises(ValueError):
        ResultSpec.sample(0)
    with pytest.raises(ValueError):
        ResultSpec(mode="shots", shots=-4)


def test_spec_key_must_be_uint32():
    with pytest.raises(ValueError):
        ResultSpec.sample(8, key=-1)
    with pytest.raises(ValueError):
        ResultSpec.sample(8, key=1 << 32)
    ResultSpec.sample(8, key=(1 << 32) - 1)      # max key is fine


def test_spec_expectation_requires_observables():
    with pytest.raises(ValueError):
        ResultSpec.expectation([])


def test_spec_noisy_requires_channels_and_observables():
    with pytest.raises(ValueError):
        ResultSpec.noisy([], [{0: "Z"}])
    with pytest.raises(ValueError):
        ResultSpec.noisy([depolarizing(0, 0.1)], [])
    with pytest.raises(ValueError):
        ResultSpec.noisy([depolarizing(0, 0.1)], [{0: "Z"}], unravelings=0)


def test_spec_channels_only_in_noisy_mode():
    with pytest.raises(ValueError):
        ResultSpec(mode="expectation", observables=({0: "Z"},),
                   channels=(depolarizing(0, 0.1),))


def test_spec_observable_normalization():
    spec = ResultSpec.expectation([{2: "z", 0: "x"}])
    assert spec.observables == (((0, "X"), (2, "Z")),)   # sorted, uppercase
    with pytest.raises(ValueError):
        ResultSpec.expectation([[(1, "Z"), (1, "X")]])   # duplicate qubit
    with pytest.raises(ValueError):
        ResultSpec.expectation([{0: "Q"}])               # unknown pauli


def test_spec_plan_key_excludes_key_and_unravelings():
    a = ResultSpec.sample(32, key=1)
    b = ResultSpec.sample(32, key=999)
    assert a.plan_key() == b.plan_key()                  # co-batchable
    assert a.plan_key() != ResultSpec.sample(64, key=1).plan_key()
    ch = [depolarizing(0, 0.1)]
    obs = [{0: "Z"}]
    x = ResultSpec.noisy(ch, obs, unravelings=2, key=5)
    y = ResultSpec.noisy(ch, obs, unravelings=16, key=7)
    assert x.plan_key() == y.plan_key()
    assert x.rows == 2 and y.rows == 16


def test_spec_validate_for_rejects_out_of_range(t5):
    with pytest.raises(ValueError):
        ResultSpec.expectation([{7: "Z"}]).validate_for(t5)
    with pytest.raises(ValueError):
        ResultSpec.noisy([depolarizing(6, 0.1)], [{0: "Z"}]).validate_for(t5)


# -- NoiseChannel -------------------------------------------------------------

def test_builtin_channels_trace_preserving():
    for ch in (depolarizing(0, 0.3), bit_flip(1, 0.2), phase_flip(0, 0.4),
               amplitude_damping(2, 0.5)):
        acc = sum(np.asarray(k).conj().T @ np.asarray(k) for k in ch.kraus)
        np.testing.assert_allclose(acc, np.eye(2), atol=1e-6)


def test_channel_kraus_counts():
    assert len(depolarizing(0, 0.1).kraus) == 4
    assert len(bit_flip(0, 0.1).kraus) == 2
    assert len(phase_flip(0, 0.1).kraus) == 2
    assert len(amplitude_damping(0, 0.1).kraus) == 2


def test_channel_structure_key_tracks_content():
    assert (depolarizing(0, 0.1).structure_key()
            == depolarizing(0, 0.1).structure_key())
    assert (depolarizing(0, 0.1).structure_key()
            != depolarizing(0, 0.2).structure_key())
    assert (depolarizing(0, 0.1).structure_key()
            != depolarizing(1, 0.1).structure_key())


def test_channel_rejects_bad_kraus():
    with pytest.raises(ValueError):
        NoiseChannel(qubits=(0,), kraus=(np.eye(4, dtype=np.complex64),))
    with pytest.raises(ValueError):
        NoiseChannel(qubits=(0,), kraus=())


# -- plan lowering ------------------------------------------------------------

def test_result_plan_items_terminal(t5):
    spec = ResultSpec.noisy([depolarizing(0, 0.1), bit_flip(3, 0.2)],
                            [{0: "Z"}], unravelings=2)
    plan = compile_plan(t5, backend="planar", target=CPU_TEST, result=spec)
    kinds = [it.kind for it in plan.items]
    assert kinds[-1] == "result" and kinds.count("result") == 1
    assert kinds[-3:-1] == ["channel", "channel"]
    assert plan.result is spec


def test_statevector_spec_normalizes_away(t5):
    plain = compile_plan(t5, backend="planar", target=CPU_TEST)
    sv = compile_plan(t5, backend="planar", target=CPU_TEST,
                      result=ResultSpec.statevector())
    assert sv.result is None
    assert [it.kind for it in sv.items] == [it.kind for it in plain.items]


def test_run_on_result_plan_covers_gate_prefix(t5, p5):
    plain = compile_plan(t5, backend="planar", target=CPU_TEST)
    shots = compile_plan(t5, backend="planar", target=CPU_TEST,
                         result=ResultSpec.sample(16, key=2))
    np.testing.assert_array_equal(np.asarray(shots.run(p5).to_dense()),
                                  np.asarray(plain.run(p5).to_dense()))


def test_executor_plan_key_cobatches_structural_twins(t5):
    ex = BatchExecutor(target=CPU_TEST, backend="planar", cache=PlanCache())
    k1 = ex.plan_key(t5, result=ResultSpec.sample(32, key=1))
    k2 = ex.plan_key(t5, result=ResultSpec.sample(32, key=2))
    k3 = ex.plan_key(t5, result=ResultSpec.sample(64, key=1))
    assert k1 == k2 and k1 != k3
    assert k1 != ex.plan_key(t5)                 # distinct from statevector


# -- shots mode ---------------------------------------------------------------

@pytest.mark.parametrize("backend", ["planar", "dense", "pallas"])
def test_shots_distribution_matches_dense_oracle(backend, t5, p5):
    sched = _make_sched(backend)
    req = sched.submit(t5, p5, result=ResultSpec.sample(4000, key=11))
    sched.drain()
    assert req.ok
    s = np.asarray(req.result)
    assert s.shape == (4000,) and s.dtype == np.int32
    probs = np.abs(_dense_state(t5, p5)) ** 2
    emp = np.bincount(s, minlength=1 << t5.n) / 4000
    assert np.abs(emp - probs).max() < 0.03


def test_shots_bitwise_across_batch_compositions(t5, p5):
    spec = ResultSpec.sample(64, key=42)
    solo = _make_sched()
    r_solo = solo.submit(t5, p5, result=spec)
    solo.drain()
    crowd = _make_sched()
    rng = np.random.default_rng(0)
    others = [crowd.submit(t5, rng.uniform(-1, 1, 2).astype(np.float32),
                           result=ResultSpec.sample(64, key=int(k)))
              for k in rng.integers(0, 2 ** 31, 5)]
    r_crowd = crowd.submit(t5, p5, result=spec)
    crowd.drain()
    assert all(o.ok for o in others) and r_crowd.ok
    np.testing.assert_array_equal(np.asarray(r_solo.result),
                                  np.asarray(r_crowd.result))


def test_shots_rerun_is_deterministic(t5, p5):
    spec = ResultSpec.sample(128, key=9)
    runs = []
    for _ in range(2):                          # fresh caches both times
        sched = _make_sched()
        r = sched.submit(t5, p5, result=spec)
        sched.drain()
        runs.append(np.asarray(r.result))
    np.testing.assert_array_equal(runs[0], runs[1])


def test_shots_differ_across_request_keys(t5, p5):
    sched = _make_sched()
    a = sched.submit(t5, p5, result=ResultSpec.sample(128, key=1))
    b = sched.submit(t5, p5, result=ResultSpec.sample(128, key=2))
    sched.drain()
    assert not np.array_equal(np.asarray(a.result), np.asarray(b.result))


def test_shots_through_ingest_server(t5, p5):
    srv = IngestServer(BatchExecutor(target=CPU_TEST, backend="planar",
                                     cache=PlanCache()), max_wait_ms=1.0)
    hs = [srv.submit(t5, p5, result=ResultSpec.sample(32, key=k))
          for k in (5, 5, 6)]
    vals = [np.asarray(h.result()) for h in hs]
    srv.close()
    np.testing.assert_array_equal(vals[0], vals[1])   # same key -> same shots
    assert not np.array_equal(vals[0], vals[2])
    assert srv.report()["mode_shots"] == 3


@settings(max_examples=8, deadline=None)
@given(key=st.integers(0, 2 ** 32 - 1), extras=st.integers(0, 4))
def test_shots_batch_invariance_property(key, extras):
    """Property (all modes' PRNG contract): shots depend only on
    (key, params), never on which co-batched neighbors pad the batch."""
    t = qaoa_template(4, 1)
    p = np.array([0.3, 0.9], np.float32)
    spec = ResultSpec.sample(16, key=key)
    base = _make_sched(max_batch=4)
    r0 = base.submit(t, p, result=spec)
    base.drain()
    mixed = _make_sched(max_batch=4)
    rng = np.random.default_rng(key & 0xFFFF)
    for _ in range(extras):
        mixed.submit(t, rng.uniform(-2, 2, 2).astype(np.float32),
                     result=ResultSpec.sample(16, key=int(rng.integers(
                         0, 2 ** 31))))
    r1 = mixed.submit(t, p, result=spec)
    mixed.drain()
    np.testing.assert_array_equal(np.asarray(r0.result),
                                  np.asarray(r1.result))


# -- expectation mode ---------------------------------------------------------

OBS = [{0: "Z"}, {2: "X"}, {1: "Y", 3: "Z"}, {0: "Z", 4: "Z"}]


@pytest.mark.parametrize("backend", ["planar", "dense", "pallas"])
def test_expectation_matches_dense_oracle(backend, t5, p5):
    sched = _make_sched(backend)
    req = sched.submit(t5, p5, result=ResultSpec.expectation(OBS))
    sched.drain()
    assert req.ok
    got = np.asarray(req.result)
    assert got.shape == (len(OBS),) and got.dtype == np.float32
    want = [_oracle_expectation(t5, p5, o) for o in OBS]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_expectation_response_never_holds_state(t5, p5):
    sched = _make_sched()
    req = sched.submit(t5, p5, result=ResultSpec.expectation([{0: "Z"}]))
    sched.drain()
    out = np.asarray(req.result)
    assert out.size == 1                     # one float, not 2**n amplitudes
    assert out.nbytes < (1 << t5.n)


def test_expectation_sweep_cobatches(t5):
    sched = _make_sched(max_batch=8)
    pm = np.linspace(-1, 1, 6 * t5.num_params).reshape(6, -1)
    reqs = sched.submit_sweep(t5, pm, result=ResultSpec.expectation([{0: "Z"}]))
    sched.drain()
    assert all(r.ok for r in reqs)
    assert sched.report()["batches"] == 1    # one co-batched dispatch
    for r, p in zip(reqs, pm):
        np.testing.assert_allclose(
            np.asarray(r.result), [_oracle_expectation(t5, p, {0: "Z"})],
            atol=2e-5)


@pytest.mark.parametrize("n", list(range(2, 11)))
def test_expectation_z_kernel_vs_ref_vs_dense(n):
    """Satellite: the Pallas streaming kernel == its planar reference ==
    dense numpy, across sizes spanning sub-lane to multi-row states.
    The lane-tiled layout needs n >= log2(lanes), so n=2 runs on a
    narrowed 4-lane variant of the test target."""
    import dataclasses
    from repro.core.statevec import random_state
    from repro.kernels.expectation import ops as E
    target = (CPU_TEST if n >= 3
              else dataclasses.replace(CPU_TEST, lanes=4))
    st_ = random_state(n, target, seed=100 + n)
    psi = np.asarray(st_.to_dense())
    for q in {0, n // 2, n - 1}:
        kern = float(E.expectation_z(st_.data, n, st_.v, q, interpret=True))
        ref = float(E.expectation_z_ref(st_.data, n, st_.v, q))
        signs = 1.0 - 2.0 * ((np.arange(1 << n) >> q) & 1)
        dense = float(np.sum((np.abs(psi) ** 2) * signs))
        assert abs(kern - ref) < 1e-5
        assert abs(kern - dense) < 1e-5


def _z_strings(n, v, seed):
    """Z-strings of every weight 1..n, plus lane-only, row-only and
    lane+row ones (qubits below ``v`` are lane qubits)."""
    rng = np.random.default_rng(seed)
    picks = [rng.choice(n, w, replace=False) for w in range(1, n + 1)]
    picks += [[0], range(min(v, n)), [0, n - 1]]
    if n > v:
        picks += [[v], range(v, n), [v - 1, n - 1]]
    return [{int(q): "Z" for q in qs} for qs in picks]


@pytest.mark.parametrize("n", [2, 3, 4, 7])
@pytest.mark.parametrize("backend", ["planar", "pallas", "dense"])
def test_z_string_pass_matches_oracle_and_per_term(backend, n):
    """The epilogue's shared Z-string pass == the dense oracle == the
    per-term apply-then-inner-product path, for strings on lane qubits,
    row qubits and both, down to states of a single row (n = 2 on a
    narrowed 4-lane target, n = 3 on the 8-lane one)."""
    import dataclasses
    target = (CPU_TEST if n >= 3
              else dataclasses.replace(CPU_TEST, lanes=4))
    t = hea_template(n, 1)
    params = np.random.default_rng(n).uniform(
        -np.pi, np.pi, t.num_params).astype(np.float32)
    spec = ResultSpec.expectation(_z_strings(n, target.lane_qubits, n))
    plan = compile_plan(t, backend=backend, target=target, result=spec)
    assert (plan.z_pass_terms, plan.fallback_terms) == (
        len(spec.observables), 0)
    got = np.asarray(plan.run_result(params))
    want = [_oracle_expectation(t, params, o) for o in spec.observables]
    np.testing.assert_allclose(got, want, atol=2e-5)
    state = plan.run(params)
    data = state.to_dense() if backend == "dense" else state.data
    per_term = [float(plan._observable_step(o)(data))
                for o in spec.observables]
    np.testing.assert_allclose(got, per_term, atol=2e-5)


MIXED_OBS = [{1: "X"}, {0: "Z"}, {0: "Z", 3: "Z"}, {2: "Y", 4: "Z"},
             {4: "Z"}, {1: "X", 2: "X"}, {1: "Z", 2: "Z", 3: "Z"}]


@pytest.mark.parametrize("mode", ["expectation", "noisy"])
@pytest.mark.parametrize("backend", ["planar", "pallas", "dense"])
def test_z_string_pass_mixed_spec_keeps_order(backend, mode, t5):
    """Z-strings between X/Y strings: every row of a vmapped sweep (and
    each trajectory row of a noisy request, zero-probability channels)
    returns the spec's order, equal to the oracle term by term."""
    spec = (ResultSpec.expectation(MIXED_OBS) if mode == "expectation"
            else ResultSpec.noisy([depolarizing(1, 0.0)], MIXED_OBS,
                                  unravelings=2, key=5))
    sched = _make_sched(backend)
    pm = np.linspace(-1, 1, 3 * t5.num_params).reshape(3, -1)
    reqs = sched.submit_sweep(t5, pm, result=spec)
    sched.drain()
    assert sched.report()["batches"] == 1
    for r, p in zip(reqs, pm):
        assert r.ok
        got = np.asarray(r.result)
        assert got.shape == (len(MIXED_OBS),) and got.dtype == np.float32
        np.testing.assert_allclose(
            got, [_oracle_expectation(t5, p, o) for o in MIXED_OBS],
            atol=2e-5)


def _state_reads(fn, shape):
    """The top-level primitives of ``fn``'s jaxpr that read its input,
    and every output shape the jaxpr produces."""
    jp = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(shape, jnp.float32))
    x = jp.jaxpr.invars[0]
    reads = [e.primitive.name for e in jp.jaxpr.eqns
             if any(v is x for v in e.invars)]
    shapes = [v.aval.shape for e in jp.jaxpr.eqns for v in e.outvars]
    return reads, shapes


def test_z_string_pass_reads_state_once():
    """An all-Z spec's epilogue reads the state in one primitive and takes
    no flat view of it; an X term adds its own reduction's reads only."""
    n = 8
    shape = (2, 1 << (n - CPU_TEST.lane_qubits), CPU_TEST.lanes)
    zs = [{i: "Z", (i + 1) % n: "Z"} for i in range(4)]
    x_term = {2: "X"}
    t = qaoa_template(n, 1)

    def epilogue(obs):
        spec = ResultSpec.expectation(obs)
        plan = compile_plan(t, backend="planar", target=CPU_TEST, result=spec)
        return plan, plan._epilogue_step(spec)

    _, epi = epilogue(zs)
    reads, shapes = _state_reads(lambda d: epi(d, None), shape)
    assert len(reads) == 1, reads
    assert not any((1 << n) in s for s in shapes), shapes
    plan, epi = epilogue(zs + [x_term])
    mixed, _ = _state_reads(lambda d: epi(d, None), shape)
    fallback, _ = _state_reads(
        plan._observable_step(((2, "X"),)), shape)
    assert len(mixed) == 1 + len(fallback)


def _qaoa_family():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "families" / "qaoa.py")
    mod_spec = importlib.util.spec_from_file_location("qaoa_family", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("case", ["qaoa20_family", "mixed"])
def test_z_pass_term_frac_counts_routes(case):
    """``z_pass_term_frac`` is the rows-weighted share of served
    observables that the shared pass took: all of the client cell's ring
    of ZZ terms, four of the mixed spec's seven."""
    from repro.engine import engine_registry
    if case == "mixed":
        t, obs, frac = qaoa_template(5, 1), MIXED_OBS, 4 / 7
    else:
        fam = _qaoa_family()
        cfg = {"n": 6, "p": 1}
        t = fam.program_template(cfg)
        obs, frac = fam.program_observables(cfg), 1.0
    sched = _make_sched()
    pm = np.zeros((3, t.num_params), np.float32)
    sched.submit_sweep(t, pm, result=ResultSpec.expectation(obs))
    sched.submit(t, pm[0])                   # statevector: no observables
    sched.drain()
    plan = sched.executor.plan_for(t, result=ResultSpec.expectation(obs))
    assert plan.z_pass_terms + plan.fallback_terms == len(obs)
    assert plan.z_pass_terms / len(obs) == pytest.approx(frac)
    act = sched.executor.activity
    assert act.summary()["z_pass_term_frac"] == pytest.approx(frac)
    # the statevector rows share the plan label and add no observable
    assert [a["z_pass_term_frac"] for a in act.per_plan().values()] == \
        pytest.approx([frac])
    snap = engine_registry(scheduler=sched).snapshot()
    assert snap["served_z_pass_term_frac"] == pytest.approx(frac)


def test_simulator_expectation_pauli_routes_pallas_kernel():
    from repro.core import circuits as C
    from repro.core.simulator import Simulator
    sim_k = Simulator(CPU_TEST, backend="pallas")
    sim_p = Simulator(CPU_TEST, backend="planar")
    stk = sim_k.run(C.ghz(6))
    stp = sim_p.run(C.ghz(6))
    for paulis in ({3: "Z"}, {0: "X"}, {1: "Z", 4: "Z"}):
        a = float(sim_k.expectation_pauli(stk, paulis))
        b = float(sim_p.expectation_pauli(stp, paulis))
        assert abs(a - b) < 1e-5


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_expectation_random_observables_property(data):
    n = 4
    t = hea_template(n, layers=1)
    rng_p = np.random.default_rng(data.draw(st.integers(0, 10 ** 6)))
    params = rng_p.uniform(-np.pi, np.pi, t.num_params).astype(np.float32)
    n_terms = data.draw(st.integers(1, n))
    qubits = data.draw(st.permutations(range(n)))[:n_terms]
    obs = {q: data.draw(st.sampled_from("XYZ")) for q in qubits}
    sched = _make_sched()
    req = sched.submit(t, params, result=ResultSpec.expectation([obs]))
    sched.drain()
    assert req.ok
    np.testing.assert_allclose(np.asarray(req.result),
                               [_oracle_expectation(t, params, obs)],
                               atol=3e-5)


# -- noisy mode ---------------------------------------------------------------

def test_noisy_zero_probability_equals_ideal(t5, p5):
    sched = _make_sched()
    spec = ResultSpec.noisy([depolarizing(0, 0.0), bit_flip(2, 0.0)],
                            [{0: "Z"}, {2: "X"}], unravelings=3, key=1)
    req = sched.submit(t5, p5, result=spec)
    sched.drain()
    want = [_oracle_expectation(t5, p5, o) for o in ({0: "Z"}, {2: "X"})]
    np.testing.assert_allclose(np.asarray(req.result), want, atol=1e-5)


def test_noisy_deterministic_channel_exact():
    # X|0> = |1>, then amplitude damping with gamma=1 resets to |0>: every
    # trajectory is identical, so the average is exact with 1 unraveling
    from repro.core import circuits as C
    from repro.engine import template_of
    t = template_of(C.Circuit(3, [G.x(1)]))
    sched = _make_sched()
    spec = ResultSpec.noisy([amplitude_damping(1, 1.0)], [{1: "Z"}],
                            unravelings=1, key=0)
    req = sched.submit(t, None, result=spec)
    sched.drain()
    np.testing.assert_allclose(np.asarray(req.result), [1.0], atol=1e-6)


def test_noisy_matches_density_matrix_oracle():
    t = qaoa_template(3, 1)
    params = np.array([0.5, 0.3], np.float32)
    channels = [depolarizing(0, 0.3), amplitude_damping(2, 0.4)]
    obs = [{0: "Z"}, {2: "Z"}]
    want = [_oracle_noisy_expectation(t, params, channels, o) for o in obs]
    sched = _make_sched(max_batch=256)
    spec = ResultSpec.noisy(channels, obs, unravelings=192, key=17)
    req = sched.submit(t, params, result=spec)
    sched.drain()
    assert req.ok
    got = np.asarray(req.result)
    assert got.shape == (2,)
    # 192 trajectories: standard error ~ 1/sqrt(192) ~ 0.07 per observable
    np.testing.assert_allclose(got, want, atol=0.25)


def test_noisy_bitwise_reproducible(t5, p5):
    spec = ResultSpec.noisy([depolarizing(1, 0.2)], [{1: "Z"}],
                            unravelings=4, key=23)
    vals = []
    for _ in range(2):
        sched = _make_sched()
        r = sched.submit(t5, p5, result=spec)
        sched.drain()
        vals.append(np.asarray(r.result))
    np.testing.assert_array_equal(vals[0], vals[1])


def test_noisy_row_expansion_and_padding(t5, p5):
    sched = _make_sched(max_batch=4)
    spec = ResultSpec.noisy([depolarizing(0, 0.1)], [{0: "Z"}],
                            unravelings=6, key=3)    # rows > max_batch
    req = sched.submit(t5, p5, result=spec)
    sched.drain()
    assert req.ok and np.asarray(req.result).shape == (1,)
    assert sched.report()["batches"] == 1            # expanded, not split


def test_reduce_result_rows_averages_segments():
    arr = np.array([[2.0], [4.0], [9.0], [7.0], [0.0]], np.float32)
    out = _reduce_result_rows(arr, [2, 2, 1])
    np.testing.assert_allclose(out[0], [3.0])
    np.testing.assert_allclose(out[1], [8.0])
    np.testing.assert_allclose(out[2], [0.0])
    single = _reduce_result_rows(np.array([[1, 2], [3, 4]], np.int32), [1, 1])
    np.testing.assert_array_equal(single[0], [1, 2])  # k=1 keeps dtype/values
    assert single[0].dtype == np.int32


@settings(max_examples=6, deadline=None)
@given(q=st.integers(0, 3), pauli=st.sampled_from("XZ"),
       seed=st.integers(0, 10 ** 6))
def test_noisy_identity_channel_property(q, pauli, seed):
    """Property: zero-probability channels are exactly the ideal circuit —
    the unraveling machinery must add no bias and no randomness."""
    t = hea_template(4, layers=1)
    rng = np.random.default_rng(seed)
    params = rng.uniform(-np.pi, np.pi, t.num_params).astype(np.float32)
    sched = _make_sched()
    spec = ResultSpec.noisy([depolarizing(q, 0.0)], [{q: pauli}],
                            unravelings=2, key=seed & 0xFFFFFFFF)
    req = sched.submit(t, params, result=spec)
    sched.drain()
    assert req.ok
    np.testing.assert_allclose(
        np.asarray(req.result),
        [_oracle_expectation(t, params, {q: pauli})], atol=3e-5)


# -- serving integration ------------------------------------------------------

def test_mixed_modes_group_into_separate_batches(t5, p5):
    sched = _make_sched()
    sv = sched.submit(t5, p5)
    sh = sched.submit(t5, p5, result=ResultSpec.sample(16, key=1))
    ex_ = sched.submit(t5, p5, result=ResultSpec.expectation([{0: "Z"}]))
    sched.drain()
    assert sv.ok and sh.ok and ex_.ok
    rep = sched.report()
    assert rep["batches"] == 3               # three distinct plan keys
    assert rep["mode_statevector"] == 1
    assert rep["mode_shots"] == 1
    assert rep["mode_expectation"] == 1
    assert hasattr(sv.result, "to_dense")    # statevector path unchanged


def test_same_mode_same_structure_requests_cobatch(t5):
    sched = _make_sched()
    rng = np.random.default_rng(3)
    reqs = [sched.submit(t5, rng.uniform(-1, 1, 2).astype(np.float32),
                         result=ResultSpec.sample(32, key=k))
            for k in (10, 20, 30, 40)]
    sched.drain()
    assert all(r.ok for r in reqs)
    assert sched.report()["batches"] == 1    # keys differ, plan key doesn't


def test_statevector_requests_unaffected_by_result_traffic(t5, p5):
    plain = _make_sched()
    a = plain.submit(t5, p5)
    plain.drain()
    mixed = _make_sched()
    mixed.submit(t5, p5, result=ResultSpec.sample(8, key=1))
    b = mixed.submit(t5, p5)
    mixed.drain()
    np.testing.assert_array_equal(np.asarray(a.result.to_dense()),
                                  np.asarray(b.result.to_dense()))


def test_submit_rejects_non_spec_result(t5, p5):
    sched = _make_sched()
    with pytest.raises(TypeError):
        sched.submit(t5, p5, result={"mode": "shots"})
    srv = IngestServer(BatchExecutor(target=CPU_TEST, backend="planar",
                                     cache=PlanCache()))
    try:
        with pytest.raises(TypeError):
            srv.submit(t5, p5, result="shots")
    finally:
        srv.close()


def test_submit_validates_spec_against_template(t5, p5):
    sched = _make_sched()
    with pytest.raises(ValueError):
        sched.submit(t5, p5, result=ResultSpec.expectation([{9: "Z"}]))
    assert sched.report()["requests"] == 0   # rejected before enqueue


def test_ingest_async_result_modes(t5, p5):
    import asyncio

    async def go():
        srv = IngestServer(BatchExecutor(target=CPU_TEST, backend="planar",
                                         cache=PlanCache()),
                           max_wait_ms=1.0)
        try:
            got = await srv.run_async(t5, p5,
                                      result=ResultSpec.sample(16, key=4))
            return np.asarray(got)
        finally:
            srv.close()

    out = asyncio.run(go())
    assert out.shape == (16,)


def test_telemetry_profile_skips_result_items(t5):
    from repro.engine import vectorization_profile
    plan = compile_plan(t5, backend="planar", target=CPU_TEST,
                        result=ResultSpec.noisy([depolarizing(0, 0.1)],
                                                [{0: "Z"}], unravelings=2))
    gates = t5.bind(np.zeros(t5.num_params, np.float32)).gates
    prof = vectorization_profile(plan, gates, CPU_TEST)
    assert prof.flops_per_amp_generic > 0    # gate work still profiled
    assert 0.0 <= prof.fast_amp_frac <= 1.0  # result epilogue excluded
