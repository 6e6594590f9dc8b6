"""Pallas kernel sweeps vs the pure-jnp oracle (interpret mode).

Per instructions: for each kernel, sweep shapes/qubit positions/controls
and assert_allclose against ref.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import circuits as C
from repro.core import gates as G
from repro.core import statevec as SV
from repro.core.simulator import Simulator
from repro.core.target import CPU_TEST
from repro.kernels.apply_gate import apply_fused_gate, apply_fused_gate_ref
from repro.kernels.apply_gate.apply_gate import make_plan
from repro.kernels.expectation import expectation_z, expectation_z_ref


def _run_both(n, qubits, controls=(), seed=0, lanes=8,
              max_block_bytes=1 << 20):
    tgt = dataclasses.replace(CPU_TEST, lanes=lanes)
    rng = np.random.default_rng(seed)
    st_ = SV.random_state(n, tgt, seed=seed)
    u = G.random_unitary(1 << len(qubits), rng)
    ur = jnp.asarray(u.real, jnp.float32)
    ui = jnp.asarray(u.imag, jnp.float32)
    out = apply_fused_gate(st_.data, n, st_.v, tuple(qubits), ur, ui,
                           controls=tuple(controls),
                           max_block_bytes=max_block_bytes)
    ref = apply_fused_gate_ref(st_.data, n, st_.v, tuple(qubits), ur, ui,
                               controls=tuple(controls))
    return np.asarray(out), np.asarray(ref)


# -- shape/position sweep ----------------------------------------------------

@pytest.mark.parametrize("n", [5, 8, 11])
@pytest.mark.parametrize("qubits", [(0,), (2,), (4,)])
def test_single_qubit_positions(n, qubits):
    if max(qubits) >= n:
        pytest.skip("qubit out of range")
    out, ref = _run_both(n, qubits)
    np.testing.assert_allclose(out, ref, atol=3e-6)


@pytest.mark.parametrize("qubits", [
    (0, 1), (0, 7), (3, 6), (6, 7),
    (1, 4, 6), (0, 2, 5, 7), (2, 3, 4, 5, 6),
])
def test_multi_qubit_sets(qubits):
    out, ref = _run_both(8, qubits, seed=7)
    np.testing.assert_allclose(out, ref, atol=3e-6)


@pytest.mark.parametrize("lanes", [8, 16, 32, 64, 128])
def test_vla_lane_width_sweep(lanes):
    """Single kernel source, many vector widths — the VLA claim."""
    n = 9
    out, ref = _run_both(n, (1, 5), seed=3, lanes=lanes)
    np.testing.assert_allclose(out, ref, atol=3e-6)


@pytest.mark.parametrize("blk", [1 << 12, 1 << 16, 1 << 20])
def test_block_size_sweep(blk):
    out, ref = _run_both(10, (4, 8), seed=5, max_block_bytes=blk)
    np.testing.assert_allclose(out, ref, atol=3e-6)


@pytest.mark.parametrize("controls", [(5,), (5, 6), (0,), (0, 7)])
def test_controlled(controls):
    qubits = (2,) if 2 not in controls else (3,)
    out, ref = _run_both(8, qubits, controls=controls, seed=11)
    np.testing.assert_allclose(out, ref, atol=3e-6)


@pytest.mark.parametrize("qubits,controls", [
    ((0,), ()), ((4, 9), ()), ((1,), (4,)), ((0, 1, 2, 3, 4), ()),
    ((2, 7, 11), (5,))])
def test_tile_bits_moved_out(qubits, controls):
    """n = 14 leaves free blocks for the lane and sublane bits of the
    8-lane target, so the kernel sees whole (8, 8) tiles."""
    out, ref = _run_both(14, qubits, controls=controls, seed=17)
    np.testing.assert_allclose(out, ref, atol=3e-6)


@pytest.mark.parametrize("qubits,perm", [
    ((1, 4, 8, 12), None), ((0, 2, 3, 5), None), ((9, 13), None),
    ((1, 4, 10), np.array([3, 2, 1, 0, 7, 6, 5, 4]))])
def test_phase_gate_tiles(qubits, perm):
    """Diagonal clusters with bits in the lane, sublane and row ranges,
    and a permutation cluster, against the dense-matrix oracle."""
    from repro.kernels.apply_gate.ops import apply_phase_gate
    from repro.kernels.apply_gate.ref import apply_phase_gate_ref
    n = 14
    st_ = SV.random_state(n, CPU_TEST, seed=3)
    ang = np.random.default_rng(4).uniform(0, 2 * np.pi, 1 << len(qubits))
    pr = jnp.asarray(np.cos(ang), jnp.float32)
    pi = jnp.asarray(np.sin(ang), jnp.float32)
    out = apply_phase_gate(st_.data, n, st_.v, qubits, pr, pi, perm=perm)
    ref = apply_phase_gate_ref(st_.data, n, st_.v, qubits, pr, pi, perm=perm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def test_unsorted_qubits_matrix_permutation():
    """qubits=(5, 1) must equal qubits=(1, 5) with permuted U."""
    out, ref = _run_both(7, (5, 1), seed=13)
    np.testing.assert_allclose(out, ref, atol=3e-6)


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_kernel_property(data):
    n = data.draw(st.integers(4, 9))
    k = data.draw(st.integers(1, min(3, n)))
    perm = data.draw(st.permutations(range(n)))
    qubits = tuple(perm[:k])
    nc = data.draw(st.integers(0, min(2, n - k)))
    controls = tuple(perm[k:k + nc])
    seed = data.draw(st.integers(0, 9999))
    out, ref = _run_both(n, qubits, controls, seed)
    np.testing.assert_allclose(out, ref, atol=5e-6)


# -- plan construction -------------------------------------------------------

def test_plan_shapes():
    plan = make_plan(10, (4, 7), (9,))
    assert np.prod(plan.dims) == 1 << 10
    assert plan.k == 2
    # gate axes full in block, others 1 (except tail)
    for d, r, b in zip(plan.dims, plan.roles, plan.block):
        if r == "gate":
            assert b == 2
        elif r != "tail":
            assert b == 1


def test_plan_tail_split_respects_budget():
    plan = make_plan(20, (19,), (), max_block_bytes=1 << 16)
    blk_bytes = 2 * 4 * np.prod(plan.block)
    assert blk_bytes <= 2 * (1 << 16)


# -- expectation kernel -------------------------------------------------------

@pytest.mark.parametrize("n,q", [(6, 0), (6, 3), (6, 5), (9, 4), (14, 1),
                                 (14, 4), (14, 12)])
def test_expectation_z(n, q):
    st_ = SV.random_state(n, CPU_TEST, seed=q)
    k = float(expectation_z(st_.data, n, st_.v, q))
    r = float(expectation_z_ref(st_.data, n, st_.v, q))
    assert abs(k - r) < 1e-5


def test_expectation_basis_states():
    # |0...0>: <Z_q> = +1 for all q
    st_ = SV.zero_state(7, CPU_TEST)
    for q in range(7):
        assert abs(float(expectation_z(st_.data, 7, st_.v, q)) - 1.0) < 1e-6


# -- end-to-end through the simulator -----------------------------------------

@pytest.mark.parametrize("name,n", [("ghz", 8), ("qft", 7), ("qv", 6)])
def test_pallas_backend_full_circuit(name, n):
    circ = C.build(name, n)
    pal = Simulator(CPU_TEST, backend="pallas", f=3).run(circ)
    ref = Simulator(CPU_TEST, backend="dense").run(circ)
    np.testing.assert_allclose(np.asarray(pal.to_dense()),
                               np.asarray(ref.to_dense()), atol=5e-6)
