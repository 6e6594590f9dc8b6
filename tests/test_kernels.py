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


def _phases(w, seed=4):
    ang = np.random.default_rng(seed).uniform(0, 2 * np.pi, (*w,))
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


# n = 14 on the 8-lane target: the (8, 8) tile holds bits 0-5; a 4 KiB block
# (512 amplitudes) cuts at bit 9, so the grid has 32 steps
@pytest.mark.parametrize("qubits,perm,blk", [
    pytest.param((1, 4, 8, 12), None, 1 << 20, id="qubits0-None"),
    pytest.param((0, 2, 3, 5), None, 1 << 20, id="qubits1-None"),
    pytest.param((9, 13), None, 1 << 20, id="qubits2-None"),
    pytest.param((1, 4, 10), np.array([3, 2, 1, 0, 7, 6, 5, 4]), 1 << 20,
                 id="qubits3-perm3"),
    pytest.param((1, 4, 7, 11, 12), None, 1 << 12, id="lane-sublane-row-outer"),
    pytest.param((2, 6, 8), None, 1 << 12, id="inside-block"),
    pytest.param((9, 11, 12), None, 1 << 12, id="above-cut"),
    # the qrc28 grid circuit's width-21 diagonal scaled to n = 14: lanes
    # full, sublanes in part, runs of row bits on both sides of the cut
    pytest.param((0, 1, 2, 3, 5, 6, 7, 9, 10, 12, 13), None, 1 << 13,
                 id="qrc28-item4-scaled")])
def test_phase_gate_tiles(qubits, perm, blk):
    """Diagonal clusters with bits in the lane, sublane and row ranges, on
    either side of the phase kernel's block cut, and a permutation cluster,
    against the dense-matrix oracle."""
    from repro.kernels.apply_gate.ops import apply_phase_gate
    from repro.kernels.apply_gate.ref import apply_phase_gate_ref
    n = 14
    st_ = SV.random_state(n, CPU_TEST, seed=3)
    pr, pi = _phases((1 << len(qubits),))
    out = apply_phase_gate(st_.data, n, st_.v, qubits, pr, pi, perm=perm,
                           max_block_bytes=blk)
    ref = apply_phase_gate_ref(st_.data, n, st_.v, qubits, pr, pi, perm=perm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-6)


def test_phase_gate_under_vmap():
    """A batch of states, each with its own phases, through a multi-step
    phase grid (the batched plan program vmaps every step)."""
    from repro.kernels.apply_gate.ops import apply_phase_gate
    from repro.kernels.apply_gate.ref import apply_phase_gate_ref
    n, qubits, batch = 12, (1, 5, 7, 10), 3
    data = jnp.stack([SV.random_state(n, CPU_TEST, seed=s).data
                      for s in range(batch)])
    pr, pi = _phases((batch, 1 << len(qubits)), seed=9)
    out = jax.vmap(lambda d, a, b: apply_phase_gate(
        d, n, 3, qubits, a, b, max_block_bytes=1 << 11))(data, pr, pi)
    for r in range(batch):
        ref = apply_phase_gate_ref(data[r], n, 3, qubits, pr[r], pi[r])
        np.testing.assert_allclose(np.asarray(out[r]), np.asarray(ref),
                                   atol=3e-6)


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


# the qrc28 grid circuit's diagonal clusters at n = 28 (TPU tile: bits 0-9),
# their bits above the tile; 1 MiB blocks cut at bit 17
QRC28_HI = [
    (10, 11, 13, 14, 15, 16, 17, 19, 20, 22, 24, 25, 26),
    (10, 11, 12, 21, 22),
    (12, 13, 15, 17, 18, 19, 22, 23, 26, 27),
    (11, 12, 13, 14, 16, 17, 18, 20, 23),
    (10, 12, 14, 18, 20, 21, 25, 27),
    (10,), (17, 18, 27), (10, 11, 12, 13, 14, 15, 16)]


@pytest.mark.parametrize("hi", QRC28_HI)
def test_phase_plan_streams_whole_blocks(hi):
    """Whatever the cluster, the phase grid has 2**(n - cut) steps that
    visit every block once, and the table block changes only with the
    cluster bits at or above the cut: at most 2**(their number) times."""
    from repro.kernels.apply_gate.apply_gate import phase_plan
    n = 28
    plan = phase_plan(n, hi, tile_bits=10, max_block_bytes=1 << 20)
    assert plan.cut == 17 and plan.steps == 1 << (n - 17)
    blocks = [plan.state_block(g) for g in range(plan.steps)]
    assert sorted(blocks) == list(range(plan.steps))
    tables = [plan.table_block(g) for g in range(plan.steps)]
    changes = sum(a != b for a, b in zip(tables, tables[1:]))
    outer = [b for b in hi if b >= 17]
    assert changes + 1 <= 1 << len(outer)
    # each step's table block is the pattern of its block's outer bits
    for blk, tab in zip(blocks, tables):
        assert tab == sum(((blk >> (b - 17)) & 1) << j
                          for j, b in enumerate(outer))
    # the in-block axes cover the row groups above the tile
    assert np.prod([s for s, _ in plan.groups]) == 1 << (17 - 10)
    assert np.prod([s for s, c in plan.groups if c]) == 1 << len(
        [b for b in hi if b < 17])


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

def test_pallas_batch_with_diagonal_items_matches_planar():
    """The batched (vmapped) Pallas program, whose QAOA cost layers run
    through the phase kernel, against the planar backend."""
    from repro.engine import BatchExecutor, PlanCache, qaoa_template
    t = qaoa_template(10, 2)
    pm = np.random.default_rng(21).uniform(
        -np.pi, np.pi, (3, t.num_params)).astype(np.float32)
    pal = BatchExecutor(target=CPU_TEST, backend="pallas", cache=PlanCache())
    assert any(it.kind == "diag" for it in pal.plan_for(t).items)
    ref = BatchExecutor(target=CPU_TEST, backend="planar", cache=PlanCache())
    for a, b in zip(pal.run_batch(t, pm), ref.run_batch(t, pm)):
        np.testing.assert_allclose(np.asarray(a.to_dense()),
                                   np.asarray(b.to_dense()), atol=5e-6)


@pytest.mark.parametrize("name,n", [("ghz", 8), ("qft", 7), ("qv", 6)])
def test_pallas_backend_full_circuit(name, n):
    circ = C.build(name, n)
    pal = Simulator(CPU_TEST, backend="pallas", f=3).run(circ)
    ref = Simulator(CPU_TEST, backend="dense").run(circ)
    np.testing.assert_allclose(np.asarray(pal.to_dense()),
                               np.asarray(ref.to_dense()), atol=5e-6)
