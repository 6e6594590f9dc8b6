"""Dense-vs-planar gate application equivalence (oracle tests)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import apply as A
from repro.core import gates as G
from repro.core import statevec as SV
from repro.core.target import CPU_TEST


def _apply_both(n, qubits, controls, seed):
    rng = np.random.default_rng(seed)
    u = G.random_unitary(1 << len(qubits), rng)
    st_ = SV.random_state(n, CPU_TEST, seed=seed)
    psi = st_.to_dense()
    dense = A.apply_gate_dense(psi, n, tuple(qubits), jnp.asarray(u),
                               tuple(controls))
    ur, ui = (jnp.asarray(u.real, jnp.float32),
              jnp.asarray(u.imag, jnp.float32))
    planar = A.apply_gate_planar(st_.data, n, tuple(qubits), ur, ui,
                                 tuple(controls))
    out = SV.State(planar, n, st_.v).to_dense()
    return np.asarray(dense), np.asarray(out)


@pytest.mark.parametrize("n,qubits,controls", [
    (5, (0,), ()),
    (5, (4,), ()),
    (6, (2, 4), ()),
    (6, (5, 0), ()),
    (7, (1, 3, 6), ()),
    (6, (3,), (5,)),
    (6, (0,), (4, 2)),
    (7, (2, 6), (0,)),
    # lane bits exchanged with a free row block (3 lane bits of the 8-lane
    # test target), and the matmul path for k >= 4
    (14, (0, 13), ()),
    (16, (2,), (15,)),
    (16, (0, 5, 9, 14), ()),
    (16, (1, 3, 8, 10), (6,)),
])
def test_dense_vs_planar(n, qubits, controls):
    d, p = _apply_both(n, qubits, controls, seed=42)
    np.testing.assert_allclose(d, p, atol=2e-6)


def _numpy_oracle(psi, n, qubits, u, controls):
    """The gate with its controls folded into one matrix, applied to the
    rank-``n`` numpy tensor (axis ``n - 1 - q`` holds qubit ``q``)."""
    full, cu = G.controlled_to_full(G.Gate(tuple(qubits), u, tuple(controls)))
    k = len(full)
    axes = [n - 1 - q for q in reversed(full)]   # MSB of cu's index first
    t = np.moveaxis(np.asarray(psi).reshape((2,) * n), axes, range(k))
    rest = t.shape[k:]
    t = (cu @ t.reshape(1 << k, -1)).reshape((2,) * k + rest)
    return np.moveaxis(t, range(k), axes).reshape(-1)


@pytest.mark.parametrize("backend", ["dense", "planar", "pallas"])
@pytest.mark.parametrize("n,qubits,controls", [
    (14, (0,), ()),
    (14, (2, 13), ()),
    (15, (1,), (3,)),
    (14, (0, 4, 9), (12,)),
    (15, (0, 2, 6, 11), ()),
])
def test_lane_swap_vs_numpy(backend, n, qubits, controls):
    """Gates on lane and sublane bits at n >= 14, where the planar and
    Pallas paths exchange those bits with a free block of row bits, checked
    against a numpy oracle that shares no code with either."""
    from repro.kernels.apply_gate import apply_fused_gate
    rng = np.random.default_rng(len(qubits) + n)
    u = G.random_unitary(1 << len(qubits), rng).astype(np.complex64)
    st_ = SV.random_state(n, CPU_TEST, seed=n)
    want = _numpy_oracle(np.asarray(st_.to_dense()), n, qubits, u, controls)
    if backend == "dense":
        got = A.apply_gate_dense(st_.to_dense(), n, qubits, jnp.asarray(u),
                                 controls)
    else:
        fn = A.apply_gate_planar if backend == "planar" else (
            lambda *a: apply_fused_gate(*a[:2], st_.v, *a[2:]))
        ur, ui = (jnp.asarray(u.real, jnp.float32),
                  jnp.asarray(u.imag, jnp.float32))
        got = SV.State(fn(st_.data, n, qubits, ur, ui, controls), n,
                       st_.v).to_dense()
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-6)


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_dense_vs_planar_property(data):
    n = data.draw(st.integers(4, 8))
    k = data.draw(st.integers(1, min(3, n - 1)))
    qubits = tuple(data.draw(
        st.permutations(range(n)).map(lambda p: p[:k])))
    rest = [q for q in range(n) if q not in qubits]
    nc = data.draw(st.integers(0, min(2, len(rest))))
    controls = tuple(rest[:nc])
    seed = data.draw(st.integers(0, 10_000))
    d, p = _apply_both(n, qubits, controls, seed)
    np.testing.assert_allclose(d, p, atol=3e-6)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(4, 8), q=st.integers(0, 7), seed=st.integers(0, 999))
def test_norm_preserved(n, q, seed):
    if q >= n:
        return
    rng = np.random.default_rng(seed)
    u = G.random_unitary(2, rng)
    st_ = SV.random_state(n, CPU_TEST, seed=seed)
    ur, ui = (jnp.asarray(u.real, jnp.float32),
              jnp.asarray(u.imag, jnp.float32))
    out = A.apply_gate_planar(st_.data, n, (q,), ur, ui)
    norm = float(jnp.sum(out.astype(jnp.float64) ** 2))
    assert abs(norm - 1.0) < 1e-5


def test_split_row_lane():
    lane, row = A.split_row_lane((0, 3, 5, 7), v=4)
    assert lane == [0, 3] and row == [5, 7]
