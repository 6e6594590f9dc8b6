"""Measurement: computational-basis sampling + Pauli-string observables.

Production simulators expose both (Qsim's ``sample`` and
``ExpectationValue``); the paper's §IV streams the expectation reduction
instead of storing states back — our Pallas expectation kernel does the
same for single-qubit Z.  This module generalizes:

* ``sample(state, n_samples, key)`` — inverse-CDF sampling over |amp|^2
  (vectorized searchsorted; exact, no Gumbel approximation).
* ``expectation_pauli(state, {qubit: 'X'|'Y'|'Z'})`` — <P> for a Pauli
  string, computed as <psi| P |psi> with P applied through the planar
  gate-apply path (no densification).
* ``marginal_probs(state, qubits)`` — marginal distribution over a subset.
"""
from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import apply as A
from repro.core import gates as G
from repro.core.statevec import State

_PAULI = {"X": G.X_M, "Y": G.Y_M, "Z": G.Z_M}


def probabilities(state: State) -> jax.Array:
    d = state.data.reshape(2, -1)
    return d[0] * d[0] + d[1] * d[1]


def sample_probs(probs: jax.Array, n_samples: int,
                 key: jax.Array) -> jax.Array:
    """Inverse-CDF sampling from a probability vector (int32 [n_samples]).

    Hardened against the two float edges of searchsorted sampling: the
    CDF is renormalized with a tiny-denominator guard (an unnormalized
    or near-zero-mass vector never divides by ~0), and the drawn index
    is clamped to the last basis state (a draw landing past ``cdf[-1]``
    through float round-off can never index out of range).
    """
    cdf = jnp.cumsum(probs)
    cdf = cdf / jnp.maximum(cdf[-1], jnp.finfo(cdf.dtype).tiny)
    u = jax.random.uniform(key, (n_samples,))
    idx = jnp.searchsorted(cdf, u)
    return jnp.minimum(idx, probs.shape[0] - 1).astype(jnp.int32)


def sample(state: State, n_samples: int, key: jax.Array) -> jax.Array:
    """Draw basis-state indices ~ |amp|^2 (int32 [n_samples])."""
    return sample_probs(probabilities(state), n_samples, key)


def expectation_pauli(state: State, paulis: Mapping[int, str]) -> jax.Array:
    """<psi| prod_q P_q |psi> for P in {X, Y, Z} (real for Hermitian P)."""
    data = state.data
    pd = data
    for q, p in sorted(paulis.items()):
        m = _PAULI[p.upper()]
        ur = jnp.asarray(m.real, jnp.float32)
        ui = jnp.asarray(m.imag, jnp.float32)
        pd = A.apply_gate_planar(pd, state.n, (q,), ur, ui)
    # Re <psi|phi> = sum(re*re' + im*im')
    a = data.reshape(2, -1)
    b = pd.reshape(2, -1)
    return jnp.sum(a[0] * b[0] + a[1] * b[1])


def marginal_probs(state: State, qubits: Sequence[int]) -> jax.Array:
    """Marginal distribution over ``qubits`` (little-endian order)."""
    dims, axis = A.span_view(state.n, qubits)
    probs = probabilities(state).reshape(dims)
    kept = {axis[q] for q in qubits}
    axes = tuple(i for i in range(len(dims)) if i not in kept)
    marg = jnp.sum(probs, axis=axes) if axes else probs
    # remaining axes are qubits sorted descending; reorder to `qubits`
    remaining = sorted(qubits, reverse=True)
    perm = [remaining.index(q) for q in qubits]
    marg = jnp.transpose(marg, perm) if perm != list(range(len(perm))) \
        else marg
    return marg.reshape(-1) if len(qubits) == 1 else marg


def bitstring_counts(samples: np.ndarray, n: int,
                     top: int = 8) -> list[tuple[str, int]]:
    """Human-readable histogram of sampled basis states."""
    vals, counts = np.unique(np.asarray(samples), return_counts=True)
    order = np.argsort(-counts)[:top]
    return [(format(int(vals[i]), f"0{n}b"), int(counts[i])) for i in order]
