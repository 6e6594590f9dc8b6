"""Pure-jnp oracle for the fused-gate kernel.

Deliberately takes a different code path from the kernel: the planar state is
converted to the dense complex vector, the gate is applied with the flat-vector
reference (``core.apply.apply_gate_dense``: shifted partners selected by index
bits, no views and no bit exchanges), and the result converted back — so a bug
in the planar index math or the tile-bit exchanges cannot cancel out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.apply import apply_gate_dense


def apply_fused_gate_ref(data: jax.Array, n: int, v: int,
                         qubits: tuple[int, ...], u_re: jax.Array,
                         u_im: jax.Array,
                         controls: tuple[int, ...] = ()) -> jax.Array:
    flat = data.reshape(2, 1 << n)
    psi = flat[0].astype(jnp.complex64) + 1j * flat[1].astype(jnp.complex64)
    u = u_re.astype(jnp.complex64) + 1j * u_im.astype(jnp.complex64)
    psi = apply_gate_dense(psi, n, tuple(qubits), u, tuple(controls))
    out = jnp.stack([jnp.real(psi), jnp.imag(psi)]).astype(jnp.float32)
    return out.reshape(data.shape)


def apply_phase_gate_ref(data: jax.Array, n: int, v: int,
                         qubits: tuple[int, ...], p_re, p_im,
                         perm=None) -> jax.Array:
    """Oracle for the diag/perm kernel, on the flat complex vector — a
    deliberately different code path (no tiles, no phase tables).  A
    diagonal multiplies amplitude ``x`` by the phase its cluster bits
    select, so clusters of any width stay cheap; a permutation
    materializes the monomial unitary densely and routes it through
    ``apply_gate_dense``."""
    import numpy as np
    w = len(qubits)
    dim = 1 << w
    if p_re is None:
        phase = np.ones(dim, np.complex64)
    else:
        phase = (np.asarray(p_re) + 1j * np.asarray(p_im)).astype(np.complex64)
    if perm is None:
        x = np.arange(1 << n)
        sel = sum(((x >> q) & 1) << m for m, q in enumerate(qubits))
        flat = np.asarray(data, np.float32).reshape(2, 1 << n)
        psi = (flat[0] + 1j * flat[1]).astype(np.complex64) * phase[sel]
        out = np.stack([psi.real, psi.imag]).astype(np.float32)
        return jnp.asarray(out.reshape(data.shape))
    src = np.asarray(perm)
    u = np.zeros((dim, dim), np.complex64)
    u[np.arange(dim), src] = phase
    return apply_fused_gate_ref(data, n, v, tuple(qubits),
                                jnp.asarray(u.real, jnp.float32),
                                jnp.asarray(u.imag, jnp.float32))
