"""Public wrapper + pure-jnp reference for the expectation kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.target import resolve_interpret
from repro.kernels.expectation.expectation import expectation_z_kernel


def expectation_z(data: jax.Array, n: int, v: int, qubit: int,
                  interpret: bool | None = None) -> jax.Array:
    """<Z_qubit> of the lane-tiled planar state ``f32[2, R, 2**v]``."""
    return expectation_z_kernel(data.reshape(2, 1 << (n - v), 1 << v), qubit,
                                interpret=resolve_interpret(interpret))


def expectation_z_ref(data: jax.Array, n: int, v: int, qubit: int) -> jax.Array:
    """Oracle: dense reduction with the qubit axis exposed by reshape."""
    p = data.reshape(2, 1 << n)
    probs = p[0] * p[0] + p[1] * p[1]
    probs = probs.reshape(1 << (n - qubit - 1), 2, 1 << qubit)
    return jnp.sum(probs[:, 0, :]) - jnp.sum(probs[:, 1, :])
