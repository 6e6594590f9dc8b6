"""Pallas reduction kernel for <Z_q> — the paper's ExpectationValue ROI.

Streams the state once, accumulating sum((-1)^{bit_q(x)} |amp_x|^2) without
storing any state back (paper §IV: "sum up the magnitude ... instead of
storing final states back to memory").  The state is read in its own
``(2, R, V)`` layout, block by block of whole vector tiles; the sign comes
from the lane or row index of each amplitude, and partial sums accumulate in
a lane-dense ``(8, V)`` output block that stays resident across the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import scopes
from repro.kernels.apply_gate.apply_gate import SUBLANES


def _kernel(x_ref, o_ref, *, qubit: int, v: int, rows_blk: int,
            acc_rows: int):
    g = pl.program_id(0)
    x = x_ref[...]
    p = x[0] * x[0] + x[1] * x[1]                      # (rows_blk, V)
    if qubit < v:
        idx = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1)
        bit = (idx >> qubit) & 1
    else:
        idx = g * rows_blk + jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        bit = (idx >> (qubit - v)) & 1
    z = jnp.where(bit == 1, -p, p)
    part = z.reshape(rows_blk // acc_rows, acc_rows, p.shape[1]).sum(axis=0)

    @pl.when(g == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += part


def expectation_z_kernel(data: jax.Array, qubit: int, interpret: bool,
                         max_block_bytes: int = 1 << 20) -> jax.Array:
    """<Z_qubit> of the planar state ``f32[2, R, V]``."""
    _, rows, lanes = data.shape
    v = lanes.bit_length() - 1
    acc_rows = min(rows, SUBLANES)
    budget = max(acc_rows, max_block_bytes // (2 * 4 * lanes))
    rows_blk = min(rows, 1 << (budget.bit_length() - 1))
    out = pl.pallas_call(
        functools.partial(_kernel, qubit=qubit, v=v, rows_blk=rows_blk,
                          acc_rows=acc_rows),
        grid=(rows // rows_blk,),
        in_specs=[pl.BlockSpec((2, rows_blk, lanes), lambda g: (0, g, 0))],
        out_specs=pl.BlockSpec((acc_rows, lanes), lambda g: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((acc_rows, lanes), jnp.float32),
        interpret=interpret,
        name="expectation_z",
        metadata=scopes.kernel_metadata(),
    )(data)
    return jnp.sum(out)
