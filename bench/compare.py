"""Comparisons that decide ``correct``.

Sums run on the device in float32 blocks of at most 2**14 amplitudes and
finish on the host in float64: one float32 sum over 2**28 amplitudes
could alone be off by about 1e-5.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 14


LANES = 128


@functools.partial(jax.jit, static_argnames=("rows",))
def _err_blocks(data, re, im, *, rows):
    # blocks of (rows, 128) amplitudes: the minor axis stays a whole lane
    # tile, so no view of the 2 GiB state needs a relayout
    a = data.reshape(2, -1, rows, LANES)
    r = re.reshape(-1, rows, LANES)
    i = im.reshape(-1, rows, LANES)
    d0, d1 = a[0] - r, a[1] - i
    return (jnp.sum(d0 * d0 + d1 * d1, axis=(-2, -1)),
            jnp.sum(r * r + i * i, axis=(-2, -1)))


def state_error(data, re, im) -> float:
    """||psi - ref|| / ||ref|| for a planar state ``data`` (real and
    imaginary planes first, any tiling of the flat amplitude index) against
    the reference planes ``(re, im)``."""
    rows = min(re.size, BLOCK) // LANES
    err, norm = _err_blocks(data, re, im, rows=rows)
    err = float(np.sum(np.asarray(err, np.float64)))
    norm = float(np.sum(np.asarray(norm, np.float64)))
    return float(np.sqrt(err / norm))


def widest_gap(got, want) -> float:
    """Largest absolute difference over every value compared."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shapes differ: {got.shape} vs {want.shape}")
    return float(np.max(np.abs(got - want)))


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    k = max(1, int(np.ceil(q / 100.0 * len(v))))
    return float(v[k - 1])
