"""Plain state-vector reference for the benchmark's correctness check.

It imports nothing of the program under test and takes nothing it made:
circuits arrive as the benchmark's own gate lists (:mod:`bench.circuits`)
and every gate matrix is written here from its textbook definition.

A state is a pair of float32 planes ``(re, im)`` of shape
``batch + (2**n // 128, 128)``; amplitude ``x`` sits at row ``x // 128``,
lane ``x % 128``, and qubit ``q`` is bit ``q`` of ``x``.  A one-qubit gate
is applied three ways, so that no view has a minor axis narrower than a
whole (8, 128) tile: on a lane qubit (``q < 7``) as a product with the
128 x 128 matrix the gate induces on the lane axis; on a sublane qubit
(``7 <= q < 10``) as a product with the 8 x 8 matrix it induces on each
tile's rows; above that by combining the two halves of a
``(..., 2, s, 128)`` view elementwise.

``precision="highest"`` computes in float32 (products at
``Precision.HIGHEST``).  ``precision="high"`` is the control: every
product is taken as three bfloat16 passes (hi*hi + hi*lo + lo*hi), which
is what a float32 product at ``Precision.HIGH`` does on a TPU; the split
into bfloat16 parts rounds with integer operations, which no compiler
flag may skip.  The check must find the control wrong.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LANE_BITS = 7
LANES = 1 << LANE_BITS
TILE_ROW_BITS = 3
PRECISIONS = ("highest", "high")
HIGHEST = jax.lax.Precision.HIGHEST


def _bf16_round(a):
    """``a`` rounded to the nearest bfloat16 (ties to even), as float32."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(a):
    hi = _bf16_round(a)
    return hi, _bf16_round(a - hi)


def _three_pass(op, a, b):
    """``op(a, b)`` for a bilinear ``op`` as three bfloat16 passes."""
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    return op(a_hi, b_hi) + (op(a_hi, b_lo) + op(a_lo, b_hi))


def _bilinear(op, precision):
    if precision == "highest":
        return op
    return functools.partial(_three_pass, op)


def _cmul(ar, ai, br, bi, op):
    """(ar + i ai) op (br + i bi) in planes, for a bilinear ``op``."""
    return op(ar, br) - op(ai, bi), op(ar, bi) + op(ai, br)


def _mul(a, b):
    return a * b


def _bit(shape, q):
    """int32 plane (last two axes of ``shape``) holding bit ``q`` of x."""
    if q < LANE_BITS:
        idx = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 1)
        return (idx >> q) & 1
    idx = jax.lax.broadcasted_iota(jnp.int32, shape[-2:], 0)
    return (idx >> (q - LANE_BITS)) & 1


def _embed(u, bits: int, q: int):
    """The 2**bits x 2**bits matrix a one-qubit ``u`` (shape
    ``batch + (2, 2)``) induces on ``bits`` index bits, acting on bit
    ``q``: m[i, j] = u[i_q, j_q] where i and j agree off bit ``q``.
    Returned transposed (``m.T``), to multiply rows from the right."""
    idx = np.arange(1 << bits)
    same = ((idx[:, None] ^ idx[None, :]) & ~(1 << q)) == 0
    bi = (idx[:, None] >> q) & 1
    bj = (idx[None, :] >> q) & 1
    m = np.where(same, u[..., bi, bj], 0.0)
    return np.swapaxes(m, -1, -2)


@functools.partial(jax.jit, static_argnames=("q", "precision"),
                   donate_argnums=(0, 1))
def _apply_lane(re, im, m_re, m_im, *, q, precision):
    op = _bilinear(lambda a, b: jnp.matmul(a, b, precision=HIGHEST),
                   precision)
    return _cmul(re, im, m_re, m_im, op)


@functools.partial(jax.jit, static_argnames=("q", "precision"),
                   donate_argnums=(0, 1))
def _apply_sublane(re, im, m_re, m_im, *, q, precision):
    shape = re.shape
    t = m_re.shape[-1]
    tiles = shape[:-2] + (shape[-2] // t, t, LANES)
    op = _bilinear(lambda m, a: jnp.einsum(
        "...ij,...tjl->...til", m, a, precision=HIGHEST), precision)
    o_re, o_im = _cmul(m_re, m_im, re.reshape(tiles), im.reshape(tiles), op)
    return o_re.reshape(shape), o_im.reshape(shape)


@functools.partial(jax.jit, static_argnames=("q", "precision"),
                   donate_argnums=(0, 1))
def _apply_rows(re, im, u_re, u_im, *, q, precision):
    shape = re.shape
    s = 1 << (q - LANE_BITS)
    halves = shape[:-2] + (shape[-2] // (2 * s), 2, s, LANES)
    re, im = re.reshape(halves), im.reshape(halves)
    op = _bilinear(_mul, precision)
    ex = (Ellipsis, None, None, None)
    out_re, out_im = [], []
    for b in (0, 1):
        a_re, a_im = _cmul(u_re[..., b, 0][ex], u_im[..., b, 0][ex],
                           re[..., 0, :, :], im[..., 0, :, :], op)
        c_re, c_im = _cmul(u_re[..., b, 1][ex], u_im[..., b, 1][ex],
                           re[..., 1, :, :], im[..., 1, :, :], op)
        out_re.append(a_re + c_re)
        out_im.append(a_im + c_im)
    axis = len(shape) - 1
    return (jnp.stack(out_re, axis=axis).reshape(shape),
            jnp.stack(out_im, axis=axis).reshape(shape))


def apply_1q(re, im, u, q: int, precision: str = "highest"):
    """Apply the complex 2x2 matrix ``u`` (numpy, shape ``batch + (2, 2)``)
    to qubit ``q``."""
    if q < LANE_BITS:
        m = _embed(u, LANE_BITS, q)
        return _apply_lane(re, im, *_planes(m), q=q, precision=precision)
    tile_bits = min(TILE_ROW_BITS, re.shape[-2].bit_length() - 1)
    if q < LANE_BITS + tile_bits:
        m = np.swapaxes(_embed(u, tile_bits, q - LANE_BITS), -1, -2)
        return _apply_sublane(re, im, *_planes(m), q=q, precision=precision)
    return _apply_rows(re, im, *_planes(u), q=q, precision=precision)


def _zz_sum(shape, pairs):
    """int32 plane: sum over ``pairs`` of z_a z_b, with z = 1 - 2 * bit."""
    total = jnp.zeros(shape[-2:], jnp.int32)
    for a, b in pairs:
        total = total + 1 - 2 * (_bit(shape, a) ^ _bit(shape, b))
    return total


@functools.partial(jax.jit, static_argnames=("pairs",),
                   donate_argnums=(0, 1))
def apply_cz_layer(re, im, *, pairs):
    """CZ on every pair: the amplitude's sign flips once per pair whose
    two bits are both 1 (a sign flip is exact in any precision)."""
    parity = jnp.zeros(re.shape[-2:], jnp.int32)
    for a, b in pairs:
        parity = parity ^ (_bit(re.shape, a) & _bit(re.shape, b))
    sign = (1 - 2 * parity).astype(jnp.float32)
    return re * sign, im * sign


@functools.partial(jax.jit, static_argnames=("pairs", "precision"),
                   donate_argnums=(0, 1))
def apply_zz_phase(re, im, gamma, *, pairs, precision="highest"):
    """exp(-i gamma C) with C = sum over ``pairs`` of Z_a Z_b (diagonal);
    ``gamma`` has shape ``batch``."""
    ang = gamma[..., None, None] * _zz_sum(re.shape, pairs).astype(
        jnp.float32)
    return _cmul(jnp.cos(ang), -jnp.sin(ang), re, im,
                 _bilinear(_mul, precision))


# -- gate matrices (textbook definitions) -------------------------------------

def h_matrix():
    s = 1 / math.sqrt(2)
    return np.array([[s, s], [s, -s]], np.complex128)


def rot_matrix(kind: str, theta):
    """exp(-i theta P / 2) for P = X, Y, Z; ``theta`` any shape."""
    t = np.asarray(theta, np.float64)
    c, s = np.cos(t / 2), np.sin(t / 2)
    z = np.zeros_like(t)
    if kind == "rx":
        m = [[c, -1j * s], [-1j * s, c]]
    elif kind == "ry":
        m = [[c, -s], [s, c]]
    elif kind == "rz":
        m = [[np.exp(-0.5j * t), z], [z, np.exp(0.5j * t)]]
    else:
        raise ValueError(f"unknown rotation {kind!r}")
    return np.moveaxis(np.array(m, np.complex128), (0, 1), (-2, -1))


def _planes(m):
    return (jnp.asarray(np.real(m), jnp.float32),
            jnp.asarray(np.imag(m), jnp.float32))


def zero_state(n: int, batch: tuple = ()):
    """|0...0> as planes of shape ``batch + (2**n // 128, 128)``."""
    if n < LANE_BITS:
        raise ValueError(f"the reference needs n >= {LANE_BITS}, got {n}")
    shape = batch + (1 << (n - LANE_BITS), LANES)
    re = jnp.zeros(shape, jnp.float32).at[..., 0, 0].set(1.0)
    return re, jnp.zeros(shape, jnp.float32)


def run_gates(n: int, gates, params, precision: str = "highest"):
    """Run a gate list on |0...0>.

    ``gates`` are the benchmark's tuples: ``("h", q)``, ``("u", q, m)``
    for a fixed 2x2 matrix ``m``, ``("cz", pairs)`` (one layer),
    ``(kind, q, index, scale)`` for a rotation by ``scale *
    params[..., index]``, and ``("zz", pairs, index, scale)`` for
    exp(-i scale params[..., index] sum Z_a Z_b).  A gate whose first
    element is callable is a circuit family's own:
    ``g[0](re, im, params, precision)`` returns the new planes.  ``params``
    has shape ``batch + (P,)``; the state gets the batch axes.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}")
    params = np.asarray(params, np.float64)
    batch = params.shape[:-1]
    re, im = zero_state(n, batch)
    for g in gates:
        kind = g[0]
        if callable(kind):
            re, im = kind(re, im, params, precision)
        elif kind == "u":
            u = np.broadcast_to(np.asarray(g[2], np.complex128),
                                batch + (2, 2))
            re, im = apply_1q(re, im, u, g[1], precision)
        elif kind == "h":
            u = np.broadcast_to(h_matrix(), batch + (2, 2))
            re, im = apply_1q(re, im, u, g[1], precision)
        elif kind == "cz":
            re, im = apply_cz_layer(re, im, pairs=g[1])
        elif kind == "zz":
            _, pairs, idx, scale = g
            gamma = jnp.asarray(scale * params[..., idx], jnp.float32)
            re, im = apply_zz_phase(re, im, gamma, pairs=pairs,
                                    precision=precision)
        else:
            _, q, idx, scale = g
            re, im = apply_1q(re, im, rot_matrix(kind, scale * params[..., idx]),
                              q, precision)
    return re, im


@functools.partial(jax.jit, static_argnames=("pairs",))
def zz_expectations(re, im, *, pairs):
    """<Z_a Z_b> for each pair, float32 per block of at most 2**14
    amplitudes: shape ``batch + (len(pairs), blocks)``; the caller adds the
    blocks in float64."""
    prob = re * re + im * im
    out = []
    for a, b in pairs:
        z = (1 - 2 * (_bit(re.shape, a) ^ _bit(re.shape, b))).astype(
            jnp.float32)
        out.append(_block_sums(prob * z))
    return jnp.stack(out, axis=-2)


def _block_sums(x):
    """Sum the last two axes in float32 blocks of at most 2**14 elements,
    each a run of whole rows (no relayout of the lane axis)."""
    rows = x.shape[-2]
    per = max(1, min(rows, (1 << 14) // x.shape[-1]))
    blocks = x.reshape(x.shape[:-2] + (rows // per, per, x.shape[-1]))
    return jnp.sum(blocks, axis=(-2, -1))
