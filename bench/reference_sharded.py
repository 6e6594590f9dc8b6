"""Plain state-vector reference for a state that no one device holds.

It imports nothing of the program under test: circuits arrive as the
benchmark's own gate lists, as for :mod:`bench.reference`, whose functions
and gate matrices it uses.

The ``n``-qubit state is ``2**s`` blocks, one per device, held as planes
``(re, im)`` of shape ``(2**s, 2**(n - s) // 128, 128)`` whose first axis
is split over the devices.  Block ``b`` holds the amplitudes whose top
``s`` bits are ``b`` (qubit ``n - s + j`` is bit ``j`` of ``b``), laid out
as :mod:`bench.reference` lays out a state of ``n - s`` qubits.  Every
gate is one ``shard_map`` over the devices, so it compiles once for all
of them, and no device holds more than its block, one partner block and
their temporaries.  A gate:

- on a local qubit (``q < n - s``) is :mod:`bench.reference`'s lane,
  sublane or row product on each block, the blocks as a batch axis;
- on a block qubit combines the two blocks it pairs, elementwise: each
  device is sent its partner block (``ppermute``) and computes its own
  new block from the two;
- a CZ layer or a ZZ phase reads a block qubit's bit from the block's
  index, a constant on each block.

``precision`` is :mod:`bench.reference`'s: ``"highest"`` computes in
float32, ``"high"`` (the control) takes every product as three bfloat16
passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from bench import reference as R

AXIS = "blocks"
BLOCKS = P(AXIS)


@functools.lru_cache(maxsize=None)
def _mesh(devices: tuple) -> Mesh:
    s = len(devices).bit_length() - 1
    if len(devices) != 1 << s:
        raise ValueError(f"{len(devices)} devices is not a power of two")
    return Mesh(np.asarray(devices), (AXIS,))


def _blockwise(body, mesh, n_blocks: int, n_rest: int):
    """``body`` on each device's blocks: its first ``n_blocks`` arguments
    are split over the devices, the next ``n_rest`` given whole to each."""
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(BLOCKS,) * n_blocks + (P(),) * n_rest,
                         out_specs=(BLOCKS, BLOCKS))


@functools.partial(jax.jit, static_argnames=("n", "mesh"))
def _zero_state(*, n, mesh):
    rows = 1 << (n - (mesh.size.bit_length() - 1) - R.LANE_BITS)

    def body():
        re = jnp.zeros((1, rows, R.LANES), jnp.float32)
        one = jnp.where(jax.lax.axis_index(AXIS) == 0, 1.0, 0.0)
        return re.at[0, 0, 0].set(one), jnp.zeros_like(re)
    return _blockwise(body, mesh, 0, 0)()


def zero_state(n: int, devices):
    """|0...0> as blocked planes over ``devices``, whose count is a power
    of two."""
    return _zero_state(n=n, mesh=_mesh(tuple(devices)))


def _view(u, q: int, row_bits: int):
    """:func:`bench.reference.apply_1q`'s view for qubit ``q`` of a block
    with ``2**row_bits`` rows: the product's name and the matrix planes."""
    if q < R.LANE_BITS:
        return "lane", R._planes(R._embed(u, R.LANE_BITS, q))
    tile_bits = min(R.TILE_ROW_BITS, row_bits)
    if q < R.LANE_BITS + tile_bits:
        m = np.swapaxes(R._embed(u, tile_bits, q - R.LANE_BITS), -1, -2)
        return "sublane", R._planes(m)
    return "rows", R._planes(u)


PRODUCTS = {"lane": R._apply_lane, "sublane": R._apply_sublane,
            "rows": R._apply_rows}


@functools.partial(jax.jit, static_argnames=("view", "q", "precision", "mesh"),
                   donate_argnums=(0, 1))
def _local_1q(re, im, m_re, m_im, *, view, q, precision, mesh):
    def body(re, im, m_re, m_im):
        return PRODUCTS[view](re, im, m_re, m_im, q=q, precision=precision)
    return _blockwise(body, mesh, 2, 2)(re, im, m_re, m_im)


@functools.partial(jax.jit, static_argnames=("j", "precision", "mesh"),
                   donate_argnums=(0, 1))
def _block_1q(re, im, u_re, u_im, *, j, precision, mesh):
    """A one-qubit gate ``u`` on block bit ``j``: new block ``b`` is
    ``u[c, c] * b + u[c, 1 - c] * partner`` where ``c`` is bit ``j`` of
    ``b`` and the partner is block ``b ^ 2**j``."""
    pairs = [(b, b ^ (1 << j)) for b in range(mesh.size)]
    op = R._bilinear(R._mul, precision)

    def body(re, im, u_re, u_im):
        c = (jax.lax.axis_index(AXIS) >> j) & 1
        p_re = jax.lax.ppermute(re, AXIS, pairs)
        p_im = jax.lax.ppermute(im, AXIS, pairs)
        x_re, x_im = R._cmul(u_re[c, c], u_im[c, c], re, im, op)
        y_re, y_im = R._cmul(u_re[c, 1 - c], u_im[c, 1 - c], p_re, p_im, op)
        return x_re + y_re, x_im + y_im
    return _blockwise(body, mesh, 2, 2)(re, im, u_re, u_im)


def _bit(shape, q: int, n_local: int, s: int):
    """Bit ``q`` of each amplitude of a block: an int32 plane for a local
    qubit, the block index's own bit for a block qubit."""
    if q < n_local:
        return R._bit(shape, q)
    return (jax.lax.axis_index(AXIS) >> (q - n_local)) & 1


@functools.partial(jax.jit, static_argnames=("pairs", "n_local", "mesh"),
                   donate_argnums=(0, 1))
def _cz(re, im, *, pairs, n_local, mesh):
    """CZ on every pair: the sign flips once per pair whose two bits are
    both 1."""
    s = mesh.size.bit_length() - 1

    def body(re, im):
        parity = jnp.zeros(re.shape[-2:], jnp.int32)
        for a, c in pairs:
            parity = parity ^ (_bit(re.shape, a, n_local, s)
                               & _bit(re.shape, c, n_local, s))
        sign = (1 - 2 * parity).astype(jnp.float32)
        return re * sign, im * sign
    return _blockwise(body, mesh, 2, 0)(re, im)


@functools.partial(jax.jit,
                   static_argnames=("pairs", "n_local", "precision", "mesh"),
                   donate_argnums=(0, 1))
def _zz(re, im, gamma, *, pairs, n_local, precision, mesh):
    """exp(-i gamma C) with C = sum over ``pairs`` of Z_a Z_b, on each
    block."""
    s = mesh.size.bit_length() - 1

    def body(re, im, gamma):
        total = jnp.zeros(re.shape[-2:], jnp.int32)
        for a, c in pairs:
            total = total + 1 - 2 * (_bit(re.shape, a, n_local, s)
                                     ^ _bit(re.shape, c, n_local, s))
        ang = gamma * total.astype(jnp.float32)
        return R._cmul(jnp.cos(ang), -jnp.sin(ang), re, im,
                       R._bilinear(R._mul, precision))
    return _blockwise(body, mesh, 2, 1)(re, im, gamma)


def _apply_1q(state, u, q: int, n_local: int, precision: str, mesh):
    re, im = state
    if q >= n_local:
        return _block_1q(re, im, *R._planes(u), j=q - n_local,
                         precision=precision, mesh=mesh)
    view, m = _view(u, q, n_local - R.LANE_BITS)
    return _local_1q(re, im, *m, view=view, q=q, precision=precision,
                     mesh=mesh)


def run_gates(n: int, gates, params, devices, precision: str = "highest"):
    """Run a gate list on |0...0>, the state in blocks over ``devices``;
    returns the blocked planes ``(re, im)``, block ``b`` on
    ``devices[b]`` (:func:`blocks` lists them).

    ``gates`` are :func:`bench.reference.run_gates`' tuples; a family's own
    gate (a callable) has no blocked form and is refused.  ``params`` has
    shape ``(P,)``.
    """
    if precision not in R.PRECISIONS:
        raise ValueError(f"precision must be one of {R.PRECISIONS}")
    params = np.asarray(params, np.float64)
    if params.ndim != 1:
        raise ValueError("the blocked reference runs one circuit at a time")
    mesh = _mesh(tuple(devices))
    state = zero_state(n, devices)
    n_local = n - (len(devices).bit_length() - 1)
    for g in gates:
        kind = g[0]
        if callable(kind):
            raise ValueError("a family's own gate has no blocked form")
        if kind == "u":
            state = _apply_1q(state, np.asarray(g[2], np.complex128), g[1],
                              n_local, precision, mesh)
        elif kind == "h":
            state = _apply_1q(state, R.h_matrix(), g[1], n_local, precision,
                              mesh)
        elif kind == "cz":
            state = _cz(*state, pairs=g[1], n_local=n_local, mesh=mesh)
        elif kind == "zz":
            _, pairs, idx, scale = g
            state = _zz(*state, jnp.float32(scale * params[idx]),
                        pairs=pairs, n_local=n_local, precision=precision,
                        mesh=mesh)
        else:
            _, q, idx, scale = g
            state = _apply_1q(state, R.rot_matrix(kind, scale * params[idx]),
                              q, n_local, precision, mesh)
    return state


def blocks(state):
    """``[(device, re, im)]`` of blocked planes, in block order; each
    block's planes have shape ``(1, rows, 128)`` and lie on its device."""
    def in_order(plane):
        return sorted(plane.addressable_shards,
                      key=lambda sh: sh.index[0].start or 0)
    re, im = state
    return [(a.device, a.data, b.data)
            for a, b in zip(in_order(re), in_order(im), strict=True)]


@functools.partial(jax.jit, static_argnames=("mesh",))
def _planar(re, im, *, mesh):
    return jax.shard_map(lambda re, im: jnp.stack([re[0], im[0]]), mesh=mesh,
                         in_specs=(BLOCKS, BLOCKS),
                         out_specs=P(None, AXIS, None))(re, im)


def planar(state):
    """The blocked planes as one array ``(2, 2**n // 128, 128)``, real and
    imaginary planes first, its rows split over the devices in block
    order."""
    re, _ = state
    return _planar(*state, mesh=re.sharding.mesh)
