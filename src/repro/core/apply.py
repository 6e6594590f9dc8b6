"""Gate application.

Two implementations live here:

* ``apply_gate_dense`` — the *naive baseline*: operates on the dense
  ``complex64[2**n]`` vector (XLA's complex storage is interleaved re/im,
  which is exactly the layout the paper shows defeats auto-vectorization).
  This is the oracle for everything else and the Fig-6 baseline.  It is
  written apart from the planar path on purpose: every view is the flat
  vector, partners are read by shifting it and selected by index bits, so
  a fault in the planar views or bit exchanges cannot cancel out.

* ``apply_gate_planar`` — the VLA design in pure JAX on the lane-tiled planar
  layout ``f32[2, R, V]``: explicit real arithmetic (4 real products per
  complex multiply, like the paper's FMA formulation), unit-stride lane loads.
  The Pallas kernels in ``repro.kernels`` implement the same contract with
  explicit VMEM staging; this function is their mid-level reference.

The planar path views the flat amplitude axis with the spans between marked
bits merged, ``(..., d_hi, 2, d_mid, 2, d_lo)`` (:func:`span_view`), so the
rank grows with the gate's width and never with ``n``.  A marked bit below
the lane boundary would make ``d_lo`` narrower than a vector tile, which a
TPU pads to whole (8, 128) tiles; instead the lane bits are first exchanged
with a free block of row bits (:func:`lane_window`, :func:`exchange` — one
transpose), so the gate only ever touches row axes.

Conventions: see ``repro.core.gates``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scopes
from repro.core.gates import Gate

HIGHEST = jax.lax.Precision.HIGHEST

# Planar gates up to this width apply as unrolled per-output sums over the
# 2**k partner slices (one fused elementwise pass); wider gates move their
# axes to the front and take one matmul.
_ELEMENTWISE_MAX_K = 3


def span_view(n: int, bits: Sequence[int]) -> tuple[tuple[int, ...], dict]:
    """``(dims, axis)``: the flat ``2**n`` index (MSB first) with each bit in
    ``bits`` its own size-2 axis and the spans between them merged; ``axis``
    maps each bit to its axis in ``dims``."""
    dims: list[int] = []
    axis: dict[int, int] = {}
    prev = n
    for b in sorted(set(bits), reverse=True):
        if prev - b - 1 > 0:
            dims.append(1 << (prev - b - 1))
        axis[b] = len(dims)
        dims.append(2)
        prev = b
    if prev > 0:
        dims.append(1 << prev)
    return tuple(dims), axis


def lane_window(n: int, v: int, bits: Sequence[int]) -> int | None:
    """Lowest ``s >= v`` such that row bits ``[s, s + v)`` hold none of
    ``bits``: the block the lane bits are exchanged with.  ``None`` when no
    gate bit is a lane bit, or when the state has no such free block (small
    states, where the plain narrow view costs little)."""
    if v == 0 or all(b >= v for b in bits):
        return None
    used = set(bits)
    for s in range(v, n - v + 1):
        if used.isdisjoint(range(s, s + v)):
            return s
    return None


def _lead(shape: tuple[int, ...], n: int) -> tuple[int, ...]:
    """The leading axes of ``shape``, before the trailing ones that flatten
    to the ``2**n`` amplitude index."""
    size = int(np.prod(shape))
    for k, d in enumerate(shape):
        if size == 1 << n:
            return tuple(shape[:k])
        size //= d
    raise ValueError(f"no trailing axes of {shape} flatten to 2**{n}")


def exchange(x: jax.Array, n: int, lo: int, w: int, s: int) -> jax.Array:
    """Exchange amplitude bits ``[lo, lo + w)`` with ``[s, s + w)``, where
    ``s >= lo + w`` (an involution: one transpose of two ``2**w`` axes).
    ``x`` is any array whose trailing axes flatten to the ``2**n``
    amplitude index; leading axes are kept.  The result is left in the
    view ``(*lead, 2**(n-s-w), 2**w, 2**(s-lo-w), 2**w, 2**lo)`` for the
    caller to reshape once: the conversion to HLO merges two reshapes in
    a row into one new reshape, which keeps none of the device attributes
    (:mod:`repro.core.scopes`).  Its operations carry
    ``repro_part="exchange"``."""
    lead = _lead(x.shape, n)
    with scopes.device_scope(part="exchange"):
        t = x.reshape(lead + (1 << (n - s - w), 1 << w, 1 << (s - lo - w),
                              1 << w, 1 << lo))
        return jnp.swapaxes(t, len(lead) + 1, len(lead) + 3)



def _partner_slices(t, axis, qubits, lead: int) -> list:
    """The ``2**k`` sub-tensors of ``t`` at each gate-bit pattern ``i``
    (bit ``m`` of ``i`` <-> ``qubits[m]``); gate axes kept with size 1."""
    out = []
    for i in range(1 << len(qubits)):
        idx = [slice(None)] * t.ndim
        for m, q in enumerate(qubits):
            b = (i >> m) & 1
            idx[lead + axis[q]] = slice(b, b + 1)
        out.append(t[tuple(idx)])
    return out


def _assemble(outs: list, axis, qubits, lead: int):
    """Inverse of :func:`_partner_slices`: concatenate the per-pattern
    outputs back along their gate axes."""
    def build(m: int, base: int):
        if m < 0:
            return outs[base]
        ax = lead + axis[qubits[m]]
        return jnp.concatenate([build(m - 1, base),
                                build(m - 1, base | (1 << m))], axis=ax)
    return build(len(qubits) - 1, 0)


def _control_mask(dims, axis, controls, lead: int):
    """Boolean mask, broadcastable over the view, true where every control
    bit is 1 (None without controls)."""
    mask = None
    for c in controls:
        shp = [1] * (lead + len(dims))
        shp[lead + axis[c]] = 2
        m = jax.lax.broadcasted_iota(jnp.int32, tuple(shp), lead + axis[c]) == 1
        mask = m if mask is None else mask & m
    return mask


# -- dense reference: complex64[2**n] -----------------------------------------

def apply_gate_dense(psi: jax.Array, n: int, qubits: tuple[int, ...],
                     u: jax.Array, controls: tuple[int, ...] = ()) -> jax.Array:
    """Naive-baseline gate application on the dense complex vector.

    ``out[x] = sum_i u[j, i] psi[x with its gate bits set to i]``, where
    ``j`` reads the gate bits of ``x`` (amplitudes whose controls are not
    all 1 are kept).  The partner ``psi[x + pos(i) - pos(j)]`` is the flat
    vector rolled by a constant for each ``(j, i)``, and ``j`` is selected
    per amplitude from an index iota: the vector is never reshaped, so no
    view has a narrow minor axis.  On a TPU each distinct shift is a
    state-sized temporary, ``3**k`` of them for a ``k``-qubit gate.
    """
    return _dense_gate(psi, n, tuple(qubits), jnp.asarray(u, jnp.complex64),
                       tuple(controls))


@functools.partial(jax.jit, static_argnums=(1, 2, 4))
def _dense_gate(psi, n: int, qubits: tuple[int, ...], u,
                controls: tuple[int, ...]):
    psi = psi.reshape(1 << n)
    x = jax.lax.iota(jnp.int32, 1 << n)

    def bit(q):
        return (x >> q) & 1

    def pos(i):
        return sum(((i >> m) & 1) << q for m, q in enumerate(qubits))

    rolled: dict[int, jax.Array] = {}

    def partner(shift):
        if shift not in rolled:
            rolled[shift] = jnp.roll(psi, shift)
        return rolled[shift]

    out = psi
    for j in range(1 << len(qubits)):
        y = sum(u[j, i] * partner(pos(j) - pos(i))
                for i in range(1 << len(qubits)))
        here = functools.reduce(jnp.logical_and,
                                [bit(q) == ((j >> m) & 1)
                                 for m, q in enumerate(qubits)])
        out = jnp.where(here, y, out)
    for c in controls:
        out = jnp.where(bit(c) == 1, out, psi)
    return out


# -- planar design: f32[2, R, V] ----------------------------------------------

def _planar_rows(data, n: int, v: int, qubits, u_re, u_im, controls):
    """The gate on row bits only, in the span view ``(2, *dims)``."""
    dims, axis = span_view(n, tuple(qubits) + tuple(controls))
    t = data.reshape((2,) + dims)
    k = len(qubits)
    if k <= _ELEMENTWISE_MAX_K:
        xs = _partner_slices(t, axis, qubits, 1)
        outs = []
        for j in range(1 << k):
            # out[j] = sum_i u[j, i] x[i] in real arithmetic
            re = sum(u_re[j, i] * x[0] - u_im[j, i] * x[1]
                     for i, x in enumerate(xs))
            im = sum(u_re[j, i] * x[1] + u_im[j, i] * x[0]
                     for i, x in enumerate(xs))
            outs.append(jnp.stack([re, im]))
        out = _assemble(outs, axis, qubits, 1)
    else:
        order = [1 + axis[q] for q in reversed(qubits)]
        s = jnp.moveaxis(t, order, range(1, k + 1))
        rest = s.shape[k + 1:]
        # the columns keep their lane axis: (2**k, rows, V) compiles far
        # faster on a TPU than one (2**k, rows * V) matrix
        lanes = min(1 << v, int(np.prod(rest)))
        s = s.reshape(2, 1 << k, -1, lanes)
        mm = functools.partial(jnp.einsum, "ij,jrl->irl", precision=HIGHEST)
        # complex matvec as 4 real matmuls (paper's FMA formulation)
        s = jnp.stack([mm(u_re, s[0]) - mm(u_im, s[1]),
                       mm(u_re, s[1]) + mm(u_im, s[0])])
        out = jnp.moveaxis(s.reshape((2,) + (2,) * k + rest),
                           range(1, k + 1), order)
    mask = _control_mask(dims, axis, controls, 1)
    if mask is not None:
        out = jnp.where(mask, out, t)
    return out


def apply_planar(data: jax.Array, n: int, v: int, qubits: tuple[int, ...],
                 u_re: jax.Array, u_im: jax.Array,
                 controls: tuple[int, ...] = ()) -> jax.Array:
    """:func:`apply_gate_planar` for ``v`` lane qubits on a state of any
    shape that flattens to ``(2, 2**n)``, returned in the view of its last
    operation (see :func:`exchange`): the plan's program reshapes it once,
    where the next item needs it."""
    s = lane_window(n, v, tuple(qubits) + tuple(controls))
    if s is not None:
        data = exchange(data, n, 0, v, s)
        mv = lambda bs: tuple(b + s if b < v else b for b in bs)
        qubits, controls = mv(qubits), mv(controls)
    out = _planar_rows(data, n, v, qubits, u_re, u_im, controls)
    return out if s is None else exchange(out, n, 0, v, s)


def apply_gate_planar(data: jax.Array, n: int, qubits: tuple[int, ...],
                      u_re: jax.Array, u_im: jax.Array,
                      controls: tuple[int, ...] = ()) -> jax.Array:
    """VLA gate application on the lane-tiled planar layout f32[2, R, V].

    Gate bits in the lane axis are first exchanged with a free block of row
    bits (a tile transpose), so the gate itself only touches row axes and
    the lane axis stays whole; the exchange is undone afterwards.
    """
    v = data.shape[-1].bit_length() - 1
    return apply_planar(data, n, v, qubits, u_re, u_im,
                        controls).reshape(data.shape)


def gate_arrays(g: Gate) -> tuple[jax.Array, jax.Array]:
    """Split a gate matrix into fp32 re/im planes (device constants)."""
    m = np.asarray(g.matrix, np.complex64)
    return jnp.asarray(m.real, jnp.float32), jnp.asarray(m.imag, jnp.float32)


def split_row_lane(qubits: Sequence[int], v: int) -> tuple[list[int], list[int]]:
    """Partition gate qubits into lane qubits (< log2 V) and row qubits."""
    lane = [q for q in qubits if q < v]
    row = [q for q in qubits if q >= v]
    return lane, row
