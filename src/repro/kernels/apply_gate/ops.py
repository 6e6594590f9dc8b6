"""Public wrappers for the fused-gate Pallas kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.apply import exchange
from repro.core.target import resolve_interpret
from repro.kernels.apply_gate.apply_gate import (
    SUBLANES, apply_fused_gate_kernel, apply_phase_kernel, make_plan,
    phase_tile_map)


@functools.lru_cache(maxsize=1024)
def _sort_perm(qubits: tuple[int, ...]) -> tuple[tuple[int, ...], np.ndarray]:
    """Permutation taking U (bit m <-> qubits[m]) to sorted-qubit order."""
    qs_sorted = tuple(sorted(qubits))
    pos = {q: m for m, q in enumerate(qubits)}
    k = len(qubits)
    perm = np.zeros(1 << k, np.int32)
    for j in range(1 << k):
        j_orig = 0
        for m in range(k):
            if (j >> m) & 1:
                j_orig |= 1 << pos[qs_sorted[m]]
        perm[j] = j_orig
    return qs_sorted, perm


def _tile_bits(n: int, v: int) -> tuple[int, int]:
    """``(tile_bits, tile_rows)`` of the state's ``(8, V)`` vector tile
    (fewer rows when the whole state is smaller than one tile)."""
    rows = min(SUBLANES, 1 << (n - v))
    return v + rows.bit_length() - 1, rows


@functools.lru_cache(maxsize=1024)
def tile_swaps(n: int, v: int, bits: tuple[int, ...]) -> tuple | None:
    """Bit-block exchanges ``(lo, w, s)`` that move every bit in ``bits``
    above the ``(8, V)`` tile: the lane block ``[0, v)`` and the sublane
    block ``[v, t)`` each trade places with a free block of high bits.
    Empty when no bit is in the tile; None when the state has no free
    blocks left (the kernel then sees narrow tail axes, which a TPU pads)."""
    t, _ = _tile_bits(n, v)
    used = set(bits)
    swaps = []
    for lo, w in ((0, v), (v, t - v)):
        if w == 0 or used.isdisjoint(range(lo, lo + w)):
            continue
        s = next((s for s in range(t, n - w + 1)
                  if used.isdisjoint(range(s, s + w))), None)
        if s is None:
            return None
        swaps.append((lo, w, s))
        used |= set(range(s, s + w))
    return tuple(swaps)


def _moved(bits, swaps) -> tuple[int, ...]:
    out = []
    for b in bits:
        for lo, w, s in swaps:
            if lo <= b < lo + w:
                b = b - lo + s
        out.append(b)
    return tuple(out)


def apply_fused_gate(data: jax.Array, n: int, v: int,
                     qubits: tuple[int, ...], u_re: jax.Array,
                     u_im: jax.Array, controls: tuple[int, ...] = (),
                     interpret: bool | None = None,
                     max_block_bytes: int = 1 << 20) -> jax.Array:
    """Apply a (fused, optionally controlled) gate to the planar state.

    data: f32[2, R, V] lane-tiled planar state (R * V = 2**n).
    qubits: target qubit ids; bit m of u's index <-> qubits[m].

    Marked bits inside the ``(8, V)`` vector tile are first exchanged with
    free high bits (one transpose per lane/sublane block, undone after), so
    the kernel's tail axes are whole tiles.
    """
    return fused_gate(data, n, v, qubits, u_re, u_im, controls, interpret,
                      max_block_bytes).reshape(data.shape)


def fused_gate(data: jax.Array, n: int, v: int, qubits: tuple[int, ...],
               u_re: jax.Array, u_im: jax.Array,
               controls: tuple[int, ...] = (), interpret: bool | None = None,
               max_block_bytes: int = 1 << 20) -> jax.Array:
    """:func:`apply_fused_gate` on a state of any shape that flattens to
    ``(2, 2**n)``, returned in the view of its last operation, so that
    no two reshapes follow each other (see ``repro.core.apply.exchange``).
    """
    interpret = resolve_interpret(interpret)
    swaps = tile_swaps(n, v, tuple(qubits) + tuple(controls)) or ()
    qubits, controls = _moved(qubits, swaps), _moved(controls, swaps)
    qs_sorted, perm = _sort_perm(tuple(qubits))
    if qs_sorted != tuple(qubits):
        p = jnp.asarray(perm)
        u_re = u_re[p][:, p]
        u_im = u_im[p][:, p]
    plan = make_plan(n, qs_sorted, tuple(sorted(controls)),
                     max_block_bytes=max_block_bytes, lanes=1 << v)
    for sw in swaps:
        data = exchange(data, n, *sw)
    out = apply_fused_gate_kernel(data, jnp.asarray(u_re, jnp.float32),
                                  jnp.asarray(u_im, jnp.float32), plan,
                                  interpret=interpret)
    for sw in reversed(swaps):
        out = exchange(out, n, *sw)
    return out


def apply_phase_gate(data: jax.Array, n: int, v: int,
                     qubits: tuple[int, ...], p_re: jax.Array | None,
                     p_im: jax.Array | None, perm=None,
                     interpret: bool | None = None,
                     max_block_bytes: int = 1 << 20) -> jax.Array:
    """Apply a diagonal/permutation (monomial) fused gate to the planar state.

    data: f32[2, R, V] lane-tiled planar state (R * V = 2**n).
    qubits: sorted cluster qubit ids; bit m of the ``2**w`` phase vector /
    ``perm`` index map corresponds to ``qubits[m]``.
    p_re/p_im: f32[2**w] phase planes (``None`` for a pure permutation).
    perm: optional int[2**w] static index map, ``out[r] = phase[r] *
    in[perm[r]]`` over the cluster rows.

    A permutation cluster is applied as its monomial matrix through the
    dense kernel (no gather inside a kernel).  A diagonal one streams the
    state once through the phase kernel in ~``max_block_bytes`` blocks:
    cluster bits above the vector tile select a phase tile of the table
    (those inside a block within it, those above from the grid), and the
    bits inside the tile are spread over each tile as a whole ``(8, V)``
    tile.
    """
    return phase_gate(data, n, v, qubits, p_re, p_im, perm, interpret,
                      max_block_bytes).reshape(data.shape)


def phase_gate(data: jax.Array, n: int, v: int, qubits: tuple[int, ...],
               p_re: jax.Array | None, p_im: jax.Array | None, perm=None,
               interpret: bool | None = None,
               max_block_bytes: int = 1 << 20) -> jax.Array:
    """:func:`apply_phase_gate` on a state of any shape that flattens to
    ``(2, 2**n)``, returned in the view of its last operation (see
    :func:`fused_gate`)."""
    qubits = tuple(qubits)
    if qubits != tuple(sorted(qubits)):
        raise ValueError(f"apply_phase_gate needs sorted qubits, got {qubits}")
    dim = 1 << len(qubits)
    if p_re is None:
        p_re, p_im = jnp.ones(dim, jnp.float32), jnp.zeros(dim, jnp.float32)
    if perm is not None:
        rows = jnp.arange(dim)
        cols = jnp.asarray(np.asarray(perm), jnp.int32)
        u_re = jnp.zeros((dim, dim), jnp.float32).at[rows, cols].set(p_re)
        u_im = jnp.zeros((dim, dim), jnp.float32).at[rows, cols].set(p_im)
        return fused_gate(data, n, v, qubits, u_re, u_im,
                          interpret=interpret,
                          max_block_bytes=max_block_bytes)
    interpret = resolve_interpret(interpret)
    t, tile_rows = _tile_bits(n, v)
    hi = tuple(q for q in qubits if q >= t)
    tmap = phase_tile_map(qubits, t)
    n_lo = len(qubits) - len(hi)

    # one (tile_rows, V) tile per pattern of ``hi``, in pattern order: the
    # tiles of one pattern of the bits above the kernel's cut are adjacent
    def table(p):
        return p.reshape(-1, 1 << n_lo)[:, tmap].reshape(-1, 1 << v)

    tab = jnp.stack([table(jnp.asarray(p_re, jnp.float32)),
                     table(jnp.asarray(p_im, jnp.float32))])
    return apply_phase_kernel(data, tab, hi, n, tile_rows, 1 << v,
                              interpret=interpret,
                              max_block_bytes=max_block_bytes)


def apply_circuit(data: jax.Array, n: int, v: int, gates,
                  interpret: bool | None = None) -> jax.Array:
    """Apply a list of core.gates.Gate sequentially through the kernel."""
    for g in gates:
        u = np.asarray(g.matrix)
        data = apply_fused_gate(
            data, n, v, g.qubits,
            jnp.asarray(u.real, jnp.float32), jnp.asarray(u.imag, jnp.float32),
            controls=g.controls, interpret=interpret)
    return data
