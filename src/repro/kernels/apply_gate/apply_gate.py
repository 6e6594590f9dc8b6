"""Pallas TPU kernels for fused-gate application (the paper's ApplyGate ROI).

The planar state ``f32[2, R, V]`` is viewed (zero-copy reshape of the flat
``2**n`` index space) as

    f32[2, d_1, d_2, ..., d_m, rows, V]

where each gate/control bit is its own size-2 axis (descending significance),
the spans between bits are single axes, and the amplitudes below the lowest
marked bit form the ``(rows, V)`` tail: whole vector tiles, ``rows`` a
multiple of the 8 sublanes whenever the marked bits sit above the
``(8, V)`` tile.  The BlockSpec takes the *full* extent of every gate axis
and one coordinate of every other axis, so a VMEM block is one state group:
``2**k`` slabs of ``(rows_blk, V)`` re+im — the paper's ``2**k`` scattered
unit-stride vector loads, staged through VMEM (load buffering, §IV-B).

Dense kernel: the block collapses to ``(2, 2**k, rows_blk, V)`` and the
gate is four real matmuls over the ``2**k`` axis (complex FMA formulation)
on the MXU at ``precision=HIGHEST``; for ``f = 7`` the matrix is a native
128x128 tile.
Control bits are grid axes; the kernel applies the unitary only where every
control coordinate is 1 and copies through otherwise (predicated iteration).
Callers move gate bits that fall inside the ``(8, V)`` tile out of it first
(``ops.apply_fused_gate``).

Phase kernel (diagonal clusters): one streaming pass over ``f32[2, R, V]``
in contiguous blocks of every amplitude bit below a cut ``C``, the largest
block within ``max_block_bytes`` (1 MiB for re plus im: ``C = 17``), whatever
the cluster (:class:`PhasePlan`).  Only the bits at or above ``C`` index the
grid.  Cluster bits inside the ``(8, V)`` tile are folded into each phase
tile (``phase_tile_map``).  Inside the kernel the block's row groups are
viewed, the ``(8, V)`` tile untouched, as alternating runs of cluster and
other bits above the tile, and the table block, whose ``2**c`` tiles are the
phases of the ``c`` cluster bits in ``[t, C)``, is broadcast over the other
runs.  The grid walks the cluster bits at or above ``C`` slowest, so
consecutive steps share a table block and its copy is skipped: a call
fetches the table once.

Each ``pallas_call`` has a stable ``name`` (``fused_gate``, ``phase``) and
``metadata`` naming the plan item and kind it runs for
(:func:`repro.core.scopes.kernel_metadata`), so a profile attributes every
call; the engine lint's EL006 keeps both on every kernel.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import scopes

HIGHEST = jax.lax.Precision.HIGHEST
SUBLANES = 8


@dataclasses.dataclass(frozen=True)
class ViewPlan:
    """How the flat state index space is factorized for the kernel."""
    dims: tuple[int, ...]          # axis sizes after the plane axis
    roles: tuple[str, ...]         # 'gate' | 'ctrl' | 'seg' | 'tail'
    block: tuple[int, ...]         # block size per axis
    grid_sizes: tuple[int, ...]    # number of blocks per axis (1 for gate axes)
    k: int                         # number of gate bits

    @property
    def grid(self) -> int:
        return math.prod(self.grid_sizes)


def make_plan(n: int, gate_bits: Sequence[int], ctrl_bits: Sequence[int],
              max_block_bytes: int = 1 << 20, lanes: int = 128) -> ViewPlan:
    """Factorize the 2**n index space around the gate/control bits.

    The two trailing axes are the ``(rows, lanes)`` tail below the lowest
    marked bit (both role ``'tail'``).  Rows are split into ``'seg'`` grid
    blocks so one block stays within ``max_block_bytes``; a split block keeps
    at least 8 rows, so its last two dims are whole ``(8, lanes)`` tiles.
    """
    marked = sorted(
        [(b, "gate") for b in gate_bits] + [(b, "ctrl") for b in ctrl_bits],
        reverse=True)
    dims: list[int] = []
    roles: list[str] = []
    prev = n
    for b, role in marked:
        seg = prev - b - 1
        if seg > 0:
            dims.append(1 << seg)
            roles.append("seg")
        dims.append(2)
        roles.append(role)
        prev = b
    tail = 1 << prev
    lane_w = min(tail, lanes)
    rows = tail // lane_w
    # the matmul pads a gate axis narrower than 8 sublanes to 8 in VMEM,
    # so a narrow gate's block is sized as if it had 8 partners
    k = len(gate_bits)
    budget_rows = max(1, max_block_bytes
                      // (2 * 4 * max(1 << k, SUBLANES) * lane_w))
    rows_blk = min(rows, max(min(rows, SUBLANES),
                             1 << (budget_rows.bit_length() - 1)))
    if rows // rows_blk > 1:
        dims.append(rows // rows_blk)
        roles.append("seg")
    dims += [rows_blk, lane_w]
    roles += ["tail", "tail"]

    block = tuple(2 if r == "gate" else (d if r == "tail" else 1)
                  for d, r in zip(dims, roles))
    grid_sizes = tuple(d // b for d, b in zip(dims, block))
    return ViewPlan(tuple(dims), tuple(roles), block, grid_sizes, k)


def _unravel(flat, sizes: Sequence[int]):
    """Split a flat index into per-axis coordinates (row-major)."""
    coords = []
    rem = flat
    stride = math.prod(sizes)
    for s in sizes:
        stride //= s
        coords.append(rem // stride)
        rem = rem % stride
    return coords


def _state_spec(plan: ViewPlan) -> pl.BlockSpec:
    def idx_map(g):
        return (0,) + tuple(_unravel(g, plan.grid_sizes))
    return pl.BlockSpec((2,) + plan.block, idx_map)


def _kernel(u_re_ref, u_im_ref, x_ref, o_ref, *, plan: ViewPlan):
    m = 1 << plan.k
    rows_blk, lane_w = plan.block[-2:]
    ctrl_axes = [i for i, r in enumerate(plan.roles) if r == "ctrl"]

    def compute():
        x = x_ref[...].reshape(2, m, rows_blk, lane_w)
        re, im = x[0], x[1]
        plane = x_ref.shape[1:]
        u_re = u_re_ref[...]
        u_im = u_im_ref[...]
        dot = functools.partial(jnp.einsum, "ij,jrl->irl", precision=HIGHEST,
                                preferred_element_type=jnp.float32)
        # complex matvec as four real matmuls (fp32 accumulation); each
        # plane is stored as it is done, so at most one is held in VMEM
        o_ref[0] = (dot(u_re, re) - dot(u_im, im)).reshape(plane)
        o_ref[1] = (dot(u_re, im) + dot(u_im, re)).reshape(plane)

    if not ctrl_axes:
        compute()
        return

    g = pl.program_id(0)
    coords = _unravel(g, plan.grid_sizes)
    pred = coords[ctrl_axes[0]] == 1
    for a in ctrl_axes[1:]:
        pred = jnp.logical_and(pred, coords[a] == 1)

    @pl.when(pred)
    def _():
        compute()

    @pl.when(jnp.logical_not(pred))
    def _():
        o_ref[...] = x_ref[...]


def apply_fused_gate_kernel(data: jax.Array, u_re: jax.Array,
                            u_im: jax.Array, plan: ViewPlan,
                            interpret: bool) -> jax.Array:
    """Run the dense kernel on the planar state (any shape that flattens to
    f32[2, 2**n]); the result is in the kernel's view ``(2, *plan.dims)``."""
    shaped = data.reshape((2,) + plan.dims)
    spec = _state_spec(plan)
    dim = u_re.shape[0]
    u_spec = pl.BlockSpec((dim, dim), lambda g: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, plan=plan),
        grid=(plan.grid,),
        in_specs=[u_spec, u_spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(shaped.shape, jnp.float32),
        interpret=interpret,
        name="fused_gate",
        metadata=scopes.kernel_metadata(),
    )(u_re, u_im, shaped)


def phase_tile_map(qubits: Sequence[int], tile_bits: int) -> np.ndarray:
    """int32[2**tile_bits]: the cluster-index bits held by each position of
    the low ``tile_bits`` amplitude bits (cluster bit ``m`` <-> sorted
    ``qubits[m]``; the tile's cluster bits are the low cluster bits)."""
    pos = np.arange(1 << tile_bits)
    low = [q for q in sorted(qubits) if q < tile_bits]
    out = np.zeros_like(pos)
    for m, q in enumerate(low):
        out |= ((pos >> q) & 1) << m
    return out.astype(np.int32)


def _runs(bits: Sequence[int]) -> list[tuple[int, int]]:
    """``(lowest, width)`` of each run of consecutive bits, ascending."""
    runs: list[list[int]] = []
    for b in sorted(bits):
        if runs and runs[-1][0] + runs[-1][1] == b:
            runs[-1][1] += 1
        else:
            runs.append([b, 1])
    return [(lo, w) for lo, w in runs]


def _deposit(x, positions: Sequence[int]):
    """Bit ``j`` of ``x`` moved to bit ``positions[j]`` (ascending); works
    on Python ints and on traced grid indices."""
    out, taken = 0, 0
    for lo, w in _runs(positions):
        out = out + (x // (1 << taken)) % (1 << w) * (1 << lo)
        taken += w
    return out


@dataclasses.dataclass(frozen=True)
class PhasePlan:
    """How the phase kernel walks a ``2**n`` state: one block of every
    amplitude bit below ``cut`` per grid step.

    ``inner`` are the cluster bits in ``[tile_bits, cut)``, resolved inside
    a block; ``outer`` the cluster bits at or above ``cut``, which pick the
    table block.  Step ``g``'s high bits are the ``outer`` coordinates and
    its low bits the other bits at or above ``cut``, so the table block
    changes only when an outer coordinate does.
    """
    n: int
    cut: int
    tile_bits: int
    inner: tuple[int, ...]
    outer: tuple[int, ...]

    @property
    def steps(self) -> int:
        return 1 << (self.n - self.cut)

    @property
    def free(self) -> tuple[int, ...]:
        """The non-cluster bits at or above ``cut``."""
        return tuple(b for b in range(self.cut, self.n)
                     if b not in self.outer)

    def state_block(self, g):
        """The block (in units of ``2**cut`` amplitudes) of step ``g``."""
        hi, lo = divmod(g, 1 << len(self.free))
        return (_deposit(hi, [b - self.cut for b in self.outer])
                + _deposit(lo, [b - self.cut for b in self.free]))

    def table_block(self, g):
        """The table block (one pattern of ``outer``) of step ``g``."""
        return g // (1 << len(self.free))

    @property
    def groups(self) -> tuple[tuple[int, bool], ...]:
        """``(size, is_cluster)`` of the block's row-group axes above the
        tile, most significant first: the runs of cluster and of other
        bits in ``[tile_bits, cut)``."""
        runs = _runs(self.inner)
        out, prev = [], self.cut
        for lo, w in reversed(runs):
            if prev > lo + w:
                out.append((1 << (prev - lo - w), False))
            out.append((1 << w, True))
            prev = lo
        if prev > self.tile_bits:
            out.append((1 << (prev - self.tile_bits), False))
        return tuple(out)


def phase_plan(n: int, hi_bits: Sequence[int], tile_bits: int,
               max_block_bytes: int = 1 << 20) -> PhasePlan:
    """The largest block of low amplitude bits whose re and im planes fit
    ``max_block_bytes``: at least one ``(8, V)`` tile, at most the state.
    ``hi_bits`` are the cluster bits at or above ``tile_bits``."""
    amps = max(1, max_block_bytes // 8)
    cut = min(n, max(tile_bits, amps.bit_length() - 1))
    hi = tuple(sorted(hi_bits))
    return PhasePlan(n, cut, tile_bits,
                     tuple(b for b in hi if b < cut),
                     tuple(b for b in hi if b >= cut))


def _phase_kernel(p_ref, x_ref, o_ref, *, plan: PhasePlan, tile: tuple):
    """Rotate one state block by its phases: ``p_ref`` holds the block's
    ``2**len(plan.inner)`` phase tiles (re, im), broadcast over the row
    groups that no cluster bit indexes."""
    view = tuple(s for s, _ in plan.groups) + tile
    pview = tuple(s if c else 1 for s, c in plan.groups) + tile
    p_re = p_ref[0].reshape(pview)
    p_im = p_ref[1].reshape(pview)
    re = x_ref[0].reshape(view)
    im = x_ref[1].reshape(view)
    plane = x_ref.shape[1:]
    # each plane is stored as it is done, so at most one is held in VMEM
    o_ref[0] = (p_re * re - p_im * im).reshape(plane)
    o_ref[1] = (p_re * im + p_im * re).reshape(plane)


def apply_phase_kernel(data: jax.Array, table: jax.Array,
                       hi_bits: Sequence[int], n: int, tile_rows: int,
                       lanes: int, interpret: bool,
                       max_block_bytes: int = 1 << 20) -> jax.Array:
    """Run the phase kernel on the planar state (any shape that flattens to
    f32[2, 2**n]); the result is ``f32[2, R, lanes]``.

    ``table`` is ``f32[2, 2**len(hi_bits) * tile_rows, lanes]``: for each
    pattern of the cluster bits above the tile (``hi_bits``, sorted; bit
    ``m`` of the pattern <-> ``hi_bits[m]``) one ``(tile_rows, lanes)``
    phase tile.  A table block is the ``2**c`` consecutive tiles of one
    pattern of the bits at or above the cut.
    """
    tile_bits = (tile_rows * lanes).bit_length() - 1
    plan = phase_plan(n, hi_bits, tile_bits, max_block_bytes)
    rows, block_rows = (1 << n) // lanes, (1 << plan.cut) // lanes
    state = pl.BlockSpec((2, block_rows, lanes),
                         lambda g: (0, plan.state_block(g), 0))
    phases = pl.BlockSpec((2, tile_rows << len(plan.inner), lanes),
                          lambda g: (0, plan.table_block(g), 0))
    return pl.pallas_call(
        functools.partial(_phase_kernel, plan=plan, tile=(tile_rows, lanes)),
        grid=(plan.steps,),
        in_specs=[phases, state],
        out_specs=state,
        out_shape=jax.ShapeDtypeStruct((2, rows, lanes), jnp.float32),
        interpret=interpret,
        name="phase",
        metadata=scopes.kernel_metadata(steps=plan.steps,
                                        block_bytes=8 << plan.cut),
    )(table, data.reshape(2, rows, lanes))
