"""Distributed state-vector simulation — multi-chip/multi-pod scaling.

The paper parallelizes state groups over threads (§IV) and scales to 288
threads / 4 NUMA domains on JUPITER.  The multi-device analogue shards the
planar state over the mesh: the top ``d = log2(#devices)`` *physical* qubit
positions are "global" — their bits select the device (mpiQulacs-style).

Gates on local positions run embarrassingly parallel inside ``shard_map``.
Gates touching a global position are preceded by a **qubit-block swap**: a
tiled ``all_to_all`` along the owning mesh axis exchanges that axis's bit
block with a block of high local bits.  The logical→physical permutation is
tracked at trace time and *left in place* after the gate (lazy unswapping),
so a window of gates on the same formerly-global qubits pays one collective —
the collective-amortization analogue of the paper's gate-fusion AI adaptation.

Everything here is pure pjit/shard_map + jax.lax collectives; the same code
lowers for the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import apply as A
from repro.core import fusion as F
from repro.core import scopes
from repro.core.circuits import Circuit
from repro.core.gates import Gate
from repro.core.target import Target, row_budget

# Mesh axis names used by the engine's sharded plan execution
# (``CompiledPlan.run_sharded_batch_raw``): the batch axis shards whole
# states of a parameter sweep, the state axis shards the row dimension of
# each state (its bits become the top "global" qubit positions).
BATCH_AXIS = "shard_batch"
STATE_AXIS = "shard_state"

# Per-device row budget for the batch-first spill policy: a 26-qubit planar
# state is 2 * 4 B * 2**26 = 512 MiB of f32 planes per device — a sensible
# single-device ceiling for both the CPU container and one TPU core's HBM
# slice.  Overridable per executor via ``max_local_qubits``.
DEFAULT_MAX_LOCAL_QUBITS = 26


# -- reusable collective machinery --------------------------------------------
#
# ``swap_block`` / ``pick_victim`` are the qubit-block-swap primitives shared
# by :class:`DistributedSimulator` (gate-by-gate path) and the engine's
# sharded plan execution (``repro.engine.plan``): one tiled ``all_to_all``
# exchanges a mesh axis's bit block with a contiguous block of local bits,
# and Belady victim selection decides *which* local block so that the lazily
# tracked logical->physical permutation amortizes collectives across runs of
# gates on the same formerly-global qubits.

def swap_block(data: jax.Array, axis: str, n_local: int, local_lo: int,
               a_bits: int) -> jax.Array:
    """``all_to_all`` swap of mesh-axis bits with the local bit block
    ``[local_lo, local_lo + a_bits)``.

    ``data``'s trailing dimensions must flatten to ``2**n_local`` local
    amplitudes (the planar ``(R_local, V)`` tile or any reshape of it);
    arbitrary leading axes (planes, batch) are preserved, so the same
    primitive serves the single-state and the batched sharded paths.  Its
    operations carry ``repro_part="collective"``
    (:mod:`repro.core.scopes`).
    """
    shape = data.shape
    pre = 1 << (n_local - local_lo - a_bits)
    mid = 1 << a_bits
    post = 1 << local_lo
    lanes = min(post, shape[-1])          # keep the lane axis whole
    with scopes.device_scope(part="collective"):
        x = data.reshape(-1, pre, mid, post // lanes, lanes)
        x = jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=2,
                               tiled=True)
        return x.reshape(shape)


def pick_victim(needed: Sequence[int], a_bits: int, top: int,
                score=None, floor: int = 0) -> int:
    """Contiguous ``a_bits``-wide local bit block in ``[0, top)`` avoiding
    every position in ``needed``; with a ``score`` function, the candidate
    whose resident logical qubits are needed furthest in the future wins
    (Belady eviction — minimizes swap thrash).

    Lane bits are legitimate victims too: a device-bit block swapped into
    lane positions simply routes later gates on those logical qubits through
    the lane path.  Blocks at or above ``floor`` are preferred whatever
    their score (callers keep the vector tile out of the exchange).  Raises
    ``ValueError`` when no block fits.
    """
    best = None
    for blk in range(top - a_bits, -1, -1):
        if any(blk <= p < blk + a_bits for p in needed):
            continue
        if score is None:
            return blk
        s = (blk >= floor, score(blk))
        if best is None or s > best[0]:
            best = (s, blk)
    if best is None:
        raise ValueError("no local bit block available for global-qubit swap")
    return best[1]


def swap_perm(perm: Sequence[int], block_lo: int, local_lo: int,
              a_bits: int) -> list[int]:
    """Update a logical->physical permutation for a block swap exchanging
    positions ``[block_lo, block_lo + a_bits)`` with ``[local_lo, ...)``."""
    remap = {}
    for o in range(a_bits):
        remap[block_lo + o] = local_lo + o
        remap[local_lo + o] = block_lo + o
    return [remap.get(p, p) for p in perm]


# -- mesh layout planning ------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """How the engine splits a device mesh between batch and state sharding.

    ``batch_shards`` devices split the batch axis of a parameter sweep;
    ``2**state_bits`` devices shard each state's row axis (mpiQulacs-style:
    the top ``state_bits`` physical qubit positions select the device).
    """

    batch_shards: int = 1
    state_bits: int = 0

    @property
    def state_shards(self) -> int:
        return 1 << self.state_bits

    @property
    def devices(self) -> int:
        return self.batch_shards << self.state_bits

    @property
    def shape(self) -> tuple[int, int]:
        return (self.batch_shards, self.state_shards)

    @property
    def is_single(self) -> bool:
        return self.devices == 1


def _pow2_ceil(x: int) -> int:
    return 1 << (x - 1).bit_length() if x >= 2 else 1


def max_state_bits(n: int, target: Target) -> int:
    """Largest state-sharding degree an ``n``-qubit plan supports.

    Constraints, in local-qubit terms (``n_local = n - s``): the
    fused-cluster width cap must stay >= 2 *after* reserving an ``s``-bit
    victim block for qubit-block swaps (``n_local - max(s, lane_qubits) >=
    2``), and ``n_local >= 2 s`` so a victim window always exists next to
    any (compacted) set of at most ``s`` protected bit positions — the
    guarantee the trailing permutation-restore swaps rely on.
    """
    s = 0
    while (n - (s + 1) - max(s + 1, target.lane_qubits) >= 2
           and n - (s + 1) >= 2 * (s + 1)):
        s += 1
    return s


def plan_shard_layout(n: int, batch: int | None, devices: int,
                      target: Target,
                      max_local_qubits: int | None = None) -> ShardSpec:
    """Batch-first device split: shard the batch axis, and spill into state
    sharding only when ``n`` exceeds the per-device row budget.

    ``batch=None`` means a single-circuit run (``Simulator.run``): there is
    no batch axis to shard, so by default the whole mesh goes to state
    sharding (clamped by :func:`max_state_bits`) — that is what passing a
    mesh to a single-circuit run asks for — unless ``max_local_qubits`` is
    explicitly set, in which case the spill rule applies there too.
    Otherwise ``state_bits`` is the smallest degree that brings the
    per-device sub-state under ``max_local_qubits`` (default
    :data:`DEFAULT_MAX_LOCAL_QUBITS`), and the remaining devices shard the
    batch axis — capped at the next power of two of ``batch`` so a small
    sweep is not padded across the whole mesh.
    """
    if devices < 1 or (devices & (devices - 1)):
        raise ValueError(f"device count must be a power of two, got {devices}")
    dbits = devices.bit_length() - 1
    cap = min(dbits, max_state_bits(n, target))
    if batch is None:
        state_bits = cap if max_local_qubits is None else \
            min(cap, max(0, n - max_local_qubits))
        batch_shards = 1
    else:
        max_local = (DEFAULT_MAX_LOCAL_QUBITS if max_local_qubits is None
                     else max_local_qubits)
        state_bits = min(cap, max(0, n - max_local))
        batch_shards = min(devices >> state_bits,
                           _pow2_ceil(max(1, batch)))
    if max_local_qubits is not None and n - state_bits > max_local_qubits:
        # the split is best-effort (bounded by device count and
        # max_state_bits), but an explicitly configured memory budget
        # being exceeded must not pass silently
        import warnings
        warnings.warn(
            f"shard layout cannot meet max_local_qubits={max_local_qubits}: "
            f"n={n} over {devices} devices leaves {n - state_bits} local "
            f"qubits per device", RuntimeWarning, stacklevel=2)
    return ShardSpec(batch_shards=batch_shards, state_bits=state_bits)


def device_pool(mesh) -> list:
    """Resolve a ``mesh=`` option (device count or ``jax.sharding.Mesh``)
    to the device list the layout planner splits.

    The one place the engine validates and normalizes mesh inputs —
    ``BatchExecutor`` and ``Simulator`` both route through it, so their
    sharded paths can never drift on what a mesh option means.  The count
    must be a power of two (the layout planner splits power-of-two grids);
    a non-conforming request is rejected rather than silently truncated.
    """
    if isinstance(mesh, int):
        avail = jax.devices()
        if not 1 <= mesh <= len(avail):
            raise ValueError(
                f"mesh={mesh} devices requested, {len(avail)} available")
        pool = avail[:mesh]
    else:                          # a jax.sharding.Mesh: reuse its devices
        pool = list(np.asarray(mesh.devices).flat)
    if not pool or len(pool) & (len(pool) - 1):
        raise ValueError(
            f"mesh device count must be a power of two, got {len(pool)}")
    return pool


def make_sim_mesh(spec: ShardSpec, devices: Sequence | None = None) -> Mesh:
    """Build the two-axis ``(BATCH_AXIS, STATE_AXIS)`` mesh for a
    :class:`ShardSpec` from the first ``spec.devices`` available devices."""
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < spec.devices:
        raise ValueError(
            f"shard layout needs {spec.devices} devices "
            f"({spec.batch_shards} batch x {spec.state_shards} state), "
            f"have {len(devs)}")
    grid = np.array(devs[:spec.devices]).reshape(spec.shape)
    return Mesh(grid, (BATCH_AXIS, STATE_AXIS))


@dataclasses.dataclass(frozen=True)
class MeshLayout:
    """How mesh axes map onto global qubit-bit blocks (top bits first)."""
    axes: tuple[str, ...]          # mesh axis names, outermost first
    bits: tuple[int, ...]          # log2(size) per axis

    @property
    def total_bits(self) -> int:
        return sum(self.bits)

    def axis_bit_range(self, i: int, n: int) -> tuple[int, int]:
        """Physical bit positions [lo, hi) owned by mesh axis i (n qubits)."""
        hi = n - sum(self.bits[:i])
        return hi - self.bits[i], hi


def mesh_layout(mesh: Mesh) -> MeshLayout:
    axes = tuple(mesh.axis_names)
    bits = tuple(int(math.log2(mesh.shape[a])) for a in axes)
    for a, b in zip(axes, bits):
        if (1 << b) != mesh.shape[a]:
            raise ValueError(f"mesh axis {a} size must be a power of two")
    return MeshLayout(axes, bits)


class DistributedSimulator:
    """Builds a single jittable, shard_map'ped function for a whole circuit."""

    def __init__(self, n: int, mesh: Mesh, target: Target,
                 f: int | None = None, fuse: bool = True):
        self.n = n
        self.mesh = mesh
        self.target = target
        self.layout = mesh_layout(mesh)
        self.d = self.layout.total_bits
        self.v = target.lane_qubits
        if n - self.d < self.v:
            raise ValueError(
                f"state too small to shard: n={n}, device bits={self.d}, "
                f"lane bits={self.v}")
        self.f = f if f is not None else (F.choose_f(target) if fuse else 0)
        self.fuse = fuse
        self.n_local = n - self.d
        self.spec = P(None, self.layout.axes, None)

    # -- state ------------------------------------------------------------
    def global_state_shape(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(
            (2, 1 << (self.n - self.v), 1 << self.v), jnp.float32)

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec)

    def zero_state(self) -> jax.Array:
        shape = self.global_state_shape().shape

        def init():
            z = jnp.zeros(shape, jnp.float32)
            return z.at[0, 0, 0].set(1.0)

        return jax.jit(init, out_shardings=self.sharding())()

    # -- circuit compilation ----------------------------------------------
    def prepare(self, circuit: Circuit) -> list[Gate]:
        if not self.fuse:
            return list(circuit.gates)
        # width cap: the *local* sub-state's row budget (see
        # repro.core.target.row_budget for the canonical rule)
        f = max(2, min(self.f, row_budget(self.n_local, self.target)))
        return F.fuse_circuit(circuit.gates, f)

    def build_step(self, circuit: Circuit):
        """Return (jitted_fn, gate_arrays, swap_count).

        jitted_fn(state_data, *u_planes) applies the whole fused circuit.
        The logical->physical permutation is tracked at trace time; the
        returned state is in *physical* order with ``final_perm`` recorded
        on the simulator for readout.
        """
        gates = self.prepare(circuit)
        u_planes: list[jax.Array] = []
        for g in gates:
            m = np.asarray(g.matrix)
            u_planes.append(jnp.asarray(
                np.stack([m.real, m.imag]), jnp.float32))

        n, d, v = self.n, self.d, self.v
        layout = self.layout
        swap_counter = {"swaps": 0}
        final_perm: list[int] = []

        # Belady lookahead: for victim selection, know when each logical
        # qubit is next used (evict the block whose residents are needed
        # furthest in the future — minimizes swap thrash).
        touch_idx: dict[int, list[int]] = {q: [] for q in range(n)}
        for gi, g in enumerate(gates):
            for q in g.qubits + g.controls:
                touch_idx[q].append(gi)

        def next_use(q: int, after: int) -> int:
            import bisect
            lst = touch_idx[q]
            j = bisect.bisect_left(lst, after)
            return lst[j] if j < len(lst) else len(gates) + n

        def local_fn(data, *planes):
            # data: local block f32[2, R_local, V]; logical q -> perm[q]
            perm = list(range(n))
            swaps = 0
            for gi, (g, up) in enumerate(zip(gates, planes)):
                phys = [perm[q] for q in g.qubits]
                cphys = [perm[q] for q in g.controls]
                # Global *targets* must be swapped down into local bits.
                # Global *controls* need no data movement: the control bit is
                # constant per device, so the gate applies under a per-device
                # predicate (zero-communication, the distributed analogue of
                # the paper's predicated iteration).
                for ai in range(len(layout.axes)):
                    lo, hi = layout.axis_bit_range(ai, n)
                    if not any(lo <= p < hi for p in phys):
                        continue
                    a_bits = layout.bits[ai]
                    needed = phys + [p for p in cphys if p < n - d]
                    inv = [0] * n
                    for q, p in enumerate(perm):
                        inv[p] = q
                    tgt = self._pick_victim(
                        needed, a_bits,
                        score=lambda blk: min(
                            next_use(inv[p], gi + 1)
                            for p in range(blk, blk + a_bits)))
                    data = self._swap_block(
                        data, layout.axes[ai], lo, tgt, a_bits)
                    # update permutation: positions lo..hi <-> tgt..
                    perm = swap_perm(perm, lo, tgt, a_bits)
                    swaps += 1
                    phys = [perm[q] for q in g.qubits]
                    cphys = [perm[q] for q in g.controls]
                local_ctrl = tuple(p for p in cphys if p < n - d)
                glob_ctrl = [p for p in cphys if p >= n - d]

                def apply(dat, phys=tuple(phys), lc=local_ctrl, up=up):
                    return A.apply_gate_planar(dat, n - d, phys,
                                               up[0], up[1], controls=lc)

                if glob_ctrl:
                    pred = None
                    for p in glob_ctrl:
                        for ai in range(len(layout.axes)):
                            lo, hi = layout.axis_bit_range(ai, n)
                            if lo <= p < hi:
                                idx = jax.lax.axis_index(layout.axes[ai])
                                bit = (idx >> (p - lo)) & 1
                                cond = bit == 1
                                pred = cond if pred is None else \
                                    jnp.logical_and(pred, cond)
                    data = jax.lax.cond(pred, apply, lambda dat: dat, data)
                else:
                    data = apply(data)
            swap_counter["swaps"] = swaps
            final_perm[:] = perm
            return data

        fn = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(self.spec,) + (P(),) * len(u_planes),
            out_specs=self.spec)
        jitted = jax.jit(fn, donate_argnums=(0,))
        return jitted, u_planes, swap_counter, final_perm

    def _pick_victim(self, needed: list[int], a_bits: int,
                     score=None) -> int:
        """Module-level :func:`pick_victim` over this simulator's local bits."""
        return pick_victim(needed, a_bits, self.n - self.d, score=score)

    def _swap_block(self, data: jax.Array, axis: str, axis_lo: int,
                    local_lo: int, a_bits: int) -> jax.Array:
        """Module-level :func:`swap_block` over this simulator's local bits."""
        return swap_block(data, axis, self.n - self.d, local_lo, a_bits)

    # -- end-to-end helper --------------------------------------------------
    def run(self, circuit: Circuit, state: jax.Array | None = None):
        if state is None:
            state = self.zero_state()
        fn, planes, swap_counter, final_perm = self.build_step(circuit)
        out = fn(state, *planes)
        return out, final_perm, swap_counter

    def to_dense(self, data: jax.Array, perm: Sequence[int]) -> jax.Array:
        """Gather to host and undo the physical permutation (readout path)."""
        flat = np.asarray(jax.device_get(data)).reshape(2, -1)
        psi = flat[0] + 1j * flat[1]
        if list(perm) != list(range(self.n)):
            psi = _permute(psi, perm, self.n)
        return jnp.asarray(psi)


def _permute(psi: np.ndarray, perm: Sequence[int], n: int) -> np.ndarray:
    """Reorder amplitudes so logical qubit q sits at bit q."""
    src = np.arange(1 << n)
    dst = np.zeros_like(src)
    for q in range(n):
        dst |= ((src >> perm[q]) & 1) << q
    out = np.empty_like(psi)
    out[dst] = psi
    return out
