"""Plan compiler + cache: one fused, jitted program per circuit structure.

``compile_plan`` runs fusion clustering once per :class:`CircuitTemplate`
structure and lowers the fused gate sequence into a *single* jitted program
``(state, params) -> state`` for the chosen backend (dense / planar /
pallas).  Parameterized rotations are spliced into their fused clusters as
traced matrices — constant member gates are folded into numpy constants at
compile time, so the per-binding work inside the program is a handful of
2x2-sized complex products before each fused gate application.

``PlanCache`` memoizes compiled plans by structure hash and execution config,
replacing the per-gate ``_jit_*`` lru_caches the simulator used to keep:
a parameter sweep of B structurally identical circuits costs one fusion pass
and one XLA compile instead of B of each.

Sharded execution (``CompiledPlan.run_sharded_batch_raw``) lowers the same
plan items inside ``shard_map`` over a two-axis device mesh: the batch axis
splits the parameter sweep, and the state axis shards each state's row
dimension so the top ``state_bits`` physical qubit positions select the
device (mpiQulacs-style, see ``repro.core.distributed``).  Items touching a
global position are preceded by one qubit-block-swap ``all_to_all``; the
logical->physical permutation is tracked at trace time and left in place
(lazy unswapping), so a run of items on the same formerly-global qubits pays
one collective — the collective analogue of the paper's fusion-based
arithmetic-intensity adaptation (§IV-D).  Plans compiled for a sharded mesh
use the *local* row budget ``n - state_bits - lane_qubits``
(:func:`repro.core.target.row_budget`), which is why plan-cache keys are
mesh-shape-aware.

Every operation a program emits carries device attributes
(:mod:`repro.core.scopes`): each gate or channel item's operations
``repro_item`` (its index in ``CompiledPlan.items``), ``repro_kind``,
``repro_width`` and ``repro_part`` (``apply``, or ``exchange`` inside
``core.apply.exchange``); the result epilogue's ``repro_kind="epilogue"``
and ``repro_term`` (``z`` on the pass shared by every Z-string, the
observable's index on each other string's reduction); the zero-state build
``repro_kind="init"``.  ``run``
puts its host stages in the profiler trace as ``repro.*`` spans.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import threading
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import apply as A
from repro.core import distributed as D
from repro.core import measure as ME
from repro.core import scopes
from repro.core import statevec as SV
from repro.core.circuits import Circuit
from repro.core.fusion import choose_f, cluster_gates, realize_cluster
from repro.core.gates import (Gate, expand_unitary, gate_class,
                              monomial_decompose)
from repro.core.target import Target, resolve_interpret, row_budget
from repro.engine.telemetry import Histogram, vectorization_profile
from repro.engine.template import PARAM_KINDS, CircuitTemplate, TemplateOp

# Structural class of a parameterized op, valid for *every* angle — the dummy
# binding used for clustering sees rx(0) = I, which would misclassify rx as
# diagonal, so the class must come from the op kind, not the bound matrix.
PARAM_OP_CLASS = {"rz": "diagonal", "phase": "diagonal",
                  "rx": "general", "ry": "general"}

# Diagonal param kinds are pure phases exp(i * theta * c[bit]): rz_m is
# diag(e^{-i theta/2}, e^{+i theta/2}), phase_m is diag(1, e^{i phi}).  The
# specialized lowering turns each such member into a static per-row angle
# coefficient vector, so a binding costs one axpy per rotation plus a single
# cos/sin — no matrix construction, no gathers from traced arrays.
DIAG_PARAM_COEFF = {"rz": (-0.5, 0.5), "phase": (0.0, 1.0)}

# Distinct fold-in salts for the result-mode program's PRNG streams: one
# base key per row (request key + trajectory index) splits into independent
# channel-trajectory and shot-sampling streams.
_CHANNEL_SALT = 0x00C0FFEE  # + channel index
_SHOT_SALT = 0x5A17


@functools.lru_cache(maxsize=4096)
def _embed_maps(sub_qubits: tuple[int, ...], full_qubits: tuple[int, ...],
                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static gather maps embedding a small unitary into a cluster space.

    For ``u`` on ``sub_qubits`` inside ``full_qubits`` the expanded matrix is
    ``where(mask, u[sr, sc], 0)`` — i.e. ``expand_unitary`` as one traced
    gather, usable on jit/vmap-traced matrices.
    """
    pos = {q: i for i, q in enumerate(full_qubits)}
    sub_pos = np.array([pos[q] for q in sub_qubits], np.int64)
    rest_pos = np.array([i for i in range(len(full_qubits))
                         if i not in set(sub_pos.tolist())], np.int64)
    idx = np.arange(1 << len(full_qubits), dtype=np.int64)

    def gather_bits(positions):
        out = np.zeros_like(idx)
        for bi, p in enumerate(positions):
            out |= ((idx >> p) & 1) << bi
        return out

    sub = gather_bits(sub_pos)
    rest = gather_bits(rest_pos)
    mask = rest[:, None] == rest[None, :]
    sr = np.broadcast_to(sub[:, None], mask.shape)
    sc = np.broadcast_to(sub[None, :], mask.shape)
    return mask, sr, sc


def _param_matrix(op: TemplateOp, params) -> jax.Array:
    return PARAM_KINDS[op.kind].jax_fn(op.scale * params[op.param])


@functools.lru_cache(maxsize=4096)
def _sub_index_map(sub_qubits: tuple[int, ...], full_qubits: tuple[int, ...],
                   ) -> np.ndarray:
    """int64[2**w]: the sub-space index formed by ``sub_qubits``' bits at
    each index of the ``full_qubits`` cluster space."""
    pos = {q: i for i, q in enumerate(full_qubits)}
    idx = np.arange(1 << len(full_qubits), dtype=np.int64)
    out = np.zeros_like(idx)
    for bi, q in enumerate(sub_qubits):
        out |= ((idx >> pos[q]) & 1) << bi
    return out


def _amp_cluster_index(qubits: tuple[int, ...], n: int) -> np.ndarray:
    """int32[2**n]: the cluster-space index of each dense amplitude (qubit
    ``q`` is bit ``q`` of the amplitude index; cluster bit ``m`` is
    ``qubits[m]``) — ``_sub_index_map`` over the full amplitude space."""
    return _sub_index_map(qubits, tuple(range(n))).astype(np.int32)


@functools.lru_cache(maxsize=4096)
def _phase_broadcast_shapes(qubits: tuple[int, ...], n: int,
                            ) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """``(dims, bshape)``: factorize the flat ``2**n`` amplitude axis (MSB
    first) with maximal contiguous runs of cluster qubits merged into single
    axes.  A diagonal application is then ``state.reshape(dims) *
    phase.reshape(bshape)`` — a reshape + broadcast elementwise multiply
    with no gather and no moveaxis; a cluster of low qubits collapses to
    just two axes."""
    dims: list[int] = []
    bshape: list[int] = []
    qs = sorted(qubits, reverse=True)
    prev = n
    i = 0
    while i < len(qs):
        j = i
        while j + 1 < len(qs) and qs[j + 1] == qs[j] - 1:
            j += 1
        hi, lo = qs[i], qs[j]
        seg = prev - hi - 1
        if seg > 0:
            dims.append(1 << seg)
            bshape.append(1)
        dims.append(1 << (hi - lo + 1))
        bshape.append(1 << (hi - lo + 1))
        prev = lo
        i = j + 1
    if prev > 0:
        dims.append(1 << prev)
        bshape.append(1)
    return tuple(dims), tuple(bshape)


@functools.lru_cache(maxsize=4096)
def _diag_layout(qubits: tuple[int, ...], n: int, v: int) -> tuple:
    """``(dims, tshape, lane_map)`` for a diagonal over sorted ``qubits`` on
    the lane-tiled planar state (``v`` lane qubits).

    The row index is factorized by :func:`_phase_broadcast_shapes` over the
    cluster's row bits and the lane axis stays whole (``dims``), so no view
    has a minor axis narrower than a vector tile.  Cluster bits in the lane
    axis are folded into the phase table instead: ``lane_map[l]`` is the
    lane-bit part of the cluster index at lane ``l``, and the table has
    shape ``tshape`` (one full lane row per row-bit pattern).
    """
    lanes = [q for q in qubits if q < v]
    rows = tuple(q - v for q in qubits if q >= v)
    rdims, rshape = _phase_broadcast_shapes(rows, n - v)
    lane_map = None
    if lanes:
        ln = np.arange(1 << v)
        lane_map = sum(((ln >> q) & 1) << m for m, q in enumerate(lanes))
    return (rdims + (1 << v,), rshape + ((1 << v) if lanes else 1,),
            lane_map)


def _diag_planes(vec, layout):
    """Shape a ``2**w`` phase plane (numpy or traced; cluster bit ``m`` <->
    sorted qubit ``m``, so lane bits are the low bits) into the broadcast
    table of :func:`_diag_layout`."""
    _, tshape, lane_map = layout
    if lane_map is None:
        return vec.reshape(tshape)
    lanes_w = 1 << (int(lane_map.max()).bit_length())
    return vec.reshape(-1, lanes_w)[:, lane_map].reshape(tshape)


def _apply_phase(data, pr, pi, layout):
    """Rotate every amplitude of the planar state by the broadcast phase
    table of :func:`_diag_planes` (6 real flops per amplitude); the result
    is in the table's view ``(2, *layout[0])``."""
    t = data.reshape((2,) + layout[0])
    re, im = t[0], t[1]
    return jnp.stack([pr * re - pi * im, pr * im + pi * re])


def _member_monomial(g: Gate, full_qubits: tuple[int, ...],
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Lift a diagonal/monomial member gate into cluster space as
    ``(P, phi)`` with ``out[x] = phi[x] * in[P[x]]``."""
    perm_s, phase_s = monomial_decompose(g.matrix)
    sub = _sub_index_map(g.qubits, full_qubits)
    pos = {q: i for i, q in enumerate(full_qubits)}
    mask = 0
    for q in g.qubits:
        mask |= 1 << pos[q]
    x = np.arange(1 << len(full_qubits), dtype=np.int64)
    src = perm_s[sub]                       # sub-space source per cluster index
    scat = np.zeros_like(x)
    for bi, q in enumerate(g.qubits):
        scat |= ((src >> bi) & 1) << pos[q]
    return (x & ~mask) | scat, phase_s[sub]


_IDENTITY_ATOL = 1e-6


@dataclasses.dataclass(frozen=True)
class PlanItem:
    """One fused gate application inside the compiled program.

    ``kind`` selects the lowering:

    * ``"dense"`` — generic ``2**w x 2**w`` complex matvec (4 real matmuls),
      built from ``factors``.
    * ``"diag"``  — elementwise phase rotation by ``phase_planes(params)``
      (6 real flops/amp, no moveaxis, no matmul).  Controls, if any, were
      folded into the phase vector, so ``controls`` is empty.
    * ``"perm"``  — static index-map gather ``perm`` over the cluster space,
      optionally followed by the phase rotation (monomial clusters).
    * ``"channel"`` — one Kraus noise channel, executed by stochastic
      trajectory unraveling: every operator in ``kraus`` is applied, one
      branch is sampled ~ its norm from the row's PRNG key, and the
      survivor is renormalized (result-mode plans only).
    * ``"result"`` — the terminal epilogue item carrying the
      :class:`~repro.engine.results.ResultSpec`: shot sampling or the
      observable reduction fused after the last gate, so non-statevector
      payloads never store the state back (paper §IV).
    """

    qubits: tuple[int, ...]
    controls: tuple[int, ...]
    factors: tuple = ()             # ("const", ndarray) | ("param", op, maps)
    kind: str = "dense"             # dense | diag | perm | channel | result
    perm: np.ndarray | None = None  # int32[2**w], kind == "perm" only
    phases: tuple = ()              # ("const", vec) | ("param", op, coeff)
    generic_flops: float | None = None  # flops/amp of the dense alternative
    kraus: tuple = ()               # complex64 operators, kind == "channel"
    result: object = None           # ResultSpec, kind == "result" only

    @property
    def is_constant(self) -> bool:
        return (all(f[0] == "const" for f in self.factors)
                and all(p[0] == "const" for p in self.phases))

    @property
    def has_param_phase(self) -> bool:
        return any(p[0] == "param" for p in self.phases)

    def unitary(self, params) -> jax.Array:
        """Fused complex64 unitary for one parameter vector (traceable)."""
        u = None
        for f in self.factors:
            if f[0] == "const":
                e = jnp.asarray(f[1])
            else:
                _, op, (mask, sr, sc) = f
                m2 = _param_matrix(op, params)
                e = jnp.where(jnp.asarray(mask), m2[(sr, sc)],
                              jnp.zeros((), jnp.complex64))
            u = e if u is None else jnp.matmul(e, u, precision=A.HIGHEST)
        return u.astype(jnp.complex64)

    def _phase_angle(self, params) -> jax.Array | None:
        """f32[2**w] accumulated rotation angle of the parameterized phase
        terms: one scalar-times-static-coefficient-vector axpy per term."""
        ang = None
        for p in self.phases:
            if p[0] != "param":
                continue
            _, op, coeff = p
            a = params[op.param] * jnp.asarray(coeff)
            ang = a if ang is None else ang + a
        return ang

    def _np_const_phase(self) -> np.ndarray | None:
        """Product of the constant phase entries (numpy), or None."""
        v = None
        for p in self.phases:
            if p[0] == "const":
                v = p[1] if v is None else (v * p[1]).astype(np.complex64)
        return v

    def phase_planes(self, params) -> tuple[jax.Array, jax.Array]:
        """f32 (re, im) planes of the phase vector — cos/sin directly, no
        complex intermediates (planar/pallas backends)."""
        const = self._np_const_phase()
        ang = self._phase_angle(params)
        if ang is None:
            return (jnp.asarray(np.real(const).astype(np.float32)),
                    jnp.asarray(np.imag(const).astype(np.float32)))
        c, s = jnp.cos(ang), jnp.sin(ang)
        if const is None:
            return c, s
        cr = jnp.asarray(np.real(const).astype(np.float32))
        ci = jnp.asarray(np.imag(const).astype(np.float32))
        return c * cr - s * ci, c * ci + s * cr

    def np_phase_vector(self) -> np.ndarray:
        """Constant phase vector as numpy (requires ``not has_param_phase``)."""
        v = np.ones(1 << len(self.qubits), np.complex64)
        for p in self.phases:
            if p[0] != "const":
                raise ValueError("parameterized phase needs phase_planes()")
            v = v * p[1]
        return v.astype(np.complex64)


def _lower_controlled_diag(g: Gate) -> PlanItem:
    """Lower a controlled cluster with a diagonal target into one phase
    vector over the full span (targets + controls): the full operator is
    diagonal — identity except where every control bit is set."""
    span = tuple(sorted(g.qubits + g.controls))
    pos = {q: i for i, q in enumerate(span)}
    cmask = 0
    for c in g.controls:
        cmask |= 1 << pos[c]
    idx = np.arange(1 << len(span), dtype=np.int64)
    sel = (idx & cmask) == cmask
    sub = _sub_index_map(g.qubits, span)
    phase = np.ones(1 << len(span), np.complex64)
    phase[sel] = np.diagonal(g.matrix)[sub[sel]]
    # the dense alternative is an 8*2^k matvec on the control-satisfied
    # 2^-c fraction of amplitudes
    generic = 8.0 * (1 << g.k) / (1 << len(g.controls))
    return PlanItem(span, (), kind="diag", phases=(("const", phase),),
                    generic_flops=generic)


def _lower_special(spec, prep: Sequence[Gate],
                   ops: Sequence[TemplateOp]) -> PlanItem | None:
    """Lower a diagonal/monomial cluster to a static index map + phase
    vector — the matmul-free fast path.

    The accumulated transform of the members applied so far is
    ``out[x] = phi[x] * in[pi[x]]`` with ``phi`` a product of one folded
    constant vector and per-parameterized-member diagonal gathers.  Applying
    the next member ``M = (P_M, phi_M)`` composes as ``phi' = phi_M *
    phi[P_M]``, ``pi' = pi[P_M]``; parameterized members (rz/phase) are
    purely diagonal, so their ``P_M`` is the identity and their traced phase
    joins as one more factor.  If the net permutation is the identity the
    cluster is *refined* to a pure diagonal (QAOA's CNOT·RZ·CNOT blocks);
    if the whole transform is the identity the item is elided entirely.
    """
    w = len(spec.qubits)
    pi = np.arange(1 << w, dtype=np.int64)
    const = np.ones(1 << w, np.complex64)
    params: list = []                # [op, coeff_vec f32] — mutable coeff
    for i in spec.members:
        op = ops[i]
        g = prep[i]
        if op.kind == "fixed":
            p_m, phi_m = _member_monomial(g, spec.qubits)
            const = (phi_m * const[p_m]).astype(np.complex64)
            for t in params:
                t[1] = t[1][p_m]
            pi = pi[p_m]
        else:
            if op.kind not in DIAG_PARAM_COEFF:
                raise AssertionError(
                    f"non-diagonal param op {op.kind!r} in special cluster")
            c0, c1 = DIAG_PARAM_COEFF[op.kind]
            bits = _sub_index_map(op.qubits, spec.qubits)
            coeff = (op.scale * np.where(bits == 1, c1, c0)).astype(np.float32)
            params.append([op, coeff])
    phases: list = []
    if np.abs(const - 1.0).max() > _IDENTITY_ATOL:
        phases.append(("const", const))
    phases += _merge_param_coeffs(params)
    is_id_perm = bool(np.array_equal(pi, np.arange(1 << w)))
    if is_id_perm and not phases:
        return None                        # identity cluster (e.g. CNOT·CNOT)
    generic = 8.0 * (1 << w)               # the dense matvec this replaces
    if is_id_perm:
        return PlanItem(spec.qubits, (), kind="diag", phases=tuple(phases),
                        generic_flops=generic)
    return PlanItem(spec.qubits, (), kind="perm", perm=pi.astype(np.int32),
                    phases=tuple(phases), generic_flops=generic)


def _merge_param_coeffs(terms) -> list:
    """Fold ``(op, coeff_vec)`` phase terms per distinct parameter index:
    ``exp(i p c1) exp(i p c2) = exp(i p (c1 + c2))`` — one axpy per
    *distinct* parameter, not per gate (QAOA: one term per cost layer
    instead of one per edge)."""
    merged: dict[int, list] = {}
    for op, coeff in terms:
        if op.param in merged:
            merged[op.param][1] = merged[op.param][1] + coeff
        else:
            merged[op.param] = [op, coeff]
    return [("param", op, coeff) for op, coeff in merged.values()]


def _merge_diag_items(run: list[PlanItem]) -> PlanItem:
    """Compose a run of consecutive diagonal items into one item over the
    union of their qubits: constants multiply, angle-coefficient vectors
    add (re-merged per distinct parameter)."""
    qubits = tuple(sorted(set().union(*[set(it.qubits) for it in run])))
    const = np.ones(1 << len(qubits), np.complex64)
    has_const = False
    terms: list = []
    for it in run:
        sub = _sub_index_map(it.qubits, qubits)
        for p in it.phases:
            if p[0] == "const":
                const = (const * p[1][sub]).astype(np.complex64)
                has_const = True
            else:
                _, op, coeff = p
                terms.append((op, coeff[sub].astype(np.float32)))
    phases: list = []
    if has_const:
        phases.append(("const", const))
    phases += _merge_param_coeffs(terms)
    generic = sum(it.generic_flops or 8.0 * (1 << len(it.qubits))
                  for it in run)
    return PlanItem(qubits, (), kind="diag", phases=tuple(phases),
                    generic_flops=generic)


def _coalesce_diag_runs(items: list[PlanItem],
                        max_width: int | None = None) -> list[PlanItem]:
    """Merge adjacent diagonal items (they commute and compose elementwise)
    into single full-width rotations: a QAOA cost stack that clustered into
    several row-budget-capped phase vectors becomes ONE state sweep — one
    cos/sin per distinct parameter, one rotation pass.  Used by the planar
    backend, whose diagonal application is pure elementwise arithmetic at
    any width; the pallas backend keeps per-item kernels so each block's
    phase vector stays within the VMEM budget.

    ``max_width`` bounds the merged span (state-sharded plans pass the
    diagonal width cap): an item's ``2**w`` phase vector is baked into the
    executable on *every* device, so a full-width merge at large ``n``
    would cost each device more constant memory than its local state block
    — the very thing state sharding exists to avoid.
    """
    out: list[PlanItem] = []
    run: list[PlanItem] = []
    run_qubits: set = set()

    def flush():
        if run:
            out.append(run[0] if len(run) == 1 else _merge_diag_items(run))
            run.clear()
            run_qubits.clear()

    for item in items:
        if item.kind == "diag":
            cand = run_qubits | set(item.qubits)
            if run and max_width is not None and len(cand) > max_width:
                flush()
                cand = set(item.qubits)
            run.append(item)
            run_qubits |= cand
            continue
        flush()
        out.append(item)
    flush()
    return out


def _lower_cluster(spec, prep: Sequence[Gate], ops: Sequence[TemplateOp],
                   diag_cap: int | None = None) -> PlanItem | None:
    """Fold a cluster into a plan item: the matmul-free diag/perm fast path
    when the cluster's class allows it (``diag_cap`` set = specialization
    on), else constant factors with param gates spliced in."""
    if spec.controls:
        # controlled clusters never contain parameterized members (param ops
        # are control-free, so clustering keeps them out) — fold in numpy.
        for i in spec.members:
            if ops[i].kind != "fixed":
                raise AssertionError("parameterized op in controlled cluster")
        g = realize_cluster(spec, prep)
        if (diag_cap is not None and spec.cls == "diagonal"
                and g.k + len(g.controls) <= diag_cap):
            return _lower_controlled_diag(g)
        return PlanItem(g.qubits, g.controls, (("const", g.matrix),))

    if diag_cap is not None and spec.cls in ("diagonal", "permutation"):
        return _lower_special(spec, prep, ops)

    factors: list = []
    acc: np.ndarray | None = None
    for i in spec.members:
        op = ops[i]
        g = prep[i]
        if op.kind == "fixed":
            e = expand_unitary(g.qubits, g.matrix, spec.qubits)
            acc = e if acc is None else (e @ acc).astype(np.complex64)
        else:
            if acc is not None:
                factors.append(("const", acc))
                acc = None
            factors.append(
                ("param", op, _embed_maps(op.qubits, spec.qubits)))
    if acc is not None or not factors:
        factors.append(("const", acc if acc is not None
                        else np.eye(1 << len(spec.qubits), dtype=np.complex64)))
    return PlanItem(spec.qubits, (), tuple(factors))


def _lower_single(op: TemplateOp, g: Gate) -> PlanItem:
    """Lower one unfused gate (dense baseline / fuse=False paths)."""
    if op.kind == "fixed":
        return PlanItem(g.qubits, g.controls, (("const", g.matrix),))
    k = len(op.qubits)
    ident = tuple(range(k))
    return PlanItem(op.qubits, op.controls,
                    (("param", op, _embed_maps(ident, ident)),))


def _full_perm_map(qubits: tuple[int, ...], n: int,
                   perm: np.ndarray) -> np.ndarray:
    """int32[2**n]: lift a cluster-space permutation to the full amplitude
    space (identity on non-cluster bits)."""
    sub = _amp_cluster_index(qubits, n).astype(np.int64)
    src = perm.astype(np.int64)[sub]
    mask = 0
    for q in qubits:
        mask |= 1 << q
    idx = np.arange(1 << n, dtype=np.int64)
    scat = np.zeros_like(idx)
    for bi, q in enumerate(qubits):
        scat |= ((src >> bi) & 1) << q
    return ((idx & ~mask) | scat).astype(np.int32)


def _flip_bits(data, n: int, v: int, qubits: tuple[int, ...]):
    """X on every qubit in ``qubits``: a reversal of their axes in the
    span view, no gather.  Lane bits are first exchanged with a free block
    of row bits (as for dense items), so only row axes are reversed and the
    lane axis stays whole."""
    s = A.lane_window(n, v, qubits)
    if s is not None:
        data = A.exchange(data, n, 0, v, s)
        qubits = tuple(q + s if q < v else q for q in qubits)
    dims, axis = A.span_view(n, qubits)
    out = jnp.flip(data.reshape((2,) + dims),
                   axis=[1 + axis[q] for q in qubits])
    return out if s is None else A.exchange(out, n, 0, v, s)


def _planar_special_step(item: PlanItem, n: int, v: int):
    """Planar program step for a diag/perm item on an ``n``-qubit state
    with ``v`` lane qubits.

    Parameterized by ``n`` rather than the plan's qubit count so the sharded
    path can build the same step on the ``n - state_bits``-qubit local block
    a ``shard_map`` device sees (after relabeling the item's cluster bits
    onto physical positions with :func:`_relabel_special_item`).
    An XOR-mask permutation (X layers, composed bit flips) is an axis
    reversal (:func:`_flip_bits`); any other permutation is one static take
    over the flat amplitude axis.  The step takes the state in any shape
    that flattens to ``(2, 2**n)`` and returns it in the view of its last
    operation (see ``repro.core.apply.exchange``).
    """
    layout = _diag_layout(item.qubits, n, v)
    has_phase = bool(item.phases)
    const_phase = (item.np_phase_vector()
                   if has_phase and not item.has_param_phase else None)
    src = flip_qs = None
    if item.perm is not None:
        mask = int(item.perm[0])
        if np.array_equal(item.perm, np.arange(len(item.perm)) ^ mask):
            flip_qs = tuple(q for m, q in enumerate(item.qubits)
                            if (mask >> m) & 1)
        else:
            src = _full_perm_map(item.qubits, n, item.perm)
    if const_phase is not None:
        pr_np = _diag_planes(np.real(const_phase).astype(np.float32), layout)
        pi_np = _diag_planes(np.imag(const_phase).astype(np.float32), layout)

    def step(data, params):
        if flip_qs is not None:
            data = _flip_bits(data, n, v, flip_qs)
        elif src is not None:
            data = data.reshape(2, -1)[:, src]
        if not has_phase:
            return data
        if const_phase is not None:
            pr, pi = jnp.asarray(pr_np), jnp.asarray(pi_np)
        else:
            pr_w, pi_w = item.phase_planes(params)
            pr, pi = _diag_planes(pr_w, layout), _diag_planes(pi_w, layout)
        return _apply_phase(data, pr, pi, layout)
    return step


# -- sharded execution helpers -------------------------------------------------

def _relabel_special_item(item: PlanItem, phys: tuple[int, ...]) -> PlanItem:
    """Relabel a diag/perm item's cluster bits onto physical positions.

    Inside the sharded program logical qubit ``item.qubits[m]`` lives at
    physical position ``phys[m]`` (the trace-time permutation).  The item's
    static phase vectors / coefficient vectors / index map are indexed by
    cluster bits in ``item.qubits`` order, so they are re-gathered onto the
    sorted physical positions — a pure numpy transform at trace time.
    """
    if phys == item.qubits:
        return item
    w = len(phys)
    order = tuple(int(i) for i in np.argsort(np.asarray(phys)))
    y = np.arange(1 << w, dtype=np.int64)
    gmap = np.zeros_like(y)             # new cluster index -> old cluster index
    for j, m in enumerate(order):
        gmap |= ((y >> j) & 1) << m
    phases = []
    for p in item.phases:
        if p[0] == "const":
            phases.append(("const", p[1][gmap].astype(np.complex64)))
        else:
            _, op, coeff = p
            phases.append(("param", op, coeff[gmap].astype(np.float32)))
    perm = None
    if item.perm is not None:
        ginv = np.zeros_like(gmap)
        ginv[gmap] = y
        perm = ginv[item.perm.astype(np.int64)[gmap]].astype(np.int32)
    return dataclasses.replace(item, qubits=tuple(sorted(phys)),
                               phases=tuple(phases), perm=perm)


def _local_perm_map(rho: tuple[int, ...]) -> np.ndarray:
    """int32 gather map applying the bit-position permutation ``rho``
    (content at position ``p`` moves to position ``rho[p]``) to a flat
    amplitude axis: ``out[y] = in[map[y]]``."""
    n_local = len(rho)
    y = np.arange(1 << n_local, dtype=np.int64)
    x = np.zeros_like(y)
    for p in range(n_local):
        x |= ((y >> rho[p]) & 1) << p
    return x.astype(np.int32)


def _apply_local_bit_perm(data: jax.Array, rho: Sequence[int]) -> jax.Array:
    """Apply a local bit-position permutation as one static gather over the
    flattened trailing (row, lane) axes; leading axes are preserved.  Its
    operations carry ``repro_part="exchange"``."""
    rho = tuple(rho)
    if rho == tuple(range(len(rho))):
        return data
    m = _local_perm_map(rho)
    shape = data.shape
    with scopes.device_scope(part="exchange"):
        flat = data.reshape(shape[:-2] + (-1,))
        return flat[..., m].reshape(shape)


def _compact_rho(needed: Sequence[int], n_local: int) -> tuple[int, ...]:
    """Local bit-position permutation packing ``needed`` local positions
    into the low bits (relative order kept): scattered positions can block
    every contiguous victim window even when enough free bits exist, and
    one static gather un-blocks them."""
    uniq = sorted(p for p in set(needed) if p < n_local)
    rho = {p: j for j, p in enumerate(uniq)}
    nxt = len(uniq)
    for p in range(n_local):
        if p not in rho:
            rho[p] = nxt
            nxt += 1
    return tuple(rho[p] for p in range(n_local))


def _sharded_diag_step(item: PlanItem, phys: tuple[int, ...], n_local: int,
                       v: int):
    """Diagonal item with cluster bits on *global* positions: applied with
    zero communication.

    A phase rotation is elementwise, and a global position's bit value is
    constant per device (it is a bit of the device index), so each device
    just selects its slice of the ``2**w`` phase vector: a static base map
    over the local cluster bits plus a traced ``axis_index`` offset for the
    global ones.  This is why a coalesced full-width diagonal run — wider
    than any local row budget — still never pays a collective: the sharded
    analogue of the paper's observation that diagonal fusion adds reduction
    without adding flops (§III/§IV-D).
    """
    w = len(phys)
    loc_ms = [m for m in range(w) if phys[m] < n_local]
    glob_ms = [m for m in range(w) if phys[m] >= n_local]
    loc_phys = tuple(phys[m] for m in loc_ms)
    order = np.argsort(np.asarray(loc_phys)) if loc_ms else []
    yl = np.arange(1 << len(loc_ms), dtype=np.int64)
    base = np.zeros_like(yl)
    for j, oj in enumerate(order):
        base |= ((yl >> j) & 1) << loc_ms[int(oj)]
    layout = _diag_layout(tuple(sorted(loc_phys)), n_local, v)

    def step(data, params):
        pr_full, pi_full = item.phase_planes(params)
        idx = jax.lax.axis_index(D.STATE_AXIS)
        off = 0
        for m in glob_ms:
            off = off + (((idx >> (phys[m] - n_local)) & 1) << m)
        gidx = jnp.asarray(base) + off
        pr = _diag_planes(jnp.take(pr_full, gidx), layout)
        pi = _diag_planes(jnp.take(pi_full, gidx), layout)
        return _apply_phase(data, pr, pi, layout).reshape(data.shape)
    return step


def _sharded_dense_step(item: PlanItem, phys: tuple[int, ...],
                        local_ctrl: tuple[int, ...],
                        glob_ctrl: tuple[int, ...], n_local: int):
    """Dense item on the local block: ``apply_gate_planar`` takes the
    physical target positions directly (gate bit ``m`` <-> ``phys[m]``, any
    order).  Global *controls* need no data movement: the control bit is
    constant per device, so the gate applies under a per-device predicate —
    the distributed analogue of the paper's predicated iteration."""

    def step(data, params):
        u = item.unitary(params)
        u_re = jnp.real(u).astype(jnp.float32)
        u_im = jnp.imag(u).astype(jnp.float32)

        def apply(d):
            return A.apply_gate_planar(d, n_local, phys, u_re, u_im,
                                       controls=local_ctrl)

        if not glob_ctrl:
            return apply(data)
        idx = jax.lax.axis_index(D.STATE_AXIS)
        pred = None
        for p in glob_ctrl:
            cond = ((idx >> (p - n_local)) & 1) == 1
            pred = cond if pred is None else jnp.logical_and(pred, cond)
        return jax.lax.cond(pred, apply, lambda d: d, data)
    return step


def sharded_windows_kept(items: Sequence[PlanItem], perm: Sequence[int],
                         first: int, n_local: int, v: int) -> bool:
    """The sharded program's victim rule: whether, under the logical to
    physical bit map ``perm``, each item from ``first`` up to the next one
    that needs a qubit-block swap can exchange its lane bits with a free
    block of row bits (:func:`repro.core.apply.lane_window`).  One that
    cannot takes a view whose minor axis is narrower than the ``2**v``
    lanes, which a tiled target pads to whole tiles (32x at n = 30 on a
    v5e, more memory than the chip has)."""
    for item in items[first:]:
        if item.kind == "diag":
            continue
        bits = [perm[q] for q in item.qubits + item.controls]
        if max(perm[q] for q in item.qubits) >= n_local:
            return True
        if min(bits) < v and A.lane_window(n_local, v, bits) is None:
            return False
    return True


def _restore_identity(data: jax.Array, perm: list[int], n: int,
                      n_local: int) -> tuple[jax.Array, int]:
    """Undo the lazily tracked physical permutation at the end of the
    sharded program, so the returned global array is an ordinary planar
    state (logical qubit ``q`` at bit ``q``).

    At most two additional ``all_to_all`` swaps and two static local
    gathers: one swap brings every should-be-global logical qubit local (a
    victim block avoiding the ones already local), a local gather stages
    them contiguously in slot order, the second swap sends them up, and a
    final gather fixes the remaining local ordering.
    """
    if perm == list(range(n)):
        return data, 0
    s = n - n_local
    swaps = 0
    if s:
        inv = [0] * n
        for q, p in enumerate(perm):
            inv[p] = q
        wanted = list(range(n_local, n))
        if inv[n_local:] != wanted:
            if any(perm[w] >= n_local for w in wanted):
                # some wanted qubits are global (possibly in wrong slots):
                # bring the whole global block down without displacing the
                # locally resident wanted qubits (victim avoids them,
                # compacting them first if they block every window)
                local_wanted = [perm[w] for w in wanted if perm[w] < n_local]
                try:
                    tgt = D.pick_victim(local_wanted, s, n_local)
                except ValueError:
                    rho = _compact_rho(local_wanted, n_local)
                    data = _apply_local_bit_perm(data, rho)
                    perm = [rho[p] if p < n_local else p for p in perm]
                    local_wanted = [rho[p] for p in local_wanted]
                    tgt = D.pick_victim(local_wanted, s, n_local)
                data = D.swap_block(data, D.STATE_AXIS, n_local, tgt, s)
                perm = D.swap_perm(perm, n_local, tgt, s)
                swaps += 1
            # every wanted qubit is local now: stage them into
            # [n_local - s, n_local) in slot order, everything else keeps
            # its relative order
            stage = n_local - s
            rho = {}
            for w in wanted:
                rho[perm[w]] = stage + (w - n_local)
            free_slots = [t for t in range(n_local) if t not in
                          set(rho.values())]
            rest = [p for p in range(n_local) if p not in rho]
            for p, t in zip(rest, free_slots):
                rho[p] = t
            rho_t = tuple(rho[p] for p in range(n_local))
            data = _apply_local_bit_perm(data, rho_t)
            perm = [rho[p] if p < n_local else p for p in perm]
            data = D.swap_block(data, D.STATE_AXIS, n_local, stage, s)
            perm = D.swap_perm(perm, n_local, stage, s)
            swaps += 1
    if perm != list(range(n)):
        # all residual misplacements are local: one gather to identity
        rho_fix = [0] * n_local
        for q in range(n):
            if perm[q] < n_local:
                rho_fix[perm[q]] = q
        data = _apply_local_bit_perm(data, tuple(rho_fix))
    return data, swaps


def _z_string_mask(obs: tuple) -> int | None:
    """The qubit mask of a canonical Pauli string whose every letter is
    ``Z`` (a diagonal observable), or None for a string with an X or Y."""
    if all(p == "Z" for _, p in obs):
        return sum(1 << q for q, _ in obs)
    return None


def _tagged(step: Callable, index: int, item: PlanItem) -> Callable:
    """``step`` with the operations it traces tagged as plan item
    ``index`` (its position in ``CompiledPlan.items``); given ``shape``,
    the result is reshaped to it inside the item's scope."""
    def tagged(state, arg, shape=None):
        with scopes.device_scope(item=index, kind=item.kind,
                                 width=len(item.qubits), part="apply"):
            out = step(state, arg)
            return out if shape is None else out.reshape(shape)
    return tagged


def _run_steps(steps: list[Callable], state, arg, shape=None):
    """Apply tagged ``steps`` in order.  Each returns the state in the view
    of its last operation and the next reshapes it once; the last reshapes
    it to ``shape``, or leaves it for a consumer that reshapes it itself.
    (Two reshapes in a row are merged into one new reshape when the
    program is converted to HLO, and that one keeps no device
    attributes.)"""
    for i, step in enumerate(steps):
        state = step(state, arg, shape if i == len(steps) - 1 else None)
    return state


@functools.lru_cache(maxsize=4096)
def _dense_gate_fn(n: int, qubits: tuple[int, ...],
                   controls: tuple[int, ...]):
    """Jitted dense-baseline gate ``(psi, u) -> psi`` for one gate shape
    (state donated)."""
    return jax.jit(lambda psi, u: A.apply_gate_dense(psi, n, qubits, u,
                                                     controls),
                   donate_argnums=(0,))


@dataclasses.dataclass
class CompiledPlan:
    """A fused, jitted execution program for one template structure."""

    MAX_BATCHED_PROGRAMS = 8

    template: CircuitTemplate
    backend: str
    target: Target
    f: int
    interpret: bool
    items: list[PlanItem]
    specialize: bool = True
    state_bits: int = 0              # state-sharding degree the plan targets
    # non-None for result-mode plans: the spec the terminal "result" item
    # carries, duplicated here so execution paths never walk the item list
    result: "object | None" = None
    compile_seconds: float = 0.0
    # static vectorization profile (ALO/ORR/AI/fast-path coverage), computed
    # once by compile_plan via repro.engine.telemetry.vectorization_profile
    profile: "object | None" = dataclasses.field(
        default=None, repr=False, compare=False)
    batch_compiles: int = 0          #: guarded-by: _plock
    batch_evictions: int = 0         #: guarded-by: _plock
    sharded_swaps: int | None = None  # all_to_alls traced by the last sharded build
    cache_stats: "CacheStats | None" = dataclasses.field(
        default=None, repr=False)
    #: guarded-by: _plock
    _single: Callable | None = dataclasses.field(default=None, repr=False)
    #: guarded-by: _plock
    _batched: collections.OrderedDict = dataclasses.field(
        default_factory=collections.OrderedDict, repr=False)
    # guards the per-plan executable caches (_single/_batched) and their
    # counters under concurrent dispatchers; execution runs outside it
    _plock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    @property
    def n(self) -> int:
        return self.template.n

    @property
    def num_params(self) -> int:
        return self.template.num_params

    @property
    def num_fused_gates(self) -> int:
        return len(self.items)

    # -- per-class stats ------------------------------------------------------
    def class_counts(self) -> dict:
        """Fused-gate counts by lowering class (diag/perm items are the
        matmul-free fast paths; dense items take the generic matvec)."""
        counts = {"diagonal": 0, "permutation": 0, "general": 0,
                  "channel": 0, "result": 0}
        for item in self.items:
            counts[{"diag": "diagonal", "perm": "permutation",
                    "channel": "channel", "result": "result"}.get(
                item.kind, "general")] += 1
        return counts

    def flops_per_amp(self) -> dict:
        """Estimated real flops per state amplitude: actual (per-class
        lowering) vs generic (each item as the dense matvec it replaces —
        recorded at lowering time, so controlled items are weighted by
        their control-satisfied ``2**-c`` amplitude fraction)."""
        generic = actual = 0.0
        for item in self.items:
            if item.kind == "result":
                continue          # reduction epilogue, not a gate lowering
            if item.kind == "channel":
                # every Kraus branch pays a dense matvec; there is no
                # cheaper generic alternative to compare against
                g = item.generic_flops if item.generic_flops is not None \
                    else 8.0 * (1 << len(item.qubits)) * len(item.kraus)
                generic += g
                actual += g
                continue
            dense = (8.0 * (1 << len(item.qubits))
                     / (1 << len(item.controls)))
            g = item.generic_flops if item.generic_flops is not None else dense
            generic += g
            if item.kind in ("diag", "perm"):
                # phase-free permutations are pure memory traffic
                actual += 6.0 if item.phases else 0.0
            else:
                actual += dense
        return {"flops_per_amp_generic": generic,
                "flops_per_amp_actual": actual,
                "flops_saved_frac": 1.0 - actual / generic if generic else 0.0}

    # -- program construction -------------------------------------------------
    def _step(self, item: PlanItem):
        """Build the per-item closure for this plan's backend.  It takes
        the state in any shape that flattens to the plan's layout and
        returns it in the view of its last operation (:func:`_run_steps`
        reshapes it where needed)."""
        n = self.n
        if item.kind in ("diag", "perm"):
            return self._special_step(item)
        if self.backend == "dense":
            def step(psi, params):
                return A.apply_gate_dense(psi, n, item.qubits,
                                          item.unitary(params), item.controls)
            return step
        v = self.target.lane_qubits
        if self.backend == "planar":
            def step(data, params):
                u = item.unitary(params)
                return A.apply_planar(
                    data, n, v, item.qubits,
                    jnp.real(u).astype(jnp.float32),
                    jnp.imag(u).astype(jnp.float32), item.controls)
            return step
        from repro.kernels.apply_gate import ops as K
        interpret = self.interpret

        def step(data, params):
            u = item.unitary(params)
            return K.fused_gate(
                data, n, v, item.qubits,
                jnp.real(u).astype(jnp.float32),
                jnp.imag(u).astype(jnp.float32),
                controls=item.controls, interpret=interpret)
        return step

    def _special_step(self, item: PlanItem):
        """Matmul-free lowering of a diag/perm item.

        planar: the ``2**w`` phase planes are broadcast over the state by a
        reshape that merges contiguous qubit runs into whole axes
        (``_phase_broadcast_shapes``) — an elementwise multiply with no
        gather and no moveaxis — XOR-mask permutations are axis reversals,
        and other permutations a single static ``take`` over the flat
        amplitude axis.  pallas: a diagonal streams the state once through
        the phase kernel, a permutation runs as its monomial matrix through
        the dense kernel (``apply_phase_gate``).  The dense backend never builds
        special items: ``resolve_f`` pins it to f=0, keeping it the
        unspecialized naive baseline / oracle.
        """
        if self.backend == "dense":
            raise AssertionError(
                "dense plans are never specialized (resolve_f forces f=0 "
                "for the naive baseline)")
        if self.backend == "planar":
            return _planar_special_step(item, self.n, self.target.lane_qubits)

        from repro.kernels.apply_gate import ops as K
        n = self.n
        v = self.target.lane_qubits
        interpret = self.interpret
        perm = item.perm
        has_phase = bool(item.phases)

        def step(data, params):
            if has_phase:
                p_re, p_im = item.phase_planes(params)
            else:
                p_re = p_im = None
            return K.phase_gate(data, n, v, item.qubits, p_re, p_im,
                                perm=perm, interpret=interpret)
        return step

    def _gate_items(self) -> list[PlanItem]:
        """The circuit part of the item list (channel/result items are
        executed only by the result-mode program paths)."""
        return [it for it in self.items if it.kind in ("dense", "diag",
                                                       "perm")]

    def _tagged_steps(self, items: list[PlanItem], build: Callable):
        """``build(item)`` for each of ``items``, tagged with the item's
        index in :attr:`items`."""
        index = {id(it): i for i, it in enumerate(self.items)}
        return [_tagged(build(it), index[id(it)], it) for it in items]

    def _program(self):
        """The ideal-circuit program ``(state, params) -> state``.

        For a result-mode plan this covers the gate items only — the
        channel/epilogue items need per-row PRNG keys and run through
        :meth:`_result_program` instead.
        """
        if self.backend not in ("dense", "planar", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}")
        steps = self._tagged_steps(self._gate_items(), self._step)

        def program(state, params):
            return _run_steps(steps, state, params, state.shape)
        return program

    def _params_array(self, params) -> jax.Array:
        if params is None:
            params = np.zeros((self.num_params,), np.float32)
        arr = jnp.asarray(params, jnp.float32).reshape(-1)
        if arr.shape[0] != self.num_params:
            raise ValueError(f"{self.template.name}: expected "
                             f"{self.num_params} parameters, got {arr.shape[0]}")
        return arr

    def _initial_data(self, initial: SV.State | None):
        if self.backend == "dense":
            if initial is not None:
                return initial.to_dense()
            with scopes.device_scope(kind="init"):
                return jnp.zeros(1 << self.n, jnp.complex64).at[0].set(1.0)
        if initial is not None:
            # the program is lowered for this plan's lane tiling; a state laid
            # out for another target must be re-tiled by the caller first
            if initial.v != self.target.lane_qubits:
                raise ValueError(
                    f"initial state lane tiling v={initial.v} does not match "
                    f"plan target {self.target.name} "
                    f"(v={self.target.lane_qubits}); convert via "
                    f"from_dense(state.to_dense(), n, target)")
            return initial.data
        with scopes.device_scope(kind="init"):
            return SV.zero_state(self.n, self.target).data

    def _wrap(self, data) -> SV.State:
        if self.backend == "dense":
            return SV.from_dense(data, self.n, self.target)
        return SV.State(data=data, n=self.n, v=self.target.lane_qubits)

    # -- execution ------------------------------------------------------------
    def run(self, params=None, initial: SV.State | None = None) -> SV.State:
        """Execute for one parameter vector; one dispatch of the fused jit.

        The dense baseline instead dispatches gate by gate
        (:func:`_dense_gate_fn`, one executable per gate shape, the state
        donated each time): a whole unfused circuit in one program keeps
        several complex states alive at once, which does not fit a chip at
        the sizes the dense reference is checked against.
        """
        if self.backend == "dense":
            psi = self._initial_data(initial)
            p = self._params_array(params)
            for item in self._gate_items():
                psi = _dense_gate_fn(self.n, item.qubits, item.controls)(
                    psi, item.unitary(p))
            return self._wrap(psi)
        with self._plock:
            if self._single is None:
                self._single = jax.jit(self._program(), donate_argnums=(0,))
        with scopes.host_span("repro.state.init"):
            data0 = self._initial_data(initial)
            if initial is not None:
                data0 = jnp.array(data0)   # don't donate the caller's buffer
        with scopes.host_span("repro.params"):
            p = self._params_array(params)
        with scopes.host_span("repro.dispatch"):
            # lint-ok: EL001 _single is write-once under _plock above; this
            # read happens after the build and the reference is never
            # cleared, so the unlocked dispatch sees either this thread's or
            # a prior build
            out = self._single(data0, p)
        return self._wrap(out)

    def run_batch_raw(self, params_matrix, initial: SV.State | None = None,
                      initial_batch=None) -> jax.Array:
        """vmap the program over a [B, P] parameter matrix; returns the
        stacked state data with a leading batch axis."""
        pm = jnp.asarray(params_matrix, jnp.float32)
        if pm.ndim != 2 or pm.shape[1] != self.num_params:
            raise ValueError(f"{self.template.name}: params matrix must be "
                             f"[B, {self.num_params}], got {tuple(pm.shape)}")
        batched_init = initial_batch is not None
        data0 = (initial_batch if batched_init
                 else self._initial_data(initial))
        key = (int(pm.shape[0]), batched_init)
        with self._plock:
            fn = self._get_or_build(
                key, lambda: self._build_batched(batched_init))
        return fn(data0, pm)

    def _get_or_build(self, key, build: Callable):
        """LRU lookup/insert in the per-plan executable dict.  Caller holds
        ``_plock``: concurrent dispatchers of the same plan must neither
        double-build a key nor lose an eviction count."""
        fn = self._batched.get(key)
        if fn is None:
            fn = build()
            self._batched[key] = fn
            self.batch_compiles += 1
            # bound the per-plan dict of batched executables: distinct batch
            # sizes / init modes would otherwise accumulate without limit
            while len(self._batched) > self.MAX_BATCHED_PROGRAMS:
                self._batched.popitem(last=False)
                self.batch_evictions += 1
                if self.cache_stats is not None:
                    self.cache_stats.bump("batch_evictions")
        else:
            self._batched.move_to_end(key)
        return fn

    def run_batch(self, params_matrix, initial: SV.State | None = None,
                  ) -> list[SV.State]:
        return self.wrap_batch(self.run_batch_raw(params_matrix,
                                                  initial=initial))

    def wrap_batch(self, raw, count: int | None = None) -> list[SV.State]:
        """Wrap the first ``count`` rows (all, by default) of a stacked
        ``run_batch_raw`` output into per-circuit states."""
        count = raw.shape[0] if count is None else count
        return [self._wrap(raw[b]) for b in range(count)]

    def _build_batched(self, batched_init: bool):
        in_axes = (0 if batched_init else None, 0)
        return jax.jit(jax.vmap(self._program(), in_axes=in_axes))

    # -- result-mode execution ------------------------------------------------
    def _row_probs(self, data) -> jax.Array:
        """|amp|^2 in dense basis order, from this backend's layout."""
        if self.backend == "dense":
            re, im = jnp.real(data), jnp.imag(data)
            return re * re + im * im
        flat = data.reshape(2, -1)
        return flat[0] * flat[0] + flat[1] * flat[1]

    def _channel_step(self, item: PlanItem):
        """Trajectory-unraveling step ``(state, key) -> state``.

        Applies every Kraus branch, draws one ~ its squared norm
        (``jax.random.categorical``), and renormalizes the survivor —
        the standard quantum-trajectories scheme, unbiased for any
        observable: E[<P>] = tr(P sum_i K_i rho K_i^dagger) exactly.
        """
        n, qubits = self.n, item.qubits
        mats = [np.asarray(k, np.complex64) for k in item.kraus]
        tiny = float(np.finfo(np.float32).tiny)

        def pick(branches, norms, key):
            total = jnp.sum(norms)
            p = norms / jnp.maximum(total, tiny)
            idx = jax.random.categorical(key, jnp.log(jnp.maximum(p, 1e-30)))
            chosen = jnp.take(branches, idx, axis=0)
            return chosen / jnp.sqrt(jnp.maximum(norms[idx], tiny))

        if self.backend == "dense":
            us = [jnp.asarray(m) for m in mats]

            def step(psi, key):
                branches = jnp.stack([A.apply_gate_dense(psi, n, qubits, u)
                                      for u in us])
                re, im = jnp.real(branches), jnp.imag(branches)
                norms = jnp.sum(re * re + im * im, axis=1)
                return pick(branches, norms, key)
            return step

        # planar and pallas share the lane-tiled layout; Kraus branches are
        # applied through the planar path (the operators are non-unitary, so
        # the mid-level reference contract is exactly what we need)
        planes = [(jnp.asarray(m.real, jnp.float32),
                   jnp.asarray(m.imag, jnp.float32)) for m in mats]

        def step(data, key):
            branches = jnp.stack([A.apply_gate_planar(data, n, qubits,
                                                      ur, ui)
                                  for ur, ui in planes])
            flat = branches.reshape(len(planes), -1)
            norms = jnp.sum(flat * flat, axis=1)
            return pick(branches, norms, key)
        return step

    def _observable_step(self, obs: tuple):
        """Apply-then-inner-product reduction ``(state) -> f32`` for one
        canonical Pauli string, on the state in any shape that flattens to
        the plan's layout.  The epilogue takes it for strings with an X or
        a Y; Z-strings share :meth:`_z_pass`.
        """
        n = self.n
        if self.backend == "dense":
            us = [(q, jnp.asarray(np.asarray(ME._PAULI[p], np.complex64)))
                  for q, p in obs]

            def step(psi):
                phi = psi
                for q, u in us:
                    phi = A.apply_gate_dense(phi, n, (q,), u)
                return jnp.real(jnp.vdot(psi, phi, precision=A.HIGHEST)).astype(jnp.float32)
            return step
        planes = [(q, jnp.asarray(np.real(ME._PAULI[p]).astype(np.float32)),
                   jnp.asarray(np.imag(ME._PAULI[p]).astype(np.float32)))
                  for q, p in obs]

        v = self.target.lane_qubits

        def step(data):
            pd = data
            for q, ur, ui in planes:
                pd = A.apply_planar(pd, n, v, (q,), ur, ui)
            a = data.reshape(2, -1)
            b = pd.reshape(2, -1)
            return jnp.sum(a[0] * b[0] + a[1] * b[1])
        return step

    def _z_pass(self, masks: Sequence[int]):
        """One reduction ``(state) -> f32[T]`` for the T Z-strings with
        qubit masks ``masks``: <Z_m> = sum_x |amp_x|^2 (-1)**popcount(x & m).

        With amplitude ``x`` at row ``x >> v``, lane ``x & (V - 1)`` the
        sign factors into a lane sign times a row sign, so the pass forms
        |amp|^2 as an ``(R, V)`` tile (no flat view), multiplies it by the
        ``(V, T)`` lane signs at HIGHEST and sums each column against its
        row signs: one read of the state for every term.  Both sign
        matrices are built in the program from ``iota`` and the masks.
        """
        n = self.n
        v = min(self.target.lane_qubits, n)
        shape = (1 << (n - v), 1 << v)
        lane_masks = np.array([m & ((1 << v) - 1) for m in masks], np.int32)
        row_masks = np.array([m >> v for m in masks], np.int32)

        def signs(size, m):
            bits = jax.lax.population_count(
                jax.lax.iota(jnp.int32, size)[:, None] & m[None, :]) & 1
            return (1 - 2 * bits).astype(jnp.float32)

        if self.backend == "dense":
            def probs(psi):
                re, im = jnp.real(psi), jnp.imag(psi)
                return (re * re + im * im).reshape(shape)
        else:
            def probs(data):
                sq = data.reshape((2,) + shape)
                sq = sq * sq
                return sq[0] + sq[1]

        def step(data):
            q = jnp.dot(probs(data), signs(shape[1], lane_masks),
                        precision=A.HIGHEST)
            return jnp.sum(q * signs(shape[0], row_masks), axis=0)
        return step

    def _epilogue_step(self, spec):
        """Fused result epilogue ``(state, key) -> payload``."""
        from repro.engine import results as R
        if spec.mode == R.MODE_SHOTS:
            shots = spec.shots

            def epi(data, key):
                with scopes.device_scope(kind="epilogue"):
                    return ME.sample_probs(self._row_probs(data), shots,
                                           jax.random.fold_in(key,
                                                              _SHOT_SALT))
            return epi
        # expectation / noisy: the Z-strings in one shared pass, every other
        # string by its own reduction; the payload keeps the spec's order
        masks = [_z_string_mask(obs) for obs in spec.observables]
        z_terms = [i for i, m in enumerate(masks) if m is not None]
        rest = [(i, self._observable_step(obs))
                for i, obs in enumerate(spec.observables)
                if masks[i] is None]
        z_pass = (self._z_pass([masks[i] for i in z_terms]) if z_terms
                  else None)
        order = np.argsort(z_terms + [i for i, _ in rest])

        def epi(data, key):
            with scopes.device_scope(kind="epilogue"):
                parts = []
                if z_pass is not None:
                    with scopes.device_scope(term="z"):
                        parts.append(z_pass(data))
                for i, s in rest:
                    with scopes.device_scope(term=i):
                        parts.append(s(data)[None])
                out = jnp.concatenate(parts).astype(jnp.float32)
                if rest and z_terms:
                    out = out[order]
                return out
        return epi

    @property
    def z_pass_terms(self) -> int:
        """Observables of the result spec that the shared Z-string pass
        serves (0 without an expectation or noisy spec)."""
        if self.result is None:
            return 0
        return sum(_z_string_mask(o) is not None
                   for o in self.result.observables)

    @property
    def fallback_terms(self) -> int:
        """Observables of the result spec served one by one by the
        apply-then-inner-product reduction (strings with an X or a Y)."""
        if self.result is None:
            return 0
        return len(self.result.observables) - self.z_pass_terms

    def _result_program(self):
        """The full result-mode program ``(state, params, rowkey) -> payload``.

        ``rowkey`` is ``uint32[2]`` = (per-request PRNG seed, trajectory
        index): randomness derives only from the request's own key fold-in,
        never from batch position — which is what makes shot payloads
        bitwise reproducible regardless of batch composition.
        """
        spec = self.result
        if spec is None:
            raise ValueError(f"{self.template.name}: plan has no result "
                             f"spec; use run/run_batch_raw")
        steps = self._tagged_steps(self._gate_items(), self._step)
        chans = self._tagged_steps(
            [it for it in self.items if it.kind == "channel"],
            self._channel_step)
        epi = self._epilogue_step(spec)

        def program(state, params, rowkey):
            # channel steps take the planar shape; the epilogue reshapes
            # the state itself
            state = _run_steps(steps, state, params,
                               state.shape if chans else None)
            key = jax.random.fold_in(jax.random.PRNGKey(rowkey[0]),
                                     rowkey[1])
            for i, ch in enumerate(chans):
                state = ch(state, jax.random.fold_in(key, _CHANNEL_SALT + i))
            return epi(state, key)
        return program

    def run_result(self, params=None, rowkey=(0, 0),
                   initial: SV.State | None = None) -> jax.Array:
        """Execute one row of a result-mode plan (shots: int32[k];
        expectation/noisy: f32[num_observables] for one trajectory)."""
        rk = jnp.asarray(np.asarray(rowkey, np.uint32).reshape(2))
        data0 = self._initial_data(initial)
        with self._plock:
            fn = self._get_or_build(("result", 1),
                                    lambda: jax.jit(self._result_program()))
        return fn(data0, self._params_array(params), rk)

    def run_batch_result_raw(self, params_matrix, rowkeys,
                             initial: SV.State | None = None) -> jax.Array:
        """vmap the result program over [B, P] params + [B, 2] rowkeys;
        returns the stacked payloads with a leading batch axis."""
        pm = jnp.asarray(params_matrix, jnp.float32)
        if pm.ndim != 2 or pm.shape[1] != self.num_params:
            raise ValueError(f"{self.template.name}: params matrix must be "
                             f"[B, {self.num_params}], got {tuple(pm.shape)}")
        rk = jnp.asarray(np.asarray(rowkeys, np.uint32))
        if rk.shape != (pm.shape[0], 2):
            raise ValueError(f"{self.template.name}: rowkeys must be "
                             f"[{pm.shape[0]}, 2], got {tuple(rk.shape)}")
        data0 = self._initial_data(initial)
        key = ("result", int(pm.shape[0]))
        with self._plock:
            fn = self._get_or_build(key, self._build_batched_result)
        return fn(data0, pm, rk)

    def _build_batched_result(self):
        return jax.jit(jax.vmap(self._result_program(), in_axes=(None, 0, 0)))

    # -- sharded execution ----------------------------------------------------
    def run_sharded_batch_raw(self, params_matrix, mesh) -> jax.Array:
        """Run a ``[B, P]`` parameter matrix sharded over a two-axis mesh.

        ``mesh`` must carry the engine's ``(BATCH_AXIS, STATE_AXIS)`` axes
        (see :func:`repro.core.distributed.make_sim_mesh`) with the state
        axis sized ``2**self.state_bits`` — the degree this plan's item
        widths were capped for at compile time.  The batch is padded to a
        multiple of the batch axis (padding rows repeat the last binding and
        are sliced off before returning), every device executes its local
        item loop with qubit-block swaps amortized across items, and the
        returned global array is an ordinary stacked planar state (the
        trailing permutation is restored inside the traced program).
        """
        pm = self._sharded_params(params_matrix)
        bs = self._sharded_mesh_check(mesh)
        b = pm.shape[0]
        padded = -(-b // bs) * bs
        if padded > b:
            pm = np.concatenate([pm, np.repeat(pm[-1:], padded - b, axis=0)])
        raw = self._run_sharded(("sharded", padded, mesh),
                                lambda: self._build_sharded(mesh, padded), pm,
                                mesh)
        return raw[:b]

    def run_sharded(self, params, mesh) -> jax.Array:
        """Run one binding state-sharded over ``mesh``, whose batch axis
        must have one device; returns the state's planar data
        ``[2, R, V]``, its rows sharded over the state axis.  The program
        itself drops the batch axis, so no copy of the whole state is made
        to drop it afterwards (``run_sharded_batch_raw(...)[0]`` makes
        one)."""
        pm = self._sharded_params(params)
        if pm.shape[0] != 1 or self._sharded_mesh_check(mesh) != 1:
            raise ValueError("run_sharded takes one binding on a mesh whose "
                             f"{D.BATCH_AXIS!r} axis has one device")
        return self._run_sharded(
            ("sharded_one", mesh),
            lambda: self._build_sharded(mesh, 1, single=True), pm, mesh)

    def _sharded_params(self, params_matrix) -> np.ndarray:
        pm = np.atleast_2d(np.asarray(params_matrix, np.float32))
        if pm.ndim != 2 or pm.shape[1] != self.num_params:
            raise ValueError(f"{self.template.name}: params matrix must be "
                             f"[B, {self.num_params}], got {tuple(pm.shape)}")
        return pm

    def _sharded_mesh_check(self, mesh) -> int:
        """Check ``mesh`` against this plan; returns its batch shards."""
        if self.backend != "planar":
            raise ValueError(
                f"sharded execution lowers items with the planar "
                f"applications; backend {self.backend!r} is not supported "
                f"(use backend='planar')")
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if (D.BATCH_AXIS not in axis_sizes or D.STATE_AXIS not in axis_sizes
                or axis_sizes[D.STATE_AXIS] != (1 << self.state_bits)):
            raise ValueError(
                f"mesh axes {axis_sizes} do not match this plan "
                f"(needs {D.BATCH_AXIS!r} and {D.STATE_AXIS!r} with "
                f"{1 << self.state_bits} state shards; recompile with the "
                f"right state_bits for a different mesh)")
        return axis_sizes[D.BATCH_AXIS]

    def _run_sharded(self, key, build: Callable, pm: np.ndarray,
                     mesh) -> jax.Array:
        from jax.sharding import NamedSharding, PartitionSpec as P
        with self._plock:
            fn, counter = self._get_or_build(key, build)
        # the parameters placed as the program takes them, so that its
        # lowering does not depend on where an uncommitted array lies
        raw = fn(jax.device_put(pm, NamedSharding(mesh, P(D.BATCH_AXIS))))
        self.sharded_swaps = counter["swaps"]
        return raw

    def _sharded_item_step(self, item: PlanItem, phys: tuple[int, ...],
                           cphys: tuple[int, ...], n_local: int):
        """Per-item closure on the local block, for the current trace-time
        physical positions: local diag/perm items are relabeled onto
        physical bits and reuse the planar special step; diagonal items on
        global positions apply communication-free via a per-device phase
        slice; dense items apply directly on the physical targets with
        global controls predicated."""
        v = self.target.lane_qubits
        if item.kind == "diag" and any(p >= n_local for p in phys):
            return _sharded_diag_step(item, phys, n_local, v)
        if item.kind in ("diag", "perm"):
            step = _planar_special_step(_relabel_special_item(item, phys),
                                        n_local, v)
            return lambda data, params: step(data, params).reshape(data.shape)
        local_ctrl = tuple(p for p in cphys if p < n_local)
        glob_ctrl = tuple(p for p in cphys if p >= n_local)
        return _sharded_dense_step(item, phys, local_ctrl, glob_ctrl, n_local)

    def _build_sharded(self, mesh, padded_b: int, single: bool = False):
        """Trace the sharded program: one ``shard_map`` whose body loops the
        plan items with trace-time permutation tracking, Belady victim
        selection, and a final permutation restore; the batch dimension is
        vmapped *inside* each item step while collectives act on the whole
        local batch block.  ``single`` (a batch of one) applies the steps to
        the state itself, with no batch axis to vmap or to return.

        Each item's operations carry its ``repro_item``, ``repro_kind`` and
        ``repro_width``, as in the single-device program; the qubit-block
        swaps (:func:`repro.core.distributed.swap_block`) carry
        ``repro_part="collective"``, the local bit gathers
        ``repro_part="exchange"``, the zero-state build
        ``repro_kind="init"`` and the restore at the end
        ``repro_kind="restore"``."""
        n, v, s = self.n, self.target.lane_qubits, self.state_bits
        n_local = n - s
        # swap victims above the (8, V) vector tile where there is room
        tile_floor = v + 3
        bl = padded_b // int(dict(zip(mesh.axis_names,
                                      mesh.devices.shape))[D.BATCH_AXIS])
        items = self.items

        # Belady lookahead: when evicting a local bit block for a
        # qubit-block swap, prefer the one whose resident logical qubits
        # are needed furthest in the future (minimizes swap thrash).
        touch: dict[int, list[int]] = {q: [] for q in range(n)}
        for ii, item in enumerate(items):
            for q in item.qubits + item.controls:
                touch[q].append(ii)

        def next_use(q: int, after: int) -> int:
            lst = touch[q]
            j = bisect.bisect_left(lst, after)
            return lst[j] if j < len(lst) else len(items) + n

        counter = {"swaps": 0}

        def local_fn(pm_local):
            # pm_local: f32[bl, P]; local state block f32[bl, 2, R_local, V],
            # or f32[2, R_local, V] for a single state (no vmap)
            lead = () if single else (bl,)
            with scopes.device_scope(kind="init"):
                data = jnp.zeros(lead + (2, 1 << (n_local - v), 1 << v),
                                 jnp.float32)
                if s:
                    amp0 = jnp.where(jax.lax.axis_index(D.STATE_AXIS) == 0,
                                     1.0, 0.0)
                else:
                    amp0 = 1.0
                data = data.at[..., 0, 0, 0].set(amp0)
            perm = list(range(n))
            swaps = 0
            # victim blocks of the exchanges so far; None once a local
            # gather has moved bits (then the general restore runs)
            swapped = []
            for ii, item in enumerate(items):
                tag = functools.partial(scopes.device_scope, item=ii,
                                        kind=item.kind,
                                        width=len(item.qubits), part="apply")
                phys = [perm[q] for q in item.qubits]
                cphys = [perm[q] for q in item.controls]
                # diagonal items never need locality (zero-communication
                # per-device phase slice); everything else must have its
                # target bits local before applying
                if (s and item.kind != "diag"
                        and any(p >= n_local for p in phys)):
                    def pick(needed):
                        inv = [0] * n
                        for q, p in enumerate(perm):
                            inv[p] = q

                        def score(blk):
                            # first a block that leaves every item up to the
                            # next swap a lane window, then Belady
                            after = D.swap_perm(perm, n_local, blk, s)
                            return (sharded_windows_kept(items, after, ii,
                                                         n_local, v),
                                    min(next_use(inv[p], ii)
                                        for p in range(blk, blk + s)))
                        return D.pick_victim(needed, s, n_local, score=score,
                                             floor=tile_floor)

                    # prefer a victim avoiding local controls too; when
                    # control-heavy items leave no room, displaced controls
                    # simply turn global and get predicated
                    needed = phys + [p for p in cphys if p < n_local]
                    if len([p for p in needed if p < n_local]) > n_local - s:
                        needed = list(phys)
                    try:
                        tgt = pick(needed)
                    except ValueError:
                        # scattered positions blocked every window: pack
                        # them into the low bits with one static gather
                        rho = _compact_rho(needed, n_local)
                        with tag():
                            data = _apply_local_bit_perm(data, rho)
                        perm = [rho[p] if p < n_local else p for p in perm]
                        needed = [rho[p] if p < n_local else p
                                  for p in needed]
                        tgt = pick(needed)
                        swapped = None
                    with tag():
                        data = D.swap_block(data, D.STATE_AXIS, n_local, tgt,
                                            s)
                    if swapped is not None:
                        swapped.append(tgt)
                    perm = D.swap_perm(perm, n_local, tgt, s)
                    swaps += 1
                    phys = [perm[q] for q in item.qubits]
                    cphys = [perm[q] for q in item.controls]
                step = self._sharded_item_step(item, tuple(phys),
                                               tuple(cphys), n_local)
                with tag():
                    data = (step(data, pm_local[0]) if single
                            else jax.vmap(step)(data, pm_local))
            with scopes.device_scope(kind="restore"):
                if swapped is not None:
                    # only block exchanges moved bits: undo them in reverse,
                    # with no local gather (a 2**n_local index map is a
                    # constant the TPU compiler handles very slowly)
                    for tgt in reversed(swapped):
                        data = D.swap_block(data, D.STATE_AXIS, n_local, tgt,
                                            s)
                    restore_swaps = len(swapped)
                else:
                    data, restore_swaps = _restore_identity(data, perm, n,
                                                            n_local)
            counter["swaps"] = swaps + restore_swaps
            return data

        from jax.sharding import PartitionSpec as P

        # a single state's rows are split over both axes, the batch axis
        # having one device: the same layout as the state axis alone
        out_specs = (P(None, (D.BATCH_AXIS, D.STATE_AXIS), None) if single
                     else P(D.BATCH_AXIS, None, D.STATE_AXIS, None))
        fn = jax.shard_map(local_fn, mesh=mesh,
                           in_specs=(P(D.BATCH_AXIS, None),),
                           out_specs=out_specs)
        return jax.jit(fn), counter


def _plan_width_budget(target: Target, n: int, state_bits: int) -> int:
    """Fused-cluster width budget of a (possibly sharded) plan.

    The canonical rule is :func:`repro.core.target.row_budget`, applied to
    the qubit count a program block actually sees: the full ``n`` for a
    single-device plan, the local ``n - state_bits`` sub-state for a sharded
    one.  Sharded plans are additionally capped at ``n_local - state_bits``
    so a ``state_bits``-wide victim block always exists for the qubit-block
    swap that precedes an item on global positions.
    """
    n_local = n - state_bits
    budget = row_budget(n_local, target)
    if state_bits:
        budget = max(2, min(budget, n_local - state_bits))
    return budget


def _tile_fit(target: Target, backend: str, n: int):
    """Cluster predicate on a tiled-memory target (``target.tiled``): a
    non-diagonal cluster must let its lowering keep the vector tile whole —
    planar moves lane bits to a free block of row bits
    (:func:`repro.core.apply.lane_window`), Pallas moves lane and sublane
    bits (:func:`repro.kernels.apply_gate.ops.tile_swaps`).  ``None`` where
    narrow views cost nothing."""
    if not target.tiled or backend == "dense":
        return None
    v = target.lane_qubits
    if backend == "pallas":
        from repro.kernels.apply_gate.ops import tile_swaps
        return lambda qs: tile_swaps(n, v, qs) is not None
    return lambda qs: (min(qs) >= v
                       or A.lane_window(n, v, qs) is not None)


def resolve_f(f: int | None, target: Target, n: int, fuse: bool,
              backend: str, state_bits: int = 0) -> int:
    """Effective fusion degree: 0 when fusion is off (dense baseline), else
    auto-chosen from the target's machine balance and capped by the state's
    qubit budget.

    Lane-tiled backends (planar/pallas) only have ``n - lane_qubits`` row
    qubits, so a fused cluster wider than that row budget would force lane
    reshuffles the block layout cannot express; the cap is
    :func:`repro.core.target.row_budget` via :func:`_plan_width_budget`
    (which shrinks the effective ``n`` for sharded plans) — the same rule
    ``DistributedSimulator.prepare`` applies to its local sub-state.
    """
    if not fuse or backend == "dense":
        return 0
    f_res = f if f is not None else choose_f(target)
    return max(2, min(f_res, n, _plan_width_budget(target, n, state_bits)))


def resolve_diag_f(f_eff: int, target: Target, n: int,
                   state_bits: int = 0) -> int:
    """Width cap for diagonal/monomial clusters: the full row budget
    (never below the general degree ``f_eff``).

    A diagonal cluster composes into a ``2**w`` phase *vector*, not a
    ``4**w`` matrix, so widening it raises fusion reduction at O(2**w)
    memory and zero extra flops per amplitude — the only binding limit is
    the lane-tiled backends' row budget
    (:func:`repro.core.target.row_budget` via :func:`_plan_width_budget`,
    mirroring :func:`resolve_f`).
    """
    return max(f_eff, 2, _plan_width_budget(target, n, state_bits))


def compile_plan(template: CircuitTemplate, *, backend: str, target: Target,
                 f: int | None = None, fuse: bool = True,
                 interpret: bool | None = None, specialize: bool = True,
                 state_bits: int = 0, result=None, verify: bool = False,
                 clock: Callable[[], float] = time.perf_counter,
                 ) -> CompiledPlan:
    """Cluster once, lower once: build the fused program for one structure.

    ``specialize`` enables gate-class-aware lowering: diagonal and
    permutation (monomial) clusters bypass the dense matvec for phase-vector
    / index-map fast paths, and diagonal runs may fuse up to
    :func:`resolve_diag_f` qubits wide.  The dense no-fusion baseline
    (``f_eff == 0``) is never specialized — it stays the naive oracle.

    ``state_bits`` compiles the plan for state-sharded execution over
    ``2**state_bits`` devices (:meth:`CompiledPlan.run_sharded_batch_raw`):
    item widths are capped by the *local* sub-state's row budget, which is
    why plans for different mesh shapes are distinct cache entries.

    ``result`` (a :class:`~repro.engine.results.ResultSpec`) compiles a
    *result-mode* plan: noise channels lower to ``"channel"`` items after
    the gate items, and a terminal ``"result"`` item carries the fused
    epilogue (shot sampling / observable reduction) — executed through
    :meth:`CompiledPlan.run_result` / ``run_batch_result_raw``.  The
    statevector spec is normalized away here, so a default-mode request
    compiles byte-identical plans to a spec-less one.

    ``verify=True`` runs the structural plan-IR verifier
    (:func:`repro.analysis.verify_plan.verify_plan`) on the result before
    returning it — the debug/CI mode the benchmark smoke configs use.
    ``clock`` injects the timebase for ``compile_seconds`` attribution
    (tests pass a fake; the default is a *reference*, never called at
    import time).
    """
    t0 = clock()
    interpret = resolve_interpret(interpret)
    dummy = template.bind(np.zeros(template.num_params))
    ops = template.ops
    f_eff = resolve_f(f, target, template.n, fuse, backend,
                      state_bits=state_bits)
    specialize = bool(specialize and f_eff)
    if f_eff:
        diag_f = resolve_diag_f(f_eff, target, template.n,
                                state_bits=state_bits) if specialize else None
        classes = ([PARAM_OP_CLASS.get(op.kind) for op in ops]
                   if specialize else None)
        prep, specs = cluster_gates(
            dummy.gates, f_eff, diag_f=diag_f, classes=classes,
            allowed=_tile_fit(target, backend, template.n - state_bits))
        diag_cap = diag_f if specialize else None
        items = [it for s in specs
                 if (it := _lower_cluster(s, prep, ops,
                                          diag_cap=diag_cap)) is not None]
        if specialize and backend != "pallas":
            # sharded plans cap the merged span: per-device phase-vector
            # constants must not outgrow the local state block
            items = _coalesce_diag_runs(
                items, max_width=diag_f if state_bits else None)
    else:
        items = [_lower_single(op, g) for op, g in zip(ops, dummy.gates)]
    from repro.engine import results as R
    if result is not None and result.mode == R.MODE_STATEVECTOR:
        result = None
    if result is not None:
        result.validate_for(template)
        # channels apply after the ideal circuit (post-circuit noise); the
        # epilogue item is terminal by construction — both are verifier
        # invariants (epilogue-terminal, channel-kraus)
        for ch in result.channels:
            items.append(PlanItem(
                qubits=ch.qubits, controls=(), kind="channel", kraus=ch.kraus,
                generic_flops=8.0 * (1 << len(ch.qubits)) * len(ch.kraus)))
        items.append(PlanItem(qubits=(), controls=(), kind="result",
                              result=result))
    plan = CompiledPlan(template=template, backend=backend, target=target,
                        f=f_eff, interpret=interpret, items=items,
                        specialize=specialize, state_bits=state_bits,
                        result=result)
    # static vectorization profile, computed once here (inside the timed
    # region: it is part of the compile, and compile_seconds attributes it)
    plan.profile = vectorization_profile(plan, dummy.gates, target)
    plan.compile_seconds = clock() - t0
    if verify:
        # imported here: repro.analysis sits above the engine in the layer
        # order (it imports this module)
        from repro.analysis.verify_plan import verify_plan
        verify_plan(plan)
    return plan


@dataclasses.dataclass
class CacheStats:
    """Plan-cache counters, safe under concurrent executors.

    Mutations go through :meth:`bump` (internal lock, created outside the
    dataclass fields), so hit/miss/eviction accounting stays exact when
    many producer threads resolve plans at once; ``as_dict`` snapshots
    under the same lock.
    """

    hits: int = 0                #: guarded-by: _lock
    misses: int = 0              #: guarded-by: _lock
    compiles: int = 0            #: guarded-by: _lock
    evictions: int = 0           #: guarded-by: _lock
    #: guarded-by: _lock
    batch_evictions: int = 0     # per-plan batched-executable LRU evictions
    #: guarded-by: _lock
    class_builds: int = 0        # shape-class executables constructed
    #: guarded-by: _lock
    class_evictions: int = 0     # shape-class index LRU evictions
    #: guarded-by: _lock
    class_batch_evictions: int = 0  # per-class batched-executable evictions
    #: guarded-by: _lock
    compile_seconds: float = 0.0  # total wall time spent in compile_plan

    def __post_init__(self):
        self._lock = threading.Lock()
        # bounded per-compile sample window for the percentile attribution;
        # the compile_seconds total above stays exact over every compile
        self._compile_hist = Histogram(1024, name="compile_seconds")

    def bump(self, name: str, k: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + k)

    def record_compile(self, seconds: float) -> None:
        """Attribute one compile_plan invocation's wall time."""
        with self._lock:
            self.compile_seconds += seconds
        self._compile_hist.record(seconds)

    def compile_summary(self) -> dict:
        """Total + percentile compile-time attribution; empty before the
        first compile (an idle cache reports no fabricated 0.0s)."""
        s = self._compile_hist.summary()
        if not s:
            return {}
        with self._lock:
            total = self.compile_seconds
        return {"seconds_total": total, "count": s["count"],
                "seconds_mean": s["mean"], "seconds_p50": s["p50"],
                "seconds_p95": s["p95"], "seconds_max": s["max"]}

    def as_dict(self) -> dict:
        with self._lock:
            return {f.name: getattr(self, f.name)
                    for f in dataclasses.fields(self)}


class PlanCache:
    """LRU cache of compiled plans keyed by structure hash + exec config.

    Thread-safe: lookups, inserts, and evictions hold one reentrant lock,
    so concurrent submitters resolving the same structure get exactly one
    compile (the loser of the race hits) and the LRU order plus the
    hit/miss/eviction counters stay consistent.  Compiles run *inside* the
    lock deliberately — racing compiles of one structure would waste far
    more than the serialization costs.
    """

    def __init__(self, max_plans: int = 256, max_classes: int = 64):
        self.max_plans = max_plans
        self.max_classes = max_classes
        self._plans: collections.OrderedDict = collections.OrderedDict()  #: guarded-by: _lock
        # shape-class index: class key -> ClassExecutable, alongside the
        # exact-key plan LRU (see repro.engine.shapeclass)
        self._classes: collections.OrderedDict = collections.OrderedDict()  #: guarded-by: _lock
        self._lock = threading.RLock()
        self.stats = CacheStats()

    @staticmethod
    def plan_key(template: CircuitTemplate, *, backend: str, target: Target,
                 f: int | None, fuse: bool, interpret: bool | None,
                 specialize: bool = True, state_bits: int = 0,
                 result=None) -> tuple:
        """Cache key: structure hash + everything that changes the lowering.

        ``state_bits`` makes the key mesh-shape-aware: a sharded plan's item
        widths are capped by the per-device sub-state (see
        :func:`compile_plan`), so the same template state-sharded a
        different number of ways is a different compiled artifact — and
        must never be served from a single-device cache hit.  The *batch*
        extent of a mesh is deliberately absent: batch-only sharding reuses
        the single-device lowering (per-mesh executables are keyed inside
        :attr:`CompiledPlan._batched`), so keying it would only fragment
        the cache with identical compiles.
        """
        f_eff = resolve_f(f, target, template.n, fuse, backend,
                          state_bits=state_bits)
        return (template.structure_key(), backend, target.name, f_eff,
                resolve_interpret(interpret) and backend == "pallas",
                bool(specialize and f_eff), state_bits,
                # structural result component only (mode, shots, observables,
                # channel constants); the per-request PRNG key and the
                # unraveling row count deliberately never fragment the cache
                result.plan_key() if result is not None else None)

    def get_or_compile(self, template: CircuitTemplate | Circuit, *,
                       backend: str, target: Target, f: int | None = None,
                       fuse: bool = True, interpret: bool | None = None,
                       specialize: bool = True,
                       state_bits: int = 0,
                       result=None,
                       verify: bool = False,
                       injector=None) -> CompiledPlan:
        """``verify=True`` runs the plan-IR verifier on cache *misses* (a
        hit was verified when it was compiled).  ``injector`` is a
        resilience :class:`~repro.engine.resilience.FaultInjector` whose
        compile site fires on misses only — a cached plan never faults."""
        if isinstance(template, Circuit):
            from repro.engine.template import template_of
            template = template_of(template)
        key = self.plan_key(template, backend=backend, target=target, f=f,
                            fuse=fuse, interpret=interpret,
                            specialize=specialize, state_bits=state_bits,
                            result=result)
        with self._lock:
            plan = self._plans.get(key)
            if plan is not None:
                self.stats.bump("hits")
                self._plans.move_to_end(key)
                return plan
            self.stats.bump("misses")
            if injector is not None:
                from repro.engine.resilience import SITE_COMPILE
                injector.fire(SITE_COMPILE)
            plan = compile_plan(template, backend=backend, target=target,
                                f=f, fuse=fuse, interpret=interpret,
                                specialize=specialize, state_bits=state_bits,
                                result=result, verify=verify)
            plan.cache_stats = self.stats
            self.stats.bump("compiles")
            self.stats.record_compile(plan.compile_seconds)
            self._plans[key] = plan
            while len(self._plans) > self.max_plans:
                self._plans.popitem(last=False)
                self.stats.bump("evictions")
        return plan

    def class_executable(self, plan: CompiledPlan):
        """Shape-class executable serving ``plan``'s class, or None if the
        plan is not class-routable (non-planar backend, sharded lowering).

        The index is a bounded LRU beside the exact-key plan LRU: the first
        member plan of a class becomes the executable's structure donor
        (constants are never read from it at execution time — they arrive
        as per-row inputs), and later members of the same class hit the
        cached entry regardless of which structure donated it.
        """
        from repro.engine import shapeclass as SC
        key = SC.shape_class_key(plan)
        if key is None:
            return None
        with self._lock:
            entry = self._classes.get(key)
            if entry is not None:
                self._classes.move_to_end(key)
                return entry
            entry = SC.ClassExecutable(plan, key)
            self._classes[key] = entry
            self.stats.bump("class_builds")
            while len(self._classes) > self.max_classes:
                self._classes.popitem(last=False)
                self.stats.bump("class_evictions")
        return entry

    def class_counts(self) -> dict:
        """Aggregate fused-gate counts by lowering class over cached plans."""
        counts = {"diagonal": 0, "permutation": 0, "general": 0,
                  "channel": 0, "result": 0}
        with self._lock:
            plans = list(self._plans.values())
        for plan in plans:
            for cls, c in plan.class_counts().items():
                counts[cls] += c
        return counts

    def flops_summary(self) -> dict:
        """Aggregate per-amplitude flops (actual vs generic lowering) over
        cached plans — the estimated specialization win."""
        generic = actual = 0.0
        with self._lock:
            plans = list(self._plans.values())
        for plan in plans:
            d = plan.flops_per_amp()
            generic += d["flops_per_amp_generic"]
            actual += d["flops_per_amp_actual"]
        return {"flops_per_amp_generic": generic,
                "flops_per_amp_actual": actual,
                "flops_saved_frac": 1.0 - actual / generic if generic else 0.0}

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self._classes.clear()
            self.stats = CacheStats()


# module-level default, shared across Simulator instances the way the old
# per-gate lru_caches were.
GLOBAL_PLAN_CACHE = PlanCache()
