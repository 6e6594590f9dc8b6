"""Shape-class canonicalization: cross-structure batching of compiled plans.

The scheduler historically co-batched only requests whose templates share an
*exact* plan key (structure hash + exec config), so a long-tailed template
mix fragments into underfull padded batches — the serving analogue of idle
vector lanes.  This module canonicalizes a compiled plan down to its
**fused-item sequence shape**: item kinds, qubit spans, factor/phase arities
and parameter wiring — with every constant *value* (phase vectors, index
maps, folded unitaries) erased.  Two structurally different templates that
lower to the same item skeleton land in one :class:`ClassExecutable`, a
vmapped program that takes the erased constants back as **per-row batch-axis
inputs** (stacked phase planes, perm maps, dense factors), so their requests
fill one batch instead of two half-empty ones.

This is the MoE routing idiom applied to plans: requests are tokens, shape
classes are experts, and the per-row constant tensors are the expert inputs;
the scheduler adds the capacity factor + overflow spill on top
(:class:`~repro.engine.scheduler.BatchScheduler` with ``class_routing=True``).

Bitwise contract: a class program mirrors the exact-key program step for
step — the same phase-plane formula variants, the same factor product
order, the same result-mode PRNG derivation — with constants arriving as
traced inputs of identical values.  Elementwise arithmetic and matmuls on
equal operands are deterministic, and a permutation executed as a gather is
the same data movement the exact path's ``flip`` specialization performs,
so class-routed results are bitwise-equal to exact-key results (the
property suite in ``tests/test_shape_routing.py`` enforces this).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import apply as A
from repro.engine.plan import (_CHANNEL_SALT, CompiledPlan, _apply_phase,
                               _diag_layout, _diag_planes, _full_perm_map,
                               _param_matrix)

# Backends a plan may class-route on.  planar is the serving backend whose
# item lowering is pure jax-traceable arithmetic; pallas bakes static phase
# vectors / perm maps into kernels (no per-row tensor inputs), dense is the
# deliberately-naive oracle baseline, and sharded plans key their collective
# schedule on constants — all of those keep exact-key grouping.
CLASS_BACKENDS = ("planar",)

_UNSET = object()


def _item_signature(item) -> tuple:
    """Shape signature of one plan item: kinds, spans, widths, and param
    arities survive; constant values (phase vectors, perm maps, folded
    unitaries, Kraus data) are erased."""
    if item.kind in ("diag", "perm"):
        has_const = item._np_const_phase() is not None
        # ordered parameter wiring of the phase terms: which template param
        # drives each angle axpy.  Order matters — angle accumulation is a
        # float sum — and the const/param split selects the phase-plane
        # formula variant, so both are part of the shape.
        param_idx = tuple(p[1].param for p in item.phases if p[0] == "param")
        return (item.kind, item.qubits, has_const, param_idx)
    if item.kind == "dense":
        factors = tuple(
            ("c",) if f[0] == "const"
            else ("p", f[1].kind, f[1].param, f[1].qubits, f[1].scale)
            for f in item.factors)
        return ("dense", item.qubits, item.controls, factors)
    if item.kind == "channel":
        # Kraus values are pinned by the result spec's structural key in the
        # class header, so arity + span is enough here
        return ("channel", item.qubits, len(item.kraus))
    if item.kind == "result":
        return ("result",)
    raise ValueError(f"unknown plan item kind {item.kind!r}")


def _compute_class_key(plan: CompiledPlan) -> tuple | None:
    """Canonicalize ``plan`` to its shape-class key (None = not routable)."""
    if plan.backend not in CLASS_BACKENDS or plan.state_bits != 0:
        return None
    header = ("shape-class", plan.n, plan.num_params, plan.backend,
              plan.target.name, plan.f, bool(plan.specialize),
              # ResultSpec.plan_key() is the structural result component —
              # per-request PRNG keys / unraveling counts never fragment
              # classes, exactly as they never fragment the plan cache
              plan.result.class_key_component()
              if plan.result is not None else None)
    try:
        items = tuple(_item_signature(it) for it in plan.items)
    except ValueError:
        return None
    return (header, items)


def shape_class_key(plan: CompiledPlan) -> tuple | None:
    """Cached :func:`_compute_class_key`; idempotent, safe to race (the
    recomputation is pure and the attribute write is atomic)."""
    key = getattr(plan, "_shape_class_key", _UNSET)
    if key is _UNSET:
        key = _compute_class_key(plan)
        plan._shape_class_key = key
    return key


def class_row_tensors(plan: CompiledPlan) -> tuple[np.ndarray, ...]:
    """The plan's erased constants as one flat tuple of numpy arrays — the
    per-row values a class batch stacks along the batch axis.

    Slot order is the canonical walk of the gate items (phase planes, then
    angle coefficient vectors, then the perm map, then dense const factors),
    mirrored exactly by :class:`ClassExecutable`'s program builder and
    independently recomputable from the key alone via
    :func:`class_slot_shapes` (the ``class-tensors`` verifier invariant).
    """
    cached = getattr(plan, "_class_row_tensors", _UNSET)
    if cached is not _UNSET:
        return cached
    n = plan.n
    out: list[np.ndarray] = []
    for item in plan._gate_items():
        if item.kind in ("diag", "perm"):
            const = item._np_const_phase()
            if const is not None:
                out.append(np.real(const).astype(np.float32))
                out.append(np.imag(const).astype(np.float32))
            for p in item.phases:
                if p[0] == "param":
                    out.append(np.asarray(p[2], np.float32))
            if item.kind == "perm":
                out.append(_full_perm_map(item.qubits, n, item.perm))
        else:
            for f in item.factors:
                if f[0] == "const":
                    out.append(np.asarray(f[1], np.complex64))
    tensors = tuple(out)
    plan._class_row_tensors = tensors
    return tensors


def class_slot_shapes(key: tuple) -> tuple[tuple[str, tuple[int, ...]], ...]:
    """Expected ``(dtype, shape)`` of every row-tensor slot, derived from
    the class key alone — the double-entry bookkeeping the plan verifier
    checks :func:`class_row_tensors` against."""
    header, items = key
    n = header[1]
    out: list[tuple[str, tuple[int, ...]]] = []
    for sig in items:
        kind = sig[0]
        if kind in ("diag", "perm"):
            _, qubits, has_const, param_idx = sig
            w = len(qubits)
            if has_const:
                out.append(("float32", (1 << w,)))
                out.append(("float32", (1 << w,)))
            out.extend(("float32", (1 << w,)) for _ in param_idx)
            if kind == "perm":
                out.append(("int32", (1 << n,)))
        elif kind == "dense":
            _, qubits, _, factors = sig
            w = len(qubits)
            out.extend(("complex64", (1 << w, 1 << w))
                       for f in factors if f[0] == "c")
    return tuple(out)


def _special_class_step(item, n: int, v: int, slot0: int):
    """Class-program step for a diag/perm item: the exact-path
    :func:`~repro.engine.plan._planar_special_step` with the static phase
    planes / coefficient vectors / perm map read from the per-row ``consts``
    tuple instead of baked in.  Formula variants match the exact path's
    ``phase_planes`` case split bitwise."""
    layout = _diag_layout(item.qubits, n, v)
    has_phase = bool(item.phases)
    has_const = item._np_const_phase() is not None
    param_ops = [p[1] for p in item.phases if p[0] == "param"]
    s = slot0
    pr_slot = pi_slot = None
    if has_const:
        pr_slot, pi_slot = s, s + 1
        s += 2
    coeff_slots = list(range(s, s + len(param_ops)))
    s += len(param_ops)
    perm_slot = None
    if item.kind == "perm":
        perm_slot = s
        s += 1

    def step(data, params, consts):
        if perm_slot is not None:
            # full-amplitude-space gather: pure data movement, bitwise-equal
            # to the exact path's flip specialization for XOR perms
            data = data.reshape(2, -1)[:, consts[perm_slot]].reshape(
                data.shape)
        if has_phase:
            ang = None
            for op, cs in zip(param_ops, coeff_slots):
                a = params[op.param] * consts[cs]
                ang = a if ang is None else ang + a
            if ang is None:
                pr, pi = consts[pr_slot], consts[pi_slot]
            else:
                c, sn = jnp.cos(ang), jnp.sin(ang)
                if not has_const:
                    pr, pi = c, sn
                else:
                    cr, ci = consts[pr_slot], consts[pi_slot]
                    pr, pi = c * cr - sn * ci, c * ci + sn * cr
            data = _apply_phase(data, _diag_planes(pr, layout),
                                _diag_planes(pi, layout),
                                layout).reshape(data.shape)
        return data
    return step, s


def _dense_class_step(item, n: int, slot0: int):
    """Class-program step for a dense item: the exact path's factor-product
    ``unitary()`` with const factors read from ``consts`` (same ``e @ u``
    order, same param-factor gather)."""
    fslots: list[int | None] = []
    s = slot0
    for f in item.factors:
        if f[0] == "const":
            fslots.append(s)
            s += 1
        else:
            fslots.append(None)
    factors = item.factors

    def step(data, params, consts):
        u = None
        for f, fs in zip(factors, fslots):
            if fs is not None:
                e = consts[fs]
            else:
                _, op, (mask, sr, sc) = f
                m2 = _param_matrix(op, params)
                e = jnp.where(jnp.asarray(mask), m2[(sr, sc)],
                              jnp.zeros((), jnp.complex64))
            u = e if u is None else jnp.matmul(e, u, precision=A.HIGHEST)
        u = u.astype(jnp.complex64)
        return A.apply_gate_planar(
            data, n, item.qubits,
            jnp.real(u).astype(jnp.float32),
            jnp.imag(u).astype(jnp.float32), item.controls)
    return step, s


class ClassExecutable:
    """One vmapped program serving every plan in a shape class.

    Built from a *representative* member plan (structure donor only — all
    constants enter as inputs); execution takes a ``[B, P]`` parameter
    matrix plus the stacked per-row constant tensors.  Batched programs are
    kept in the same bounded per-size LRU discipline as
    :attr:`CompiledPlan._batched` (``MAX_BATCHED_PROGRAMS``), with
    evictions surfaced through the shared :class:`~repro.engine.plan.
    CacheStats` (``class_batch_evictions``).
    """

    MAX_BATCHED_PROGRAMS = 8

    def __init__(self, rep: CompiledPlan, key: tuple | None = None):
        self.key = key if key is not None else shape_class_key(rep)
        if self.key is None:
            raise ValueError(
                f"{rep.template.name}: plan is not class-routable "
                f"(backend={rep.backend!r}, state_bits={rep.state_bits})")
        self.rep = rep
        self.num_slots = len(class_slot_shapes(self.key))
        self.batch_compiles = 0          #: guarded-by: _plock
        self.batch_evictions = 0         #: guarded-by: _plock
        #: guarded-by: _plock
        self._batched: collections.OrderedDict = collections.OrderedDict()
        self._plock = threading.Lock()

    def _steps(self):
        steps = []
        slot = 0
        for item in self.rep._gate_items():
            if item.kind in ("diag", "perm"):
                step, slot = _special_class_step(
                    item, self.rep.n, self.rep.target.lane_qubits, slot)
            else:
                step, slot = _dense_class_step(item, self.rep.n, slot)
            steps.append(step)
        if slot != self.num_slots:
            raise AssertionError(
                f"slot walk built {slot} inputs, key expects "
                f"{self.num_slots} (class_slot_shapes drifted)")
        return steps

    def _program(self, with_result: bool) -> Callable:
        rep = self.rep
        steps = self._steps()
        if not with_result:
            def program(state, params, consts):
                for st in steps:
                    state = st(state, params, consts)
                return state
            return program
        spec = rep.result
        if spec is None:
            raise ValueError(f"{rep.template.name}: class has no result "
                             f"spec; use run_class_batch_raw without rowkeys")
        # channel + epilogue closures are shared with the representative:
        # their constants (Kraus data, observables, shot count) are pinned
        # by the result component of the class key, so every member's are
        # equal — and the PRNG derivation stays identical to _result_program
        chans = [rep._channel_step(it) for it in rep.items
                 if it.kind == "channel"]
        epi = rep._epilogue_step(spec)

        def program(state, params, rowkey, consts):
            for st in steps:
                state = st(state, params, consts)
            key = jax.random.fold_in(jax.random.PRNGKey(rowkey[0]),
                                     rowkey[1])
            for i, ch in enumerate(chans):
                state = ch(state, jax.random.fold_in(key, _CHANNEL_SALT + i))
            return epi(state, key)
        return program

    def _get_or_build(self, key, build: Callable):
        """LRU lookup/insert in the per-class executable dict.  Caller holds
        ``_plock`` (same discipline as :meth:`CompiledPlan._get_or_build`)."""
        fn = self._batched.get(key)
        if fn is None:
            fn = build()
            self._batched[key] = fn
            self.batch_compiles += 1
            while len(self._batched) > self.MAX_BATCHED_PROGRAMS:
                self._batched.popitem(last=False)
                self.batch_evictions += 1
                if self.rep.cache_stats is not None:
                    self.rep.cache_stats.bump("class_batch_evictions")
        else:
            self._batched.move_to_end(key)
        return fn

    def _build(self, with_result: bool):
        program = self._program(with_result)
        in_axes = (None, 0, 0, 0) if with_result else (None, 0, 0)
        return jax.jit(jax.vmap(program, in_axes=in_axes))

    def run_class_batch_raw(self, params_matrix, consts, rowkeys=None):
        """Execute stacked class rows; returns the unwaited device output.

        ``consts`` is the tuple of stacked per-row constant tensors (one
        ``[B, ...]`` array per slot of :func:`class_slot_shapes`);
        ``rowkeys`` selects the result-mode program, exactly as on
        :meth:`CompiledPlan.run_batch_result_raw`.
        """
        rep = self.rep
        pm = jnp.asarray(params_matrix, jnp.float32)
        if pm.ndim != 2 or pm.shape[1] != rep.num_params:
            raise ValueError(f"class {self.key[0][:3]}: params matrix must "
                             f"be [B, {rep.num_params}], got "
                             f"{tuple(pm.shape)}")
        if len(consts) != self.num_slots:
            raise ValueError(f"expected {self.num_slots} row-tensor slots, "
                             f"got {len(consts)}")
        cs = tuple(jnp.asarray(c) for c in consts)
        data0 = rep._initial_data(None)
        if rowkeys is None:
            with self._plock:
                fn = self._get_or_build(
                    (int(pm.shape[0]), False),
                    lambda: self._build(False))
            return fn(data0, pm, cs)
        rk = jnp.asarray(np.asarray(rowkeys, np.uint32))
        if rk.shape != (pm.shape[0], 2):
            raise ValueError(f"rowkeys must be [{pm.shape[0]}, 2], "
                             f"got {tuple(rk.shape)}")
        with self._plock:
            fn = self._get_or_build(
                (int(pm.shape[0]), True),
                lambda: self._build(True))
        return fn(data0, pm, rk, cs)


@dataclasses.dataclass
class ClassDispatch:
    """Finalize-side handle for one class-batched dispatch.

    Quacks like the :class:`CompiledPlan` slots
    :class:`~repro.engine.scheduler.InFlightBatch` touches: ``result`` for
    the mode split and ``wrap_batch`` for statevector wrapping — but wraps
    each row with *its own* member plan.
    """

    executable: ClassExecutable
    plans: list                      # one CompiledPlan per pre-padding row
    result: object = None            # the chunk's ResultSpec (None = states)

    def wrap_batch(self, raw, count: int | None = None):
        count = raw.shape[0] if count is None else count
        return [self.plans[b]._wrap(raw[b]) for b in range(count)]


def class_label(key: tuple) -> str:
    """Short stable digest of a class key, for counters and reports."""
    import hashlib
    return hashlib.sha1(repr(key).encode()).hexdigest()[:8]
