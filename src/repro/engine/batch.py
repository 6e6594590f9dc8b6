"""Batch executor: one compiled plan serving a whole parameter sweep.

``BatchExecutor`` is the engine's execution front end: hand it a
:class:`CircuitTemplate` plus a ``[B, P]`` parameter matrix and it resolves
one plan through the cache, then vmaps that plan's program over the batch —
B structurally identical circuits for the price of one fusion pass and one
XLA compile.  Shot batches (one circuit, many initial states) go through
``run_states``.

With ``mesh=`` (a device count or a ``jax.sharding.Mesh``) batches execute
sharded: the device split follows the batch-first policy of
:func:`repro.core.distributed.plan_shard_layout` — shard the batch axis,
and spill into state sharding (qubit-block-swap collectives inside the
plan's ``shard_map`` program) only when ``n`` exceeds the per-device row
budget ``max_local_qubits``.  Plans compiled for a sharded mesh are
distinct cache entries (mesh-shape-aware plan keys), because the per-device
sub-state shrinks their fused-cluster width caps.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distributed as D
from repro.core import statevec as SV
from repro.core.circuits import Circuit
from repro.core.target import Target, device_target, resolve_interpret
from repro.engine.plan import CacheStats, CompiledPlan, PlanCache
from repro.engine.resilience import SITE_DISPATCH, SITE_FINALIZE
from repro.engine.telemetry import ServedActivity
from repro.engine.template import CircuitTemplate, template_of


@dataclasses.dataclass
class BatchExecutor:
    """Executes batches of parameter bindings against cached plans."""

    target: Target | None = None     # None = the device's (device_target)
    backend: str = "planar"          # dense | planar | pallas
    f: int | None = None             # fusion degree; None = auto
    fuse: bool = True
    interpret: bool | None = None    # Pallas interpret mode; None = platform's
    specialize: bool = True          # gate-class-specialized plan lowering
    cache: PlanCache | None = None
    mesh: object | None = None       # device count | jax Mesh | None
    max_local_qubits: int | None = None  # per-device row budget (spill knob)
    verify: bool = False             # run the plan-IR verifier on each compile
    injector: object | None = None   # resilience.FaultInjector (chaos testing)
    breaker: object | None = None    # resilience.PlanBreaker (quarantine)

    def __post_init__(self):
        if self.target is None:
            self.target = device_target()
        self.interpret = resolve_interpret(self.interpret)
        if self.cache is None:
            self.cache = PlanCache()
        # served vectorization activity, aggregated per plan key: what lane
        # occupancy / fast-path coverage the dispatched traffic actually ran
        self.activity = ServedActivity()
        # ingest lock discipline: the executor is shared by every producer
        # thread and the drain loop.  Plan resolution is serialized inside
        # PlanCache (one compile per structure, exact counters), per-plan
        # executable caches inside CompiledPlan; this lock covers the one
        # remaining shared mutable — the mesh dict.  dispatch_batch itself
        # stays lock-free so launches overlap device execution.
        self._mesh_lock = threading.Lock()
        self._meshes: dict = {}      #: guarded-by: _mesh_lock
        self._device_pool: list | None = None
        if self.mesh is None:
            return
        if self.backend != "planar":
            raise ValueError(
                "sharded execution lowers plans with the planar "
                "applications inside shard_map; use backend='planar' "
                f"(got {self.backend!r})")
        self._device_pool = D.device_pool(self.mesh)

    # -- shard layout ---------------------------------------------------------
    @property
    def mesh_devices(self) -> int:
        """Total devices the executor may spread work over (1 = no mesh)."""
        return len(self._device_pool) if self._device_pool else 1

    def shard_spec_for(self, n: int, batch: int) -> D.ShardSpec:
        """Batch-first device split for an ``n``-qubit, ``batch``-row sweep
        (:func:`repro.core.distributed.plan_shard_layout`)."""
        if self._device_pool is None:
            return D.ShardSpec()
        return D.plan_shard_layout(n, batch, self.mesh_devices, self.target,
                                   max_local_qubits=self.max_local_qubits)

    def _mesh_for(self, spec: D.ShardSpec):
        with self._mesh_lock:
            mesh = self._meshes.get(spec)
            if mesh is None:
                mesh = D.make_sim_mesh(spec, self._device_pool)
                self._meshes[spec] = mesh
            return mesh

    # -- plan resolution ------------------------------------------------------
    def plan_for(self, template: CircuitTemplate | Circuit,
                 result=None) -> CompiledPlan:
        if isinstance(template, Circuit):
            template = template_of(template)
        spec = self.shard_spec_for(template.n, 1)
        specialize = self.specialize
        if self.breaker is not None and specialize:
            # quarantined plan keys fall back to the generic lowering — a
            # distinct cache entry, so a poisoned specialized compile is
            # never re-attempted while its breaker is open
            key = self.cache.plan_key(
                template, backend=self.backend, target=self.target, f=self.f,
                fuse=self.fuse, interpret=self.interpret,
                specialize=True, state_bits=spec.state_bits, result=result)
            if self.breaker.is_open(key):
                specialize = False
                self.breaker.record_fallback()
        return self.cache.get_or_compile(
            template, backend=self.backend, target=self.target, f=self.f,
            fuse=self.fuse, interpret=self.interpret,
            specialize=specialize, state_bits=spec.state_bits,
            result=result, verify=self.verify, injector=self.injector)

    def plan_key(self, template: CircuitTemplate | Circuit,
                 result=None) -> tuple:
        """The cache key :meth:`plan_for` resolves ``template`` to — the
        grouping key schedulers batch requests by.  Mesh-shape-aware: a
        structure that state-shards is a different plan (batch-only
        sharding reuses the single-device lowering by design).  A
        result spec contributes its *structural* component only, so
        requests differing just in PRNG key or unraveling count still
        co-batch (see :meth:`ResultSpec.plan_key`)."""
        if isinstance(template, Circuit):
            template = template_of(template)
        spec = self.shard_spec_for(template.n, 1)
        return self.cache.plan_key(
            template, backend=self.backend, target=self.target, f=self.f,
            fuse=self.fuse, interpret=self.interpret,
            specialize=self.specialize, state_bits=spec.state_bits,
            result=result)

    def class_key(self, template: CircuitTemplate | Circuit,
                  result=None) -> tuple | None:
        """The shape-class key :meth:`dispatch_class_batch` would route
        ``template`` under, or None when class routing does not apply (a
        mesh is configured, a non-planar backend, or a non-canonicalizable
        plan).  Resolving the key compiles the plan — the canonical form is
        a property of the *lowered* item sequence, not the template."""
        if self._device_pool is not None:
            return None
        from repro.engine import shapeclass as SC
        if self.backend not in SC.CLASS_BACKENDS:
            return None
        return SC.shape_class_key(self.plan_for(template, result=result))

    # -- execution ------------------------------------------------------------
    def run(self, template: CircuitTemplate | Circuit, params=None,
            initial: SV.State | None = None) -> SV.State:
        """Single binding — sequential baseline / batch-of-one path.

        With a mesh configured, this routes through the sharded dispatch
        path (a batch of one), so the same executor never mixes execution
        semantics between ``run`` and ``dispatch_batch``.
        """
        if self._device_pool is None:
            plan = self.plan_for(template)
            out = plan.run(params=params, initial=initial)
            self.activity.record(plan, 1)
            return out
        if isinstance(template, Circuit):
            template = template_of(template)
        pm = (np.zeros((1, template.num_params), np.float32) if params is None
              else np.asarray(params, np.float32).reshape(1, -1))
        plan, raw = self.dispatch_batch(template, pm, initial=initial)
        return plan.wrap_batch(raw)[0]

    def run_batch(self, template: CircuitTemplate | Circuit,
                  params_matrix, initial: SV.State | None = None,
                  ) -> list[SV.State]:
        """Run a [B, P] parameter matrix through one compiled plan."""
        plan, raw = self.dispatch_batch(template, params_matrix,
                                        initial=initial)
        return plan.wrap_batch(raw)

    def dispatch_batch(self, template: CircuitTemplate | Circuit,
                       params_matrix, initial: SV.State | None = None,
                       result=None, rowkeys=None,
                       ) -> tuple[CompiledPlan, jax.Array]:
        """Non-blocking launch: resolve the plan and dispatch the batched
        program, returning the *unwaited* stacked device output.

        The host returns as soon as the computation is enqueued, so the
        caller can stage the next batch while this one executes; retire with
        :meth:`finalize_batch` (or ``jax.block_until_ready`` + ``wrap_batch``).
        With a mesh configured the dispatch shards the batch (and, when the
        spill policy says so, the state rows) over the devices.

        ``result`` (a :class:`~repro.engine.results.ResultSpec`) dispatches
        the result-mode program instead; ``rowkeys`` is the matching
        ``uint32[B, 2]`` of per-row (request key, trajectory index) pairs —
        all-zeros when omitted.
        """
        params_matrix = np.atleast_2d(np.asarray(params_matrix, np.float32))
        if isinstance(template, Circuit):
            template = template_of(template)
        plan = self.plan_for(template, result=result)
        if self.injector is not None:
            # fires *before* the activity accounting: a faulted dispatch
            # never counts as served rows
            self.injector.fire(SITE_DISPATCH)
        # rows include any scheduler padding: this counts what the device is
        # asked to run.  Recorded *before* the launch so the accounting never
        # sits between enqueue and the caller's first readiness check
        self.activity.record(plan, params_matrix.shape[0])
        if plan.result is not None:
            if self._device_pool is not None and not self.shard_spec_for(
                    template.n, params_matrix.shape[0]).is_single:
                raise ValueError(
                    "result-mode dispatch is single-device for now; "
                    "state-sharded meshes serve statevector mode only")
            if rowkeys is None:
                rowkeys = np.zeros((params_matrix.shape[0], 2), np.uint32)
            return plan, plan.run_batch_result_raw(params_matrix, rowkeys,
                                                   initial=initial)
        if self._device_pool is None:
            return plan, plan.run_batch_raw(params_matrix, initial=initial)
        if initial is not None:
            raise ValueError(
                "sharded dispatch builds |0...0> on-device; initial states "
                "are not supported with mesh=")
        spec = self.shard_spec_for(template.n, params_matrix.shape[0])
        if spec.is_single:
            return plan, plan.run_batch_raw(params_matrix)
        return plan, plan.run_sharded_batch_raw(params_matrix,
                                                self._mesh_for(spec))

    def dispatch_class_batch(self, templates: Sequence, params_matrix,
                             result=None, rowkeys=None):
        """Class-routed sibling of :meth:`dispatch_batch`: one row per
        template, every template in the *same shape class*, executed by the
        class's shared vmapped program with each row's erased constants
        stacked as batch-axis inputs.

        Returns ``(dispatch, raw)`` where ``dispatch`` is a
        :class:`~repro.engine.shapeclass.ClassDispatch` — it quacks like
        the plan half of :meth:`dispatch_batch`'s return (``result`` +
        ``wrap_batch``) but wraps each row with its own member plan.
        ``templates`` may be shorter than the batch (scheduler padding):
        filler rows re-run the last template's constants, which is safe
        precisely because filler parameter rows and rowkeys are inert.
        """
        from repro.engine import shapeclass as SC
        if self._device_pool is not None:
            raise ValueError("class-routed dispatch is single-device; "
                             "meshes keep exact-key grouping")
        params_matrix = np.atleast_2d(np.asarray(params_matrix, np.float32))
        if not templates:
            raise ValueError("dispatch_class_batch needs >= 1 template")
        plans = [self.plan_for(t, result=result) for t in templates]
        entry = self.cache.class_executable(plans[0])
        if entry is None:
            raise ValueError(f"{plans[0].template.name}: plan is not "
                             f"class-routable")
        # membership is a hard correctness precondition, not a debug check:
        # a mis-routed row would silently execute another structure's item
        # skeleton over its own constants
        for p in plans:
            k = SC.shape_class_key(p)
            if k != entry.key:
                raise ValueError(
                    f"{p.template.name}: plan re-canonicalizes to a "
                    f"different shape class than this batch")
        if self.verify:
            from repro.analysis.verify_plan import verify_class_members
            verify_class_members(entry, plans)
        if self.injector is not None:
            self.injector.fire(SITE_DISPATCH)
        B = params_matrix.shape[0]
        if B < len(plans):
            raise ValueError(f"params matrix has {B} rows for "
                             f"{len(plans)} templates")
        # per-plan served-activity attribution; padding rows ran the last
        # member's constants, so they are billed to it
        tally: dict[int, tuple[CompiledPlan, int]] = {}
        for b in range(B):
            p = plans[min(b, len(plans) - 1)]
            prev = tally.get(id(p))
            tally[id(p)] = (p, (prev[1] if prev else 0) + 1)
        for p, rows in tally.values():
            self.activity.record(p, rows)
        tensors = [SC.class_row_tensors(p) for p in plans]
        if B > len(tensors):
            tensors.extend([tensors[-1]] * (B - len(tensors)))
        consts = tuple(np.stack([t[i] for t in tensors])
                       for i in range(entry.num_slots))
        if plans[0].result is not None and rowkeys is None:
            rowkeys = np.zeros((B, 2), np.uint32)
        raw = entry.run_class_batch_raw(params_matrix, consts,
                                        rowkeys=rowkeys)
        dispatch = SC.ClassDispatch(entry, plans, result=plans[0].result)
        return dispatch, raw

    def finalize_batch(self, plan: CompiledPlan, raw,
                       count: int | None = None) -> list[SV.State]:
        """Blocking retire step for :meth:`dispatch_batch`: wait for device
        results and wrap the first ``count`` rows (all, by default) into
        :class:`~repro.core.statevec.State` objects."""
        if self.injector is not None:
            self.injector.fire(SITE_FINALIZE)
        jax.block_until_ready(raw)
        return plan.wrap_batch(raw, count=count)

    def run_states(self, template: CircuitTemplate | Circuit,
                   initials: Sequence[SV.State], params=None,
                   ) -> list[SV.State]:
        """Shot-batch path: one circuit over B initial states (always
        single-device — caller-provided states bypass the sharded path)."""
        initials = list(initials)
        if not initials:
            raise ValueError("run_states needs at least one initial state "
                             "(got an empty sequence)")
        plan = self.plan_for(template)
        if plan.backend == "dense":
            data0 = jnp.stack([s.to_dense() for s in initials])
        else:
            data0 = jnp.stack([s.data for s in initials])
        pm = jnp.broadcast_to(plan._params_array(params),
                              (len(initials), plan.num_params))
        out = plan.run_batch_raw(pm, initial_batch=data0)
        self.activity.record(plan, len(initials))
        return [plan._wrap(out[b]) for b in range(out.shape[0])]

    # -- stats ----------------------------------------------------------------
    @property
    def stats(self) -> CacheStats:
        return self.cache.stats

    def class_counts(self) -> dict:
        """Fused-gate counts by lowering class across all cached plans —
        how much of the compiled traffic runs matmul-free."""
        return self.cache.class_counts()
