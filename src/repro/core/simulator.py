"""High-level simulator API.

``Simulator`` ties together layout (statevec), fusion, and the execution
backend:

* ``backend="dense"``  — naive baseline: complex64 interleaved, gate-by-gate,
  no fusion (the paper's auto-vectorized Qsim stand-in).
* ``backend="planar"`` — VLA design in pure JAX on the lane-tiled layout.
* ``backend="pallas"`` — VLA design with explicit Pallas VMEM kernels
  (interpret mode on CPU; compiled on TPU).

The target and the Pallas interpret mode follow the device
(:func:`repro.core.target.device_target`, :func:`~repro.core.target.
resolve_interpret`) unless given.

Fusion degree ``f`` defaults to ``choose_f(target)`` — the machine-balance
adaptation of paper §IV-D.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import numpy as np

from repro.core import scopes
from repro.core import statevec as SV
from repro.core.circuits import Circuit
from repro.core.fusion import choose_f, fuse_circuit
from repro.core.gates import Gate
from repro.core.target import Target, device_target, resolve_interpret


@dataclasses.dataclass
class Simulator:
    target: Target | None = None   # None = the device's (device_target)
    backend: str = "planar"        # dense | planar | pallas
    f: int | None = None           # horizontal fusion degree; None = auto
    fuse: bool = True
    interpret: bool | None = None  # Pallas interpret mode; None = platform's
    specialize: bool = True        # gate-class-specialized plan lowering
    plan_cache: object | None = None  # engine.PlanCache; None = shared global
    mesh: object | None = None     # device count | jax Mesh: sharded plan runs
    max_local_qubits: int | None = None  # per-device row budget (spill knob)

    def __post_init__(self):
        if self.target is None:
            self.target = device_target()
        self.interpret = resolve_interpret(self.interpret)
        if self.f is None:
            self.f = choose_f(self.target) if self.fuse else 0
        if self.plan_cache is None:
            from repro.engine.plan import GLOBAL_PLAN_CACHE
            self.plan_cache = GLOBAL_PLAN_CACHE
        self._device_pool = None
        self._meshes = {}
        if self.mesh is not None:
            if self.backend != "planar":
                raise ValueError(
                    "mesh execution lowers plans with the planar "
                    f"applications; use backend='planar' (got {self.backend!r})")
            from repro.core import distributed as D
            self._device_pool = D.device_pool(self.mesh)

    # -- sharding -------------------------------------------------------------
    def _shard_spec(self, n: int):
        """Single-circuit runs have no batch axis to shard, so the whole
        mesh goes to state sharding (``plan_shard_layout`` with
        ``batch=None``, clamped by ``max_state_bits``) — unless
        ``max_local_qubits`` is explicitly set, in which case states that
        fit one device stay unsharded (the spill rule)."""
        from repro.core import distributed as D
        if self._device_pool is None:
            return D.ShardSpec()
        return D.plan_shard_layout(n, None, len(self._device_pool),
                                   self.target,
                                   max_local_qubits=self.max_local_qubits)

    def _mesh_for(self, spec):
        from repro.core import distributed as D
        mesh = self._meshes.get(spec)
        if mesh is None:
            mesh = D.make_sim_mesh(spec, self._device_pool)
            self._meshes[spec] = mesh
        return mesh

    # -- preparation ----------------------------------------------------------
    def prepare(self, circuit: Circuit) -> list[Gate]:
        if not self.fuse or self.backend == "dense":
            return list(circuit.gates)
        # cap f so fused gates stay within the row/lane budget of the state
        f = max(2, min(self.f, circuit.n))
        return fuse_circuit(circuit.gates, f)

    def plan_for(self, circuit: Circuit):
        """Resolve the compiled execution plan for a circuit or template.

        With a mesh configured, plans are compiled for the state-sharded
        local sub-state and cached under mesh-shape-aware keys.
        """
        if self.backend not in ("dense", "planar", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}")
        spec = self._shard_spec(circuit.n)
        return self.plan_cache.get_or_compile(
            circuit, backend=self.backend, target=self.target, f=self.f,
            fuse=self.fuse, interpret=self.interpret,
            specialize=self.specialize, state_bits=spec.state_bits)

    # -- execution ------------------------------------------------------------
    def run(self, circuit: Circuit, initial: SV.State | None = None,
            params: Sequence[float] | np.ndarray | None = None) -> SV.State:
        """Execute one circuit (or one binding of a circuit template).

        Fusion + lowering + jit happen once per circuit *structure* through
        the plan cache (``repro.engine.plan``); repeat runs of the same
        structure are single dispatches of the compiled program.  With
        ``mesh=`` set the program executes state-sharded over the devices
        (``CompiledPlan.run_sharded``), and the state it returns keeps its
        rows sharded over them.

        The call is the profiler span ``repro.sim.run``, the plan lookup
        ``repro.plan.lookup`` inside it (:mod:`repro.core.scopes`).
        """
        with scopes.host_span("repro.sim.run", n=circuit.n):
            with scopes.host_span("repro.plan.lookup"):
                plan = self.plan_for(circuit)
                spec = self._shard_spec(circuit.n)
            if spec.is_single:
                return plan.run(params=params, initial=initial)
            if initial is not None:
                raise ValueError("sharded runs build |0...0> on-device; "
                                 "initial states are not supported with "
                                 "mesh=")
            pm = (np.zeros((1, plan.num_params), np.float32)
                  if params is None
                  else np.asarray(params, np.float32).reshape(1, -1))
            with scopes.host_span("repro.dispatch"):
                data = plan.run_sharded(pm, self._mesh_for(spec))
            return plan._wrap(data)

    # -- observables -----------------------------------------------------------
    def expectation_z(self, state: SV.State, qubit: int) -> jax.Array:
        """<Z_q> — computed as a streaming reduction (paper's
        ExpectationValue avoids storing states back)."""
        from repro.kernels.expectation import ops as E
        if self.backend == "pallas":
            return E.expectation_z(state.data, state.n, state.v, qubit,
                                   interpret=self.interpret)
        return E.expectation_z_ref(state.data, state.n, state.v, qubit)

    def probabilities(self, state: SV.State) -> jax.Array:
        """|amplitude|^2 in dense basis order (see ``State.probabilities``)."""
        return state.probabilities()

    def sample(self, state: SV.State, n_samples: int,
               key: jax.Array | None = None) -> jax.Array:
        from repro.core import measure as ME
        key = key if key is not None else jax.random.PRNGKey(0)
        return ME.sample(state, n_samples, key)

    def expectation_pauli(self, state: SV.State, paulis) -> jax.Array:
        """<P> for a Pauli string ``{qubit: 'X'|'Y'|'Z'}``.

        The single-qubit-Z case on the pallas backend routes through the
        streaming expectation kernel (one pass over the state, no
        apply-then-inner-product round trip); everything else takes the
        planar reduction in ``repro.core.measure``.
        """
        from repro.core import measure as ME
        items = list(paulis.items())
        if (self.backend == "pallas" and len(items) == 1
                and str(items[0][1]).upper() == "Z"):
            from repro.kernels.expectation import ops as E
            return E.expectation_z(state.data, state.n, state.v, items[0][0],
                                   interpret=self.interpret)
        return ME.expectation_pauli(state, paulis)
