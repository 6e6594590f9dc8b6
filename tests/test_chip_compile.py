"""Compile rehearsal for one TPU v5e: the main-path kernels at real size.

Each test compiles (nothing runs) for a described ``v5e:2x2`` chip at
n = 24 and checks that the program's temporaries stay within 4x the state.
The topology is described inside a fixture, so a process that cannot load
the TPU compiler skips these tests and no module import touches it.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import apply as A
from repro.core import circuits as C
from repro.core.target import TPU_V5E
from repro.engine.plan import compile_plan
from repro.engine.template import template_of
from repro.kernels.apply_gate import ops as K
from repro.kernels.expectation import ops as E

N = 24
V = TPU_V5E.lane_qubits
STATE = jax.ShapeDtypeStruct((2, 1 << (N - V), 1 << V), jnp.float32)
STATE_BYTES = 2 * 4 << N


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *args, donate=True):
    """Compile ``fn`` (state first, donated when ``fn`` returns a state) for
    the chip; returns the compiled program."""
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
             for a in args]
    compiled = jax.jit(fn, donate_argnums=(0,) if donate else ()).lower(
        *specs).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 4 * STATE_BYTES, f"temporaries {temp} > 4x state"
    return compiled


def _unitary(k):
    return jax.ShapeDtypeStruct((1 << k, 1 << k), jnp.float32)


@pytest.mark.parametrize("qubits", [(10, 11), (2, 20)])
def test_planar_gate(one_chip, qubits):
    k = len(qubits)
    _compile(one_chip,
             lambda d, ur, ui: A.apply_gate_planar(d, N, qubits, ur, ui),
             STATE, _unitary(k), _unitary(k))


@pytest.mark.parametrize("qubits,controls", [
    ((0,), ()), ((3, 7), ()), ((15, 16, 17, 18, 19), ()), ((4,), (12,))])
def test_pallas_fused_gate(one_chip, qubits, controls):
    k = len(qubits)
    _compile(one_chip,
             lambda d, ur, ui: K.apply_fused_gate(
                 d, N, V, qubits, ur, ui, controls=controls,
                 interpret=False),
             STATE, _unitary(k), _unitary(k))


@pytest.mark.parametrize("qubits,perm", [
    ((2, 9, 13, 21), None), ((0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11), None),
    ((3, 12, 20), np.array([1, 0, 3, 2, 5, 4, 7, 6])),
    # lowest bit above the (8, 128) tile at its top, 10, and cluster bits
    # on both sides of the block's cut (17)
    ((3, 10, 11, 15, 21), None),
    # the qrc28 grid circuit's first width-21 diagonal, bits below 24
    ((0, 1, 2, 4, 5, 6, 7, 9, 10, 11, 13, 14, 15, 16, 17, 19, 20, 22),
     None)])
def test_pallas_phase_gate(one_chip, qubits, perm):
    w = 1 << len(qubits)
    compiled = _compile(
        one_chip,
        lambda d, pr, pi: K.apply_phase_gate(d, N, V, qubits, pr, pi,
                                             perm=perm, interpret=False),
        STATE, jax.ShapeDtypeStruct((w,), jnp.float32),
        jax.ShapeDtypeStruct((w,), jnp.float32))
    if perm is None:
        # a diagonal streams 1 MiB blocks (2**17 amplitudes), whatever
        # its bits: 2**(24 - 17) grid steps
        meta = compiled.as_text().replace("\n", "")
        assert '"block_bytes":"1048576"' in meta
        assert '"steps":"128"' in meta


@pytest.mark.parametrize("qubit", [3, 9, 20])
def test_expectation_z(one_chip, qubit):
    _compile(one_chip,
             lambda d: E.expectation_z(d, N, V, qubit, interpret=False),
             STATE, donate=False)


def test_z_string_epilogue(one_chip):
    """The result epilogue's shared Z-string pass for a ring of ZZ terms
    (the client cell's observables) compiles for the chip at HIGHEST."""
    from repro.engine import ResultSpec, qaoa_template
    spec = ResultSpec.expectation([{i: "Z", (i + 1) % N: "Z"}
                                   for i in range(N)])
    plan = compile_plan(qaoa_template(N, 1), backend="planar",
                        target=TPU_V5E, result=spec)
    epi = plan._epilogue_step(spec)
    compiled = _compile(one_chip, lambda d: epi(d, None), STATE,
                        donate=False)
    assert "operand_precision={highest,highest}" in compiled.as_text()


def test_planar_qrc_plan(one_chip):
    plan = compile_plan(template_of(C.qrc(N, depth=8)), backend="planar",
                        target=TPU_V5E)
    params = jax.ShapeDtypeStruct((plan.num_params,), jnp.float32)
    _compile(one_chip, plan._program(), STATE, params)


@pytest.mark.parametrize("qubit,controls", [(0, ()), (3, (12,)), (20, (2,))])
def test_dense_reference_gate(one_chip, qubit, controls):
    """The one-qubit (optionally controlled) gates the dense reference runs
    for a QRC; each of the 3**k partner shifts of a k-qubit gate is a
    state-sized temporary on the chip."""
    psi = jax.ShapeDtypeStruct((1 << N,), jnp.complex64)
    u = jax.ShapeDtypeStruct((2, 2), jnp.complex64)
    _compile(one_chip,
             lambda p, m: A.apply_gate_dense(p, N, (qubit,), m, controls),
             psi, u)




def test_planar_x_layer_plan(one_chip):
    """X layers lower to XOR-mask permutations, which are axis reversals:
    no ``2**n`` index map to compile."""
    from repro.core import gates as G
    gs = [G.h(q) for q in range(N)]
    gs += [G.cz(q, q + 1) for q in range(N - 1)]
    gs += [G.x(q) for q in range(0, N, 3)]
    gs += [G.cz(q, q + 1) for q in range(N - 1)]
    gs += [G.x(q) for q in range(N)]
    plan = compile_plan(template_of(C.Circuit(N, gs)), backend="planar",
                        target=TPU_V5E)
    assert any(it.kind == "perm" for it in plan.items)
    params = jax.ShapeDtypeStruct((plan.num_params,), jnp.float32)
    _compile(one_chip, plan._program(), STATE, params)


def _grid_rcs(n: int):
    """The ``qrc30`` cell's 2-D grid random circuit cut to a 4 x 6 grid."""
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench"
            / "families" / "grid_rcs.py")
    spec = importlib.util.spec_from_file_location("grid_rcs", path)
    family = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(family)
    return family.program_template({"name": "grid_rcs", "rows": 4,
                                    "cols": n // 4, "n": n, "depth": 14})


@pytest.mark.parametrize("circuit", ["qrc", "grid_rcs"])
def test_sharded_qrc_plan(topo, circuit):
    """The whole-circuit program state-sharded over the four chips of the
    described host (``CompiledPlan._build_sharded``, a batch of one), 22
    local qubits a chip, for the 1-D brickwork and for the ``qrc30`` cell's
    2-D grid circuit: each plan item's lane bits keep a block of row bits
    to be exchanged with, so no view pads the lanes, and each chip's
    temporaries stay within 4x its local state."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import distributed as D
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4),
                (D.BATCH_AXIS, D.STATE_AXIS))
    template = (template_of(C.qrc(N, depth=8)) if circuit == "qrc"
                else _grid_rcs(N))
    plan = compile_plan(template, backend="planar", target=TPU_V5E,
                        state_bits=2)
    fn, counter = plan._build_sharded(mesh, 1, single=True)
    params = jax.ShapeDtypeStruct((1, plan.num_params), jnp.float32,
                                  sharding=NamedSharding(mesh,
                                                         P(D.BATCH_AXIS)))
    compiled = fn.lower(params).compile()
    assert counter["swaps"] >= 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 4 * STATE_BYTES // 4, f"temporaries {temp} > 4x local"
