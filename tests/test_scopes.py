"""Device attributes and profiler host spans (``repro.core.scopes``).

The compiled programs carry ``repro_*`` frontend attributes on every
operation the program emits (plan item, kind, width, exchange or apply,
collective, epilogue term); the attributes change nothing else in the
compiled program; and ``Simulator.run`` and the serving path put
``repro.*`` host spans, nested by stage, into a profile.  The
state-sharded program needs four devices, so it is compiled in a
subprocess with four host devices.
"""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scopes
from repro.core.simulator import Simulator
from repro.core.target import CPU_TEST
from repro.engine import (BatchExecutor, IngestServer, PlanCache, ResultSpec,
                          hea_template, qaoa_template)
from repro.engine.plan import compile_plan

N = 12
STATE = jax.ShapeDtypeStruct((2, 1 << (N - CPU_TEST.lane_qubits),
                              CPU_TEST.lanes), jnp.float32)
INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) ([a-z][\w\-]*)\(")
HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")


def _programs():
    """(name, jitted program, argument shapes) of the three program
    kinds the benchmark drives: whole-circuit planar and Pallas
    (interpret mode), and the batched expectation program."""
    t = hea_template(N, 2)
    out = []
    for backend in ("planar", "pallas"):
        plan = compile_plan(t, backend=backend, target=CPU_TEST)
        out.append((backend, jax.jit(plan._program(), donate_argnums=(0,)),
                    (STATE, jax.ShapeDtypeStruct((t.num_params,),
                                                 jnp.float32))))
    q = qaoa_template(N, 2)
    spec = ResultSpec.expectation([{i: "Z", (i + 1) % N: "Z"}
                                   for i in range(N)] + [{2: "X"}])
    plan = compile_plan(q, backend="planar", target=CPU_TEST, result=spec)
    out.append(("expectation", plan._build_batched_result(),
                (STATE, jax.ShapeDtypeStruct((4, q.num_params), jnp.float32),
                 jax.ShapeDtypeStruct((4, 2), jnp.uint32))))
    return out


def _optimized(fn, args) -> str:
    text = fn.lower(*args).compile().as_text()
    # a Pallas call's kernel_metadata is JSON printed over several lines
    return re.sub(r"kernel_metadata=\{[^}]*\}",
                  lambda m: m.group(0).replace("\n", ""), text)


def _instructions(text):
    """``(computation, name, shape, opcode, line)`` in program order."""
    comp = None
    for line in text.splitlines():
        head = HEAD.match(line)
        if head:
            comp = head.group(1)
            continue
        m = INSTR.match(line)
        if m:
            yield comp, m.group(1), m.group(2), m.group(3), line


def _xla_made(name: str, root_opcode: str | None) -> bool:
    """Fusions XLA builds around operations it creates itself, which carry
    no frontend attributes: a root ``bitcast`` or ``copy`` (layout
    assignment) and the single-operation ``wrapped_*`` fusions (its
    reduce-window and broadcast rewrites)."""
    return name.startswith("wrapped_") or root_opcode in ("bitcast", "copy")


SHARDED = textwrap.dedent("""
    import json, re, sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core import distributed as D
    from repro.core.target import CPU_TEST
    from repro.engine import hea_template
    from repro.engine.plan import compile_plan
    t = hea_template({n}, 2)
    plan = compile_plan(t, backend="planar", target=CPU_TEST, state_bits=2)
    mesh = Mesh(np.array(jax.devices()).reshape(1, 4),
                (D.BATCH_AXIS, D.STATE_AXIS))
    fn, counter = plan._build_sharded(mesh, 1, single=True)
    lowered = fn.lower(jax.ShapeDtypeStruct((1, t.num_params), jnp.float32))
    text = lowered.compile().as_text()
    print(json.dumps({{"swaps": counter["swaps"], "optimized": text,
                      "hlo": lowered.as_text("hlo")}}))
""").format(src=os.path.join(os.path.dirname(__file__), "..", "src"), n=N)


def _sharded():
    """The whole-circuit program state-sharded over four host devices
    (``CompiledPlan._build_sharded``, a batch of one): its optimized and
    its lowered HLO text, and the swaps it traced."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _cpu_collective_rewrite(line: str, root_opcode: str | None) -> bool:
    """On the CPU, XLA splits each ``all_to_all`` of the sharded program
    into slices and a concatenate, merges them with the concatenates
    around, and folds constant indices into operand-less fusions.  The
    slices and concatenates it builds keep the scope's name in their
    metadata (``item=...``, ``kind=...``) but not its attributes.  The TPU
    keeps the ``all_to_all`` whole."""
    if re.search(r"= \S+ fusion\(\)", line):
        return True
    name = re.search(r'op_name="([^"]*)"', line)
    return (root_opcode in ("slice", "concatenate") and name is not None
            and re.search(r"(item|kind)=", name.group(1)) is not None)


@pytest.fixture(scope="module")
def sharded():
    return _sharded()


@pytest.fixture(scope="module")
def compiled(sharded):
    out = {name: _optimized(fn, args) for name, fn, args in _programs()}
    out["sharded"] = sharded["optimized"]
    return out


@pytest.mark.parametrize("program",
                         ["planar", "pallas", "expectation", "sharded"])
def test_every_emitted_op_is_attributed(compiled, program):
    text = compiled[program]
    instrs = list(_instructions(text))
    roots = {comp: op for comp, _, _, op, line in instrs
             if line.lstrip().startswith("ROOT")}
    checked = untagged = 0
    for _, name, _, op, line in instrs:
        if op not in ("fusion", "custom-call", "dot"):
            continue
        callee = re.search(r"calls=%?([\w.\-]+)", line)
        root = roots.get(callee.group(1)) if callee else None
        if _xla_made(name, root):
            continue
        checked += 1
        if program == "sharded" and _cpu_collective_rewrite(line, root):
            continue
        if "repro_item=" not in line and "repro_kind=" not in line:
            untagged += 1
            print("untagged:", line[:200])
    assert checked > 20
    assert untagged == 0


@pytest.mark.parametrize("program",
                         ["planar", "pallas", "expectation", "sharded"])
def test_exchanges_are_tagged(compiled, program):
    assert 'repro_part="exchange"' in compiled[program]


def test_sharded_swaps_are_tagged_collectives(sharded):
    """Each qubit-block swap is one ``all_to_all`` tagged
    ``repro_part="collective"``, the restores at the end (``repro_kind=
    "restore"``) included, and their count is the program's swap
    counter; the plan items keep their item tags."""
    a2a = [ln for ln in sharded["hlo"].splitlines()
           if re.search(r"= \S+ all-to-all\(", ln)]
    assert sharded["swaps"] >= 3
    assert len(a2a) == sharded["swaps"]
    assert all('repro_part="collective"' in ln for ln in a2a)
    assert any('repro_kind="restore"' in ln for ln in a2a)
    assert any("repro_item=" in ln for ln in a2a)
    items = set(re.findall(r'repro_item="(\d+)"', sharded["optimized"]))
    assert len(items) > 5
    assert 'repro_kind="init"' in sharded["optimized"]


def test_items_and_epilogue_terms_are_tagged(compiled):
    items = set(re.findall(r'repro_item="(\d+)"', compiled["planar"]))
    assert len(items) > 5
    # the N Z-strings share one pass; the X string is term N of the spec
    terms = set(re.findall(r'repro_term="(\w+)"', compiled["expectation"]))
    assert terms == {"z", str(N)}
    assert 'repro_kind="epilogue"' in compiled["expectation"]


def test_scopes_nest_and_name_the_kernel_item():
    """Inner scopes add keys and win on a clash; a Pallas call's
    metadata names the item and kind of the scope it is traced in."""
    assert scopes.current_attrs() == {}
    assert scopes.kernel_metadata() == {"item": "", "kind": ""}
    with scopes.device_scope(item=3, kind="dense", part="apply"):
        with scopes.device_scope(part="exchange"):
            assert scopes.current_attrs() == {
                "item": "3", "kind": "dense", "part": "exchange"}
            assert scopes.kernel_metadata() == {"item": "3", "kind": "dense"}
        assert scopes.current_attrs()["part"] == "apply"
    assert scopes.current_attrs() == {}


@pytest.mark.parametrize("program", ["planar", "pallas", "expectation"])
def test_attributes_leave_the_program_unchanged(monkeypatch, compiled,
                                                program):
    """The optimized program has the same (opcode, shape) sequence with
    the attributes as with ``device_scope`` a null context."""
    def ops(text):
        return [(op, re.sub(r"\{[^}]*\}", "", shape))
                for _, _, shape, op, _ in _instructions(text)]
    monkeypatch.setattr(scopes, "device_scope",
                        lambda **kw: contextlib.nullcontext())
    (fn, args), = [(f, a) for name, f, a in _programs() if name == program]
    bare = _optimized(fn, args)
    assert "repro_" not in bare
    assert ops(bare) == ops(compiled[program])


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats))
                      for e in line.events if e.name.startswith("repro.")]
    return spans


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_simulator_run_spans_nest(tmp_path):
    t = hea_template(8, 1)
    sim = Simulator(backend="planar", plan_cache=PlanCache())
    p = np.zeros(t.num_params, np.float32)
    sim.run(t, params=p).data.block_until_ready()       # compile first
    with jax.profiler.trace(str(tmp_path)):
        sim.run(t, params=p).data.block_until_ready()
    spans = _host_spans(tmp_path)
    (run,) = [s for s in spans if s[0] == "repro.sim.run"]
    assert run[3] == {"n": 8}
    for stage in ("repro.plan.lookup", "repro.state.init", "repro.params",
                  "repro.dispatch"):
        (s,) = [s for s in spans if s[0] == stage]
        assert _inside(s, run), stage


def test_ingest_batch_spans_nest(tmp_path):
    t = qaoa_template(8, 1)
    server = IngestServer(BatchExecutor(backend="planar", cache=PlanCache()),
                          max_batch=4, autostart=False)
    warm = [server.submit(t, np.zeros(t.num_params)) for _ in range(4)]
    while not all(h.done() for h in warm):
        server.step(force=True)
    with jax.profiler.trace(str(tmp_path)):
        handles = [server.submit(t, np.full(t.num_params, 0.1))
                   for _ in range(3)]
        deadline = time.monotonic() + 60
        while not all(h.done() for h in handles):
            assert time.monotonic() < deadline
            server.step(force=True)
    server.close()
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    assert {"repro.ingest.submit", "repro.ingest.collect", "repro.sched.poll",
            "repro.sched.stage", "repro.sched.dispatch",
            "repro.sched.finalize", "repro.ingest.deliver"} <= names
    assert len([s for s in spans if s[0] == "repro.ingest.submit"]) == 3
    req0 = handles[0].request.req_id
    polls = [s for s in spans if s[0] == "repro.sched.poll"]
    for stage in ("repro.sched.stage", "repro.sched.dispatch",
                  "repro.sched.finalize"):
        (s,) = [s for s in spans if s[0] == stage]
        # the batch of 3 real rows, padded to 4, opened by the first request
        assert s[3] == {"rows": 3, "padded": 4, "req": req0}, stage
        assert any(_inside(s, p) for p in polls), stage
