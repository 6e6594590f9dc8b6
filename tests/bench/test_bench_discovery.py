"""A circuit family, a configuration, a traffic loop, a traffic mix, a
per-layer metric and a cell are added as new files and new BENCHMARK.json
entries only, and a run finds them by name; every file the benchmark
already had stays as it was."""
import hashlib
import json
import pathlib

import bench_testlib as L
from bench import run

READER = '''"""Twice the plan's items (a test metric)."""


def read(ctx):
    return 2.0 * len(ctx.counters["plan_items"])
'''

# a family that brings a gate of its own to the reference (the callable)
FAMILY = '''"""Rotations on a chain, CZs between neighbours, X on qubit 0."""
import numpy as np

from bench import reference

X = np.array([[0, 1], [1, 0]], np.complex128)


def _x0(re, im, params, precision):
    u = np.broadcast_to(X, params.shape[:-1] + (2, 2))
    return reference.apply_1q(re, im, u, 0, precision)


def num_params(cfg):
    return cfg["n"]


def instance(cfg, rng):
    return rng.uniform(0.0, 2 * np.pi, cfg["n"])


def _pairs(cfg):
    return tuple((q, q + 1) for q in range(cfg["n"] - 1))


def reference_gates(cfg):
    gates = [("ry", q, q, 1.0) for q in range(cfg["n"])]
    return gates + [("cz", _pairs(cfg)), (_x0,)]


def observables(cfg):
    return ()


def program_template(cfg):
    from repro.core import gates as G
    from repro.engine.template import CircuitTemplate, TemplateOp, fixed_op
    ops = [TemplateOp("ry", (q,), param=q) for q in range(cfg["n"])]
    ops += [fixed_op(G.cz(a, b)) for a, b in _pairs(cfg)]
    ops.append(fixed_op(G.x(0)))
    return CircuitTemplate(cfg["n"], tuple(ops), num_params=cfg["n"])


def program_observables(cfg):
    return []
'''

# a loop that times a fixed count of circuits and checks the last one
LOOP = '''"""A fixed count of circuits after one warm-up circuit (a test loop)."""
import time

from bench import compare, reference
from bench.harness import Profiler, RunRecord, Spans
from bench.traffic_gen import Instances


def run(cfg, traffic, family, *, seed, seconds, trace, devices, log):
    from repro.core.simulator import Simulator
    sim = Simulator(backend=traffic["backend"])
    template = family.program_template(cfg)
    params = Instances(seed, cfg, family)
    sim.run(template, params=params(0)).data.block_until_ready()
    items = [(it.kind, len(it.qubits), len(it.controls))
             for it in sim.plan_for(template).items]
    log(f"setup: plan_items={len(items)}")
    count = traffic["circuits"]
    prof = Profiler(trace)
    prof.start()
    t0 = time.perf_counter()
    with Spans(trace)("window"):
        for i in range(1, count + 1):
            st = sim.run(template, params=params(i))
            st.data.block_until_ready()
    t1 = time.perf_counter()
    prof.stop()
    re, im = reference.run_gates(cfg["n"], family.reference_gates(cfg),
                                 params(count))
    err = compare.state_error(st.data, re, im)
    return RunRecord(
        e2e={"circuit_s": (t1 - t0) / count},
        counters={"plan_items": items, "circuits": count},
        checks=[("state_err", err, cfg["limits"]["state_err"])],
        attempted=count, failed=0, memory_peak_bytes=None, window_start=t0,
        trace_dir=prof.dir)


def control(cfg, traffic, family):
    raise NotImplementedError


FAULTS = {}
'''


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _add_cell(root, family=FAMILY):
    """Add the family, loop, configuration, traffic and cell above as new
    files and entries."""
    bench = root / "bench"
    (bench / "families" / "chain_ry.py").write_text(family)
    (bench / "loops" / "fixed_count.py").write_text(LOOP)
    (bench / "configs" / "chain9.json").write_text(json.dumps(
        {"name": "chain9", "circuit": "chain_ry", "n": 9,
         "limits": {"state_err": 2e-5}}))
    (bench / "traffic" / "three_planar.json").write_text(json.dumps(
        {"loop": "fixed_count", "backend": "planar", "circuits": 3}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "chain9", "source": "test",
                           "file": "bench/configs/chain9.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "chain9.three", "config": "chain9",
                             "traffic": "three_planar", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] == "circuit_s":
            m["workloads"].append("chain9.three")
    return doc


def test_new_files_and_entries_only(tmp_path):
    root = L.small_checkout(tmp_path)
    before = _digests(root)
    doc = _add_cell(root)
    (root / "bench" / "metrics" / "double_items.circuit.py").write_text(
        READER)
    doc["per_layer"].append({"name": "double_items.circuit", "unit": "items",
                             "better": "lower", "source": "program_counter",
                             "layer": "plan compiler", "moves": "circuit_s",
                             "workloads": ["chain9.three"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    after = _digests(root)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {pathlib.Path("BENCHMARK.json")}
    assert set(after) - set(before) == {pathlib.Path(p) for p in (
        "bench/families/chain_ry.py", "bench/loops/fixed_count.py",
        "bench/configs/chain9.json", "bench/traffic/three_planar.json",
        "bench/metrics/double_items.circuit.py")}

    rc, line, err = L.run_cell(run.main, root,
                               L.argv("chain9.three", trace=1))
    assert rc == 0, err
    assert line["correct"] is True
    items = next(ln for ln in err.splitlines() if "plan_items=" in ln)
    n_items = int(items.split("plan_items=")[1].split()[0])
    assert line["metrics"]["double_items.circuit"]["value"] == 2.0 * n_items
    rc, line, err = L.run_cell(run.main, root, L.argv("chain9.three"))
    assert rc == 0, err
    assert line["correct"] is True
    assert line["attempted"] == 3
    assert set(line["metrics"]) == {"circuit_s", "setup_s"}


def test_a_new_family_that_disagrees_is_not_correct(tmp_path):
    # the new family's program puts its X on qubit 1, its reference on 0
    root = L.small_checkout(tmp_path)
    doc = _add_cell(root, FAMILY.replace("fixed_op(G.x(0))",
                                         "fixed_op(G.x(1))"))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    rc, line, err = L.run_cell(run.main, root, L.argv("chain9.three"))
    assert rc == 0, err
    assert line["correct"] is False
