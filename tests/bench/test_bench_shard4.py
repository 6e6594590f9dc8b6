"""The state-sharded cell ``qrc30.shard4`` at test size: its circuit cut to
n = 12 (a 3 x 4 grid, 10 local qubits a device) in the test's own checkout,
run on four host devices in subprocesses, as the cell needs a mesh.

- the blocked reference (``bench.reference_sharded``), its blocks put back
  together, equals ``bench.reference``'s uncut state;
- the cell runs in-process through ``bench/run.py``'s ``main`` and reads
  ``correct: true``: ``Simulator(mesh=4).run`` agrees with the blocked
  reference on the seeded circuits; a traced run reports the swap counter;
- its control and its planted faults read ``correct: false``, the
  ``unchanged`` fault planted in the sharded program's item steps.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import bench_testlib as L

CELL = "qrc30.shard4"
SCRIPT = textwrap.dedent("""
    import json, pathlib, sys
    sys.path[:0] = [{tests!r}, {repo!r}, {src!r}]
    import numpy as np
    import bench_testlib as L
    root, what = pathlib.Path(sys.argv[1]), sys.argv[2:]
    out = {{}}
    for w in what:
        if w == "reassembled":
            import jax
            from bench import reference, reference_sharded
            from bench.spec import Benchmark
            from bench.traffic_gen import Instances
            bm = Benchmark(root)
            cfg = bm.config("qrc30")
            fam = bm.family(cfg["circuit"])
            p = Instances(4294967311, cfg, fam)(0)
            gates = fam.reference_gates(cfg)
            blocks = reference_sharded.blocks(reference_sharded.run_gates(
                cfg["n"], gates, p, jax.devices()))
            re, im = reference.run_gates(cfg["n"], gates, p)
            got = np.concatenate([np.asarray(a).ravel() + 1j *
                                  np.asarray(b).ravel()
                                  for _, a, b in blocks])
            want = np.asarray(re).ravel() + 1j * np.asarray(im).ravel()
            out[w] = {{"devices": sorted(str(d) for d, _, _ in blocks),
                      "gap": float(np.max(np.abs(got - want))),
                      "norm": float(np.linalg.norm(want))}}
            continue
        from bench import control, run
        argv = ["--workload", {cell!r}, "--seed", "4294967323",
                "--seconds", "0.5"]
        if w in ("run", "trace"):
            rc, line, err = L.run_cell(
                run.main, root, argv + ["--trace", str(int(w == "trace"))])
        else:
            rc, line, err = L.run_cell(
                control.main, root,
                argv + ([] if w == "control" else ["--fault", w]))
        out[w] = {{"rc": rc, "line": line, "err": err[-4000:]}}
    print(json.dumps(out))
""").format(tests=str(L.REPO / "tests" / "bench"), repo=str(L.REPO),
            src=str(L.SRC), cell=CELL)
GROUPS = (("reassembled", "run", "control"), ("altered", "nan"),
          ("unchanged", "trace"))


def _checkout(tmp):
    """A checkout at test size with the cell's circuit cut to n = 12."""
    root = L.small_checkout(tmp)
    for path, change in (("configs/qrc30.json", {"rows": 3, "cols": 4,
                                                 "n": 12}),
                         ("traffic/serial_shard4.json",
                          {"max_local_qubits": 10})):
        f = root / "bench" / path
        f.write_text(json.dumps({**json.loads(f.read_text()), **change}))
    return root


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    """Every group of runs at once, one subprocess a group, each on four
    host devices; ``{run: result}``."""
    root = _checkout(tmp_path_factory.mktemp("shard4"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    procs = [subprocess.Popen([sys.executable, "-c", SCRIPT, str(root),
                               *group], env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for group in GROUPS]
    out = {}
    for p in procs:
        stdout, stderr = p.communicate(timeout=480)
        assert p.returncode == 0, stderr[-4000:]
        out.update(json.loads(stdout.strip().splitlines()[-1]))
    return out


def test_reassembled_blocks_equal_the_uncut_reference(results):
    r = results["reassembled"]
    assert len(set(r["devices"])) == 4          # one block a device
    assert r["norm"] == pytest.approx(1.0, abs=1e-5)
    assert r["gap"] <= 1e-6, r


def test_sharded_cell_agrees_with_the_blocked_reference(results):
    r = results["run"]
    assert r["rc"] == 0, r["err"]
    line = r["line"]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"circuit_s", "setup_s"}
    c = line["checks"]["state_err"]
    assert 0 < c["value"] <= c["limit"]
    assert line["device"]["count"] == 4
    setup = next(ln for ln in r["err"].splitlines()
                 if ln.startswith("setup:"))
    assert "state_bits=2" in setup
    window = next(ln for ln in r["err"].splitlines()
                  if ln.startswith("window:"))
    assert "compiles=0 " in window


def test_traced_run_reports_the_swap_counter(results):
    """A ``--trace 1`` run: the CPU has no device plane, so of the cell's
    per-layer metrics only the program's swap counter reads."""
    r = results["trace"]
    assert r["rc"] == 0, r["err"]
    assert r["line"]["correct"] is True
    setup = next(ln for ln in r["err"].splitlines()
                 if ln.startswith("setup:"))
    swaps = int(setup.split("swaps=")[1].split()[0])
    assert swaps >= 2
    assert r["line"]["metrics"] == {
        "swaps.shard4": {"value": float(swaps), "unit": "swaps"}}


@pytest.mark.parametrize("run,check", [("control", "value"),
                                       ("altered", "value"),
                                       ("nan", "nan"),
                                       ("unchanged", "value")])
def test_control_and_faults_are_not_correct(results, run, check):
    r = results[run]
    assert r["rc"] == 0, r["err"]
    c = r["line"]["checks"]["state_err"]
    assert r["line"]["correct"] is False
    if check == "nan":
        assert c["value"] == "nan"
    else:
        assert c["value"] > c["limit"]


def test_loop_refuses_a_program_without_the_victim_rule(monkeypatch):
    """A program whose sharded plan lacks the lane-window victim rule is
    refused before anything compiles (the parent's n = 30 program asks a
    chip for 32 GiB and fails only after many minutes of compiling)."""
    import importlib
    if str(L.SRC) not in sys.path:
        monkeypatch.syspath_prepend(str(L.SRC))
    plan = importlib.import_module("repro.engine.plan")
    loop = importlib.import_module("bench.loops.serial_sharded")
    loop._check_program()
    monkeypatch.delattr(plan, "sharded_windows_kept")
    with pytest.raises(RuntimeError, match="victim rule"):
        loop._check_program()
