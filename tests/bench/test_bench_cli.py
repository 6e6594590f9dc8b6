"""The command as a benchmark check runs it: on a machine without a TPU, and in
a directory holding only the benchmark's own files, it exits non-zero
and prints no result line."""
import json
import os
import shutil
import subprocess
import sys

import bench_testlib as L

ARGS = ["--workload", "qrc28.planar", "--seed", "4294967296",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_chip_no_result():
    p = _run(L.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    doc = json.loads((L.REPO / "BENCHMARK.json").read_text())
    for path in doc["paths"]:
        shutil.copytree(L.REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(L.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_workload_gives_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "nope",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=L.REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
