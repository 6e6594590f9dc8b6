"""Helpers for the benchmark's tests: a checkout of the benchmark at test
size (the configurations' qubit counts and the client fleet cut down,
everything else as committed) and a way to run a cell in-process on the
CPU, past the harness's look for a chip."""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
SRC = REPO / "src"
SMALL = {"qrc28": {"rows": 3, "cols": 4, "n": 12},
         "qaoa20": {"n": 12, "max_batch": 4}}
SMALL_TRAFFIC = {"clients192": {"clients": 16, "check_requests": 64}}

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def small_checkout(tmp: pathlib.Path) -> pathlib.Path:
    """A copy of ``BENCHMARK.json`` and ``bench/`` under ``tmp`` with the
    configurations and traffic cut to test size."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    for c in doc["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(SMALL.get(c["name"], {}))
        path.write_text(json.dumps(cfg))
    for name, change in SMALL_TRAFFIC.items():
        path = root / "bench" / "traffic" / f"{name}.json"
        traffic = json.loads(path.read_text())
        traffic.update(change)
        path.write_text(json.dumps(traffic))
    return root


@contextlib.contextmanager
def cache_restored():
    """Put JAX's compilation-cache settings back after a run, which
    points them at the checkout's cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def run_cell(main, root, argv, **kw):
    """Run ``main(argv, root=root, src=SRC, require_chip=False, **kw)``;
    returns ``(exit code, parsed last stdout line or None, stderr)``."""
    out, err = io.StringIO(), io.StringIO()
    with cache_restored(), contextlib.redirect_stdout(out), \
            contextlib.redirect_stderr(err):
        rc = main(argv, root=root, src=SRC, require_chip=False, **kw)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()


def argv(cell, seed=4294967311, seconds=0.5, trace=0):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace)]
