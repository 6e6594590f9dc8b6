"""Finding the benchmark's parts by name.

``BENCHMARK.json`` at the checkout's root names every cell, configuration
and metric; the rest is found by name under ``bench/``:

- a configuration at the ``file`` its entry gives; its ``circuit`` names
  a family, ``bench/families/<circuit>.py``, which builds the circuit for
  the program and for the reference (``num_params``, ``instance``,
  ``reference_gates``, ``observables``, ``program_template``,
  ``program_observables``);
- a traffic mix at ``bench/traffic/<traffic>.json``, data only; its
  ``loop`` names a loop, ``bench/loops/<loop>.py`` (``run``, ``control``
  and ``FAULTS``, see :mod:`bench.harness`);
- a per-layer metric's reader at ``bench/metrics/<metric>.py`` (a module
  with ``read(ctx)``).

Adding any of them adds files and entries, never an edit here.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _load_json(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


class Benchmark:
    """``BENCHMARK.json`` of the checkout at ``root`` and its files."""

    def __init__(self, root):
        self.root = pathlib.Path(root)
        self.doc = _load_json(self.root / "BENCHMARK.json")
        self.bench = self.root / "bench"

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load_json(self.root / c["file"])
        raise SpecError(f"no configuration named {name!r}")

    def traffic(self, name: str) -> dict:
        return _load_json(self.bench / "traffic" / f"{name}.json")

    def peaks(self, kind: str) -> dict:
        table = _load_json(self.bench / "peaks.json")["devices"]
        if kind not in table:
            raise SpecError(f"device kind {kind!r} is not in bench/peaks.json")
        return table[kind]

    def end_to_end(self, cell: str) -> list:
        """The end-to-end metrics ``cell`` reports: those that list it,
        and those that list no cells."""
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", (cell,))]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics ``cell`` reports: those that list it, and
        those that list no cells but move an end-to-end metric it
        reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def _module(self, kind: str, name: str):
        """The module ``bench/<kind>/<name>.py``."""
        path = self.bench / kind / f"{name}.py"
        if not path.exists():
            raise SpecError(f"no module {path} for {kind} {name!r}")
        mod_name = f"bench_{kind}_{name.replace('.', '_')}"
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        # registered before it runs, as an import would, so that its
        # dataclasses can find their module
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        return mod

    def family(self, circuit: str):
        """The circuit family module ``bench/families/<circuit>.py``."""
        return self._module("families", circuit)

    def loop(self, loop: str):
        """The traffic loop module ``bench/loops/<loop>.py``."""
        return self._module("loops", loop)

    def reader(self, metric: str):
        """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
        return self._module("metrics", metric).read
