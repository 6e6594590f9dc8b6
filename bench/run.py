#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its circuit family, its traffic mix and
loop, and its metrics are found by name from ``BENCHMARK.json`` (see
:mod:`bench.spec`).  Set-up (imports,
compiles or compile-cache loads, warm-up) runs first and counts as
``setup_s``; then the window measures for ``--seconds`` seconds
(``--trace 0``: the end-to-end metrics, host clock) or profiles a short
window (``--trace 1``: the per-layer metrics).  Once the window has closed,
a seeded sample of what it produced is compared with the plain reference.

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``--trace 1``: and
``breakdown``), and last ``checks``: each number compared beside its limit,
which are also the last lines of standard error.  Without a TPU, with a
device kind missing from ``bench/peaks.json``, with fewer chips than the
cell asks for, or without the repository's ``src/`` beside ``bench/``, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
EXIT_NO_CHIP = 3
EXIT_SPEC = 4
EXIT_NO_RESULT = 5


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root=ROOT, src=None, require_chip=True,
         t_start=None) -> int:
    """Run one cell; returns the exit code.  ``require_chip=False`` skips
    the look for a TPU (tests on the CPU drive the rest of a run)."""
    t_start = T_START if t_start is None else t_start
    args = parse(argv)
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from bench import harness, trace
    from bench.spec import Benchmark, SpecError

    try:
        bm = Benchmark(root)
        cell = bm.cell(args.workload)
        cfg = bm.config(cell["config"])
        traffic = bm.traffic(cell["traffic"])
        family = bm.family(cfg["circuit"])
        loop = bm.loop(traffic["loop"])
    except (SpecError, KeyError) as e:
        _log(f"FAIL: {e!r}")
        return EXIT_SPEC
    src = pathlib.Path(src) if src is not None else root / "src"
    if not (src / "repro").is_dir():
        _log(f"FAIL: the program's sources are not at {src}")
        return EXIT_SPEC
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            _log(f"FAIL: no accelerator: JAX found {dev.platform!r}")
            return EXIT_NO_CHIP
        if len(devices) < cell["chips"]:
            _log(f"FAIL: {args.workload} needs {cell['chips']} chips, "
                 f"found {len(devices)}")
            return EXIT_NO_CHIP
        try:
            peaks = bm.peaks(dev.device_kind)
        except SpecError as e:
            _log(f"FAIL: {e}")
            return EXIT_NO_CHIP
    else:
        peaks = bm.peaks("TPU v5 lite")

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program, however fast it compiles, is written to the cache, so
    # a second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    used = devices[:cell["chips"]]
    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
         f"workload={args.workload} seed={args.seed} "
         f"seconds={args.seconds} trace={args.trace} compile_cache={cache}")

    record = loop.run(cfg, traffic, family, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=used, log=_log)
    try:
        metrics, extra = {}, {}
        if not args.trace:
            values = dict(record.e2e, setup_s=record.window_start - t_start)
            missing = [m["name"] for m in bm.end_to_end(args.workload)
                       if m["name"] not in values]
            if missing:
                _log(f"FAIL: the window measured no {missing}")
                return EXIT_NO_RESULT
            for m in bm.end_to_end(args.workload):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        else:
            summary = trace.summarize(*trace.load(record.trace_dir))
            ctx = types.SimpleNamespace(
                trace=summary, counters=record.counters, peaks=peaks,
                config=cfg, traffic=traffic, cell=cell)
            for m in bm.per_layer(args.workload):
                value = bm.reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            extra = {"busy_s": summary.busy_s, "window_s": summary.window_s}
            breakdown = trace.breakdown(summary)
    finally:
        harness.cleanup(record)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": record.memory_peak_bytes, **extra}
    checks = {name: {"value": _number(value), "limit": limit}
              for name, value, limit in record.checks}
    correct = all(value <= limit for _, value, limit in record.checks)
    line = {"correct": correct, "attempted": record.attempted,
            "failed": record.failed, "metrics": metrics, "device": device}
    if args.trace:
        line["breakdown"] = breakdown
    line["checks"] = checks
    for name, value, limit in record.checks:
        _log(f"check {name}: {value!r} limit {limit!r} "
             f"{'ok' if value <= limit else 'FAIL'}")
    print(json.dumps(line), flush=True)
    return 0


def _number(v):
    """``v``, or its name (``"nan"``, ``"inf"``) where it is not finite,
    which JSON has no number for."""
    return v if math.isfinite(v) else repr(float(v))


if __name__ == "__main__":
    sys.exit(main())
