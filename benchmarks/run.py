"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig6 tab4  # subset
"""
from __future__ import annotations

import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

from benchmarks import (batch_throughput, chaos_serve, concurrent_ingest,
                        fig6_overall, fig10_fusion, fig11_ai, fig12_ablation,
                        fig13_scaling, fig14_projection, gate_classes,
                        result_modes, roofline, serve_mixed, shape_routing,
                        sharded_batch, tab3_gate_ops, tab4_vectorization,
                        telemetry_overhead)

MODULES = {
    "fig6": fig6_overall,
    "tab3": tab3_gate_ops,
    "tab4": tab4_vectorization,
    "fig10": fig10_fusion,
    "fig11": fig11_ai,
    "fig12": fig12_ablation,
    "fig13": fig13_scaling,
    "fig14": fig14_projection,
    "roofline": roofline,
    "batch": batch_throughput,
    "serve": serve_mixed,
    "ingest": concurrent_ingest,
    "chaos": chaos_serve,
    "classes": gate_classes,
    "results": result_modes,
    "routing": shape_routing,
    "sharded": sharded_batch,
    "telemetry": telemetry_overhead,
}


def main() -> int:
    which = sys.argv[1:] or list(MODULES)
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name in which:
        t0 = time.time()
        try:
            MODULES[name].main()
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
