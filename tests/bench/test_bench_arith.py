"""Metric arithmetic: the nearest-rank tail over all requests, the item
and call bounds of the roofline count, and the readers built on them."""
import math
import types

import numpy as np
import pytest

import bench_testlib
from bench import compare, harness, roofline
from bench import trace as T
from bench.spec import Benchmark

PEAKS = {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_percentile_is_nearest_rank_over_every_value():
    vals = list(range(1, 101))
    assert compare.percentile(vals, 95) == 95
    assert compare.percentile(vals[::-1], 95) == 95
    assert compare.percentile([5.0], 95) == 5.0
    # 19 fast and 1 slow request: the tail is the slow one only at p100
    assert compare.percentile([1.0] * 19 + [9.0], 95) == 1.0
    assert compare.percentile([1.0] * 19 + [9.0], 96) == 9.0


def test_worse_keeps_nan_and_comparisons_propagate_it():
    assert harness.worse(0.0, 1e-6) == 1e-6
    assert math.isnan(harness.worse(0.0, math.nan))
    assert math.isnan(harness.worse(math.nan, 1.0))
    # NaN stays the worst whatever finite errors come after it
    worst = 0.0
    for err in (1e-7, math.nan, 1e-6):
        worst = harness.worse(worst, err)
    assert math.isnan(worst)
    assert math.isnan(compare.widest_gap([[0.5, np.nan]], [[0.5, 0.25]]))


def test_item_flops_and_bounds():
    n, sb = 28, 8 << 28
    assert roofline.item_flops("dense", 7, 0, n) == 8 * 128 * 2.0 ** 28
    assert roofline.item_flops("dense", 2, 1, n) == 8 * 4 * 2.0 ** 27
    assert roofline.item_flops("diag", 6, 0, n) == 6 * 2.0 ** 28
    assert roofline.item_flops("perm", 3, 0, n) == 0.0
    hbm = 2 * sb / 819e9
    assert roofline.item_bound_s("dense", 7, 0, n, sb, PEAKS) == \
        pytest.approx(hbm)
    # HBM binds every item of an f <= 7 plan; a 9-qubit dense item would
    # be bound by compute
    assert roofline.item_flops("dense", 7, 0, n) / 197e12 < hbm
    assert roofline.item_bound_s("dense", 9, 0, n, sb, PEAKS) == \
        pytest.approx(roofline.item_flops("dense", 9, 0, n) / 197e12)
    items = [("dense", 7, 0), ("diag", 6, 0), ("perm", 2, 0)]
    assert roofline.circuit_bound_s(items, n, sb, PEAKS) == \
        pytest.approx(3 * hbm)


def _ctx(ops, counters, window=(0.0, 1.0)):
    summary = T.summarize(ops, [], window=window)
    return types.SimpleNamespace(trace=summary, counters=counters,
                                 peaks=PEAKS)


def _readers():
    return Benchmark(bench_testlib.REPO).reader


def test_sweep_hbm_pct_is_bound_over_busy_per_circuit():
    n, sb = 28, 8 << 28
    items = [("dense", 7, 0)] * 4
    hbm = 2 * sb / 819e9
    # 2 circuits, device busy 10x the bound per circuit
    ops = [T.Interval("fusion", 0.0, 2 * 4 * hbm * 10)]
    ctx = _ctx(ops, {"plan_items": items, "circuits": 2, "n": n,
                     "state_bytes": sb}, window=(0.0, 1.0))
    assert _readers()("sweep_hbm_pct.planar")(ctx) == pytest.approx(10.0)


def test_kernel_readers_match_calls_to_items():
    n, sb = 28, 8 << 28
    items = [("dense", 7, 0), ("diag", 6, 0)]
    hbm = 2 * sb / 819e9
    ops, t = [], 0.0
    for _ in range(3):              # three circuits
        for _ in items:
            ops.append(T.Interval("program.1", t, t + 4 * hbm, stats={
                "hlo": '%program.1 = f32[2,8,128] custom-call(), '
                       'custom_call_target="tpu_custom_call"'}))
            ops.append(T.Interval("transpose.2", t + 4 * hbm,
                                  t + 5 * hbm))
            t += 5 * hbm
    counters = {"plan_items": items, "circuits": 3, "n": n,
                "state_bytes": sb}
    ctx = _ctx(ops, counters, window=(0.0, t))
    read = _readers()
    assert read("kernel_hbm_pct.pallas")(ctx) == pytest.approx(25.0)
    assert read("kernel_busy_pct.pallas")(ctx) == pytest.approx(80.0)
    # a call count that does not match the plan gives no reading
    ctx.counters = dict(counters, circuits=2)
    assert read("kernel_hbm_pct.pallas")(ctx) is None


def test_serve_readers():
    read = _readers()
    ctx = types.SimpleNamespace(
        trace=None, peaks=PEAKS,
        counters={"batch_rows": 192, "padded_slots": 64,
                  "queue_wait_s": [0.1, 0.3, 0.2]})
    assert read("batch_fill_pct.serve")(ctx) == pytest.approx(75.0)
    assert read("queue_wait_ms.serve")(ctx) == pytest.approx(200.0)
    assert read("device_idle_pct.serve")(ctx) is None
    ctx.counters = {}
    assert read("batch_fill_pct.serve")(ctx) is None
    assert read("queue_wait_ms.serve")(ctx) is None
