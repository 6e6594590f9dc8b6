"""Readers of the program's device attributes, on synthetic intervals:
the exchange and epilogue shares of busy time, and the dense kernel's
roofline share with calls matched to plan items by ``repro_item``.  Ops
without attributes (a program that tags nothing) give no reading."""
import types

import pytest

import bench_testlib
from bench import scopes
from bench import trace as T
from bench.spec import Benchmark

PEAKS = {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
N, SB = 28, 8 << 28
HBM = 2 * SB / 819e9            # one item's HBM bound at n = 28


def _op(name, start, end, device=0, **attrs):
    tags = ",".join(f'repro_{k}="{v}"' for k, v in attrs.items())
    hlo = f"%{name} = f32[2,8,128] fusion()"
    if tags:
        hlo += f", frontend_attributes={{{tags}}}"
    return T.Interval(name, start, end, device, {"hlo": hlo})


def _call(name, start, end, **attrs):
    op = _op(name, start, end, **attrs)
    meta = '{\n"item":"%s",\n"kind":"%s"\n}' % (attrs.get("item", ""),
                                               attrs.get("kind", ""))
    op.stats["hlo"] = op.stats["hlo"].replace(
        "fusion()", 'custom-call(), custom_call_target="tpu_custom_call"'
    ).replace("frontend_attributes={",
              f"frontend_attributes={{kernel_metadata={meta},")
    return op


def _ctx(ops, counters=None, window=(0.0, 10.0)):
    return types.SimpleNamespace(trace=T.summarize(ops, [], window=window),
                                 counters=counters or {}, peaks=PEAKS)


def _read(name):
    return Benchmark(bench_testlib.REPO).reader(name)


def test_attrs_parse_the_hlo_text_and_skip_kernel_metadata():
    op = _call("fused_gate.3", 0, 1, item=4, kind="dense", part="apply",
               width=7)
    assert scopes.attrs(op) == {"item": "4", "kind": "dense",
                                "part": "apply", "width": "7"}
    assert scopes.attrs(T.Interval("copy.1", 0, 1)) == {}
    assert scopes.attrs(_op("copy.2", 0, 1)) == {}


def test_exchange_share_is_the_union_over_busy_time():
    ops = [_op("copy.1", 0.0, 2.0, item=0, part="exchange"),
           _op("copy.2", 1.0, 3.0, item=0, part="exchange"),   # overlaps
           _op("fusion.1", 3.0, 7.0, item=0, part="apply"),
           _op("copy-done.1", 7.0, 8.0)]                       # untagged
    ctx = _ctx(ops)
    assert ctx.trace.busy_s == pytest.approx(8.0)
    assert _read("exchange_pct.circuit")(ctx) == pytest.approx(37.5)
    # tagged ops but no exchange: a measured zero
    assert _read("exchange_pct.circuit")(_ctx(ops[2:])) == 0.0


def test_epilogue_share_is_the_union_over_busy_time():
    ops = [_op("fusion.1", 0.0, 6.0, item=3, kind="dense", part="apply"),
           _op("fusion.2", 6.0, 8.0, kind="epilogue", term=0),
           _op("copy.1", 8.0, 9.0, kind="epilogue", term=1,
               part="exchange"),
           _op("copy-done.1", 9.0, 10.0)]
    assert _read("epilogue_pct.serve")(_ctx(ops)) == pytest.approx(30.0)


def test_dense_kernel_share_matches_calls_to_items_by_attribute():
    items = [("dense", 7, 0), ("diag", 12, 0), ("dense", 3, 1)]
    counters = {"plan_items": items, "circuits": 2, "n": N,
                "state_bytes": SB}
    ops, t = [], 0.0
    for _ in range(2):                  # two circuits
        ops.append(_call("fused_gate.1", t, t + 4 * HBM, item=0,
                         kind="dense"))
        ops.append(_call("phase.1", t + 4 * HBM, t + 5 * HBM, item=1,
                         kind="diag"))
        ops.append(_op("copy.1", t + 5 * HBM, t + 6 * HBM, item=2,
                       part="exchange"))
        ops.append(_call("fused_gate.2", t + 6 * HBM, t + 10 * HBM,
                         item=2, kind="dense"))
        t += 10 * HBM
    ctx = _ctx(ops, counters, window=(0.0, t))
    assert _read("dense_kernel_hbm_pct.pallas")(ctx) == pytest.approx(25.0)
    # the order of the calls does not matter, the attribute does
    ctx = _ctx(ops[::-1], counters, window=(0.0, t))
    assert _read("dense_kernel_hbm_pct.pallas")(ctx) == pytest.approx(25.0)


def test_dense_kernel_share_needs_every_call_tagged():
    counters = {"plan_items": [("dense", 7, 0)], "circuits": 1, "n": N,
                "state_bytes": SB}
    ok = _call("fused_gate.1", 0.0, 4 * HBM, item=0, kind="dense")
    bare = _call("fused_gate.2", 4 * HBM, 8 * HBM)
    bare.stats["hlo"] = bare.stats["hlo"].split(", frontend")[0]
    read = _read("dense_kernel_hbm_pct.pallas")
    assert read(_ctx([ok, bare], counters, window=(0.0, 8 * HBM))) is None
    assert read(_ctx([ok], {}, window=(0.0, 4 * HBM))) is None


@pytest.mark.parametrize("metric", ["exchange_pct.circuit",
                                    "epilogue_pct.serve",
                                    "dense_kernel_hbm_pct.pallas"])
def test_untagged_program_reads_nothing(metric):
    """A program without the attributes (the parent of the change that
    adds them) gives no reading, never a false zero."""
    counters = {"plan_items": [("dense", 7, 0)], "circuits": 1, "n": N,
                "state_bytes": SB}
    call = T.Interval("program.32", 0.0, 1.0, stats={
        "hlo": '%program.32 = f32[2,8,128] custom-call(), '
               'custom_call_target="tpu_custom_call", '
               'frontend_attributes={kernel_metadata={}}'})
    ops = [call, T.Interval("reshape.137", 1.0, 2.0,
                            stats={"hlo": "%reshape.137 = f32[2] reshape()"})]
    read = _read(metric)
    assert read(_ctx(ops, counters, window=(0.0, 2.0))) is None
    assert read(types.SimpleNamespace(trace=None, counters=counters,
                                      peaks=PEAKS)) is None
