"""Readers of the state-sharded cell's qubit-block swaps, on synthetic
intervals of two chips: the swaps' share of busy time, the part of it no
other operation hides, and their share of the interconnect roofline.  A
trace without a swap, or a chip the interconnect table lacks, gives no
reading."""
import types

import pytest

import bench_testlib
from bench import trace as T
from bench.spec import Benchmark

PEAKS = {"peak_flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
SHARD4 = {"circuits": 1, "swaps": 2, "state_bits": 2,
          "local_state_bytes": 8 << 28, "device_kind": "TPU v5 lite"}


def _op(name, start, end, device=0, **attrs):
    tags = ",".join(f'repro_{k}="{v}"' for k, v in attrs.items())
    hlo = f"%{name} = f32[2,8,128] fusion()"
    if tags:
        hlo += f", frontend_attributes={{{tags}}}"
    return T.Interval(name, start, end, device, {"hlo": hlo})


def _a2a(name, start, end, device):
    """An ``all-to-all`` as XLA's TPU pipeline leaves it: no attributes."""
    return T.Interval(name, start, end, device, {
        "hlo": f"%{name} = f32[2,4,4,16,128] all-to-all(%copy.1), "
               "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={2}"})


def _sharded_ops():
    """Two chips: a swap at [4, 6) on each (a tagged view, then the
    untagged all-to-all), hidden on chip 1 by an apply op until 5."""
    return [_op("fusion.1", 0.0, 4.0, 0, item=0, part="apply"),
            _op("fusion.9", 4.0, 4.5, 0, item=1, part="collective"),
            _a2a("all_to_all.1", 4.5, 6.0, 0),
            _op("fusion.2", 6.0, 8.0, 0, item=1, part="apply"),
            _op("fusion.1", 0.0, 5.0, 1, item=0, part="apply"),
            _op("fusion.9", 4.0, 4.5, 1, item=1, part="collective"),
            _a2a("all_to_all.1", 4.5, 6.0, 1),
            _op("fusion.2", 6.0, 8.0, 1, item=1, part="apply")]


def _ctx(ops, counters=None, window=(0.0, 10.0)):
    return types.SimpleNamespace(trace=T.summarize(ops, [], window=window),
                                 counters=counters or {}, peaks=PEAKS)


def _read(name):
    return Benchmark(bench_testlib.REPO).reader(name)


def test_collective_shares_are_averaged_over_the_chips():
    ctx = _ctx(_sharded_ops(), SHARD4)
    assert ctx.trace.busy_s == pytest.approx(8.0)
    # 2 s of swap on each chip of 8 busy
    assert _read("collective_pct.shard4")(ctx) == pytest.approx(25.0)
    # exposed: all 2 s on chip 0, 1 s on chip 1
    assert _read("collective_exposed_pct.shard4")(ctx) == pytest.approx(
        100.0 * 3.0 / 2 / 8.0)
    # each swap sends 3/4 of the 2 GiB block at 200e9 B/s; 2 s of swaps
    sent = 2 * 0.75 * (8 << 28)
    assert _read("a2a_ici_pct.shard4")(ctx) == pytest.approx(
        100.0 * sent / 200e9 / 2.0)
    assert _read("swaps.shard4")(ctx) == 2.0


def test_collective_shares_need_a_swap_and_a_known_chip():
    """A trace with no swap (no ``all-to-all``, nothing tagged
    ``collective``) gives no reading, nor does a device the interconnect
    table lacks."""
    ops = [o for o in _sharded_ops()
           if 'repro_part="collective"' not in o.stats["hlo"]
           and "all-to-all(" not in o.stats["hlo"]]
    for name in ("collective_pct.shard4", "collective_exposed_pct.shard4",
                 "a2a_ici_pct.shard4"):
        assert _read(name)(_ctx(ops, SHARD4)) is None, name
    cpu = dict(SHARD4, device_kind="cpu")
    assert _read("a2a_ici_pct.shard4")(_ctx(_sharded_ops(), cpu)) is None
    assert _read("swaps.shard4")(_ctx(ops, {})) is None


@pytest.mark.parametrize("metric", ["collective_pct.shard4",
                                    "collective_exposed_pct.shard4",
                                    "a2a_ici_pct.shard4"])
def test_untagged_sharded_program_reads_nothing(metric):
    """A program without the attributes and without a swap (the parent of
    the change that adds them) gives no reading, never a false zero."""
    call = T.Interval("program.32", 0.0, 1.0, stats={
        "hlo": '%program.32 = f32[2,8,128] custom-call(), '
               'custom_call_target="tpu_custom_call", '
               'frontend_attributes={kernel_metadata={}}'})
    ops = [call, T.Interval("reshape.137", 1.0, 2.0,
                            stats={"hlo": "%reshape.137 = f32[2] reshape()"})]
    read = _read(metric)
    assert read(_ctx(ops, SHARD4, window=(0.0, 2.0))) is None
    assert read(types.SimpleNamespace(trace=None, counters=SHARD4,
                                      peaks=PEAKS)) is None
