"""Trace reduction on synthetic intervals: busy union, idle share, time
per named op, idle gaps labelled by the open benchmark span."""
import pytest

import bench_testlib  # noqa: F401  (puts the repository root on sys.path)
from bench import trace as T

I = T.Interval


def test_union_seconds_merges_overlaps():
    assert T.union_seconds([(0, 1), (0.5, 1.5), (3, 4), (3.5, 3.6)]) == 2.5
    assert T.union_seconds([]) == 0.0


def test_busy_idle_and_op_time():
    ops = [I("fusion.1", 0.0, 1.0), I("fusion.2", 0.5, 1.5),
           I("fusion.1", 3.0, 4.0)]
    spans = [I("bench.window", 0.0, 5.0), I("bench.run", 0.0, 2.0),
             I("bench.block", 2.0, 5.0)]
    s = T.summarize(ops, spans)
    assert s.window_s == 5.0
    assert s.busy_s == pytest.approx(2.5)
    assert s.idle_share == pytest.approx(0.5)
    assert s.op_seconds == {"fusion.1": 2.0, "fusion.2": 1.0}
    assert s.op_counts == {"fusion.1": 2, "fusion.2": 1}
    assert s.idle_gaps == [("bench.block", 1.5), ("bench.block", 1.0)]


def test_window_clips_ops_and_labels_gaps_without_a_span():
    ops = [I("a", -1.0, 1.0), I("b", 9.0, 12.0)]
    s = T.summarize(ops, [], window=(0.0, 10.0))
    assert s.busy_s == pytest.approx(2.0)
    assert s.op_seconds == {"a": 1.0, "b": 1.0}
    assert s.idle_gaps == [("no bench span", 8.0)]


def test_busy_is_averaged_over_devices():
    ops = [I("a", 0.0, 4.0, device=0), I("a", 0.0, 2.0, device=1)]
    s = T.summarize(ops, [], window=(0.0, 4.0))
    assert s.devices == 2
    assert s.busy_s == pytest.approx(3.0)


def test_breakdown_keeps_the_ten_largest():
    ops = [I(f"op{i}", i, i + 0.01 * (i + 1)) for i in range(12)]
    b = T.breakdown(T.summarize(ops, [], window=(0.0, 12.0)))
    assert [name for name, _ in b["device_ops"]] == \
        [f"op{i}" for i in range(11, 1, -1)]
    assert len(b["idle_gaps"]) == 10


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError):
        T.summarize([I("a", 0, 1)], [I("bench.run", 0, 1)])
