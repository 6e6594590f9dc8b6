"""Telemetry layer: registry exactness, span integrity, activity profiles.

Span-integrity methodology: every traced request must yield exactly one
well-formed span tree — ``SpanTracer.span_trees()`` *raises* on orphans,
duplicated stages, missing/double terminals, or timestamps that decrease
along the stage order — so the concurrency tests only need to drive the
8-producer hammer and call it.  ``FakeClock`` injection makes span
durations exact, and the disabled-telemetry test reuses the bitwise-replay
methodology of ``test_ingest``: same plan cache -> same compiled
executables -> tracing must change nothing, bit for bit.
"""
import os
import sys
import json
import threading

import numpy as np
import pytest

from repro.core.target import CPU_TEST
from repro.engine import (BatchExecutor, BatchScheduler, Histogram,
                          IngestServer, MetricsRegistry, NULL_TRACER,
                          PlanCache, SpanTracer, engine_registry,
                          hea_template, qaoa_template)
from repro.engine.scheduler import SchedulerStats
from repro.engine.telemetry import (STAGE_DISPATCH, STAGE_DONE,
                                    STAGE_ENQUEUE, STAGE_FAILED,
                                    STAGE_SUBMIT, ServedActivity)
from repro.testing import FakeClock, run_producers
from test_ingest import VALID_HISTORIES, _broken_template, _dense

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- instruments ---------------------------------------------------------------

def test_histogram_bounded_memory_exact_totals():
    h = Histogram(8, name="t")
    for i in range(100):
        h.record(float(i))
    assert len(h) == 100                      # total count, not window size
    assert h.count == 100
    assert len(h.window()) == 8               # fixed-capacity ring
    s = h.summary()
    assert s["count"] == 100
    assert s["mean"] == pytest.approx(np.mean(np.arange(100.0)))  # exact sum
    assert s["max"] == 99.0                   # exact max survives eviction
    # percentiles cover the retained window (the 8 most recent samples)
    assert s["p50"] == pytest.approx(np.percentile(np.arange(92.0, 100.0), 50))


def test_histogram_empty_and_validation():
    h = Histogram(4)
    assert h.summary() == {}                  # idle: no fabricated 0.0s
    with pytest.raises(ValueError, match="empty"):
        h.percentile(50)
    with pytest.raises(ValueError, match="capacity"):
        Histogram(0)


def test_registry_create_or_get_and_type_conflicts():
    """The registry is its sources: each is polled at snapshot time and
    its keys appear under its prefix, so a snapshot always reads the
    sources' current values and never a copy."""
    reg = MetricsRegistry()
    assert reg.snapshot() == {}
    state = {"k": 7}
    reg.register_source("src", lambda: dict(state))
    reg.register_source("other", lambda: {"k": "x", "n": 1})
    assert reg.snapshot() == {"src_k": 7, "other_k": "x", "other_n": 1}
    state["k"] = 8
    assert reg.snapshot()["src_k"] == 8
    for gone in ("counter", "gauge", "histogram"):
        assert not hasattr(reg, gone)


def test_scheduler_stats_latencies_bounded():
    """Satellite: the unbounded latency list is now a fixed-memory
    histogram with the same summary fields and len() semantics."""
    stats = SchedulerStats(latencies=Histogram(16, name="latency"))
    for i in range(200):
        stats.add_latency(0.001 * (i + 1))
    assert len(stats.latencies) == 200        # total count preserved
    assert len(stats.latencies.window()) == 16  # memory stays bounded
    s = stats.summary()
    assert s["latency_mean_ms"] == pytest.approx(
        np.mean(np.arange(1.0, 201.0)))       # mean exact over all samples
    assert "latency_p50_ms" in s and "latency_p99_ms" in s
    assert "latency_p50_ms" not in SchedulerStats().summary()  # idle: none


# -- span tracer validation ----------------------------------------------------

def test_span_tree_shape_and_validation_errors():
    tr = SpanTracer()
    tr.record(0, STAGE_ENQUEUE, 1.0, seq=0)
    tr.record(0, STAGE_SUBMIT, 2.0, template="t")
    tr.record(0, STAGE_DISPATCH, 3.0, batch=0, rows=1, padded=1)
    tr.record(0, "device_ready", 5.0)
    tr.record(0, STAGE_DONE, 6.0)
    (root,) = tr.span_trees()
    assert root.name == "request"
    assert root.start == 1.0 and root.end == 6.0 and root.duration == 5.0
    assert [c.name for c in root.children] == [
        "ingest.wait", "sched.queue", "device.execute", "finalize"]
    assert root.args["status"] == STAGE_DONE
    assert root.args["template"] == "t" and root.args["req_id"] == 0

    orphan = SpanTracer()
    orphan.record(1, STAGE_DISPATCH, 0.0)
    with pytest.raises(ValueError, match="no submit"):
        orphan.span_trees()

    dup = SpanTracer()
    dup.record(2, STAGE_SUBMIT, 0.0)
    dup.record(2, STAGE_SUBMIT, 1.0)
    with pytest.raises(ValueError, match="duplicate"):
        dup.span_trees()

    open_span = SpanTracer()
    open_span.record(3, STAGE_SUBMIT, 0.0)
    with pytest.raises(ValueError, match="terminal"):
        open_span.span_trees()

    both = SpanTracer()
    both.record(4, STAGE_SUBMIT, 0.0)
    both.record(4, STAGE_DONE, 1.0)
    both.record(4, STAGE_FAILED, 1.0)
    with pytest.raises(ValueError, match="exactly one terminal"):
        both.span_trees()

    backwards = SpanTracer()
    backwards.record(5, STAGE_SUBMIT, 2.0)
    backwards.record(5, STAGE_DISPATCH, 1.0)
    backwards.record(5, STAGE_DONE, 3.0)
    with pytest.raises(ValueError, match="decrease"):
        backwards.span_trees()


def test_null_tracer_is_disabled_and_inert():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.record(0, STAGE_SUBMIT, 1.0)  # no-op, no error
    sched = BatchScheduler(BatchExecutor(backend="planar", cache=PlanCache()))
    assert sched.tracer is NULL_TRACER        # untraced by default


# -- end-to-end span integrity -------------------------------------------------

@pytest.mark.timeout(300)
def test_span_integrity_under_8_producers():
    """The tentpole contract under the PR-5 hammer: 8 barrier producers x
    mixed structures through a traced IngestServer -> exactly one
    well-formed span tree per request, covering ingest enqueue to done."""
    templates = [qaoa_template(5, 1), qaoa_template(5, 2), hea_template(5, 1)]
    per_producer = 6
    tracer = SpanTracer()
    srv = IngestServer(BatchExecutor(backend="planar", cache=PlanCache()),
                       max_batch=4, max_wait_ms=60_000.0, tracer=tracer)

    def producer(i: int):
        rng = np.random.default_rng(200 + i)
        return [srv.submit(templates[j % len(templates)],
                           rng.uniform(-np.pi, np.pi,
                                       templates[j % 3].num_params))
                for j in range(per_producer)]

    handles = [h for hs in run_producers(8, producer, timeout=240)
               for h in hs]
    assert srv.flush(timeout=240)
    srv.close()
    assert all(h.request.ok for h in handles)

    trees = tracer.span_trees()               # raises on any malformed span
    assert len(trees) == 48                   # one tree per request, none lost
    assert ({t.args["req_id"] for t in trees}
            == {h.request.req_id for h in handles})
    for t in trees:
        assert t.args["status"] == STAGE_DONE
        names = [c.name for c in t.children]
        # ingest-submitted requests always carry the producer-side wait
        assert names == ["ingest.wait", "sched.queue", "device.execute",
                         "finalize"]
    # span trees and enforced request histories describe the same lifecycle
    for h in handles:
        assert h.request.history == VALID_HISTORIES[0]


@pytest.mark.timeout(120)
def test_fake_clock_spans_exact_and_failed_requests_traced():
    clock = FakeClock()
    tracer = SpanTracer()
    srv = IngestServer(BatchExecutor(backend="planar", cache=PlanCache()),
                       max_batch=16, max_wait_ms=5.0, clock=clock,
                       tracer=tracer, autostart=False)
    t = qaoa_template(4, 1)
    h = srv.submit(t, [0.1, 0.2])
    clock.advance(0.001)
    srv.step()                                # ingested; 1ms < 5ms: queued
    clock.advance(0.006)
    srv.step()                                # aged out: dispatched
    assert srv.flush(timeout=60)
    bad = srv.submit(_broken_template(), None)
    srv.step(force=True)
    assert srv.flush(timeout=60)
    srv.close()
    assert h.request.ok and bad.request is not None and not bad.request.ok

    ok_tree, bad_tree = sorted(tracer.span_trees(),
                               key=lambda s: s.args["req_id"])
    # every stamp is off the fake clock: enqueue at 0, submit at 1ms
    assert ok_tree.start == 0.0
    wait = ok_tree.children[0]
    assert wait.name == "ingest.wait" and wait.duration == pytest.approx(0.001)
    queue = ok_tree.children[1]
    assert queue.name == "sched.queue" and queue.duration == pytest.approx(
        0.006)
    # timestamps along the tree are monotone (span_trees enforced it)
    assert ok_tree.start <= queue.start <= ok_tree.end
    # the broken request fails at compile: submit -> failed, no dispatch
    assert bad_tree.args["status"] == STAGE_FAILED
    assert [c.name for c in bad_tree.children] == ["ingest.wait",
                                                   "sched.queue"]
    assert bad_tree.args.get("error") == "ValueError"


@pytest.mark.timeout(300)
def test_disabled_telemetry_bitwise_identical():
    """Tracing must be observation only: the same traffic on the same plan
    cache (same compiled executables) with tracing on vs off produces
    bitwise-identical states — and the untraced engine records nothing."""
    cache = PlanCache()
    t = qaoa_template(5, 2)
    rng = np.random.default_rng(7)
    params = [rng.uniform(-np.pi, np.pi, t.num_params) for _ in range(12)]

    def serve(tracer):
        sched = BatchScheduler(BatchExecutor(backend="planar", cache=cache),
                               max_batch=4, tracer=tracer)
        reqs = [sched.submit(t, p) for p in params]
        sched.drain()
        assert all(r.ok for r in reqs)
        return [_dense(r.result) for r in reqs]

    plain = serve(None)
    tracer = SpanTracer()
    traced = serve(tracer)
    again = serve(None)
    assert len(tracer.span_trees()) == 12     # traced run: full record
    for a, b, c in zip(plain, traced, again):
        assert np.array_equal(a, b) and np.array_equal(a, c)


# -- exports -------------------------------------------------------------------

def test_chrome_trace_and_jsonl_exports(tmp_path):
    tracer = SpanTracer()
    sched = BatchScheduler(BatchExecutor(backend="planar", cache=PlanCache()),
                           max_batch=4, tracer=tracer)
    t = qaoa_template(4, 1)
    reqs = [sched.submit(t, [0.1 * i, 0.2]) for i in range(3)]
    sched.drain()
    assert all(r.ok for r in reqs)

    trace_path = tmp_path / "trace.json"
    jsonl_path = tmp_path / "events.jsonl"
    assert tracer.write_chrome_trace(str(trace_path)) == 3
    assert tracer.write_jsonl(str(jsonl_path)) == 3 * 4  # 4 stages/request

    obj = json.loads(trace_path.read_text())
    events = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in events} == {
        "request", "sched.queue", "device.execute", "finalize"}
    for e in events:
        assert e["dur"] >= 0 and e["ts"] >= 0     # µs, relative to t0
    roots = [e for e in events if e["name"] == "request"]
    assert len(roots) == 3 and all("req_id" in e["args"] for e in roots)

    lines = [json.loads(line)
             for line in jsonl_path.read_text().splitlines()]
    assert all({"req_id", "stage", "ts"} <= set(ev) for ev in lines)
    assert [ev["ts"] for ev in lines] == sorted(ev["ts"] for ev in lines)

    # tools/trace_report.py accepts both export formats
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import trace_report
        assert trace_report.main([str(trace_path)]) == 0
        assert trace_report.main([str(jsonl_path)]) == 0
    finally:
        sys.path.pop(0)


def test_trace_report_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [{"ph": "X", "name": "x"}]}))
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import trace_report
        assert trace_report.main([str(bad)]) == 1
    finally:
        sys.path.pop(0)


# -- compile-time attribution (satellite) --------------------------------------

def test_compile_seconds_surfaced_in_cache_stats_and_report():
    cache = PlanCache()
    assert cache.stats.compile_summary() == {}     # idle: no keys at all
    ex = BatchExecutor(backend="planar", cache=cache)
    sched = BatchScheduler(ex, max_batch=4)
    rep = sched.report()
    assert not any(k.startswith("compile_") for k in rep)
    for t in (qaoa_template(4, 1), qaoa_template(4, 2)):
        sched.submit(t, np.zeros(t.num_params))
    sched.drain()
    assert cache.stats.compile_seconds > 0.0
    s = cache.stats.compile_summary()
    assert s["count"] == 2
    assert s["seconds_total"] == pytest.approx(cache.stats.compile_seconds)
    assert 0.0 < s["seconds_p50"] <= s["seconds_max"] <= s["seconds_total"]
    rep = sched.report()
    assert rep["compile_count"] == 2
    assert rep["compile_seconds_total"] == pytest.approx(s["seconds_total"])
    assert rep["cache_compile_seconds"] == pytest.approx(s["seconds_total"])


# -- vectorization-activity observability --------------------------------------

def test_compiled_plan_carries_vectorization_profile():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    plan = ex.plan_for(qaoa_template(10, 2))       # big enough to specialize
    prof = plan.profile
    assert prof is not None
    assert 0 < prof.alo <= prof.lanes == CPU_TEST.lanes
    assert prof.orr > 0 and prof.ai > 0
    # QAOA cost layers are rz ladders: the specialized plan routes a real
    # fraction of amplitude traffic through the diag/perm fast path
    assert 0.0 < prof.fast_amp_frac <= 1.0
    assert prof.flops_per_amp_actual <= prof.flops_per_amp_generic
    assert prof.flops_saved_frac == pytest.approx(
        1.0 - prof.flops_per_amp_actual / prof.flops_per_amp_generic)
    # the unspecialized oracle takes no fast paths
    dense = BatchExecutor(backend="dense", cache=PlanCache())
    dprof = dense.plan_for(qaoa_template(10, 2)).profile
    assert dprof.fast_amp_frac == 0.0 and dprof.flops_saved_frac == 0.0


def test_served_activity_aggregates_per_plan_key():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    t1, t2 = qaoa_template(6, 1), hea_template(6, 1)
    ex.run_batch(t1, np.zeros((4, t1.num_params)))
    ex.run_batch(t1, np.zeros((2, t1.num_params)))
    ex.run_batch(t2, np.zeros((3, t2.num_params)))
    per = ex.activity.per_plan()
    assert len(per) == 2
    (k1,) = [k for k in per if k.startswith(t1.name)]
    (k2,) = [k for k in per if k.startswith(t2.name)]
    assert per[k1]["rows"] == 6 and per[k1]["batches"] == 2
    assert per[k2]["rows"] == 3 and per[k2]["batches"] == 1
    assert per[k1]["amps"] == 6 * 2**6            # amplitude-weighted
    agg = ex.activity.summary()
    assert agg["rows"] == 9 and agg["plans"] == 2
    prof = ex.plan_for(t1).profile
    assert per[k1]["alo"] == pytest.approx(prof.alo)
    assert per[k1]["orr"] == pytest.approx(prof.orr)


@pytest.mark.timeout(120)
def test_served_activity_exact_under_concurrent_dispatch():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    t = qaoa_template(5, 1)
    ex.run_batch(t, np.zeros((1, t.num_params)))   # warm: compile once

    def producer(i: int):
        for _ in range(10):
            ex.run_batch(t, np.zeros((2, t.num_params)))
        return 10

    run_producers(8, producer)
    agg = ex.activity.summary()
    assert agg["rows"] == 1 + 8 * 10 * 2
    assert agg["batches"] == 1 + 8 * 10


# -- the unified registry ------------------------------------------------------

@pytest.mark.timeout(120)
def test_engine_registry_unifies_all_sources(tmp_path):
    tracer = SpanTracer()
    srv = IngestServer(BatchExecutor(backend="planar", cache=PlanCache()),
                       max_batch=4, max_wait_ms=None, tracer=tracer)
    t = qaoa_template(5, 1)
    handles = [srv.submit(t, [0.1 * i, 0.2]) for i in range(8)]
    assert srv.drain(timeout=120)
    srv.close()
    assert all(h.request.ok for h in handles)

    reg = engine_registry(server=srv)
    snap = reg.snapshot()
    assert snap["scheduler_requests"] == 8         # SchedulerStats
    assert snap["scheduler_failed"] == 0
    assert snap["cache_compiles"] == 1             # CacheStats
    assert snap["compile_count"] == 1              # compile attribution
    assert snap["served_rows"] == 8                # ServedActivity
    assert snap["ingest_outstanding"] == 0         # ingest front end
    assert snap["ingest_producers"] >= 1
    assert snap["scheduler_latency_p99_ms"] > 0

    out = tmp_path / "metrics.json"
    written = reg.write_json(str(out))
    assert json.loads(out.read_text()) == json.loads(
        json.dumps(written, default=str))
