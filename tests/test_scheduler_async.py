"""Scheduler lifecycle, failure, and async streaming pipeline tests."""
import numpy as np
import pytest

from repro.core.simulator import Simulator
from repro.core.target import CPU_TEST
from repro.engine import (BatchExecutor, BatchScheduler, PlanCache,
                          RequestState, SchedulerStats, hea_template,
                          qaoa_template)
from repro.engine.template import CircuitTemplate, TemplateOp


def _dense(state) -> np.ndarray:
    return np.asarray(state.to_dense())


def _broken_template(n: int = 4) -> CircuitTemplate:
    """A template whose execution genuinely raises: the fixed op's matrix
    shape disagrees with its qubit count, so lowering fails at dispatch."""
    return CircuitTemplate(
        n, (TemplateOp("fixed", (0,), matrix=np.eye(4, dtype=np.complex64)),),
        num_params=0, name="broken")


def _traffic(sched, templates, counts, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for t, c in zip(templates, counts):
        for _ in range(c):
            reqs.append(sched.submit(t, rng.uniform(-1, 1, t.num_params)))
    return reqs


# -- failure lifecycle ---------------------------------------------------------

def test_failing_batch_does_not_drop_other_requests():
    """Regression: a chunk whose execution raises must mark exactly its own
    requests FAILED (error + latency recorded) and every other group's
    requests must still complete DONE."""
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=4)
    good_t = qaoa_template(5, 1)
    reqs_before = _traffic(sched, [good_t], [3])
    bad = sched.submit(_broken_template())
    reqs_after = _traffic(sched, [hea_template(5, 1)], [2], seed=1)

    done = sched.drain()
    assert len(done) == 6 and not sched.pending
    for r in reqs_before + reqs_after:
        assert r.state == RequestState.DONE and r.error is None
        assert r.result is not None and r.latency is not None
    assert bad.state == RequestState.FAILED
    assert isinstance(bad.error, Exception)
    assert bad.result is None and bad.latency is not None
    rep = sched.report()
    assert rep["failed"] == 1 and rep["requests"] == 6

    # results of the surviving groups are correct
    sim = Simulator(CPU_TEST, backend="planar", plan_cache=ex.cache)
    for r in reqs_before + reqs_after:
        ref = sim.run(r.template, params=r.params)
        np.testing.assert_allclose(_dense(r.result), _dense(ref), atol=1e-5)


def test_failed_requests_not_requeued_on_next_drain():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=4)
    bad = sched.submit(_broken_template())
    sched.drain()
    assert bad.state == RequestState.FAILED
    assert sched.drain() == []                 # nothing silently re-runs
    assert sched.stats.failed == 1


def test_async_drain_records_failures_terminal():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=4, inflight=2)
    good = sched.submit(qaoa_template(5, 1), [0.3, -0.4])
    bad = sched.submit(_broken_template())
    sched.drain_async()
    sched.sync()
    assert good.state == RequestState.DONE
    assert bad.state == RequestState.FAILED and bad.error is not None


# -- idle / empty stats --------------------------------------------------------

def test_idle_scheduler_reports_no_latency():
    """Regression: an idle scheduler must not fabricate 0.0 ms percentiles."""
    s = SchedulerStats().summary()
    assert s["requests"] == 0
    assert not any(k.startswith("latency") for k in s)
    rep = BatchScheduler(BatchExecutor(backend="planar",
                                       cache=PlanCache())).report()
    assert "latency_p99_ms" not in rep and rep["requests"] == 0


def test_latency_keys_present_once_requests_complete():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=4)
    sched.submit(qaoa_template(4, 1), [0.1, 0.2])
    sched.drain()
    rep = sched.report()
    for k in ("latency_mean_ms", "latency_p50_ms", "latency_p99_ms"):
        assert rep[k] > 0.0


# -- request lifecycle / future API -------------------------------------------

def test_request_lifecycle_states_and_wait():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=4, inflight=4)
    req = sched.submit(qaoa_template(5, 1), [0.5, 0.5])
    assert req.state == RequestState.QUEUED and not req.done
    with pytest.raises(RuntimeError):
        req.wait()                              # queued: nothing to wait on
    sched.drain_async()
    assert req.state == RequestState.DISPATCHED
    req.wait()
    assert req.state == RequestState.DONE and req.ok
    assert req.latency is not None and req.result is not None
    req.wait()                                  # idempotent once terminal


def test_streaming_triggers_full_group_dispatches_on_submit():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=2, max_wait_ms=60_000.0)
    t = qaoa_template(4, 1)
    a = sched.submit(t, [0.1, 0.2])
    assert a.state == RequestState.QUEUED
    b = sched.submit(t, [0.3, 0.4])             # group reaches max_batch
    assert a.state == RequestState.DISPATCHED
    assert b.state == RequestState.DISPATCHED
    a.wait(), b.wait()
    assert a.ok and b.ok


def test_streaming_triggers_aged_group_dispatches():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=64, max_wait_ms=0.0)
    t = qaoa_template(4, 1)
    a = sched.submit(t, [0.1, 0.2])             # age 0 >= max_wait 0 -> launch
    assert a.state == RequestState.DISPATCHED
    sched.sync()
    assert a.ok


# -- async window: ordering, determinism, accounting ---------------------------

@pytest.mark.parametrize("inflight", (0, 1, 2, 4))
def test_async_results_independent_of_window_depth(inflight):
    """Results and completion bookkeeping must not depend on how deep the
    in-flight window is (or whether batches retire early under pressure)."""
    templates = [qaoa_template(5, 1), qaoa_template(5, 2), hea_template(5, 1)]
    counts = [5, 3, 4]

    ref_ex = BatchExecutor(backend="planar", cache=PlanCache())
    ref_sched = BatchScheduler(ref_ex, max_batch=4)
    ref_reqs = _traffic(ref_sched, templates, counts)
    ref_sched.drain()

    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=4, inflight=inflight)
    reqs = _traffic(sched, templates, counts)
    returned = sched.drain_async()
    sched.sync()

    assert [r.req_id for r in returned] != []
    assert all(r.ok for r in reqs)
    for a, b in zip(ref_reqs, reqs):
        np.testing.assert_allclose(_dense(a.result), _dense(b.result),
                                   atol=1e-6)
    # identical batching/padding accounting in sync and async modes
    assert sched.stats.batches == ref_sched.stats.batches
    assert sched.stats.padded_slots == ref_sched.stats.padded_slots


def test_drain_async_returns_submit_order_within_groups():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=8, inflight=2)
    t1, t2 = qaoa_template(4, 1), hea_template(4, 1)
    reqs = _traffic(sched, [t1, t2, t1], [2, 2, 2])
    returned = sched.drain_async()
    sched.sync()
    assert len(returned) == 6
    # within each plan group the FIFO submit order is preserved
    for t in (t1, t2):
        ids = [r.req_id for r in returned if r.template is t]
        assert ids == sorted(ids)


def test_padding_accounting_async():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    sched = BatchScheduler(ex, max_batch=8, inflight=2)
    t = qaoa_template(4, 1)
    _traffic(sched, [t], [5])                   # 5 -> pad to 8
    sched.drain_async()
    sched.sync()
    assert sched.stats.padded_slots == 3
    assert sched.report()["padded_slots"] == 3


# -- plan-cache counters through report() --------------------------------------

def test_plan_cache_counters_through_report():
    cache = PlanCache(max_plans=2)
    ex = BatchExecutor(backend="planar", cache=cache)
    sched = BatchScheduler(ex, max_batch=4)
    t1, t2, t3 = (qaoa_template(4, 1), qaoa_template(4, 2),
                  hea_template(4, 1))
    _traffic(sched, [t1, t2], [2, 2])
    sched.drain()
    _traffic(sched, [t1], [1])                  # same structure -> cache hit
    sched.drain()
    rep = sched.report()
    assert rep["cache_compiles"] == 2
    assert rep["cache_hits"] >= 1 and rep["cache_misses"] == 2
    assert rep["cache_evictions"] == 0
    # a third structure overflows max_plans=2 -> eviction surfaces in report
    _traffic(sched, [t3], [1])
    sched.drain()
    rep = sched.report()
    assert rep["cache_compiles"] == 3
    assert rep["cache_evictions"] == 1
    assert len(cache) == 2


# -- input validation (executor + sweep) ---------------------------------------

def test_run_states_empty_initials_raises():
    ex = BatchExecutor(backend="planar", cache=PlanCache())
    with pytest.raises(ValueError, match="initial state"):
        ex.run_states(qaoa_template(4, 1), [])


def test_submit_sweep_single_param_rows():
    """A 1-D array for a single-parameter template is B separate bindings."""
    t = CircuitTemplate(4, (TemplateOp("rx", (0,), param=0),),
                        num_params=1, name="rx1")
    sched = BatchScheduler(BatchExecutor(backend="planar", cache=PlanCache()),
                           max_batch=8)
    reqs = sched.submit_sweep(t, [0.1, 0.2, 0.3])
    assert len(reqs) == 3
    assert [float(r.params[0]) for r in reqs] == pytest.approx([0.1, 0.2, 0.3])
    sched.drain()
    assert all(r.ok for r in reqs)
    # and the bindings really differ
    assert not np.allclose(_dense(reqs[0].result), _dense(reqs[2].result))


def test_submit_sweep_1d_row_multi_param():
    t = qaoa_template(4, 1)                     # num_params == 2
    sched = BatchScheduler(BatchExecutor(backend="planar", cache=PlanCache()))
    reqs = sched.submit_sweep(t, [0.1, 0.2])    # one 2-param binding
    assert len(reqs) == 1
    with pytest.raises(ValueError, match="params matrix"):
        sched.submit_sweep(t, np.zeros((2, 3)))


# -- fusion row-budget cap (small-n lane-tiled regression) ---------------------

def test_resolve_f_caps_at_row_budget():
    from repro.engine.plan import resolve_f
    v = CPU_TEST.lane_qubits                    # 3 for the 8-lane test target
    assert resolve_f(None, CPU_TEST, 4, True, "planar") == 2
    assert resolve_f(7, CPU_TEST, 5, True, "pallas") == 2
    assert resolve_f(7, CPU_TEST, 12, True, "planar") == min(7, 12 - v)
    assert resolve_f(None, CPU_TEST, 4, True, "dense") == 0


@pytest.mark.parametrize("backend", ("planar", "pallas"))
def test_small_n_auto_fusion_correct_on_lane_tiled(backend):
    """Auto-chosen f on small n must respect the row budget and still match
    the dense oracle."""
    n = 4                                       # n - v = 1 < choose_f result
    t = qaoa_template(n, 2)
    rng = np.random.default_rng(5)
    pm = rng.uniform(-np.pi, np.pi, (3, t.num_params)).astype(np.float32)
    ex = BatchExecutor(target=CPU_TEST, backend=backend, cache=PlanCache())
    states = ex.run_batch(t, pm)
    plan = ex.plan_for(t)
    assert plan.f <= max(2, n - CPU_TEST.lane_qubits)
    oracle = Simulator(CPU_TEST, backend="dense", plan_cache=PlanCache())
    for b in range(pm.shape[0]):
        ref = oracle.run(t.bind(pm[b]))
        np.testing.assert_allclose(_dense(states[b]), _dense(ref), atol=1e-5)
