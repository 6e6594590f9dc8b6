"""Mutation fuzzing for the plan-IR verifier + fixtures for the engine lint.

The verifier half compiles real plans, corrupts them one invariant at a
time (swap perm entries, push a phase off the unit circle, widen an item
past the row budget, desync the class counters...), and asserts each
corruption is caught with the *right* invariant code and item index — the
verifier is itself verified.  The lint half feeds one minimal offending and
one conforming snippet per EL rule through ``lint_source``, and covers the
baseline add/expire workflow and the inline-suppression contract.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from repro.analysis import (Baseline, Finding, PlanVerificationError,
                            lint_source, verify_plan)
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.verify_plan import INVARIANTS
from repro.core.target import CPU_TEST
from repro.engine.plan import PlanCache, compile_plan
from repro.engine.template import hea_template, qaoa_template


# -- fixtures -----------------------------------------------------------------

@pytest.fixture(scope="module")
def perm_plan():
    """Planar HEA plan: carries perm items (CNOT ladders) + dense items."""
    return compile_plan(hea_template(6, layers=2), backend="planar",
                        target=CPU_TEST)


@pytest.fixture(scope="module")
def diag_plan():
    """State-sharded planar QAOA plan: carries a diag item and uses the
    LOCAL (mesh-aware) width budget."""
    return compile_plan(qaoa_template(6, 2), backend="planar",
                        target=CPU_TEST, state_bits=1)


def _with_item(plan, idx, **changes):
    """Fresh plan whose ``items[idx]`` is replaced (never mutates the
    module-scoped fixture plan).  Drops the jitted program caches so the
    corrupted item list is what actually executes."""
    import collections
    items = list(plan.items)
    items[idx] = dataclasses.replace(items[idx], **changes)
    return dataclasses.replace(plan, items=items, _single=None,
                               _batched=collections.OrderedDict())


def _index_of(plan, kind):
    for i, item in enumerate(plan.items):
        if item.kind == kind:
            return i
    pytest.skip(f"fixture plan grew no {kind!r} item")


def _expect(plan, invariant, idx=None, semantic=False):
    with pytest.raises(PlanVerificationError) as exc:
        verify_plan(plan, semantic=semantic)
    err = exc.value
    assert err.invariant == invariant, str(err)
    if idx is not None:
        assert err.item_index == idx, str(err)
        assert f"item[{idx}]" in str(err)    # failures name the item
    assert f"[{invariant}]" in str(err)      # ... and the invariant code
    return err


# -- verifier: clean plans pass ----------------------------------------------

def test_clean_plans_verify(perm_plan, diag_plan):
    assert verify_plan(perm_plan) is perm_plan
    assert verify_plan(diag_plan) is diag_plan
    assert _index_of(perm_plan, "perm") is not None
    assert _index_of(diag_plan, "diag") is not None


def test_clean_plan_semantic_roundtrip(perm_plan):
    verify_plan(perm_plan, semantic=True)


def test_every_invariant_is_documented():
    import pathlib
    doc = pathlib.Path(__file__).resolve().parents[1] / "docs" / "ANALYSIS.md"
    text = doc.read_text(encoding="utf-8")
    for code in INVARIANTS:
        assert f"`{code}`" in text, f"invariant {code} missing from ANALYSIS.md"


# -- verifier: each corruption is caught with the right code -------------------

def test_perm_non_bijection_caught(perm_plan):
    i = _index_of(perm_plan, "perm")
    bad = np.array(perm_plan.items[i].perm, copy=True)
    bad[0] = bad[1]                          # duplicate entry: not a bijection
    _expect(_with_item(perm_plan, i, perm=bad), "perm-bijection", i)


def test_swapped_perm_entries_caught_semantically(perm_plan):
    """Swapping two perm entries keeps a valid bijection — structurally
    legal, semantically a different unitary.  Only the dense-oracle
    round-trip can catch it."""
    i = _index_of(perm_plan, "perm")
    bad = np.array(perm_plan.items[i].perm, copy=True)
    bad[0], bad[1] = bad[1], bad[0]
    corrupted = _with_item(perm_plan, i, perm=bad)
    verify_plan(corrupted)                   # structural check can't see it
    _expect(corrupted, "semantic", semantic=True)


def test_identity_perm_caught(perm_plan):
    i = _index_of(perm_plan, "perm")
    size = 1 << len(perm_plan.items[i].qubits)
    ident = np.arange(size, dtype=np.int32)
    _expect(_with_item(perm_plan, i, perm=ident), "perm-identity", i)


def test_phase_off_unit_circle_caught(diag_plan):
    i = _index_of(diag_plan, "diag")
    size = 1 << len(diag_plan.items[i].qubits)
    off = np.full(size, 1.01, np.complex64)  # modulus 1.01 everywhere
    phases = (("const", off),) + tuple(
        p for p in diag_plan.items[i].phases if p[0] != "const")
    _expect(_with_item(diag_plan, i, phases=phases), "phase-unit", i)


def test_phase_wrong_length_caught(diag_plan):
    i = _index_of(diag_plan, "diag")
    phases = (("const", np.ones(3, np.complex64)),)
    _expect(_with_item(diag_plan, i, phases=phases), "phase-unit", i)


def test_param_coeff_wrong_shape_caught(diag_plan):
    i = _index_of(diag_plan, "diag")
    item = diag_plan.items[i]
    params = [p for p in item.phases if p[0] == "param"]
    if not params:
        pytest.skip("diag item carries no parameterized phase")
    _, op, coeff = params[0]
    bad = (("param", op, np.asarray(coeff)[:-1]),)    # truncated vector
    _expect(_with_item(diag_plan, i, phases=bad), "phase-param", i)


def test_dense_width_past_budget_caught(perm_plan):
    i = _index_of(perm_plan, "dense")
    assert perm_plan.f > 0
    wide = tuple(range(perm_plan.f + 1))
    _expect(_with_item(perm_plan, i, qubits=wide), "width-dense", i)


def test_diag_width_past_local_budget_caught(diag_plan):
    """Sharded plans must respect the LOCAL row budget: a diag item widened
    to the full register would bake a per-device phase constant larger
    than the local state block."""
    i = _index_of(diag_plan, "diag")
    assert diag_plan.state_bits == 1
    wide = tuple(range(diag_plan.n))
    _expect(_with_item(diag_plan, i, qubits=wide), "width-special", i)


def test_planar_single_device_diag_may_exceed_budget(perm_plan):
    """The documented exception: single-device planar coalescing merges
    diagonal runs past the row budget (up to n) legally."""
    from repro.core.target import row_budget
    n = perm_plan.n
    assert n > row_budget(n, perm_plan.target)
    wide = tuple(range(n))
    item = dict(qubits=wide, controls=(), factors=(), kind="diag", perm=None,
                phases=(("const", np.ones(1 << n, np.complex64)),),
                generic_flops=None)
    items = list(perm_plan.items) + [dataclasses.replace(
        perm_plan.items[0], **item)]
    verify_plan(dataclasses.replace(perm_plan, items=items))


def test_unknown_kind_caught(perm_plan):
    _expect(_with_item(perm_plan, 0, kind="weird"), "kind", 0)


def test_unsorted_span_caught(perm_plan):
    i = _index_of(perm_plan, "perm")
    rev = tuple(reversed(perm_plan.items[i].qubits))
    _expect(_with_item(perm_plan, i, qubits=rev), "span-sorted", i)


def test_out_of_range_qubit_caught(perm_plan):
    i = _index_of(perm_plan, "perm")
    qs = perm_plan.items[i].qubits
    bad = qs[:-1] + (perm_plan.n + 3,)
    _expect(_with_item(perm_plan, i, qubits=bad), "span-bounds", i)


def test_control_target_overlap_caught(perm_plan):
    i = _index_of(perm_plan, "dense")
    qs = perm_plan.items[i].qubits
    _expect(_with_item(perm_plan, i, controls=(qs[0],)), "span-bounds", i)


def test_class_counts_desync_caught(perm_plan):
    plan = dataclasses.replace(perm_plan, items=list(perm_plan.items))
    plan.class_counts = lambda: {"diagonal": 99, "permutation": 0,
                                 "general": 0}
    _expect(plan, "class-counts")


def test_flops_desync_caught(perm_plan):
    plan = dataclasses.replace(perm_plan, items=list(perm_plan.items))
    plan.flops_per_amp = lambda: {"flops_per_amp_generic": 1.0,
                                  "flops_per_amp_actual": 999.0,
                                  "flops_saved_frac": 0.0}
    _expect(plan, "flops")


# -- verifier: result-mode plans (channel items + terminal epilogue) ----------

def _noisy_spec(n=5):
    from repro.engine import results as R
    return R.ResultSpec.noisy([R.depolarizing(0, 0.1)], [{0: "Z"}],
                              unravelings=2, key=3)


def _noisy_plan_fresh(n=5):
    """A fresh (never-cached, never-shared) noisy-mode plan — tests that
    tamper with the spec object in place must not touch a fixture."""
    return compile_plan(qaoa_template(n, 1), backend="planar",
                        target=CPU_TEST, result=_noisy_spec(n))


@pytest.fixture(scope="module")
def noisy_plan():
    return _noisy_plan_fresh()


def test_clean_result_plans_verify(noisy_plan):
    from repro.engine import results as R
    assert verify_plan(noisy_plan, semantic=True) is noisy_plan
    for spec in (R.ResultSpec.sample(16, key=1),
                 R.ResultSpec.expectation([{0: "Z"}, {1: "X"}])):
        plan = compile_plan(qaoa_template(4, 1), backend="planar",
                            target=CPU_TEST, result=spec)
        verify_plan(plan, semantic=True)


def test_kraus_non_trace_preserving_caught(noisy_plan):
    i = _index_of(noisy_plan, "channel")
    doubled = tuple(np.asarray(k) * 2.0 for k in noisy_plan.items[i].kraus)
    _expect(_with_item(noisy_plan, i, kraus=doubled), "channel-kraus", i)


def test_kraus_wrong_shape_caught(noisy_plan):
    i = _index_of(noisy_plan, "channel")
    bad = (np.eye(4, dtype=np.complex64),)   # 2-qubit op on a 1-qubit span
    _expect(_with_item(noisy_plan, i, kraus=bad), "channel-kraus", i)


def test_kraus_missing_caught(noisy_plan):
    i = _index_of(noisy_plan, "channel")
    _expect(_with_item(noisy_plan, i, kraus=()), "channel-kraus", i)


def test_kraus_on_gate_item_caught(noisy_plan):
    i = _index_of(noisy_plan, "dense")
    stray = (np.eye(2, dtype=np.complex64),)
    _expect(_with_item(noisy_plan, i, kraus=stray), "channel-kraus", i)


def test_result_item_not_terminal_caught(noisy_plan):
    import collections
    items = list(noisy_plan.items)
    items.insert(0, items.pop())             # epilogue hoisted to the front
    _expect(dataclasses.replace(noisy_plan, items=items, _single=None,
                                _batched=collections.OrderedDict()),
            "epilogue-terminal")


def test_duplicate_result_item_caught(noisy_plan):
    import collections
    items = list(noisy_plan.items) + [noisy_plan.items[-1]]
    _expect(dataclasses.replace(noisy_plan, items=items, _single=None,
                                _batched=collections.OrderedDict()),
            "epilogue-terminal")


def test_result_items_without_spec_caught(noisy_plan):
    import collections
    _expect(dataclasses.replace(noisy_plan, result=None, _single=None,
                                _batched=collections.OrderedDict()),
            "epilogue-terminal")


def test_channel_interleaving_gates_caught(noisy_plan):
    import collections
    items = list(noisy_plan.items)
    i = _index_of(noisy_plan, "channel")
    items.insert(0, items.pop(i))            # channel hoisted before gates
    _expect(dataclasses.replace(noisy_plan, items=items, _single=None,
                                _batched=collections.OrderedDict()),
            "epilogue-terminal")


def test_channel_count_vs_spec_caught(noisy_plan):
    import collections
    items = [it for it in noisy_plan.items if it.kind != "channel"]
    _expect(dataclasses.replace(noisy_plan, items=items, _single=None,
                                _batched=collections.OrderedDict()),
            "result-key")


def test_tampered_spec_key_caught():
    plan = _noisy_plan_fresh()
    object.__setattr__(plan.result, "key", 1 << 40)  # dodge __post_init__
    _expect(plan, "result-key")


def test_tampered_spec_mode_caught():
    plan = _noisy_plan_fresh()
    object.__setattr__(plan.result, "mode", "teleport")
    _expect(plan, "result-key")


def test_tampered_observable_qubit_caught():
    plan = _noisy_plan_fresh()
    object.__setattr__(plan.result, "observables", (((99, "Z"),),))
    _expect(plan, "result-key")


# -- verify= threading ---------------------------------------------------------

def test_compile_plan_verify_flag():
    plan = compile_plan(hea_template(4, layers=1), backend="planar",
                        target=CPU_TEST, verify=True)
    assert plan.items


def test_plan_cache_verify_flag():
    cache = PlanCache()
    t = hea_template(4, layers=1)
    p1 = cache.get_or_compile(t, backend="planar", target=CPU_TEST,
                              verify=True)
    p2 = cache.get_or_compile(t, backend="planar", target=CPU_TEST,
                              verify=True)
    assert p1 is p2                          # hit path skips re-verification
    assert cache.stats.as_dict()["hits"] == 1


def test_executor_verify_flag():
    from repro.engine.batch import BatchExecutor
    ex = BatchExecutor(target=CPU_TEST, backend="planar", cache=PlanCache(),
                       verify=True)
    assert ex.plan_for(hea_template(4, layers=1)).items


# -- lint: one offending + one conforming snippet per rule ---------------------

ENGINE_PATH = "src/repro/engine/fixture.py"
TEST_PATH = "tests/test_fixture.py"


def _codes(findings):
    return [f.rule for f in findings]


def test_el001_offending_and_conforming():
    offending = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.hits = 0  #: guarded-by: _lock\n"
        "    def touch(self):\n"
        "        self.hits += 1\n")
    found = lint_source(offending, ENGINE_PATH)
    assert _codes(found) == ["EL001"]
    assert found[0].scope == "S.touch" and found[0].symbol == "hits"

    conforming = offending.replace(
        "    def touch(self):\n        self.hits += 1\n",
        "    def touch(self):\n"
        "        with self._lock:\n"
        "            self.hits += 1\n")
    assert lint_source(conforming, ENGINE_PATH) == []


def test_el001_lock_aliases_and_caller_holds():
    src = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "        self._work = threading.Condition(self._lock)\n"
        "        self.q = []  #: guarded-by: _lock, _work\n"
        "    def via_condition(self):\n"
        "        with self._work:\n"
        "            return len(self.q)\n"
        "    def _locked_helper(self):\n"
        "        \"\"\"Caller holds ``_lock``.\"\"\"\n"
        "        return self.q.pop()\n")
    assert lint_source(src, ENGINE_PATH) == []


def test_el001_suppression_requires_justification():
    base = (
        "import threading\n"
        "class S:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.n = 0  #: guarded-by: _lock\n"
        "    def peek(self):\n"
        "        return self.n{sup}\n")
    ok = base.format(sup="  # lint-ok: EL001 monotonic snapshot read")
    assert lint_source(ok, ENGINE_PATH) == []
    bare = base.format(sup="  # lint-ok: EL001")
    assert _codes(lint_source(bare, ENGINE_PATH)) == ["EL001", "EL001"]


def test_el002_offending_and_conforming():
    offending = ("import time\n"
                 "def stamp():\n"
                 "    return time.perf_counter()\n")
    found = lint_source(offending, ENGINE_PATH)
    assert _codes(found) == ["EL002"] and found[0].symbol == "time.perf_counter"

    conforming = ("import time\n"
                  "def stamp(clock=time.perf_counter):\n"
                  "    return clock()\n")          # reference, not a call
    assert lint_source(conforming, ENGINE_PATH) == []
    # the rule is engine-scoped: the same call is fine in tools/
    assert lint_source(offending, "tools/fixture.py") == []


def test_el003_offending_and_conforming():
    offending = ("class S:\n"
                 "    def retire(self, rid, now):\n"
                 "        self.tracer.record(rid, 'done', now)\n")
    found = lint_source(offending, ENGINE_PATH)
    assert _codes(found) == ["EL003"]

    conforming = ("class S:\n"
                  "    def retire(self, rid, now):\n"
                  "        if self.tracer.enabled:\n"
                  "            self.tracer.record(rid, 'done', now)\n")
    assert lint_source(conforming, ENGINE_PATH) == []


def test_el004_offending_and_conforming():
    offending = ("import numpy as np\n"
                 "class S:\n"
                 "    def poll(self):\n"
                 "        return np.asarray(self.raw)\n"
                 "    def drain_async(self):\n"
                 "        return self.raw.block_until_ready()\n")
    assert _codes(lint_source(offending, ENGINE_PATH)) == ["EL004", "EL004"]

    conforming = ("import numpy as np\n"
                  "class S:\n"
                  "    def poll(self):\n"
                  "        return self.window.popleft()\n"
                  "    def finalize(self):\n"
                  "        return np.asarray(self.raw)\n")  # not a drain body
    assert lint_source(conforming, ENGINE_PATH) == []


def test_el005_offending_and_conforming():
    offending = ("import random\n"
                 "import numpy as np\n"
                 "def test_x():\n"
                 "    a = random.random()\n"
                 "    b = np.random.rand(3)\n"
                 "    rng = np.random.default_rng()\n")
    assert _codes(lint_source(offending, TEST_PATH)) == ["EL005"] * 3

    conforming = ("import random\n"
                  "import numpy as np\n"
                  "def test_x(seed=7):\n"
                  "    rng = np.random.default_rng(seed)\n"
                  "    r = random.Random(seed)\n")
    assert lint_source(conforming, TEST_PATH) == []
    # tests-only rule: the engine uses seeded generators by other means
    assert lint_source(offending, ENGINE_PATH) == []


KERNEL_PATH = "src/repro/kernels/fixture/fixture.py"


def test_el006_kernels_name_their_pallas_calls():
    """A planted kernel that drops out of the attribution is found."""
    offending = ("from jax.experimental import pallas as pl\n"
                 "def run(x, k):\n"
                 "    a = pl.pallas_call(k, out_shape=x, grid=(1,))(x)\n"
                 "    return pl.pallas_call(k, out_shape=x, name='k')(a)\n")
    found = lint_source(offending, KERNEL_PATH)
    assert _codes(found) == ["EL006", "EL006"]
    assert "name and metadata" in found[0].message
    assert "without metadata" in found[1].message

    conforming = ("from jax.experimental import pallas as pl\n"
                  "from repro.core import scopes\n"
                  "def run(x, k):\n"
                  "    return pl.pallas_call(\n"
                  "        k, out_shape=x, name='k',\n"
                  "        metadata=scopes.kernel_metadata())(x)\n")
    assert lint_source(conforming, KERNEL_PATH) == []
    # kernels-only rule
    assert lint_source(offending, ENGINE_PATH) == []


def test_syntax_rule():
    found = lint_source("def broken(:\n", "tools/fixture.py")
    assert _codes(found) == ["SYNTAX"]


# -- baseline add / expire -----------------------------------------------------

def _finding(**kw):
    base = dict(path="src/x.py", line=3, rule="EL002", scope="f",
                symbol="time.time", message="m")
    base.update(kw)
    return Finding(**base)


def test_baseline_add_and_expire(tmp_path):
    f1, f2 = _finding(), _finding(rule="EL003", symbol="t.record")
    path = tmp_path / "baseline.json"
    Baseline.save(path, [f1])

    # f1 accepted, f2 new
    new, old, stale = Baseline.load(path).split([f1, f2])
    assert (new, old, stale) == ([f2], [f1], [])

    # line moves don't expire a baselined finding (no line in fingerprint)
    moved = _finding(line=99)
    new, old, stale = Baseline.load(path).split([moved])
    assert not new and old == [moved] and not stale

    # the finding is fixed: its entry is stale and must fail the run
    new, old, stale = Baseline.load(path).split([])
    assert not new and not old and len(stale) == 1

    assert Baseline.load(tmp_path / "missing.json").entries == []


def test_lint_cli_exit_codes(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n", encoding="utf-8")
    dirty = tmp_path / "engine" / "dirty.py"
    dirty.parent.mkdir()
    dirty.write_text("import time\n\n\ndef f():\n"
                     "    return time.perf_counter()\n", encoding="utf-8")
    baseline = tmp_path / "baseline.json"

    assert analysis_main(["lint", str(clean),
                          "--baseline", str(baseline)]) == 0
    assert analysis_main(["lint", str(dirty),
                          "--baseline", str(baseline)]) == 1
    # accept it, then the same run is green
    assert analysis_main(["lint", str(dirty), "--baseline", str(baseline),
                          "--update-baseline"]) == 0
    assert analysis_main(["lint", str(dirty),
                          "--baseline", str(baseline)]) == 0
    # fix the code: the stale entry now fails the run (expire behavior)
    dirty.write_text("def f(clock):\n    return clock()\n", encoding="utf-8")
    assert analysis_main(["lint", str(dirty),
                          "--baseline", str(baseline)]) == 1


# -- the repo itself is lint-clean --------------------------------------------

def test_repo_is_lint_clean():
    """The shipped baseline is EMPTY: every real finding in engine/ was
    fixed or inline-justified in place.  New violations fail here (and in
    the CI analysis job) immediately."""
    import pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    from repro.analysis.lint import lint_paths
    findings = lint_paths([root / "src", root / "tests", root / "tools"],
                          root=root)
    baseline = Baseline.load(root / "analysis-baseline.json")
    new, _, stale = baseline.split(findings)
    assert not new, "\n".join(f.render() for f in new)
    assert not stale, stale
    assert baseline.entries == []            # nothing hidden in the baseline
