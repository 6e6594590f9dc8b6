"""AST-based engine lint: codebase-specific concurrency/telemetry rules.

The engine's correctness conventions — every stats/cache/window field is
touched under its lock, clocks are injected (never called raw) so tests and
replay stay deterministic, tracer work is gated on ``.enabled`` so the
NULL_TRACER path is free, the drain loop never host-syncs — were established
by PRs 5–6 and verified by example-based tests.  This module turns them into
machine-checked rules with stable codes:

========  ==============================================================
EL001     lock discipline: attributes declared ``#: guarded-by: <lock>``
          may only be touched inside ``with self.<lock>`` (any declared
          alias) or a method documented ``Caller holds \\`\\`<lock>\\`\\```.
EL002     no raw wall-clock calls (``time.time``/``perf_counter``/
          ``monotonic``) in ``engine/`` — pass clocks in as callables;
          *references* (e.g. ``clock=time.perf_counter`` defaults) are the
          sanctioned injectable-clock sites and are not calls.
EL003     tracer gating: ``*tracer.record(...)`` calls in ``engine/``
          (outside the tracer implementation itself) must sit inside an
          ``if ... .enabled`` block so NULL_TRACER-reachable paths pay
          nothing.
EL004     no host sync in the drain loop: ``block_until_ready`` /
          ``np.asarray`` / ``.item()`` calls inside ``poll`` / ``drain*``
          bodies stall the pipeline.
EL005     unseeded randomness in tests: bare ``random.*`` /
          ``np.random.*`` calls (or zero-arg ``default_rng()`` /
          ``Random()``) make failures unreproducible — construct a
          seeded generator and log the seed.
EL006     kernel attribution: every ``pallas_call`` under ``kernels/``
          passes ``name=`` and ``metadata=`` (``scopes.kernel_metadata()``),
          so each kernel execution in a profile names its plan item.
SYNTAX    the file failed to parse (guards the tools/ scripts in CI).
========  ==============================================================

A finding is suppressed by an inline ``# lint-ok: EL00X <justification>``
comment on the offending line; the justification text is mandatory.
Accepted pre-existing findings live in a checked-in JSON baseline
(``analysis-baseline.json``): baselined findings don't fail CI, *stale*
baseline entries (fixed code, leftover entry) do — see docs/ANALYSIS.md.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import re
from pathlib import Path

RULES = {
    "EL001": "guarded-by attribute touched outside its declared lock",
    "EL002": "raw wall-clock call in engine/ (inject a clock instead)",
    "EL003": "tracer record not gated on .enabled",
    "EL004": "host sync inside a poll/drain loop body",
    "EL005": "unseeded randomness in tests",
    "EL006": "pallas_call without name= and metadata=",
    "SYNTAX": "file failed to parse",
}

_GUARDED_RE = re.compile(r"#:\s*guarded-by:\s*([\w,\s]+)")
_SELF_ATTR_RE = re.compile(r"self\.(\w+)\s*[:=][^=]")
_CLASS_ATTR_RE = re.compile(r"^\s*(\w+)\s*[:=][^=]")
_LINT_OK_RE = re.compile(r"#\s*lint-ok:\s*(EL\d{3}|SYNTAX)\b[ \t]*(.*)")
_CALLER_HOLDS_RE = re.compile(r"Caller holds\s+`{0,2}(\w+)`{0,2}")

_CLOCK_CALLS = {"time", "perf_counter", "monotonic"}
_HOST_SYNC_ATTRS = {"block_until_ready", "asarray", "item"}
_SEEDED_FACTORIES = {"default_rng", "Random", "RandomState", "SystemRandom",
                     "Generator", "PCG64"}
# random-module functions that draw from the hidden global stream
_RNG_MODULE_NAMES = {"random"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One lint violation.  ``fingerprint`` (path, rule, scope, symbol)
    deliberately omits the line number so baselines survive unrelated
    edits to the same file."""

    path: str          # repo-relative posix path
    line: int
    rule: str
    scope: str         # "Class.method", "function", or "<module>"
    symbol: str        # the offending attribute / call name
    message: str

    @property
    def fingerprint(self) -> tuple:
        return (self.path, self.rule, self.scope, self.symbol)

    def render(self) -> str:
        return (f"{self.path}:{self.line}: {self.rule} [{self.scope}] "
                f"{self.message}")


class Baseline:
    """Checked-in set of accepted findings (see docs/ANALYSIS.md).

    ``split`` partitions live findings into (new, baselined) and reports
    stale entries — fingerprints in the file that no longer fire, which
    must be removed (run with ``--update-baseline``) so the baseline only
    ever shrinks toward zero.
    """

    def __init__(self, entries: list[dict] | None = None):
        self.entries = entries or []

    @classmethod
    def load(cls, path: str | Path) -> "Baseline":
        p = Path(path)
        if not p.exists():
            return cls([])
        data = json.loads(p.read_text(encoding="utf-8"))
        return cls(data.get("findings", []))

    @staticmethod
    def _key(e: dict) -> tuple:
        return (e["path"], e["rule"], e["scope"], e["symbol"])

    def split(self, findings: list[Finding],
              ) -> tuple[list[Finding], list[Finding], list[dict]]:
        """-> (new, baselined, stale_entries)."""
        live = {f.fingerprint for f in findings}
        known = {self._key(e) for e in self.entries}
        new = [f for f in findings if f.fingerprint not in known]
        old = [f for f in findings if f.fingerprint in known]
        stale = [e for e in self.entries if self._key(e) not in live]
        return new, old, stale

    @staticmethod
    def save(path: str | Path, findings: list[Finding]) -> None:
        entries = sorted(
            {f.fingerprint for f in findings})
        data = {"findings": [
            {"path": p, "rule": r, "scope": s, "symbol": y}
            for p, r, s, y in entries]}
        Path(path).write_text(json.dumps(data, indent=2) + "\n",
                              encoding="utf-8")


# -- source-level helpers -----------------------------------------------------

def _suppressions(lines: list[str]) -> tuple[dict[int, set], list[tuple]]:
    """-> ({line_no: {rules}}, [(line_no, rule) missing justification]).

    A trailing ``# lint-ok: EL00X why`` suppresses findings on its own
    line; on a comment-only line it binds to the next code line (the
    justification may continue over following comment lines).
    """
    sup: dict[int, set] = {}
    bad: list[tuple] = []
    for i, text in enumerate(lines, 1):
        m = _LINT_OK_RE.search(text)
        if not m:
            continue
        if not m.group(2).strip():
            bad.append((i, m.group(1)))
            continue
        target = i
        if text.split("#")[0].strip() == "":
            j = i                   # 0-based index of the following line
            while j < len(lines) and lines[j].split("#")[0].strip() == "":
                j += 1
            if j < len(lines):
                target = j + 1
        sup.setdefault(target, set()).add(m.group(1))
        sup.setdefault(i, set()).add(m.group(1))
    return sup, bad


def _guarded_decls(lines: list[str]) -> dict[int, dict[str, frozenset]]:
    """Parse ``#: guarded-by: lock[, alias...]`` markers.

    -> {decl_line_no: {attr_name: frozenset(lock aliases)}}.  The marker
    binds to the attribute assigned on its own line, else to the one on the
    next non-blank line (marker-above-field style for dataclass fields).
    """
    out: dict[int, dict[str, frozenset]] = {}

    def attr_on(text: str) -> str | None:
        code = text.split("#")[0]
        m = _SELF_ATTR_RE.search(code)
        if m:
            return m.group(1)
        m = _CLASS_ATTR_RE.match(code)
        return m.group(1) if m else None

    for i, text in enumerate(lines, 1):
        m = _GUARDED_RE.search(text)
        if not m:
            continue
        locks = frozenset(t.strip() for t in m.group(1).split(",") if t.strip())
        name = attr_on(text)
        bind_line = i
        if name is None:
            for j in range(i, min(i + 3, len(lines))):
                name = attr_on(lines[j])
                if name is not None:
                    bind_line = j + 1
                    break
        if name is not None and locks:
            out.setdefault(bind_line, {})[name] = locks
    return out


def _attr_chain(node: ast.AST) -> list[str]:
    """['self', 'tracer', 'record'] for ``self.tracer.record`` etc."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    else:
        parts.append("?")
    return parts[::-1]


def _contains_enabled(node: ast.AST) -> bool:
    return any(isinstance(sub, ast.Attribute) and sub.attr == "enabled"
               for sub in ast.walk(node))


# -- rule visitors ------------------------------------------------------------

class _FileLinter:
    def __init__(self, relpath: str, source: str):
        self.relpath = relpath
        self.lines = source.splitlines()
        self.findings: list[Finding] = []
        self.suppressed, missing = _suppressions(self.lines)
        for line_no, rule in missing:
            self._raw(line_no, rule, "<module>", "lint-ok",
                      f"suppression of {rule} without a justification "
                      f"(write `# lint-ok: {rule} <why this is safe>`)")
        self.in_engine = "/engine/" in f"/{relpath}"
        self.in_tests = relpath.startswith("tests/") or "/tests/" in relpath
        self.in_kernels = "/kernels/" in f"/{relpath}"
        self.is_tracer_impl = relpath.endswith("telemetry.py")
        self.decls_by_line = _guarded_decls(self.lines)

    # -- emission --
    def _raw(self, line: int, rule: str, scope: str, symbol: str,
             message: str) -> None:
        self.findings.append(Finding(self.relpath, line, rule, scope,
                                     symbol, message))

    def emit(self, node: ast.AST, rule: str, scope: str, symbol: str,
             message: str) -> None:
        line = getattr(node, "lineno", 0)
        if rule in self.suppressed.get(line, ()):
            return
        self._raw(line, rule, scope, symbol, message)

    # -- entry --
    def run(self) -> list[Finding]:
        try:
            tree = ast.parse("\n".join(self.lines))
        except SyntaxError as e:
            self._raw(e.lineno or 0, "SYNTAX", "<module>", "parse",
                      f"syntax error: {e.msg}")
            return self.findings
        self._lint_clock_and_tracer(tree)
        self._lint_lock_discipline(tree)
        self._lint_drain_sync(tree)
        if self.in_tests:
            self._lint_randomness(tree)
        if self.in_kernels:
            self._lint_kernel_names(tree)
        return self.findings

    # -- scope bookkeeping --
    def _scopes(self, tree: ast.Module):
        """Yield (scope_name, func_node) for class methods and module-level
        functions."""
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                for sub in node.body:
                    if isinstance(sub, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                        yield f"{node.name}.{sub.name}", sub
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node.name, node

    def _scope_of(self, tree: ast.Module, node: ast.AST) -> str:
        """Innermost ``Class.function`` (or function / class alone)
        containing the node's line."""
        line = getattr(node, "lineno", 0)
        cls_name = fn_name = None
        cls_span = fn_span = None
        for sub in ast.walk(tree):
            end = getattr(sub, "end_lineno", None)
            if end is None or not (sub.lineno <= line <= end):
                continue
            span = end - sub.lineno
            if isinstance(sub, ast.ClassDef):
                if cls_span is None or span < cls_span:
                    cls_name, cls_span = sub.name, span
            elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if fn_span is None or span < fn_span:
                    fn_name, fn_span = sub.name, span
        if cls_name and fn_name:
            return f"{cls_name}.{fn_name}"
        return fn_name or cls_name or "<module>"

    # -- EL002 / EL003 --
    def _lint_clock_and_tracer(self, tree: ast.Module) -> None:
        if not self.in_engine:
            return
        # names bound by `from time import perf_counter` style imports
        from_time: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                from_time |= {a.asname or a.name for a in node.names}

        gated: set[int] = set()     # line numbers inside an .enabled-if body

        def mark_gated(body: list[ast.stmt]) -> None:
            for stmt in body:
                for sub in ast.walk(stmt):
                    if hasattr(sub, "lineno"):
                        gated.add(sub.lineno)

        for node in ast.walk(tree):
            if isinstance(node, ast.If) and _contains_enabled(node.test):
                mark_gated(node.body)
            if isinstance(node, ast.IfExp) and _contains_enabled(node.test):
                gated.add(node.lineno)

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            name = chain[-1]
            # EL002: a *call* through the time module (references are the
            # sanctioned injectable-clock default sites and don't match)
            if ((len(chain) >= 2 and chain[-2] == "time"
                 and name in _CLOCK_CALLS)
                    or (len(chain) == 1 and name in from_time
                        and name in _CLOCK_CALLS)):
                self.emit(node, "EL002", self._scope_of(tree, node),
                          f"time.{name}",
                          f"raw wall-clock call time.{name}() — inject a "
                          "clock callable (clock=time.perf_counter default "
                          "reference is the sanctioned pattern)")
            # EL003: tracer record outside an .enabled gate
            if (name == "record" and not self.is_tracer_impl
                    and any("tracer" in part.lower() for part in chain[:-1])
                    and node.lineno not in gated):
                self.emit(node, "EL003", self._scope_of(tree, node),
                          ".".join(chain),
                          f"{'.'.join(chain)}(...) not gated on "
                          "`.enabled` — NULL_TRACER paths must pay nothing")

    # -- EL001 --
    def _lint_lock_discipline(self, tree: ast.Module) -> None:
        if not self.decls_by_line:
            return
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            end = getattr(cls, "end_lineno", cls.lineno)
            guarded: dict[str, frozenset] = {}
            for line_no, decls in self.decls_by_line.items():
                if cls.lineno <= line_no <= end:
                    # bind to the innermost class containing the line
                    inner = any(
                        isinstance(c, ast.ClassDef) and c is not cls
                        and c.lineno <= line_no
                        <= getattr(c, "end_lineno", c.lineno)
                        and cls.lineno <= c.lineno
                        for c in ast.walk(cls))
                    if not inner:
                        guarded.update(decls)
            if guarded:
                self._check_class_locks(cls, guarded)

    def _check_class_locks(self, cls: ast.ClassDef,
                           guarded: dict[str, frozenset]) -> None:
        all_locks = frozenset().union(*guarded.values())
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if fn.name in ("__init__", "__post_init__"):
                continue           # construction precedes sharing
            held: set[str] = set()
            doc = ast.get_docstring(fn) or ""
            for m in _CALLER_HOLDS_RE.finditer(doc):
                held.add(m.group(1))
            self._walk_held(fn.body, held, all_locks, guarded,
                            f"{cls.name}.{fn.name}")

    def _walk_held(self, body, held: set, all_locks: frozenset,
                   guarded: dict[str, frozenset], scope: str) -> None:
        for stmt in body:
            self._visit_held(stmt, held, all_locks, guarded, scope)

    def _visit_held(self, node: ast.AST, held: set, all_locks: frozenset,
                    guarded: dict[str, frozenset], scope: str) -> None:
        if isinstance(node, (ast.With, ast.AsyncWith)):
            newly = set()
            for item in node.items:
                chain = _attr_chain(item.context_expr)
                if (len(chain) == 2 and chain[0] == "self"
                        and chain[1] in all_locks):
                    newly.add(chain[1])
            self._walk_held(node.body, held | newly, all_locks, guarded,
                            scope)
            return
        if isinstance(node, ast.Attribute):
            chain = _attr_chain(node)
            if (len(chain) == 2 and chain[0] == "self"
                    and chain[1] in guarded
                    and not (held & guarded[chain[1]])):
                need = "/".join(sorted(guarded[chain[1]]))
                self.emit(node, "EL001", scope, chain[1],
                          f"self.{chain[1]} is `guarded-by: {need}` but "
                          f"accessed with locks held: "
                          f"{sorted(held) or 'none'}")
            # still recurse: self.a.b chains nest Attribute under Attribute
        for child in ast.iter_child_nodes(node):
            self._visit_held(child, held, all_locks, guarded, scope)

    # -- EL004 --
    def _lint_drain_sync(self, tree: ast.Module) -> None:
        if not self.in_engine:
            return
        for scope, fn in self._scopes(tree):
            base = fn.name
            if not (base == "poll" or base.startswith("drain")):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                chain = _attr_chain(node.func)
                name = chain[-1]
                if name not in _HOST_SYNC_ATTRS:
                    continue
                if name == "asarray" and not any(
                        p in ("np", "numpy") for p in chain[:-1]):
                    continue       # jnp.asarray stays on device
                if name == "item" and node.args:
                    continue       # e.g. dict-like .item(key) lookalikes
                self.emit(node, "EL004", scope, ".".join(chain),
                          f"host sync {'.'.join(chain)}(...) inside "
                          f"{base}() blocks the drain loop — defer to "
                          "finalize/result paths")

    # -- EL005 --
    def _lint_randomness(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            name = chain[-1]
            is_random_mod = (len(chain) == 2 and chain[0] == "random")
            is_np_random = (len(chain) == 3 and chain[1] == "random"
                            and chain[0] in ("np", "numpy"))
            if not (is_random_mod or is_np_random):
                continue
            if name in _SEEDED_FACTORIES:
                if node.args or node.keywords:
                    continue       # explicitly seeded constructor
                self.emit(node, "EL005", self._scope_of(tree, node),
                          ".".join(chain),
                          f"{'.'.join(chain)}() without a seed — pass an "
                          "explicit (logged) seed")
                continue
            if name == "seed":
                continue           # seeding the global stream is the fix
            self.emit(node, "EL005", self._scope_of(tree, node),
                      ".".join(chain),
                      f"{'.'.join(chain)}(...) draws from the hidden "
                      "global stream — use a seeded Generator and log "
                      "the seed")


    # -- EL006 --
    def _lint_kernel_names(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _attr_chain(node.func)[-1] == "pallas_call"):
                continue
            given = {kw.arg for kw in node.keywords}
            missing = [k for k in ("name", "metadata") if k not in given]
            if missing:
                self.emit(node, "EL006", self._scope_of(tree, node),
                          "pallas_call",
                          f"pallas_call(...) without {' and '.join(missing)}"
                          "= — a profile could not tell which plan item "
                          "this kernel ran for")


# -- public API ---------------------------------------------------------------

def lint_source(source: str, relpath: str) -> list[Finding]:
    """Lint one file's source text (relpath selects which rules apply)."""
    return _FileLinter(relpath.replace("\\", "/"), source).run()


def lint_paths(paths: list[str | Path],
               root: str | Path | None = None) -> list[Finding]:
    """Lint every ``.py`` file under the given files/directories."""
    root = Path(root) if root else Path.cwd()
    files: list[Path] = []
    for p in paths:
        p = Path(p)
        if p.is_dir():
            files.extend(sorted(p.rglob("*.py")))
        elif p.suffix == ".py":
            files.append(p)
    findings: list[Finding] = []
    for f in files:
        try:
            rel = f.resolve().relative_to(root.resolve()).as_posix()
        except ValueError:
            rel = f.as_posix()
        findings.extend(lint_source(f.read_text(encoding="utf-8"), rel))
    findings.sort(key=lambda x: (x.path, x.line, x.rule))
    return findings
