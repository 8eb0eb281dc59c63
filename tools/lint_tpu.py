#!/usr/bin/env python
"""Multi-pass AST analyzer: host-sync hazards in device code plus the
concurrency passes (stdlib `ast` only).

The mesh pipeline's performance rests on fragment chains staying
device-resident; one stray `.item()` or `np.asarray` on a device value
inserts a silent host round-trip that no test fails but every benchmark
pays.  This linter walks `trino_tpu/ops/`, `trino_tpu/parallel/`, and
`trino_tpu/expr/` flagging the hazard patterns statically, at review time:

  rule              | flags
  ------------------+----------------------------------------------------
  host-sync-item    | `x.item()` — always a blocking device->host sync
  host-sync-cast    | `float()/int()/bool()` applied to a jnp expression
  host-sync-asarray | `np.asarray(...)` / `np.array(...)` of a jnp value
  host-transfer     | `jax.device_get` / `block_until_ready` calls: the
                    | one device->host door is `host_pull(tree, why)`
                    | (columnar/batch.py), which counts the pull and
                    | records its span; `int()` / `np.asarray()` of a
                    | `host_pull(...)` result is host arithmetic
  raw-jit           | `jax.jit` (call, decorator or partial) under ops/,
                    | parallel/ and runtime/local_planner.py: the one
                    | launch door is `jit_program(fn, step, ...)`
                    | (telemetry/programs.py), which names the program
                    | and counts its launches
  untyped-symbol    | `Symbol(name)` built without a type — untyped
                    | PlanNode construction poisons downstream typing
  raw-perf-counter  | `time.perf_counter()` phase timing in device code —
                    | use `trino_tpu.telemetry.now` (the shared clock spans
                    | and MeshProfile phases read) so wall attribution
                    | stays comparable across the telemetry surfaces
  raw-http-timeout  | `timeout=<number>` literals in the HTTP tier
                    | (trino_tpu/server/ + parallel/remote.py) — socket
                    | waits must derive from the query deadline
                    | (`lifecycle.request_timeout`) or a named constant

A second pass — the concurrency analyzer (trino_tpu/verify/concurrency.py)
— runs over ALL of trino_tpu/:

  unguarded-state   | read/write of a lock-guarded `self._x` attribute
                    | outside any lock in its class (guarded-state
                    | inference); survivors triage through the
                    | `unguarded_state` baseline map in
                    | tools/lint_baseline.json, one justification per entry
  thread-discipline | `threading.Thread(...)` without `name=` or an
                    | explicit `daemon=`
  lock-order-cycle  | nested `with <lock>:` statements whose repo-wide
                    | acquisition-order graph has a cycle (the static half;
                    | verify.lockgraph is the dynamic half)

A third pass — telemetry discipline — also runs over ALL of trino_tpu/:

  stray-metrics-registry | `MetricsRegistry()` constructed outside
                         | telemetry/metrics.py — counters in a private
                         | registry never reach /v1/metrics or the
                         | system.metrics tables
  ledger-bypass          | assignment to a `["decisions"]` key outside
                         | telemetry/decisions.py + profile_store.py —
                         | decisions emitted past the ledger API skip
                         | hindsight stamping, the plan_decisions counter,
                         | and the check_decisions completeness gate
                         | (survivors triage through the
                         | `telemetry_discipline` baseline map)

Rules are path-scoped: device rules run over ops/parallel/expr;
raw-http-timeout runs over trino_tpu/server/ and parallel/remote.py (and
only that rule runs over server/ — host transfers are legal there).

Suppression: append `# lint: allow(<rule>)` (comma-separate several rules,
or `allow(*)` for all) to the offending line or to the enclosing `def` /
`class` line — a def-level allowance declares the whole function a genuine
host boundary.  Run `python tools/lint_tpu.py` from the repo root; exits 1
when findings remain.  Wired into CI and tests/test_verify.py so the gate
also runs under plain pytest.

Suppression budget: the repo-wide `allow()` count is capped by the
checked-in baseline (tools/lint_baseline.json).  New suppressions beyond
the budget fail the lint — declaring a new host boundary means paying it
down elsewhere (or consciously raising the baseline in review).  Shrinking
the count below the baseline prints a reminder to ratchet it down.
"""

from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from dataclasses import dataclass

#: directories holding device code (paths relative to the repo root)
DEFAULT_PATHS = (
    "trino_tpu/ops",
    "trino_tpu/parallel",
    "trino_tpu/expr",
    # HTTP tier: linted ONLY for raw-http-timeout (see _rules_for_path) —
    # host transfers are legal there, hardcoded socket timeouts are not
    "trino_tpu/server",
    # the local planner: linted ONLY for the launch and host-pull doors
    "trino_tpu/runtime/local_planner.py",
)

RULES = {
    "host-sync-item": ".item() blocks on a device->host transfer",
    "host-sync-cast": "python scalar cast of a jnp value syncs the device",
    "host-sync-asarray": "np.asarray/np.array of a jnp value syncs the device",
    "host-transfer": "explicit device->host transfer outside the "
                     "host_pull door",
    "raw-jit": "jax.jit outside the jit_program door: the program has no "
               "name and its launches are not counted",
    "untyped-symbol": "Symbol constructed without a type",
    "raw-perf-counter": "raw time.perf_counter() phase timing outside "
                        "telemetry/ and query_stats.py",
    "raw-http-timeout": "hardcoded timeout literal on an intra-cluster "
                        "call — derive it from the query deadline "
                        "(lifecycle.request_timeout) or a named constant",
    "numeric-safety": "numeric hazard in device code: a narrowing integer "
                      "astype with no visible bound (silent wrap) or a "
                      "validity-aware function constructing a Column with "
                      "its validity plane dropped; triage survivors "
                      "through tools/lint_baseline.json `numeric_safety`",
    "module-level-knob": "module/class-level numeric knob literal — load "
                         "it from the typed config (trino_tpu/config) so "
                         "deployments can tune it without a code change",
    # concurrency pass (verify/concurrency.py)
    "unguarded-state": "lock-guarded attribute accessed outside any lock",
    "thread-discipline": "threading.Thread without name= / explicit daemon=",
    "lock-order-cycle": "inconsistent nested lock acquisition order",
    # telemetry-discipline pass (repo-wide over trino_tpu/)
    "stray-metrics-registry": "MetricsRegistry constructed outside "
                              "telemetry/metrics.py — counters registered "
                              "in a private registry never reach the "
                              "/v1/metrics expositions or the system "
                              "tables",
    "ledger-bypass": "direct write to a `decisions` artifact key outside "
                     "the ledger API (telemetry/decisions) — decisions "
                     "emitted past the ledger skip hindsight, metrics, "
                     "and the completeness gate",
}

#: paths the concurrency pass walks (everything; locks live in runtime/,
#: server/, telemetry/, parallel/, partitioning/, config)
CONCURRENCY_PATHS = ("trino_tpu",)

#: rules that only make sense in device code (ops/parallel/expr)
_DEVICE_RULES = frozenset(RULES) - {"raw-http-timeout", "module-level-knob"}
#: files whose tunables must ALL live in the typed config: PR 5 flagged the
#: fixed breaker/retry knobs in the remote tier, PR 7 moved them into
#: trino_tpu/config — this rule keeps new numeric knobs from creeping back
_KNOB_FREE_PATHS = ("trino_tpu/parallel/remote.py",)
#: the HTTP tier: every socket wait must be bounded by what the query has
#: left to live (runtime/lifecycle.request_timeout), so numeric timeout
#: literals are flagged here (reference: HttpRemoteTask deriving every
#: request deadline from the query's remaining time)
_HTTP_PATHS = ("trino_tpu/server/", "trino_tpu/parallel/remote.py")


def _rules_for_path(path: str) -> frozenset:
    p = path.replace(os.sep, "/")
    http = any(h in p for h in _HTTP_PATHS)
    if "trino_tpu/server/" in p:
        return frozenset({"raw-http-timeout"})
    if p.endswith("runtime/local_planner.py"):
        return frozenset({"raw-jit", "host-transfer"})
    rules = frozenset(RULES) if http else _DEVICE_RULES
    if not any(k in p for k in _KNOB_FREE_PATHS):
        rules = rules - {"module-level-knob"}
    return rules

_ALLOW_RE = re.compile(r"#\s*lint:\s*allow\(([^)]*)\)")


@dataclass
class Finding:
    file: str
    line: int
    rule: str
    message: str
    #: stable triage key for baseline-mapped rules (numeric-safety:
    #: `relpath:qualname:pattern`), None for immediate-fail rules
    baseline_key: str = None

    def __str__(self):
        return f"{self.file}:{self.line}: [{self.rule}] {self.message}"


def _allowances(source: str) -> dict:
    """line number -> set of allowed rule names ('*' = all)."""
    out: dict[int, set] = {}
    for i, text in enumerate(source.splitlines(), start=1):
        m = _ALLOW_RE.search(text)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def _contains_jnp(node: ast.AST) -> bool:
    """Heuristic for 'this expression produces a device value': the subtree
    references `jnp` (every device op in this codebase routes through the
    jax.numpy namespace).  What `host_pull(...)` returns is a host value:
    the door's arguments are not looked into."""
    if isinstance(node, ast.Call) and _call_name(node) in (
        "host_pull", "_host_pull"
    ):
        return False
    if isinstance(node, ast.Name) and node.id == "jnp":
        return True
    return any(_contains_jnp(c) for c in ast.iter_child_nodes(node))


def _call_name(node: ast.Call):
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return fn.id if isinstance(fn, ast.Name) else None


def _is_jax_jit(node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr == "jit"
        and isinstance(node.value, ast.Name)
        and node.value.id == "jax"
    )


#: narrow integer dtype names: an astype to one of these can silently wrap
#: values that fit the wider source representation
_NARROW_INT_DTYPES = frozenset(
    {"int8", "int16", "int32", "uint8", "uint16", "uint32"}
)

#: call names that visibly BOUND a value before a narrowing cast — the
#: sound reasons a narrow astype cannot wrap
_BOUNDING_CALLS = frozenset(
    {"clip", "searchsorted", "argsort", "argmax", "argmin", "sign",
     "minimum", "maximum", "mod", "remainder", "zeros", "ones", "arange"}
)


def _narrow_dtype_of(node):
    """'int32' when the AST node names a narrow integer dtype (jnp.int32 /
    np.int32 / 'int32'), else None."""
    if isinstance(node, ast.Attribute) and node.attr in _NARROW_INT_DTYPES:
        if isinstance(node.value, ast.Name) and node.value.id in ("jnp", "np"):
            return node.attr
    if isinstance(node, ast.Constant) and node.value in _NARROW_INT_DTYPES:
        return node.value
    return None


def _is_bool_dtype(node) -> bool:
    return (
        (isinstance(node, ast.Name) and node.id == "bool")
        or (isinstance(node, ast.Attribute) and node.attr in ("bool_", "bool"))
        or (isinstance(node, ast.Constant) and node.value == "bool")
    )


def _visibly_bounded(node) -> bool:
    """The value subtree carries a visible bound: modulo/mask/shift
    arithmetic, a clip-family call, a comparison result, a bool source, or
    a `where` selecting among constants."""
    for n in ast.walk(node):
        if isinstance(n, ast.BinOp) and isinstance(
            n.op, (ast.Mod, ast.BitAnd, ast.RShift)
        ):
            return True
        if isinstance(n, ast.Compare):
            return True
        if isinstance(n, ast.Call):
            fn = n.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else None
            )
            if name in _BOUNDING_CALLS:
                return True
            if name == "astype" and n.args and _is_bool_dtype(n.args[0]):
                return True
            if (
                name == "where"
                and len(n.args) == 3
                and all(
                    isinstance(a, (ast.Constant, ast.UnaryOp, ast.IfExp))
                    for a in n.args[1:3]
                )
            ):
                return True
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str, rules=None, relpath=None):
        self.path = path
        self.relpath = (relpath or path).replace(os.sep, "/")
        self.findings: list[Finding] = []
        self.allow = _allowances(source)
        #: rules enabled for this file (path-scoped; None = all)
        self.rules = frozenset(RULES) if rules is None else frozenset(rules)
        #: stack of (def/class line, end line) carrying def-level allowances
        self._scopes: list[tuple[int, int]] = []
        #: qualname stack for numeric-safety baseline keys (Class.method)
        self._names: list[str] = []
        #: stack of "enclosing function reads a `.valid` attribute" flags
        self._valid_aware: list[bool] = []

    # -- suppression ----------------------------------------------------------

    def _allowed(self, rule: str, line: int) -> bool:
        for at in (line, *[s for s, e in self._scopes if s <= line <= e]):
            rules = self.allow.get(at)
            if rules and (rule in rules or "*" in rules):
                return True
        return False

    def _flag(self, rule: str, node: ast.AST, message: str) -> None:
        if rule in self.rules and not self._allowed(rule, node.lineno):
            self.findings.append(
                Finding(self.path, node.lineno, rule, message)
            )

    def _visit_scope(self, node) -> None:
        self._scopes.append((node.lineno, node.end_lineno or node.lineno))
        self._names.append(node.name)
        self.generic_visit(node)
        self._names.pop()
        self._scopes.pop()

    def _flag_raw_jit(self, node: ast.AST) -> None:
        self._flag(
            "raw-jit", node,
            "`jax.jit` outside the launch door; build the program with "
            "`jit_program(fn, step, ...)` (telemetry/programs.py)",
        )

    def _visit_fn_scope(self, node) -> None:
        for dec in node.decorator_list:
            if _is_jax_jit(dec):
                self._flag_raw_jit(dec)
        self._fn_depth += 1
        self._valid_aware.append(
            any(
                isinstance(n, ast.Attribute) and n.attr == "valid"
                for n in ast.walk(node)
            )
        )
        self._visit_scope(node)
        self._valid_aware.pop()
        self._fn_depth -= 1

    visit_FunctionDef = _visit_fn_scope
    visit_AsyncFunctionDef = _visit_fn_scope
    visit_ClassDef = _visit_scope

    #: ranges of `if` bodies whose test mentions a bool dtype — a narrowing
    #: astype under such a guard converts a bool column (bounded 0/1)
    _bool_if_ranges: list = None

    def visit_If(self, node: ast.If) -> None:
        mentions_bool = any(
            (isinstance(n, ast.Attribute) and n.attr in ("bool_", "bool"))
            or (isinstance(n, ast.Name) and n.id == "bool")
            for n in ast.walk(node.test)
        )
        if mentions_bool:
            if self._bool_if_ranges is None:
                self._bool_if_ranges = []
            self._bool_if_ranges.append(
                (node.lineno, node.end_lineno or node.lineno)
            )
        self.generic_visit(node)

    def _under_bool_guard(self, line: int) -> bool:
        return any(
            s <= line <= e for s, e in (self._bool_if_ranges or ())
        )

    def _qualname(self) -> str:
        return ".".join(self._names) if self._names else "<module>"

    def _flag_numeric(self, node: ast.AST, pattern: str, message: str) -> None:
        """numeric-safety findings carry a stable baseline key
        (relpath:qualname:pattern) and triage through the numeric_safety
        map instead of failing immediately."""
        if "numeric-safety" not in self.rules or self._allowed(
            "numeric-safety", node.lineno
        ):
            return
        self.findings.append(
            Finding(
                self.path, node.lineno, "numeric-safety", message,
                baseline_key=f"{self.relpath}:{self._qualname()}:{pattern}",
            )
        )

    #: nesting depth inside function bodies (0 = module/class level)
    _fn_depth = 0

    # -- rules ----------------------------------------------------------------

    @staticmethod
    def _numeric_constant(node) -> bool:
        if isinstance(node, ast.UnaryOp) and isinstance(
            node.op, (ast.USub, ast.UAdd)
        ):
            node = node.operand
        return (
            isinstance(node, ast.Constant)
            and isinstance(node.value, (int, float))
            and not isinstance(node.value, bool)
        )

    def _check_knob(self, node, value) -> None:
        """module/class-level `NAME = <number>` in a knob-free file: the
        tunable belongs in the typed config, not in code."""
        if self._fn_depth == 0 and self._numeric_constant(value):
            self._flag(
                "module-level-knob", node,
                "numeric knob literal at module/class level; declare it in "
                "trino_tpu/config (a ConfigSection knob) instead",
            )

    def visit_Assign(self, node: ast.Assign) -> None:
        self._check_knob(node, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._check_knob(node, node.value)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        # x.item()
        if isinstance(fn, ast.Attribute) and fn.attr == "item" and not node.args:
            self._flag(
                "host-sync-item", node,
                "`.item()` forces a blocking device->host sync; keep the "
                "value on device or move this to a declared host boundary",
            )
        # float(jnp...), int(jnp...), bool(jnp...)
        if (
            isinstance(fn, ast.Name)
            and fn.id in ("float", "int", "bool")
            and node.args
            and _contains_jnp(node.args[0])
        ):
            self._flag(
                "host-sync-cast", node,
                f"`{fn.id}(...)` of a jnp expression syncs the device; "
                "use jnp casts inside the program",
            )
        # np.asarray(jnp...) / np.array(jnp...)
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr in ("asarray", "array")
            and isinstance(fn.value, ast.Name)
            and fn.value.id == "np"
            and node.args
            and _contains_jnp(node.args[0])
        ):
            self._flag(
                "host-sync-asarray", node,
                "`np.%s(...)` of a jnp value copies it to the host; stay in "
                "jnp or declare a host boundary" % fn.attr,
            )
        # jax.device_get(...) / x.block_until_ready()
        if _call_name(node) in ("device_get", "block_until_ready"):
            self._flag(
                "host-transfer", node,
                f"`{_call_name(node)}` moves device data to the host "
                "uncounted; go through `host_pull(tree, why)` "
                "(columnar/batch.py)",
            )
        # jax.jit(...) / functools.partial(jax.jit, ...); a bare `@jax.jit`
        # decorator is caught in _visit_fn_scope
        if _is_jax_jit(fn) or any(_is_jax_jit(a) for a in node.args):
            self._flag_raw_jit(node)
        # time.perf_counter() / perf_counter() — phase timing belongs to the
        # telemetry clock (trino_tpu.telemetry.now), which spans and
        # MeshProfile phases share; raw readings drift out of the trace
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr == "perf_counter"
            and isinstance(fn.value, ast.Name)
            and fn.value.id == "time"
        ) or (isinstance(fn, ast.Name) and fn.id == "perf_counter"):
            self._flag(
                "raw-perf-counter", node,
                "raw `perf_counter()` phase timing in device code; import "
                "`now` from trino_tpu.telemetry (the shared span/profile "
                "clock) instead",
            )
        # timeout=<numeric literal> on an intra-cluster call: socket waits
        # in the HTTP tier must shrink with the query's remaining run time
        # (runtime/lifecycle.request_timeout) or at minimum come from a
        # named module constant reviewers can reason about in one place
        for kw in node.keywords:
            if (
                kw.arg == "timeout"
                and isinstance(kw.value, ast.Constant)
                and isinstance(kw.value.value, (int, float))
                and not isinstance(kw.value.value, bool)
            ):
                self._flag(
                    "raw-http-timeout", node,
                    f"hardcoded timeout={kw.value.value!r}; derive the bound "
                    "from the query deadline (lifecycle.request_timeout) or "
                    "a named constant",
                )
        # numeric-safety pass 1: narrowing integer astype with no visible
        # bound on the value — the kernel wraps silently where the
        # reference engine would raise
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr == "astype"
            and node.args
        ):
            dt = _narrow_dtype_of(node.args[0])
            if (
                dt is not None
                and not _visibly_bounded(fn.value)
                and not self._under_bool_guard(node.lineno)
            ):
                self._flag_numeric(
                    node, "astype-narrow",
                    f"narrowing astype({dt}) with no visible bound on the "
                    "value (no clip/mask/modulo in sight): values wider "
                    f"than {dt} wrap silently — prove the bound and record "
                    "it in the numeric_safety baseline, or clip explicitly",
                )
        # (jnp.asarray(x, int32) is NOT flagged: with an explicit dtype it
        # declares the representation — dictionary codes and gather indices
        # are int32 by construction throughout the columnar layer)
        # numeric-safety pass 2: a validity-AWARE function (it reads some
        # column's .valid) constructing a Column with an explicit None
        # validity plane — the dropped-validity hazard surface
        if (
            isinstance(fn, ast.Name)
            and fn.id == "Column"
            and len(node.args) >= 3
            and isinstance(node.args[2], ast.Constant)
            and node.args[2].value is None
            and self._valid_aware
            and self._valid_aware[-1]
        ):
            self._flag_numeric(
                node, "validity-drop",
                "validity-aware function builds a Column with validity "
                "None: NULLs upstream resurface as values — thread the "
                "plane through, or justify the drop in the "
                "numeric_safety baseline",
            )
        # Symbol("name") without a type
        if (
            (isinstance(fn, ast.Name) and fn.id == "Symbol")
            or (
                isinstance(fn, ast.Attribute)
                and fn.attr == "Symbol"
                and isinstance(fn.value, ast.Name)
                and fn.value.id in ("P", "plan")
            )
        ):
            n_pos = len(node.args)
            kw = {k.arg for k in node.keywords}
            if n_pos < 2 and "type" not in kw:
                self._flag(
                    "untyped-symbol", node,
                    "Symbol constructed without a type — untyped plan "
                    "symbols break the dtype checkers downstream",
                )
        self.generic_visit(node)


def lint_file(path: str, root: str = None) -> list:
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, "syntax-error", str(e))]
    rel = path
    if root is not None:
        try:
            rel = os.path.relpath(path, root)
        except ValueError:
            rel = path
    linter = _Linter(path, source, rules=_rules_for_path(path), relpath=rel)
    linter.visit(tree)
    return linter.findings


def _lint_files(paths, root: str) -> list:
    paths = list(paths) if paths else list(DEFAULT_PATHS)
    files = []
    for p in paths:
        full = os.path.join(root, p)
        if os.path.isfile(full):
            files.append(full)
            continue
        for dirpath, _, names in os.walk(full):
            files.extend(
                os.path.join(dirpath, n) for n in names if n.endswith(".py")
            )
    return sorted(files)


def _run_lint_full(paths=None, root: str = "."):
    """-> (surviving findings, stale numeric_safety AST keys)."""
    findings = []
    for f in _lint_files(paths, root):
        findings.extend(lint_file(f, root=root))
    findings, stale = apply_numeric_baseline(
        findings, numeric_safety_baseline(root)
    )
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    return findings, stale


def run_lint(paths=None, root: str = ".") -> list:
    """Lint every .py file under `paths` (files or directories, relative to
    `root`); returns all findings sorted by location.  numeric-safety
    findings are triaged through the `numeric_safety` baseline map
    (tools/lint_baseline.json) — a baselined finding is dropped here."""
    return _run_lint_full(paths, root)[0]


def numeric_safety_baseline(root: str = ".") -> dict:
    """{key -> justification} from tools/lint_baseline.json
    `numeric_safety`.  Keys are either `relpath:qualname:pattern` (the AST
    pass here) or `rule:signature` (the expression sweep in
    trino_tpu/verify/numeric.py) — one shared triage map.  DELIBERATE twin
    of verify/numeric.numeric_safety_baseline: this module must stay
    stdlib-only for the dependency-free CI lint job, so the two passes
    share the JSON contract, not code — change it in BOTH places."""
    import json

    path = os.path.join(root, "tools", "lint_baseline.json")
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return dict(json.load(fh).get("numeric_safety") or {})
    except (OSError, ValueError):
        return {}


def apply_numeric_baseline(findings, baseline: dict):
    """-> (surviving findings, stale AST-pass baseline keys)."""
    kept, used = [], set()
    for f in findings:
        key = getattr(f, "baseline_key", None)
        if key is not None and key in baseline:
            used.add(key)
            continue
        kept.append(f)
    # only AST-pass keys (path-prefixed) are checked for staleness here;
    # rule:signature keys belong to the expression sweep
    stale = sorted(
        k for k in baseline
        if k.startswith("trino_tpu/") and k not in used
    )
    return kept, stale


def count_suppressions(paths=None, root: str = ".") -> int:
    """Repo-wide `# lint: allow(...)` count over the linted paths."""
    n = 0
    for f in _lint_files(paths, root):
        with open(f, "r", encoding="utf-8") as fh:
            n += len(_ALLOW_RE.findall(fh.read()))
    return n


def suppression_budget(root: str = ".") -> int:
    """Checked-in allow() budget (tools/lint_baseline.json)."""
    import json

    path = os.path.join(root, "tools", "lint_baseline.json")
    with open(path, "r", encoding="utf-8") as fh:
        return int(json.load(fh)["allow_budget"])


#: paths the telemetry-discipline pass walks (the whole package: a stray
#: registry or a ledger bypass is a hazard wherever it lives)
TELEMETRY_PATHS = ("trino_tpu",)

#: files where the flagged constructs ARE the implementation
_TELEMETRY_EXEMPT = (
    "trino_tpu/telemetry/metrics.py",
    "trino_tpu/telemetry/decisions.py",
    "trino_tpu/telemetry/profile_store.py",
)


class _TelemetryLinter(ast.NodeVisitor):
    """Telemetry-discipline pass: every counter must land in THE process
    registry (`telemetry.metrics.REGISTRY` — a private `MetricsRegistry()`
    never reaches /v1/metrics or system.metrics), and every plan-decision
    emission must go through the ledger API (`telemetry/decisions` —
    writing an artifact's `decisions` key by hand skips hindsight
    stamping, the plan_decisions counter, and the check_decisions
    completeness gate).  Survivors triage through the
    `telemetry_discipline` baseline map in tools/lint_baseline.json."""

    def __init__(self, relpath: str, source: str):
        self.relpath = relpath.replace(os.sep, "/")
        self.findings: list[Finding] = []
        self.allow = _allowances(source)
        #: (def/class line, end line) stack: allowances on an enclosing
        #: definition line cover the whole body (same contract as the
        #: device pass)
        self._scopes: list[tuple[int, int]] = []

    def _flag(self, rule: str, node: ast.AST, message: str) -> None:
        for at in (
            node.lineno,
            *[s for s, e in self._scopes if s <= node.lineno <= e],
        ):
            rules = self.allow.get(at)
            if rules and (rule in rules or "*" in rules):
                return
        self.findings.append(
            Finding(
                self.relpath, node.lineno, rule, message,
                baseline_key=f"{self.relpath}:{rule}",
            )
        )

    def _visit_scope(self, node) -> None:
        self._scopes.append((node.lineno, node.end_lineno or node.lineno))
        self.generic_visit(node)
        self._scopes.pop()

    visit_FunctionDef = _visit_scope
    visit_AsyncFunctionDef = _visit_scope
    visit_ClassDef = _visit_scope

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        name = fn.id if isinstance(fn, ast.Name) else (
            fn.attr if isinstance(fn, ast.Attribute) else None
        )
        if name == "MetricsRegistry":
            self._flag(
                "stray-metrics-registry", node,
                "MetricsRegistry() constructed outside telemetry/metrics.py"
                " — register counters in the shared REGISTRY so both "
                "exposition endpoints and system.metrics see them",
            )
        self.generic_visit(node)

    def _check_decisions_write(self, target: ast.AST) -> None:
        if (
            isinstance(target, ast.Subscript)
            and isinstance(target.slice, ast.Constant)
            and target.slice.value == "decisions"
        ):
            self._flag(
                "ledger-bypass", target,
                "direct `[\"decisions\"]` write — emit through "
                "telemetry.decisions (record_decision/DecisionLedger) so "
                "the choice gets hindsight, metrics, and gate coverage",
            )

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._check_decisions_write(t)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._check_decisions_write(node.target)
        self.generic_visit(node)


def telemetry_discipline_baseline(root: str = ".") -> dict:
    """{relpath:rule -> justification} from tools/lint_baseline.json
    `telemetry_discipline`."""
    import json

    path = os.path.join(root, "tools", "lint_baseline.json")
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return dict(json.load(fh).get("telemetry_discipline") or {})
    except (OSError, ValueError):
        return {}


def run_telemetry_discipline(root: str = ".", baseline=None):
    """The telemetry-discipline pass over trino_tpu/ (stray registries +
    ledger bypasses), triaged through the `telemetry_discipline` baseline.
    Returns (failing findings, stale baseline keys)."""
    if baseline is None:
        baseline = telemetry_discipline_baseline(root)
    findings = []
    for f in _lint_files(TELEMETRY_PATHS, root):
        rel = os.path.relpath(f, root).replace(os.sep, "/")
        if rel in _TELEMETRY_EXEMPT:
            continue
        with open(f, "r", encoding="utf-8") as fh:
            source = fh.read()
        try:
            tree = ast.parse(source, filename=f)
        except SyntaxError:
            continue  # the device pass reports syntax errors
        linter = _TelemetryLinter(rel, source)
        linter.visit(tree)
        findings.extend(linter.findings)
    kept, used = [], set()
    for f in findings:
        if f.baseline_key in baseline:
            used.add(f.baseline_key)
            continue
        kept.append(f)
    stale = sorted(k for k in baseline if k not in used)
    return kept, stale


def check_suppression_budget(paths=None, root: str = ".") -> list:
    """-> [error message] when the allow() count exceeds the baseline."""
    try:
        budget = suppression_budget(root)
    except (OSError, KeyError, ValueError):
        return []  # partial checkouts / custom paths: budget not enforced
    count = count_suppressions(paths, root)
    if count > budget:
        return [
            f"suppression budget exceeded: {count} `# lint: allow()` "
            f"suppressions > baseline {budget} "
            "(tools/lint_baseline.json) — remove a suppression or "
            "consciously raise the baseline in review"
        ]
    return []


def unguarded_state_baseline(root: str = ".") -> dict:
    """{file:Class.attr -> justification} from tools/lint_baseline.json."""
    import json

    path = os.path.join(root, "tools", "lint_baseline.json")
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return dict(json.load(fh).get("unguarded_state") or {})
    except (OSError, ValueError):
        return {}


def _load_concurrency(root: str):
    """Load verify/concurrency.py by FILE PATH, not package import: the
    trino_tpu package imports jax at init, and this lint must keep running
    in the dependency-free CI lint job (the analyzer itself is pure
    stdlib-ast)."""
    import importlib.util

    path = os.path.join(root, "trino_tpu", "verify", "concurrency.py")
    spec = importlib.util.spec_from_file_location("_lint_concurrency", path)
    mod = importlib.util.module_from_spec(spec)
    # dataclass processing resolves cls.__module__ through sys.modules
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def run_concurrency(root: str = ".", baseline=None):
    """The concurrency pass (verify/concurrency.py) over trino_tpu/:
    guarded-state inference + thread discipline + static lock-order cycles,
    with the unguarded-state findings triaged through the baseline.
    Returns (failing findings, stale baseline keys)."""
    conc = _load_concurrency(root)
    findings, _ = conc.analyze_paths(CONCURRENCY_PATHS, root=root)
    if baseline is None:
        baseline = unguarded_state_baseline(root)
    return conc.apply_baseline(findings, baseline)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="multi-pass AST analyzer: host-sync hazards in TPU "
        "device code + the concurrency passes"
    )
    ap.add_argument(
        "paths", nargs="*", default=None,
        help=f"files/dirs to lint (default: {', '.join(DEFAULT_PATHS)}; "
        "when given, only the device pass runs)",
    )
    ap.add_argument(
        "--root", default=None,
        help="repo root (default: parent of this script's directory)",
    )
    ap.add_argument(
        "--only", choices=("device", "concurrency", "telemetry"),
        default=None,
        help="run a single pass (default: all)",
    )
    ap.add_argument(
        "--check-stale", action="store_true",
        help="FAIL (exit 1) when a tools/lint_baseline.json entry no "
        "longer matches any current finding — justified suppressions must "
        "not outlive the code they excused (on in CI; without the flag "
        "stale entries only print ratchet reminders)",
    )
    args = ap.parse_args(argv)
    if args.only == "concurrency" and args.paths:
        # the concurrency pass is repo-wide (its lock-order graph and
        # baseline are whole-tree artifacts): path-scoping it would
        # silently verify nothing
        ap.error("--only concurrency does not take path arguments "
                 "(the pass is repo-wide)")
    root = args.root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    findings = []
    numeric_stale = []
    if args.only not in ("concurrency", "telemetry"):
        device, numeric_stale = _run_lint_full(args.paths or None, root=root)
        findings.extend(device)
    stale = []
    if args.only in (None, "concurrency") and not args.paths:
        conc, stale = run_concurrency(root)
        findings.extend(conc)
    tele_stale = []
    if args.only in (None, "telemetry") and not args.paths:
        tele, tele_stale = run_telemetry_discipline(root)
        findings.extend(tele)
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    for f in findings:
        print(f)
        if getattr(f, "baseline_key", None):
            print(f"  baseline key: {f.baseline_key!r}")
    stale_word = "STALE" if args.check_stale else "note"
    for k in stale:
        print(
            f"{stale_word}: baseline entry {k!r} has no live finding — "
            "ratchet tools/lint_baseline.json (unguarded_state) down"
        )
    if not args.paths:
        for k in numeric_stale:
            print(
                f"{stale_word}: numeric_safety baseline entry {k!r} has no "
                "live finding — ratchet tools/lint_baseline.json down"
            )
        for k in tele_stale:
            print(
                f"{stale_word}: telemetry_discipline baseline entry {k!r} "
                "has no live finding — ratchet tools/lint_baseline.json "
                "down"
            )
    # stale-baseline detector (--check-stale, on in CI): a justified
    # suppression whose finding no longer fires has outlived the code it
    # excused — failing here forces the ratchet instead of letting dead
    # justifications accumulate.  Path-scoped runs skip it: staleness is
    # only meaningful against the FULL finding set.
    stale_errors = []
    if args.check_stale and not args.paths:
        stale_errors = [
            f"stale baseline entry (no live finding): {k!r}"
            for k in list(stale) + list(numeric_stale) + list(tele_stale)
        ]
        if stale_errors:
            print(
                f"{len(stale_errors)} stale baseline entr"
                f"{'y' if len(stale_errors) == 1 else 'ies'} — delete them "
                "from tools/lint_baseline.json (--check-stale)"
            )
    budget_errors = []
    if not args.paths:  # budget is repo-wide; skip for targeted runs
        budget_errors = check_suppression_budget(None, root)
        for e in budget_errors:
            print(e)
    if findings or budget_errors or stale_errors:
        if findings:
            print(f"\n{len(findings)} finding(s) across "
                  f"{len({f.file for f in findings})} file(s)")
        return 1
    count = count_suppressions(None, root)
    try:
        budget = suppression_budget(root)
        slack = (
            f" ({budget - count} under budget — consider ratcheting "
            "tools/lint_baseline.json down)"
            if count < budget
            else ""
        )
        print(f"lint_tpu: clean ({count}/{budget} suppressions{slack})")
    except (OSError, KeyError, ValueError):
        print("lint_tpu: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
