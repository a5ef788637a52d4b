"""Layer 1 of grape-lint: AST checks R1-R9 over the library source.

Each checker's docstring names the historical, actually-shipped bug it
fossilizes (see analysis/rules.py for the catalogue and CHANGES.md for
the incident reports).  The analysis is deliberately intraprocedural +
pattern-anchored: it models the specific idioms this codebase uses
(runner builders behind `_cached_runner`, traced `stepper` closures,
`GuardConfig.resolve` guard arming) rather than attempting whole-
program dataflow — a lint that needs no annotations and produces
near-zero false positives on the shipped tree, with the intentional
exceptions named in analysis/baseline.json.

Entry points: `lint_source(src, relpath)` for one module,
`lint_paths(paths, root=...)` for trees (skips __pycache__/scratch).
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set

from libgrape_lite_tpu.analysis.report import Finding

# function wrappers whose function-valued argument becomes traced code
_TRACE_WRAPPERS = {"jit", "shard_map", "pallas_call", "vmap", "pmap"}
# np/jnp constructors whose result is an array worth worrying about
# (dtype scalars like jnp.int32(x) are deliberately absent: a closure-
# captured scalar constant is harmless)
_ARRAY_FNS = {
    "array", "asarray", "ascontiguousarray", "zeros", "ones", "full",
    "empty", "arange", "linspace", "concatenate", "stack", "vstack",
    "hstack", "tile", "repeat", "zeros_like", "ones_like", "full_like",
    "eye", "tri", "tril", "triu", "loadtxt", "frombuffer", "fromfile",
}
_ARRAY_MODULES = {"np", "jnp", "numpy"}

# per-dispatch code paths: a jit (or builder) constructed here runs
# once per query/batch/ingest, not once per session
_DISPATCH_RE = re.compile(
    r"^_?(query|pump|drain|dispatch|ingest|serve|run|host_compute"
    r"|observe|check|resolve|submit|probe)"
)
# runner/probe builders: constructing a jit here is the point — the
# CALLER is responsible for routing through the cache (checked by the
# builder-call-site half of R2)
_BUILDER_RE = re.compile(r"^_?(make|compile|build)")
# the call-site half matches only the library's private runner-builder
# naming (a public Fragment.build() is a graph build, not a compile)
_BUILDER_CALL_RE = re.compile(r"^_(make|compile)_")

_FRAGISH_PARAM = re.compile(r"^(frag|fragment|dev)$|^frag_|_frag$")


class _Scope:
    def __init__(self, node, name: str, parent: Optional["_Scope"],
                 kind: str):
        self.node = node
        self.name = name
        self.parent = parent
        self.kind = kind  # module | class | function
        self.children: List[_Scope] = []
        self.params: Set[str] = set()
        self.assigned: Dict[str, str] = {}   # name -> arrayish|other
        self.assign_values: Dict[str, ast.AST] = {}
        self.cache_stored: Set[str] = set()  # names stored via x[...] = v
        self.calls: List[ast.Call] = []
        self.traced = False
        if parent is not None:
            parent.children.append(self)

    @property
    def qualname(self) -> str:
        parts = []
        s = self
        while s is not None and s.kind != "module":
            parts.append(s.name)
            s = s.parent
        return ".".join(reversed(parts)) or "<module>"

    def fn_chain(self) -> List["_Scope"]:
        """This scope and its enclosing FUNCTION scopes, innermost
        first (classes/module excluded)."""
        out, s = [], self
        while s is not None:
            if s.kind == "function":
                out.append(s)
            s = s.parent
        return out

    def binding_scope(self, name: str) -> Optional["_Scope"]:
        s = self.parent
        while s is not None:
            if s.kind == "function" and (
                name in s.params or name in s.assigned
            ):
                return s
            if s.kind == "module" and name in s.assigned:
                return s
            s = s.parent
        return None


def _callee_base(func) -> Optional[str]:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _root_name(node) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _shallow(node):
    """Child nodes of `node` without descending into nested function /
    lambda / class scopes (each nested scope is analyzed on its own)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(n))


def _classify_value(scope: _Scope, value) -> str:
    """'arrayish' when the RHS plausibly builds a device/host array
    the tracer would bake as a constant."""
    if isinstance(value, ast.Call):
        f = value.func
        if (
            isinstance(f, ast.Attribute)
            and f.attr in _ARRAY_FNS
            and _root_name(f) in _ARRAY_MODULES
        ):
            return "arrayish"
    if isinstance(value, ast.Attribute) and value.attr == "dev":
        return "arrayish"
    if (
        isinstance(value, ast.Name)
        and scope.assigned.get(value.id) == "arrayish"
    ):
        return "arrayish"
    return "other"


def _collect_params(node) -> Set[str]:
    a = node.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return set(names)


def _build_scopes(tree: ast.Module) -> _Scope:
    module = _Scope(tree, "<module>", None, "module")

    def build(node, scope: _Scope):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                s = _Scope(child, child.name, scope, "function")
                s.params = _collect_params(child)
                _scan_body(s)
                build(child, s)
            elif isinstance(child, ast.Lambda):
                s = _Scope(child, "<lambda>", scope, "function")
                s.params = _collect_params(child)
                _scan_body(s)
                build(child, s)
            elif isinstance(child, ast.ClassDef):
                s = _Scope(child, child.name, scope, "class")
                build(child, s)
            else:
                build(child, scope)

    def _scan_body(scope: _Scope):
        node = scope.node
        for n in _shallow(node):
            if isinstance(n, ast.Assign):
                kind = _classify_value(scope, n.value)
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        scope.assigned[t.id] = kind
                        scope.assign_values[t.id] = n.value
                    elif isinstance(t, ast.Subscript):
                        for sub in ast.walk(n.value):
                            if isinstance(sub, ast.Name):
                                scope.cache_stored.add(sub.id)
                    elif isinstance(t, (ast.Tuple, ast.List)):
                        for el in t.elts:
                            if isinstance(el, ast.Name):
                                scope.assigned.setdefault(el.id, "other")
            elif isinstance(n, ast.AnnAssign):
                if isinstance(n.target, ast.Name):
                    scope.assigned[n.target.id] = (
                        _classify_value(scope, n.value)
                        if n.value is not None else "other"
                    )
            elif isinstance(n, (ast.For, ast.AsyncFor)):
                for el in ast.walk(n.target):
                    if isinstance(el, ast.Name):
                        scope.assigned.setdefault(el.id, "other")
            elif isinstance(n, (ast.Import, ast.ImportFrom)):
                for alias in n.names:
                    scope.assigned.setdefault(
                        (alias.asname or alias.name).split(".")[0],
                        "other",
                    )
            elif isinstance(n, ast.withitem) and n.optional_vars:
                for el in ast.walk(n.optional_vars):
                    if isinstance(el, ast.Name):
                        scope.assigned.setdefault(el.id, "other")
            elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                scope.assigned.setdefault(n.name, "other")
            elif isinstance(n, ast.Call):
                scope.calls.append(n)

    # module-level assigns/imports/calls
    _scan_body(module)
    build(tree, module)
    return module


def _all_scopes(scope: _Scope):
    yield scope
    for c in scope.children:
        yield from _all_scopes(c)


def _mark_traced(module: _Scope) -> None:
    # decorator-traced functions
    for s in _all_scopes(module):
        node = s.node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                for n in ast.walk(dec):
                    name = (
                        n.attr if isinstance(n, ast.Attribute)
                        else n.id if isinstance(n, ast.Name) else None
                    )
                    if name in ("jit", "pallas_call"):
                        s.traced = True

    # functions passed (possibly through partial) to a trace wrapper
    def resolve(scope: _Scope, name: str) -> Optional[_Scope]:
        s = scope
        while s is not None:
            for c in s.children:
                if c.kind == "function" and c.name == name:
                    return c
            s = s.parent
        return None

    def mark_arg(scope: _Scope, arg) -> None:
        if isinstance(arg, ast.Name):
            target = resolve(scope, arg.id)
            if target is not None:
                target.traced = True
        elif isinstance(arg, ast.Lambda):
            for c in scope.children:
                if c.node is arg:
                    c.traced = True
        elif (
            isinstance(arg, ast.Call)
            and _callee_base(arg.func) == "partial"
            and arg.args
        ):
            mark_arg(scope, arg.args[0])

    for s in _all_scopes(module):
        for call in s.calls:
            if _callee_base(call.func) in _TRACE_WRAPPERS:
                for arg in call.args:
                    mark_arg(s, arg)

    # everything nested inside a traced function is traced
    def propagate(s: _Scope, inherited: bool):
        s.traced = s.traced or inherited
        for c in s.children:
            propagate(c, s.traced if s.kind == "function" else inherited)

    propagate(module, False)


# ---------------------------------------------------------------------------
# R1 — baked constants
# ---------------------------------------------------------------------------


def _check_r1(module: _Scope, path: str, findings: List[Finding]) -> None:
    """R1 baked-constant.  Historical bug: PR 3's guard probe closed
    over `frag.dev`, baking MB-scale fragment CSR arrays into the
    probe executable as XLA literal constants; the fix (dev as a jit
    ARGUMENT) is the pattern this rule enforces everywhere a traced
    body touches an np/jnp array or a frag/.dev attribute."""
    for s in _all_scopes(module):
        if not (s.kind == "function" and s.traced):
            continue
        seen: Set[str] = set()
        for n in _shallow(s.node):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load):
                name = n.id
                if (
                    name in seen or name in s.params
                    or name in s.assigned
                ):
                    continue
                b = s.binding_scope(name)
                if b is None or b.traced:
                    continue
                arrayish = b.assigned.get(name) == "arrayish"
                fragish = (
                    b.kind == "function" and name in b.params
                    and _FRAGISH_PARAM.match(name)
                )
                if arrayish or fragish:
                    seen.add(name)
                    findings.append(Finding(
                        "R1", path, n.lineno, s.qualname,
                        f"traced body captures {name!r} from the "
                        f"enclosing (untraced) scope "
                        f"{b.qualname!r}; pass it as a parameter or "
                        "XLA bakes it in as a literal constant",
                    ))
            elif (
                isinstance(n, ast.Attribute)
                and isinstance(n.ctx, ast.Load)
                and n.attr in ("dev", "fragment")
            ):
                root = _root_name(n)
                if root is None:
                    continue
                if root == "self":
                    key = f"self.{n.attr}"
                    if key not in seen:
                        seen.add(key)
                        findings.append(Finding(
                            "R1", path, n.lineno, s.qualname,
                            f"traced body reads {key} — fragment "
                            "arrays must ride as jit parameters, not "
                            "closure state",
                        ))
                elif root not in s.params and root not in s.assigned:
                    b = s.binding_scope(root)
                    if b is not None and not b.traced:
                        key = f"{root}.{n.attr}"
                        if key not in seen:
                            seen.add(key)
                            findings.append(Finding(
                                "R1", path, n.lineno, s.qualname,
                                f"traced body reads {key} captured "
                                f"from {b.qualname!r}; pass the "
                                "device fragment as a parameter",
                            ))


# ---------------------------------------------------------------------------
# R2 — per-dispatch jit / builder construction
# ---------------------------------------------------------------------------


def _is_cache_stored(call: ast.Call, scope: _Scope,
                     parents: Dict) -> bool:
    """True when the jit result is stored into a subscripted cache
    (`per_frag[cap] = fn` / `cache[key] = (probe, ...)`) within the
    same function — the models' per-fragment memo pattern."""
    n = call
    while n is not None and n is not scope.node:
        p = parents.get(n)
        if isinstance(p, ast.Assign) and n is p.value:
            for t in p.targets:
                if isinstance(t, ast.Subscript):
                    return True
                if isinstance(t, ast.Name):
                    return t.id in scope.cache_stored
            return False
        n = p
    return False


def _check_r2(module: _Scope, path: str, parents: Dict,
              findings: List[Finding]) -> None:
    """R2 uncached-jit.  Historical bug: PR 6's guarded serve path
    minted a fresh `jax.jit` wrapper around the batched PEval on every
    dispatch — steady guarded streams re-traced and re-compiled every
    batch, invisible to the zero-recompile counters (jit caches by
    wrapper identity, and the wrapper was new each time).  Two halves:
    a `jax.jit` call inside a per-dispatch function (unless its result
    lands in a subscripted cache), and a `_make_*`/`_compile_*`
    builder invoked from a per-dispatch function instead of through
    `_cached_runner`."""
    for s in _all_scopes(module):
        if s.kind != "function" or s.traced:
            continue
        chain = s.fn_chain()
        names = [f.name for f in chain]
        dispatchy = any(_DISPATCH_RE.match(n) for n in names)
        buildery = any(_BUILDER_RE.match(n) for n in names)
        for call in s.calls:
            base = _callee_base(call.func)
            if base == "jit":
                if buildery or not dispatchy:
                    continue
                if _is_cache_stored(call, s, parents):
                    continue
                findings.append(Finding(
                    "R2", path, call.lineno, s.qualname,
                    "jax.jit constructed on a per-dispatch path — a "
                    "fresh wrapper retraces and recompiles every "
                    "query; build it once behind the runner cache",
                ))
            elif (
                base is not None
                and _BUILDER_CALL_RE.match(base)
                and isinstance(call.func, ast.Attribute)
                and dispatchy
                and not buildery
                and not isinstance(s.node, ast.Lambda)
            ):
                findings.append(Finding(
                    "R2", path, call.lineno, s.qualname,
                    f"runner builder {base!r} invoked per dispatch; "
                    "route it through _cached_runner so repeated "
                    "queries reuse the compile",
                ))


# ---------------------------------------------------------------------------
# R3 — cache-key completeness
# ---------------------------------------------------------------------------


def _check_r3(module: _Scope, path: str, findings: List[Finding]) -> None:
    """R3 cache-key-field.  Historical bug: the fused-runner cache key
    originally omitted `max_rounds`, so a second query with a
    different round limit silently reused the first compile's baked
    while_loop bound (regression-pinned in PR 6,
    tests/test_worker.py::test_runner_cache_keys_max_rounds).  Every
    parameter of a function that calls `_cached_runner(key, ...)`
    must appear somewhere in the key expression."""
    for s in _all_scopes(module):
        if s.kind != "function":
            continue
        for call in s.calls:
            if _callee_base(call.func) != "_cached_runner":
                continue
            if not call.args:
                continue
            key_expr = call.args[0]
            if isinstance(key_expr, ast.Name):
                key_expr = s.assign_values.get(key_expr.id, key_expr)
            key_names = {
                n.id for n in ast.walk(key_expr)
                if isinstance(n, ast.Name)
            }
            for p in sorted(s.params - {"self", "cls"}):
                if p not in key_names:
                    findings.append(Finding(
                        "R3", path, call.lineno, s.qualname,
                        f"builder argument {p!r} is read by "
                        f"{s.name!r} but missing from its "
                        "_cached_runner key — two queries differing "
                        "only in it would share one compile",
                    ))


# ---------------------------------------------------------------------------
# R4 — query-path parity (stale dyn view + guard resolution)
# ---------------------------------------------------------------------------


def _method_facts(cls_node: ast.ClassDef):
    facts = {}
    for item in cls_node.body:
        if not isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        self_calls: Set[str] = set()
        marks: Set[str] = set()
        for n in ast.walk(item):
            if not isinstance(n, ast.Call):
                continue
            f = n.func
            if isinstance(f, ast.Attribute):
                if (
                    isinstance(f.value, ast.Name)
                    and f.value.id == "self"
                ):
                    self_calls.add(f.attr)
                    if f.attr in ("_check_dyn_view", "_ensure_dyn_view"):
                        marks.add("dyn_view")
                if (
                    f.attr == "resolve"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "GuardConfig"
                ):
                    marks.add("guard_resolve")
        facts[item.name] = (item.lineno, self_calls, marks)
    return facts


def _reaches(facts, start: str, mark: str) -> bool:
    seen: Set[str] = set()
    stack = [start]
    while stack:
        m = stack.pop()
        if m in seen or m not in facts:
            continue
        seen.add(m)
        _, calls, marks = facts[m]
        if mark in marks:
            return True
        stack.extend(calls)
    return False


def _check_r4(module: _Scope, path: str, findings: List[Finding]) -> None:
    """R4 dyn-view-parity.  Historical bug (PR 7, found post-hoc in
    review): GUARDED `query_batch` ran the stale-view check only
    AFTER the guard routing, and `query_stepwise` (the public
    profiling surface) skipped `_check_dyn_view` entirely — both
    silently computed on the pre-delta base graph while delta edges
    sat staged in the overlay.  Every public `query*` entrypoint of a
    class that defines `_check_dyn_view` must (transitively, through
    self-calls) reach both the stale-view check and
    `GuardConfig.resolve`; a serving class that defines
    `_ensure_dyn_view` must reach it from its `_dispatch` callback."""
    for s in _all_scopes(module):
        if s.kind != "class" or not isinstance(s.node, ast.ClassDef):
            continue
        facts = _method_facts(s.node)
        if "_check_dyn_view" in facts:
            for name, (lineno, _, _) in sorted(facts.items()):
                if not name.startswith("query"):
                    continue
                if not _reaches(facts, name, "dyn_view"):
                    findings.append(Finding(
                        "R4", path, lineno, f"{s.name}.{name}",
                        "public query entrypoint never reaches "
                        "_check_dyn_view — it would silently compute "
                        "on a stale dyn view",
                    ))
                if not _reaches(facts, name, "guard_resolve"):
                    findings.append(Finding(
                        "R4", path, lineno, f"{s.name}.{name}",
                        "public query entrypoint never resolves the "
                        "guard config (GuardConfig.resolve) — "
                        "env-armed guards would be silently ignored",
                    ))
        if "_ensure_dyn_view" in facts and "_dispatch" in facts:
            lineno = facts["_dispatch"][0]
            if not _reaches(facts, "_dispatch", "dyn_view"):
                findings.append(Finding(
                    "R4", path, lineno, f"{s.name}._dispatch",
                    "dispatch callback never reaches "
                    "_ensure_dyn_view — uncontracted apps would read "
                    "a stale dyn view",
                ))


# ---------------------------------------------------------------------------
# R5 — eager logging + bool-in-numeric-schema
# ---------------------------------------------------------------------------


def _eager_msg(node) -> bool:
    if isinstance(node, ast.JoinedStr):
        return True
    if isinstance(node, ast.BinOp) and isinstance(
        node.op, (ast.Mod, ast.Add)
    ):
        # ANY + or % in the message argument builds the string per
        # call — including "round " + str(r), which is not literal
        # concatenation and pays str() + allocation at disabled levels
        return True
    if isinstance(node, ast.Call):
        return (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "format"
        )
    return False


def _check_r5(module: _Scope, path: str,
              findings: List[Finding]) -> None:
    """R5 eager-log / bool-in-numeric-schema.  Historical bugs (both
    PR 5): per-round f-strings in worker vlogs were formatted and
    then DROPPED at disabled levels — a measurable per-superstep cost
    the lazy `%`-args form avoids (utils/logging.py); and the bench
    schema checker accepted `True` in numeric fields because bool is
    an int subclass, silently typing a whole BENCH column wrong."""
    for s in _all_scopes(module):
        if s.kind == "class":
            continue
        for call in s.calls:
            base = _callee_base(call.func)
            if base == "vlog" and len(call.args) >= 2:
                if _eager_msg(call.args[1]):
                    findings.append(Finding(
                        "R5", path, call.lineno, s.qualname,
                        "vlog message is formatted eagerly — pass "
                        "printf-style args so disabled levels pay "
                        "one int compare, not the formatting",
                    ))

    # bool-in-numeric-schema: validator functions using
    # isinstance(x, int/(int,float)) without any bool rejection
    module_tuples = {
        name: val for name, val in module.assign_values.items()
        if isinstance(val, ast.Tuple)
    }

    def numeric_classinfo(node) -> bool:
        if isinstance(node, ast.Name):
            if node.id in ("int", "float"):
                return True
            t = module_tuples.get(node.id)
            return t is not None and numeric_classinfo(t)
        if isinstance(node, ast.Tuple):
            return any(numeric_classinfo(e) for e in node.elts)
        return False

    for s in _all_scopes(module):
        if s.kind != "function":
            continue
        if not re.search(r"valid|check|schema", s.name):
            continue
        has_bool_guard = any(
            isinstance(n, ast.Name) and n.id == "bool"
            for n in ast.walk(s.node)
        )
        if has_bool_guard:
            continue
        for n in ast.walk(s.node):
            if (
                isinstance(n, ast.Call)
                and _callee_base(n.func) == "isinstance"
                and len(n.args) == 2
                and numeric_classinfo(n.args[1])
            ):
                findings.append(Finding(
                    "R5", path, n.lineno, s.qualname,
                    "numeric schema check accepts bool — bool is an "
                    "int subclass; reject isinstance(x, bool) "
                    "explicitly",
                ))


# ---------------------------------------------------------------------------
# R7 — host syncs on the async pump's dispatch stage
# ---------------------------------------------------------------------------

_R7_PATH_RE = re.compile(r"(^|/)serve/pipeline\.py$")
_R7_DISPATCH_RE = re.compile(r"^_?(dispatch|fill)")


def _pump_harvest_contract():
    """The audited harvest contract: the pump module's own declaration
    of which methods may force a host sync.  Imported from the runtime
    module so the lint judges fixtures and the shipped tree against one
    set."""
    try:
        from libgrape_lite_tpu.serve.pipeline import PUMP_HARVEST_SYNCS
    except Exception:  # pragma: no cover — partial checkouts
        return frozenset()
    return frozenset(PUMP_HARVEST_SYNCS)


def _r7_sync_forcer(call: ast.Call) -> Optional[str]:
    """A human-readable tag when `call` forces a host sync, else None:
    block_until_ready / device_get, np/jnp.asarray (materialises the
    device buffer), .item()/.tolist(), and the builtins int()/float()
    on a non-literal argument (converting a device scalar blocks on
    it)."""
    base = _callee_base(call.func)
    if base in ("block_until_ready", "device_get"):
        return f"{base}()"
    if (
        base == "asarray"
        and isinstance(call.func, ast.Attribute)
        and _root_name(call.func) in _ARRAY_MODULES
    ):
        return "asarray() (materialises the device buffer)"
    if (
        isinstance(call.func, ast.Name)
        and base in ("int", "float")
        and call.args
        and not isinstance(call.args[0], ast.Constant)
    ):
        return f"{base}() on a non-literal value"
    if base in ("item", "tolist") and isinstance(call.func, ast.Attribute):
        return f".{base}()"
    return None


def _check_r7(module: _Scope, path: str, findings: List[Finding]) -> None:
    """R7 sync-in-pump.  The async serve pump's dispatch stage
    (serve/pipeline.py `_fill*`/`_dispatch*` self-call chains) exists
    to keep a window of batches in flight; a single host-sync forcer
    on that path silently re-serialises the whole window — the exact
    defect class the pump replaced (the synchronous loop blocked
    pulling every lane's result before the next batch could
    dispatch).  The harvest stage is WHERE syncs belong, and the pump
    module names its harvest-side methods in `PUMP_HARVEST_SYNCS`;
    this rule walks every self-call chain rooted at a dispatch-stage
    method, stops at contract names, and flags any sync forcer it
    reaches.  Nested functions are skipped: a deferred thunk built at
    dispatch time runs at harvest time.  Path-scoped to
    serve/pipeline.py — the synchronous session/queue loop is ALLOWED
    to sync; only the pump's dispatch stage carries the contract."""
    if not _R7_PATH_RE.search(path):
        return
    contract = _pump_harvest_contract()

    def scan(fs: _Scope, owner: str) -> None:
        for n in _shallow(fs.node):
            if isinstance(n, ast.Call):
                what = _r7_sync_forcer(n)
                if what is not None:
                    findings.append(Finding(
                        "R7", path, n.lineno, owner,
                        f"{what} reached from the pump's dispatch "
                        "stage outside the audited harvest contract "
                        "(serve/pipeline.PUMP_HARVEST_SYNCS) — one "
                        "stray sync re-serialises the dispatch "
                        "window; move it to the harvest stage or "
                        "audit and name the method in the contract",
                    ))

    for s in _all_scopes(module):
        if s.kind == "class" and isinstance(s.node, ast.ClassDef):
            facts = _method_facts(s.node)
            roots = [
                m for m in facts
                if _R7_DISPATCH_RE.match(m) and m not in contract
            ]
            if not roots:
                continue
            seen: Set[str] = set()
            stack = list(roots)
            while stack:
                m = stack.pop()
                if m in seen or m in contract or m not in facts:
                    continue
                seen.add(m)
                _, calls, _ = facts[m]
                stack.extend(c for c in calls if c not in contract)
            scopes = {
                c.name: c for c in s.children if c.kind == "function"
            }
            for name in sorted(seen):
                fs = scopes.get(name)
                if fs is not None:
                    scan(fs, f"{s.name}.{name}")
        elif (
            s.kind == "function"
            and s.parent is not None
            and s.parent.kind == "module"
            and _R7_DISPATCH_RE.match(s.name)
            and s.name not in contract
        ):
            scan(s, s.qualname)


# ---------------------------------------------------------------------------
# R8 — module-level *_STATS surfaces outside the stats federation
# ---------------------------------------------------------------------------

_R8_NAME_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*_STATS$")
_R8_FED_MODULE = "libgrape_lite_tpu.obs.federation"
_R8_OBS_MODULE = "libgrape_lite_tpu.obs"


def _r8_federation_names(tree: ast.Module):
    """Names under which this module can reach the federation:
    (module aliases of obs.federation / obs, direct `register` names,
    direct `FederatedStats` constructor names).  Function-level lazy
    imports count — registering inside an init helper is still
    registering."""
    mod_aliases: Set[str] = set()
    reg_names: Set[str] = set()
    ctor_names: Set[str] = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.ImportFrom):
            if n.module == _R8_FED_MODULE:
                for a in n.names:
                    bound = a.asname or a.name
                    if a.name == "register":
                        reg_names.add(bound)
                    elif a.name == "FederatedStats":
                        ctor_names.add(bound)
            elif n.module == _R8_OBS_MODULE:
                for a in n.names:
                    bound = a.asname or a.name
                    if a.name == "federation":
                        mod_aliases.add(bound)
                    elif a.name == "FederatedStats":
                        ctor_names.add(bound)
        elif isinstance(n, ast.Import):
            for a in n.names:
                if a.name == _R8_FED_MODULE:
                    mod_aliases.add(
                        a.asname or _R8_FED_MODULE.split(".")[0]
                    )
    return mod_aliases, reg_names, ctor_names


def _check_r8(module: _Scope, path: str,
              findings: List[Finding]) -> None:
    """R8 unfederated-stats.  A module-level ``*_STATS`` assignment
    declares an operational ledger; the stats federation
    (obs/federation.py) is THE registry every such surface must join
    so one ``snapshot()`` — and therefore the live exporter and every
    postmortem bundle — sees all of them.  A surface passes when its
    value is constructed as ``FederatedStats(...)`` (self-registering)
    or when the module calls ``federation.register(...)`` anywhere
    (lazy/function-level registration counts).  obs/federation.py
    itself is exempt: the registry cannot register into itself."""
    if path.endswith("obs/federation.py"):
        return
    tree = module.node
    mod_aliases, reg_names, ctor_names = _r8_federation_names(tree)

    def registers(call: ast.Call) -> bool:
        f = call.func
        if isinstance(f, ast.Name) and f.id in reg_names:
            return True
        return (
            isinstance(f, ast.Attribute)
            and f.attr == "register"
            and _root_name(f) in mod_aliases
        )

    if any(
        isinstance(n, ast.Call) and registers(n)
        for n in ast.walk(tree)
    ):
        return
    for stmt in tree.body:
        targets = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
            value = stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets = [stmt.target]
            value = stmt.value
        else:
            continue
        names = [
            t.id for t in targets
            if isinstance(t, ast.Name) and _R8_NAME_RE.match(t.id)
        ]
        if not names:
            continue
        if (
            isinstance(value, ast.Call)
            and _callee_base(value.func) in ctor_names
        ):
            continue
        for name in names:
            findings.append(Finding(
                "R8", path, stmt.lineno, name,
                f"module-level stats surface {name} is not in the "
                "stats federation — construct it as "
                "obs.federation.FederatedStats or call "
                "federation.register(namespace, snapshot, reset) in "
                "this module, so federation.snapshot(), the live "
                "/metrics exporter, and postmortem bundles can see it",
            ))


# ---------------------------------------------------------------------------
# R9 cache-key-completeness
# ---------------------------------------------------------------------------

#: the result-cache identity contract (autopilot/cache.py
#: CACHE_KEY_FIELDS) with the synonyms a call site may spell each
#: field with — "fence" is the router's graph-version fence, which
#: bare sessions carry as an ingest epoch and replicas as a version
_R9_KEY_FIELDS = (
    ("compat", ("compat",)),
    ("source", ("source",)),
    ("fence", ("fence", "epoch", "version")),
)
_R9_CACHE_METHODS = {"lookup", "store"}


def _r9_idents(node: ast.AST) -> Set[str]:
    """Every identifier-ish token an argument expression names: Name
    ids, Attribute attrs, and string constants — the surface a key
    field could be spelled on."""
    out: Set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            out.add(n.id)
        elif isinstance(n, ast.Attribute):
            out.add(n.attr)
        elif isinstance(n, ast.Constant) and isinstance(n.value, str):
            out.add(n.value)
    return out


def _check_r9(module: _Scope, path: str,
              findings: List[Finding]) -> None:
    """R9 cache-key-completeness.  A `.lookup(...)`/`.store(...)`
    call whose receiver chain names a cache (``self._cache``,
    ``queue.result_cache``, a bare ``cache``) is a result-cache call
    site; its arguments must name EVERY field of the result identity
    — the compat key, the lane source, and the fence epoch
    (autopilot/cache.py CACHE_KEY_FIELDS) — or two structurally
    different queries / two graph versions could share one cached
    answer.  autopilot/cache.py itself is exempt (it IS the keyed
    surface; its internals take the fields apart)."""
    if path.endswith("autopilot/cache.py"):
        return
    for n in ast.walk(module.node):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if not (isinstance(f, ast.Attribute)
                and f.attr in _R9_CACHE_METHODS):
            continue
        chain = []
        v = f.value
        while isinstance(v, ast.Attribute):
            chain.append(v.attr)
            v = v.value
        if isinstance(v, ast.Name):
            chain.append(v.id)
        if not any("cache" in part.lower() for part in chain):
            continue
        idents = set()
        for a in n.args:
            idents |= _r9_idents(a)
        for kw in n.keywords:
            if kw.arg:
                idents.add(kw.arg)
            idents |= _r9_idents(kw.value)
        lowered = {i.lower() for i in idents}
        missing = [
            field for field, synonyms in _R9_KEY_FIELDS
            if not any(s in tok for s in synonyms for tok in lowered)
        ]
        if missing:
            findings.append(Finding(
                "R9", path, n.lineno, f.attr,
                f"result-cache {f.attr}() does not name the full "
                f"result identity — missing {', '.join(missing)}: "
                "every lookup/store must carry every compat_key "
                "field plus the lane source and the fence epoch "
                "(autopilot/cache.py CACHE_KEY_FIELDS), or a stale "
                "or structurally different answer can be served as "
                "a hit",
            ))


#: module-level names that declare a pricing RATE.  Op-count
#: conventions (_ITEM_VPU, DEFAULT_OPS_PER_EDGE, stage heights) are
#: NOT rates — they must stay literal so the recount gates remain
#: independent of the planners they audit.
_R10_NAME_RE = re.compile(
    r"(_BPS|_HZ|_CYC_PER_ELEM|_PER_CYCLE|_ROWS_PER_CYCLE)$"
    r"|^_?GATHER_RATES$"
)


def _r10_literal_number(value: ast.AST) -> bool:
    """True when `value` is (or contains, for dict tables) a numeric
    literal — a profile-attribute read (`default_profile().hbm_bps`)
    is the sanctioned form and has no literal to flag."""
    if isinstance(value, ast.Constant):
        return isinstance(value.value, (int, float)) and not isinstance(
            value.value, bool
        )
    if isinstance(value, ast.BinOp):
        return (_r10_literal_number(value.left)
                and _r10_literal_number(value.right))
    if isinstance(value, ast.UnaryOp):
        return _r10_literal_number(value.operand)
    if isinstance(value, ast.Dict):
        return any(_r10_literal_number(v) for v in value.values)
    return False


def _check_r10(module: _Scope, path: str,
               findings: List[Finding]) -> None:
    """R10 pinned-rate-constant.  A module-level assignment whose name
    declares a pricing rate (``*_BPS``, ``*_HZ``, ``*_CYC_PER_ELEM``,
    ``*_PER_CYCLE``, a ``GATHER_RATES`` table) bound to a numeric
    LITERAL outside ops/calibration.py is a private rate copy: the
    calibration pass cannot fit it and the drift gate cannot see it.
    Reading the shared profile (``default_profile().hbm_bps``) passes
    — the name then tracks THE rate, pinned or fitted."""
    if path.endswith("ops/calibration.py"):
        return
    for stmt in module.node.body:
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        else:
            continue
        names = [
            t.id for t in targets
            if isinstance(t, ast.Name) and _R10_NAME_RE.search(t.id)
        ]
        if not names or not _r10_literal_number(value):
            continue
        for name in names:
            findings.append(Finding(
                "R10", path, stmt.lineno, name,
                f"pricing rate {name} is pinned as a numeric literal "
                "outside ops/calibration.py — a private copy the "
                "calibration fit cannot update and the drift gate "
                "cannot audit; read it from the shared RateProfile "
                "(ops/calibration.default_profile / active_profile) "
                "instead",
            ))


#: the sanctioned spellings of the SUMMA mesh axis names — the string
#: values of parallel/comm_spec.VC_ROW_AXIS / VC_COL_AXIS.  Inlined
#: (not imported) on purpose: the lint must keep flagging the raw
#: strings even if the runtime constants are renamed out from under
#: the literal copies it hunts.
_R11_AXIS_LITERALS = ("vcrow", "vccol")


def _check_r11(module: _Scope, path: str,
               findings: List[Finding]) -> None:
    """R11 raw-axis-name.  A models/ module that spells a SUMMA mesh
    axis name as a raw string literal ('vcrow'/'vccol') holds a
    private copy of the mesh contract: every pmin/psum/ppermute over
    the 2-D mesh is only correct because its axis name matches
    mesh2d()'s, and a renamed or extended mesh would miss the literal
    silently — wrong-axis collective, not an import error.  Importing
    VC_ROW_AXIS/VC_COL_AXIS from parallel/comm_spec.py is the
    sanctioned form (the defining module itself, and non-model layers
    like the worker/bench that never open a collective over the axis,
    are out of scope)."""
    if "/models/" not in "/" + path:
        return
    for n in ast.walk(module.node):
        if (
            isinstance(n, ast.Constant)
            and isinstance(n.value, str)
            and n.value in _R11_AXIS_LITERALS
        ):
            findings.append(Finding(
                "R11", path, n.lineno, "<module>",
                f"raw SUMMA axis name {n.value!r} in models/ — a "
                "private copy of the mesh contract; import "
                "VC_ROW_AXIS/VC_COL_AXIS from parallel/comm_spec.py "
                "so a mesh rename is a compile-time error instead of "
                "a wrong-axis collective",
            ))


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def lint_source(src: str, relpath: str) -> List[Finding]:
    """All R1-R11 findings (R6 is retired) for one module's source
    text."""
    relpath = relpath.replace(os.sep, "/")
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(
            "E0", relpath, e.lineno or 0, "<module>",
            f"syntax error: {e.msg}",
        )]
    module = _build_scopes(tree)
    _mark_traced(module)
    parents = {
        child: parent
        for parent in ast.walk(tree)
        for child in ast.iter_child_nodes(parent)
    }
    findings: List[Finding] = []
    _check_r1(module, relpath, findings)
    _check_r2(module, relpath, parents, findings)
    _check_r3(module, relpath, findings)
    _check_r4(module, relpath, findings)
    _check_r5(module, relpath, findings)
    _check_r7(module, relpath, findings)
    _check_r8(module, relpath, findings)
    _check_r9(module, relpath, findings)
    _check_r10(module, relpath, findings)
    _check_r11(module, relpath, findings)
    return findings


_SKIP_DIRS = {"__pycache__", "scratch", ".git", ".pytest_cache",
              "node_modules"}


def iter_py_files(path: str):
    if os.path.isfile(path):
        yield path
        return
    if not os.path.isdir(path):
        # a mistyped path must FAIL the gate, not lint zero files and
        # report clean (os.walk on a missing dir silently yields nothing)
        raise FileNotFoundError(f"lint path does not exist: {path!r}")
    for dirpath, dirnames, filenames in os.walk(path):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d not in _SKIP_DIRS]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def lint_paths(paths, root: Optional[str] = None) -> List[Finding]:
    """Findings over files/trees; paths in findings are relative to
    `root` (default: the repo root two levels above this package) so
    fingerprints stay stable regardless of invocation directory."""
    if root is None:
        root = repo_root()
    findings: List[Finding] = []
    for p in paths:
        for f in iter_py_files(p):
            rel = os.path.relpath(os.path.abspath(f), root)
            with open(f, encoding="utf-8") as fh:
                findings.extend(lint_source(fh.read(), rel))
    return findings


def repo_root() -> str:
    """The directory holding the libgrape_lite_tpu package."""
    return os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
