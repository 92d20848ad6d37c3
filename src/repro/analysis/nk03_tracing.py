"""NK03 — JAX tracing hygiene.

``jax.jit`` runs the Python body *once*, at trace time.  A
``time.perf_counter()`` or ``random.random()`` inside a jitted function
is baked into the compiled graph as a constant — timing exactly nothing
on every subsequent call; a ``float(x)``/``x.item()`` forces a host sync
that blocks the dispatch stream (and fails outright under tracing in
some paths).  These bugs don't crash: they produce plausible, wrong
numbers, which is the worst failure mode for a reproduction repo.

The rule finds jit roots —

* functions decorated ``@jax.jit`` or ``@functools.partial(jax.jit, ...)``,
* functions wrapped by a ``jax.jit(f)`` call expression,
* kernels passed (directly or via ``functools.partial(kernel, ...)``) as
  the first argument of ``pl.pallas_call``,

— then walks each root and, transitively (depth 2, resolved through
import aliases), every project-local function it calls, flagging:

* **impure calls**: ``time.*``, ``random.*``, ``np.random.*``, ``print``,
  ``open``, ``input`` — trace-time side effects frozen into the graph;
  and the program's own spans and counters (``repro.core.timing``:
  ``timing.span``, ``timing.count``, ...), which would record once, at
  trace time, and never on a call;
* **host coercions**: ``float(x)`` / ``int(x)`` on non-literal values and
  ``.item()`` — host syncs inside traced code;
* **non-static static_argnums/static_argnames**: the ``jax.jit`` call
  site must pass literal ints/strings (or tuples thereof); anything else
  is unhashable or varies at runtime and defeats the compile cache.

A deliberate trace-time constant (e.g. choosing interpret mode from
``jax.default_backend()``) is a legitimate pattern — annotate it
``# nk: allow[NK03]`` with a word of justification.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from repro.analysis.core import (Finding, Module, Project, Rule,
                                 dotted_name, import_aliases)

IMPURE_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.",
                   "os.urandom", "repro.core.timing.")
IMPURE_BARE = frozenset({"print", "open", "input"})
# environment queries: legal Python, but the answer is frozen at trace
# time — almost always a bug unless deliberately chosen per-backend
TRACE_ENV = frozenset({"jax.default_backend", "os.getenv", "os.environ.get"})
MAX_DEPTH = 2


def _is_jax_jit(name: Optional[str], aliases: Dict[str, str]) -> bool:
    if name is None:
        return False
    resolved = aliases.get(name, name)
    return resolved in ("jax.jit", "jit") or resolved.endswith(".jit")


def _is_pallas_call(name: Optional[str], aliases: Dict[str, str]) -> bool:
    if name is None:
        return False
    resolved = aliases.get(name.split(".")[0], name.split(".")[0])
    return name.endswith("pallas_call") or resolved.endswith("pallas_call")


def _partial_target(call: ast.Call) -> Tuple[Optional[str],
                                             List[ast.keyword]]:
    """``functools.partial(f, ...)`` -> (dotted name of f, partial kwargs)."""
    fn = dotted_name(call.func)
    if fn is not None and fn.split(".")[-1] == "partial" and call.args:
        return dotted_name(call.args[0]), list(call.keywords)
    return None, []


def _index_functions(project: Project) -> Dict[str, Tuple[Module,
                                                          ast.FunctionDef]]:
    """'<module dotted name>.<func>' -> (module, def), top level only."""
    out: Dict[str, Tuple[Module, ast.FunctionDef]] = {}
    for module in project.modules:
        for node in module.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out[f"{module.name}.{node.name}"] = (module, node)
    return out


class TracingHygieneRule(Rule):
    id = "NK03"
    title = "impure or host-sync code inside jitted functions"
    severity = "error"

    def run(self, project: Project) -> Iterator[Finding]:
        findings: List[Finding] = []
        funcs = _index_functions(project)
        roots: List[Tuple[Module, ast.FunctionDef]] = []

        for module in project.modules:
            aliases = import_aliases(module.tree)
            local = {n.name: n for n in module.tree.body
                     if isinstance(n, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))}

            def as_root(expr: ast.expr) -> Optional[ast.FunctionDef]:
                """Resolve a function-valued expression to a local def."""
                if isinstance(expr, ast.Name):
                    return local.get(expr.id)
                if isinstance(expr, ast.Call):
                    target, _ = _partial_target(expr)
                    if target is not None:
                        return local.get(target.split(".")[-1])
                return None

            for node in ast.walk(module.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for dec in node.decorator_list:
                        if isinstance(dec, ast.Call):
                            target, kws = _partial_target(dec)
                            if _is_jax_jit(target, aliases):
                                roots.append((module, node))
                                self._check_static_args(
                                    module, dec, kws, findings)
                            elif _is_jax_jit(dotted_name(dec.func), aliases):
                                roots.append((module, node))
                                self._check_static_args(
                                    module, dec, list(dec.keywords), findings)
                        elif _is_jax_jit(dotted_name(dec), aliases):
                            roots.append((module, node))
                elif isinstance(node, ast.Call):
                    name = dotted_name(node.func)
                    if _is_jax_jit(name, aliases) and node.args:
                        fn = as_root(node.args[0])
                        if fn is not None:
                            roots.append((module, fn))
                        self._check_static_args(module, node,
                                                list(node.keywords), findings)
                    elif _is_pallas_call(name, aliases) and node.args:
                        fn = as_root(node.args[0])
                        if fn is not None:
                            roots.append((module, fn))

        seen: Set[Tuple[str, int]] = set()
        for module, fn in roots:
            self._check_body(project, funcs, module, fn, 0, seen, findings)
        return iter(findings)

    # -- static_argnums / static_argnames -------------------------------

    def _check_static_args(self, module: Module, site: ast.Call,
                           keywords: List[ast.keyword],
                           findings: List[Finding]) -> None:
        for kw in keywords:
            if kw.arg not in ("static_argnums", "static_argnames"):
                continue
            want = int if kw.arg == "static_argnums" else str
            if not self._static_literal(kw.value, want):
                findings.append(module.finding(
                    self, site,
                    f"{kw.arg} must be a literal "
                    f"{'int' if want is int else 'str'} or tuple of them "
                    f"(hashable, trace-stable); got a computed or "
                    f"unhashable value"))

    @staticmethod
    def _static_literal(node: ast.expr, want: type) -> bool:
        if isinstance(node, ast.Constant):
            return isinstance(node.value, want)
        if isinstance(node, ast.Tuple):
            return all(isinstance(e, ast.Constant)
                       and isinstance(e.value, want) for e in node.elts)
        return False

    # -- body purity ----------------------------------------------------

    def _check_body(self, project: Project,
                    funcs: Dict[str, Tuple[Module, ast.FunctionDef]],
                    module: Module, fn: ast.FunctionDef, depth: int,
                    seen: Set[Tuple[str, int]],
                    findings: List[Finding]) -> None:
        key = (module.path, fn.lineno)
        if key in seen:
            return
        seen.add(key)
        aliases = import_aliases(module.tree)

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)

            # impure calls
            if name is not None:
                resolved = aliases.get(name.split(".")[0],
                                       name.split(".")[0])
                full = resolved if "." not in name else \
                    f"{resolved}.{name.split('.', 1)[1]}"
                if name in IMPURE_BARE:
                    findings.append(module.finding(
                        self, node,
                        f"{name}() inside a jitted function runs at trace "
                        f"time only (side effect frozen into the graph)"))
                    continue
                if any(full.startswith(p) or name.startswith(p)
                       for p in IMPURE_PREFIXES):
                    findings.append(module.finding(
                        self, node,
                        f"{name}() inside a jitted function executes once "
                        f"at trace time — the compiled graph sees a "
                        f"constant, not a fresh value"))
                    continue
                if full in TRACE_ENV or name in TRACE_ENV:
                    findings.append(module.finding(
                        self, node,
                        f"{name}() is evaluated once at trace time; if the "
                        f"per-backend constant is deliberate, annotate the "
                        f"site '# nk: allow[NK03]'"))
                    continue

            # host coercions
            if name in ("float", "int") and node.args and \
                    not isinstance(node.args[0], ast.Constant):
                findings.append(module.finding(
                    self, node,
                    f"{name}() on a traced value forces a host sync "
                    f"inside jit; keep it as an array or hoist the "
                    f"coercion outside the jitted function"))
                continue
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                findings.append(module.finding(
                    self, node,
                    ".item() inside a jitted function is a host sync; "
                    "return the array and coerce outside jit"))
                continue

            # transitive expansion through project-local calls
            if depth >= MAX_DEPTH or name is None:
                continue
            target = None
            if "." not in name:
                target = funcs.get(f"{module.name}.{name}")
            else:
                head, _, tail = name.partition(".")
                mod_target = aliases.get(head)
                if mod_target is not None and "." not in tail:
                    target = funcs.get(f"{mod_target}.{tail}")
            if target is not None:
                self._check_body(project, funcs, target[0], target[1],
                                 depth + 1, seen, findings)
