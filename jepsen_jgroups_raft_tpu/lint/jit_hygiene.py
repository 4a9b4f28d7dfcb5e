"""JIT / trace hygiene analyzer for the device hot path.

Two failure modes this repo has paid for (BASELINE.md rounds 2-4):

* A host sync (``np.asarray``, ``.item()``, ``int()`` on a traced value,
  ``block_until_ready``) inside a jitted body silently serializes a
  device→host round trip per launch.
* Recompile hazards (unhashable static args, mutable defaults, Python
  branching on tracers) turn the jit cache into a per-call recompile
  storm, or fail at trace time deep inside a batch run.

Rules
-----
``jit-host-sync``
    Host-forcing call inside a traced body: any ``np.*`` call whose
    argument derives from a traced value, ``.item()``,
    ``.block_until_ready()``, or ``int()/float()/bool()`` on a traced
    value. (``jnp.*`` is device-side and fine; ``x.shape``/``x.dtype``
    are static and break the taint.)
``jit-python-branch``
    ``if``/``while``/``assert`` whose test involves a traced value — a
    trace-time ConcretizationError at best, silently baked-in control
    flow at worst. Use ``lax.cond``/``jnp.where``.
``jit-recompile-hazard``
    Mutable default argument (list/dict/set) on a traced function — the
    default is part of the trace cache key, so it is either unhashable
    (TypeError at call time) or a shared-mutation recompile hazard.
``host-sync``
    Outside traced bodies, in a *launch function* (one that builds a
    kernel via ``jax.jit`` / a ``make_*``/``_build_*``/``*_kernel``
    factory and then calls it): ``np.asarray``/``np.array`` on a
    non-parameter value, ``.item()``, or ``block_until_ready``. These
    block the async dispatch pipeline, so every one must be an
    *intentional, annotated* hop: suppress with ``# lint:
    allow(host-sync)`` on the line (the pattern in
    checker/linearizable.py).

Traced bodies are found structurally: ``@jax.jit`` decorators, and local
function names flowing (through local assignments) into ``jax.jit``,
``jax.vmap``, ``shard_map``, ``pl.pallas_call``, or a ``lax`` control-flow
combinator (``scan``/``cond``/``while_loop``/``fori_loop``/``map``/
``switch``). The pragma is honored for ``host-sync`` only; the in-trace
rules are strict (an intentional sync inside a jitted body is a
contradiction).

Scan set (CLI): ``ops/``, ``checker/``, ``parallel/``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .base import Finding, SourceFile

#: (callee-name, positional indexes holding traced callables).
TRACE_WRAPPERS: Dict[str, Tuple[int, ...]] = {
    "jit": (0,),
    "vmap": (0,),
    "pmap": (0,),
    "shard_map": (0,),
    "pallas_call": (0,),
    "scan": (0,),
    "map": (0,),          # lax.map only (attribute call, see below)
    "while_loop": (0, 1),
    "fori_loop": (2,),
    "cond": (1, 2),
    "switch": (),         # branch list is rarely resolvable statically
    "checkify": (0,),
    "grad": (0,),
    "value_and_grad": (0,),
}

#: bare-name calls allowed to seed traces (plain `map` is a builtin).
BARE_WRAPPERS = {"jit", "shard_map", "pallas_call"}

SYNC_METHODS = {"item", "block_until_ready"}
HOST_CASTS = {"int", "float", "bool", "complex"}
TAINT_BREAKERS = {"shape", "dtype", "ndim", "size", "sharding"}

SCAN_PREFIXES = ("ops/", "checker/", "parallel/")


def applies_to(relpath: str) -> bool:
    rp = relpath.replace("\\", "/")
    rp = rp.split("jepsen_jgroups_raft_tpu/", 1)[-1]
    return rp.startswith(SCAN_PREFIXES)


def _callee_name(call: ast.Call) -> str:
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return ""


def _is_np_call(call: ast.Call) -> Optional[str]:
    """'asarray' etc. when the call is np.<fn>(...); None otherwise."""
    fn = call.func
    if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name) \
            and fn.value.id in ("np", "numpy"):
        return fn.attr
    return None


class _Scope:
    """One function (or module) body: local defs + assignment graph."""

    def __init__(self, node):
        self.node = node
        self.defs: Dict[str, ast.FunctionDef] = {}
        self.assigns: Dict[str, ast.expr] = {}
        body = node.body if hasattr(node, "body") else []
        for stmt in body:
            self._index(stmt)

    def _index(self, stmt):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.defs[stmt.name] = stmt
            return  # nested defs get their own scope
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            self.assigns[stmt.targets[0].id] = stmt.value
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.stmt):
                self._index(child)

    def resolve_def(self, name: str, depth: int = 0) -> \
            Optional[ast.FunctionDef]:
        """Follow `x = jax.vmap(y)`-style chains to a local def."""
        if depth > 8:
            return None
        if name in self.defs:
            return self.defs[name]
        expr = self.assigns.get(name)
        if isinstance(expr, ast.Name):
            return self.resolve_def(expr.id, depth + 1)
        if isinstance(expr, ast.Call):
            cname = _callee_name(expr)
            idxs = TRACE_WRAPPERS.get(cname)
            if idxs:
                for i in idxs:
                    if i < len(expr.args) and \
                            isinstance(expr.args[i], ast.Name):
                        d = self.resolve_def(expr.args[i].id, depth + 1)
                        if d is not None:
                            return d
        return None


def _decorated_jit(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = ""
        if isinstance(target, ast.Name):
            name = target.id
        elif isinstance(target, ast.Attribute):
            name = target.attr
        if name == "jit":
            return True
        if name == "partial" and isinstance(dec, ast.Call) and dec.args:
            inner = dec.args[0]
            iname = inner.attr if isinstance(inner, ast.Attribute) else (
                inner.id if isinstance(inner, ast.Name) else "")
            if iname == "jit":
                return True
    return False


def _collect_traced(tree: ast.Module) -> Set[ast.FunctionDef]:
    """Every function def that is traced by jax (see module docstring)."""
    traced: Set[ast.FunctionDef] = set()
    # index scopes: module + every function
    scopes = [_Scope(tree)]
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scopes.append(_Scope(node))
            if _decorated_jit(node):
                traced.add(node)
    for scope in scopes:
        for node in ast.walk(scope.node):
            if not isinstance(node, ast.Call):
                continue
            cname = _callee_name(node)
            idxs = TRACE_WRAPPERS.get(cname)
            if idxs is None:
                continue
            if isinstance(node.func, ast.Name) and \
                    cname not in BARE_WRAPPERS:
                continue  # bare `map(...)`/`scan(...)` is not jax's
            for i in idxs:
                if i < len(node.args) and isinstance(node.args[i], ast.Name):
                    d = scope.resolve_def(node.args[i].id)
                    if d is not None:
                        traced.add(d)
    return traced


# --------------------------------------------------------------- taint walk


class _TraceChecker(ast.NodeVisitor):
    """Flag host syncs / tracer branching inside one traced body."""

    def __init__(self, src: SourceFile, fn: ast.FunctionDef):
        self.src = src
        self.fn = fn
        self.findings: List[Finding] = []
        args = fn.args
        self.tainted: Set[str] = {
            a.arg for a in (args.posonlyargs + args.args + args.kwonlyargs)}
        if args.vararg:
            self.tainted.add(args.vararg.arg)

    # -- taint -------------------------------------------------------------

    def _expr_tainted(self, node: Optional[ast.expr]) -> bool:
        """Any tainted name used outside a .shape/.dtype/... chain?"""
        if node is None:
            return False
        tainted = self.tainted

        class V(ast.NodeVisitor):
            hot = False

            def visit_Attribute(self, a):  # noqa: N802
                if a.attr in TAINT_BREAKERS:
                    return  # static metadata: do not descend
                self.generic_visit(a)

            def visit_Name(self, n):  # noqa: N802
                if n.id in tainted:
                    self.hot = True

        v = V()
        v.visit(node)
        return v.hot

    def _taint_assign(self, node: ast.Assign):
        if self._expr_tainted(node.value):
            for tgt in node.targets:
                for sub in ast.walk(tgt):
                    if isinstance(sub, ast.Name):
                        self.tainted.add(sub.id)

    # -- visitors ----------------------------------------------------------

    def visit_FunctionDef(self, node):  # noqa: N802
        if node is self.fn:
            self.generic_visit(node)
        # nested defs are visited via their own _TraceChecker if traced

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node):  # noqa: N802
        self._taint_assign(node)
        self.generic_visit(node)

    def visit_AugAssign(self, node):  # noqa: N802
        if self._expr_tainted(node.value) and \
                isinstance(node.target, ast.Name):
            self.tainted.add(node.target.id)
        self.generic_visit(node)

    def visit_Call(self, node):  # noqa: N802
        np_fn = _is_np_call(node)
        if np_fn is not None and any(self._expr_tainted(a)
                                     for a in node.args):
            self.findings.append(Finding(
                self.src.path, node.lineno, "jit-host-sync",
                f"np.{np_fn}() on a traced value inside a jitted body — "
                "forces a device→host sync per launch; use jnp or move "
                "the conversion outside the trace"))
        cname = _callee_name(node)
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in SYNC_METHODS:
            self.findings.append(Finding(
                self.src.path, node.lineno, "jit-host-sync",
                f".{node.func.attr}() inside a jitted body — host sync"))
        if isinstance(node.func, ast.Name) and cname in HOST_CASTS and \
                any(self._expr_tainted(a) for a in node.args):
            self.findings.append(Finding(
                self.src.path, node.lineno, "jit-host-sync",
                f"{cname}() on a traced value inside a jitted body — "
                "concretizes the tracer (host sync / trace error)"))
        self.generic_visit(node)

    def _branch(self, node, kind: str):
        if self._expr_tainted(node.test):
            self.findings.append(Finding(
                self.src.path, node.lineno, "jit-python-branch",
                f"Python `{kind}` on a traced value inside a jitted body "
                "— use lax.cond/jnp.where (trace-time concretization)"))

    def visit_If(self, node):  # noqa: N802
        self._branch(node, "if")
        self.generic_visit(node)

    def visit_While(self, node):  # noqa: N802
        self._branch(node, "while")
        self.generic_visit(node)

    def visit_Assert(self, node):  # noqa: N802
        self._branch(node, "assert")
        self.generic_visit(node)


def _check_defaults(src: SourceFile, fn: ast.FunctionDef) -> List[Finding]:
    out = []
    defaults = list(fn.args.defaults) + [
        d for d in fn.args.kw_defaults if d is not None]
    for d in defaults:
        if isinstance(d, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                          ast.DictComp, ast.SetComp)):
            out.append(Finding(
                src.path, d.lineno, "jit-recompile-hazard",
                f"mutable default argument on traced `{fn.name}` — "
                "unhashable as a static arg and a recompile/aliasing "
                "hazard; use None or a tuple"))
    return out


# ------------------------------------------------------------ launch sites

_FACTORY_HINTS = ("kernel", "checker")


def _is_factory_call(expr: ast.expr) -> bool:
    if not isinstance(expr, ast.Call):
        return False
    name = _callee_name(expr)
    if name == "jit":
        return True
    return (name.startswith(("make_", "_build_")) or
            name.endswith(_FACTORY_HINTS)) and any(
        h in name for h in _FACTORY_HINTS + ("call",))


def _launch_findings(src: SourceFile, fn: ast.FunctionDef,
                     traced: Set[ast.FunctionDef]) -> List[Finding]:
    """host-sync rule for non-traced launch functions (pragma-suppressible).

    Nested defs are separate scopes — only this function's own statements
    count (a deferred finalizer closure syncs by design).
    """
    own_nodes: List[ast.AST] = []
    stack: List[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue  # nested function: its own scope
        own_nodes.append(node)
        stack.extend(ast.iter_child_nodes(node))

    kernels: Set[str] = set()
    for node in own_nodes:
        if isinstance(node, ast.Assign) and _is_factory_call(node.value):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    kernels.add(tgt.id)
    launches = any(
        isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
        and node.func.id in kernels for node in own_nodes)
    if not launches:
        return []

    params = {a.arg for a in fn.args.posonlyargs + fn.args.args +
              fn.args.kwonlyargs}
    out: List[Finding] = []
    for node in own_nodes:
        if not isinstance(node, ast.Call):
            continue
        np_fn = _is_np_call(node)
        if np_fn in ("asarray", "array") and node.args and not (
                isinstance(node.args[0], ast.Name) and
                node.args[0].id in params):
            out.append(Finding(
                src.path, node.lineno, "host-sync",
                f"np.{np_fn}() in kernel-launch function "
                f"`{fn.name}` blocks async dispatch (device→host); "
                "if intentional, annotate with "
                "`# lint: allow(host-sync)`"))
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in SYNC_METHODS:
            out.append(Finding(
                src.path, node.lineno, "host-sync",
                f".{node.func.attr}() in kernel-launch function "
                f"`{fn.name}` — annotate if intentional"))
    return out


def analyze_source(src: SourceFile) -> List[Finding]:
    try:
        tree = ast.parse(src.text)
    except SyntaxError as e:
        return [Finding(src.path, e.lineno or 1, "parse-error", str(e))]
    traced = _collect_traced(tree)
    findings: List[Finding] = []
    for fn in traced:
        checker = _TraceChecker(src, fn)
        checker.visit(fn)
        findings.extend(checker.findings)       # strict: no pragma
        findings.extend(_check_defaults(src, fn))
    host: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                node not in traced:
            host.extend(_launch_findings(src, node, traced))
    findings.extend(f for f in host if not src.allowed(f.line, f.rule))
    return findings


def analyze_file(path) -> List[Finding]:
    return analyze_source(SourceFile.load(path))
