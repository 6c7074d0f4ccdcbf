"""replint — AST trace-safety lint for the JAX/Pallas substrate (Level 2).

A static pass over Python source that flags the pitfalls which only
surface at trace/serving time in this codebase: Python control flow on
traced values, host synchronisations inside device code, int literals
past the int32 lattice, shapes derived from traced counts, and
``shard_map`` calls that never took an explicit ``check_vma`` decision.

The lint is *scoped*: most rules only apply inside functions the
analyzer believes are traced.  A function is traced when any of
these hold, closed under the intra-module call graph:

* it is decorated with a tracer (``jax.jit``, ``vmap``, ``pmap``,
  ``shard_map``, ``pallas_call``, possibly through ``partial``);
* its name is passed to a tracer call site anywhere in the module
  (``jax.jit(self._program, ...)``, ``lax.scan(body, ...)``,
  ``shard_map(fn, mesh, ...)``);
* its name marks it as device code (``device_*`` / ``_device*``);
* it is called (by simple name or ``self.name``) from a traced function.

Taint model (which expressions hold *traced values*): results of
``jnp.* / lax.* / pl.*`` calls and of calls to ``device_*``-named
functions are tainted; taint propagates through arithmetic,
comparisons, subscripts and assignments.  Calls to other local helpers
are *untainted* even when those helpers are themselves traced — in this
codebase they return trace-static metadata (column tuples, bound flags),
and branching on their results is the supported idiom.  ``.shape`` /
``.ndim`` / ``.dtype`` / ``.size`` attribute reads are always untainted
(static under trace).  ``.n`` / ``.data`` / ``.overflow`` reads are
always tainted: those are the JBindings device-value attributes, and a
shape derived from ``.n`` is the classic retrace bug.  Function
parameters are untainted — jitted entry points routinely take static
arguments, and the rules target values that are *provably*
device-resident, not possibly so.  A Python list/tuple/set holding
traced values is tracked as a *container* (level 2): iterating or
truth-testing it is host-side and fine, but indexing it yields a traced
value and handing it to ``np.*`` (e.g. ``np.stack(masks)``) is still a
host sync.

Suppressions: ``# replint: disable=<rule> -- <justification>`` on the
offending line, or standing alone on the line directly above it.  A
directive without the ``-- <justification>`` tail is itself a finding
(``bare-suppression``) and cannot be suppressed.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

__all__ = ["LintFinding", "RULES", "lint_source", "lint_file", "lint_paths"]

INT32_MAX = 2**31 - 1
INT32_MIN = -(2**31)

#: rule id -> one-line description (the lint catalog; docs/architecture.md
#: mirrors this table).
RULES: Dict[str, str] = {
    "traced-branch":
        "Python if/while/for/ternary on a traced value inside a traced "
        "function — concretizes the tracer (ConcretizationTypeError) or "
        "bakes one branch into the compiled program.",
    "host-sync":
        "Host synchronisation inside a traced function: .item(), or "
        "np.* / float() / int() / bool() applied to a traced value — "
        "blocks on device transfer and breaks tracing.",
    "int32-overflow":
        "Integer literal outside int32 range inside a traced function — "
        "silently promotes the lattice past the engine's int32 id space.",
    "nonstatic-shape":
        "Array constructor whose shape derives from a traced value "
        "(e.g. a JBindings .n) — shapes must be static under jit; this "
        "retraces per distinct value or fails outright.",
    "shard-map-check-vma":
        "shard_map call without an explicit check_vma decision — "
        "replication checking must be chosen deliberately (and the "
        "choice justified) at every call site.",
    "bare-suppression":
        "replint suppression without a '-- <justification>' tail — "
        "unexplained suppressions are not allowed.",
}

#: names that establish a traced context when used as a decorator or when
#: a function is passed to them at a call site.
_TRACER_NAMES = {
    "jit", "vmap", "pmap", "shard_map", "pallas_call", "scan",
    "while_loop", "fori_loop", "cond", "checkpoint", "remat", "custom_vjp",
}

#: module aliases whose call results are device (traced) values.
_DEVICE_MODULES = {"jnp", "lax", "pl", "pltpu"}

#: host modules whose calls on traced values force a device->host sync.
_HOST_MODULES = {"np", "numpy"}

#: attribute reads that are static under trace (never tainted).
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}

#: attribute reads that are always traced values (JBindings convention).
_TAINTED_ATTRS = {"n", "data", "overflow"}

#: array constructors whose first positional / ``shape=`` argument must be
#: static under trace.
_SHAPE_FNS = {"zeros", "ones", "full", "empty", "arange", "broadcast_to"}


@dataclass(frozen=True)
class LintFinding:
    """One lint violation at a source location."""

    path: str
    line: int
    col: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.rule}: {self.message}"


def _is_tracer_name(name: Optional[str]) -> bool:
    if not name:
        return False
    return (name in _TRACER_NAMES
            or name.endswith("shard_map") or name.endswith("smap"))


def _terminal(node: ast.expr) -> Optional[str]:
    """The last dotted component of a Name/Attribute chain, else None."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _root(node: ast.expr) -> Optional[str]:
    """The base Name of a Name/Attribute chain (``np`` in ``np.a.b``)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _is_tracer_decorator(dec: ast.expr) -> bool:
    if isinstance(dec, (ast.Name, ast.Attribute)):
        return _is_tracer_name(_terminal(dec))
    if isinstance(dec, ast.Call):
        if _is_tracer_name(_terminal(dec.func)):
            return True
        # functools.partial(jax.jit, ...) style
        if _terminal(dec.func) == "partial":
            return any(_is_tracer_name(_terminal(a))
                       for a in dec.args
                       if isinstance(a, (ast.Name, ast.Attribute)))
    return False


class _ModuleScan(ast.NodeVisitor):
    """First pass: function registry, traced seeds, call graph, and the
    module-wide ``shard-map-check-vma`` check."""

    def __init__(self, path: str) -> None:
        self.path = path
        self.funcs: Dict[str, ast.AST] = {}
        self.seeds: Set[str] = set()
        self.callees: Dict[str, Set[str]] = {}
        self.findings: List[LintFinding] = []
        self._stack: List[str] = []

    # -- functions -----------------------------------------------------------
    def _handle_def(self, node) -> None:
        self.funcs[node.name] = node
        if node.name.startswith("device_") or node.name.startswith("_device"):
            self.seeds.add(node.name)
        if any(_is_tracer_decorator(d) for d in node.decorator_list):
            self.seeds.add(node.name)
        self.callees.setdefault(node.name, set())
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _handle_def
    visit_AsyncFunctionDef = _handle_def

    # -- call sites ----------------------------------------------------------
    def visit_Call(self, node: ast.Call) -> None:
        term = _terminal(node.func)
        if self._stack and term:
            self.callees[self._stack[-1]].add(term)
        if _is_tracer_name(term):
            # a function object handed to a tracer is traced
            for arg in node.args:
                at = _terminal(arg)
                if at:
                    self.seeds.add(at)
        if term is not None and term.endswith("shard_map"):
            if not any(kw.arg == "check_vma" for kw in node.keywords):
                self.findings.append(LintFinding(
                    self.path, node.lineno, node.col_offset,
                    "shard-map-check-vma",
                    "shard_map call without an explicit check_vma= decision"))
        self.generic_visit(node)

    def traced(self) -> Set[str]:
        """Seeds closed under the intra-module call graph."""
        traced = set(self.seeds)
        changed = True
        while changed:
            changed = False
            for fn in list(traced):
                for callee in self.callees.get(fn, ()):
                    if callee in self.funcs and callee not in traced:
                        traced.add(callee)
                        changed = True
        return traced


#: taint lattice: 0 = clean (host / trace-static), 1 = traced device
#: value, 2 = host container holding traced values.
_CLEAN, _TRACED, _CONTAINER = 0, 1, 2


class _TracedChecker:
    """Second pass: taint-tracking walk over one traced function body."""

    def __init__(self, path: str, traced: Set[str],
                 findings: List[LintFinding]) -> None:
        self.path = path
        self.traced = traced
        self.findings = findings
        self.tainted: Dict[str, int] = {}

    def _emit(self, node: ast.AST, rule: str, message: str) -> None:
        self.findings.append(LintFinding(
            self.path, node.lineno, node.col_offset, rule, message))

    # -- statements ----------------------------------------------------------
    def run(self, body: Sequence[ast.stmt]) -> None:
        for stmt in body:
            self.stmt(stmt)

    def stmt(self, s: ast.stmt) -> None:
        if isinstance(s, ast.Assign):
            t = self.expr(s.value)
            for target in s.targets:
                self._bind(target, t)
        elif isinstance(s, ast.AnnAssign):
            t = self.expr(s.value) if s.value is not None else _CLEAN
            self._bind(s.target, t)
        elif isinstance(s, ast.AugAssign):
            t = self.expr(s.value)
            if isinstance(s.target, ast.Name):
                prev = self.tainted.get(s.target.id, _CLEAN)
                if max(t, prev):
                    self.tainted[s.target.id] = max(t, prev)
        elif isinstance(s, ast.If):
            if self.expr(s.test) == _TRACED:
                self._emit(s, "traced-branch",
                           "Python `if` on a traced value")
            self.run(s.body)
            self.run(s.orelse)
        elif isinstance(s, ast.While):
            if self.expr(s.test) == _TRACED:
                self._emit(s, "traced-branch",
                           "Python `while` on a traced value")
            self.run(s.body)
            self.run(s.orelse)
        elif isinstance(s, ast.For):
            it = self.expr(s.iter)
            if it == _TRACED:
                self._emit(s, "traced-branch",
                           "Python `for` iterating a traced value")
            # iterating a traced array or a container of traced values
            # binds traced elements either way
            self._bind(s.target, _TRACED if it else _CLEAN)
            self.run(s.body)
            self.run(s.orelse)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested defs inherit the closure's taint
            self.run(s.body)
        elif isinstance(s, ast.Return):
            if s.value is not None:
                self.expr(s.value)
        elif isinstance(s, ast.Expr):
            self.expr(s.value)
        elif isinstance(s, ast.With):
            for item in s.items:
                self.expr(item.context_expr)
            self.run(s.body)
        elif isinstance(s, ast.Try):
            self.run(s.body)
            for h in s.handlers:
                self.run(h.body)
            self.run(s.orelse)
            self.run(s.finalbody)
        elif isinstance(s, (ast.Assert, ast.Raise, ast.Delete)):
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.expr(child)
        # pass/import/global/... carry no expressions we track

    def _bind(self, target: ast.expr, taint: int) -> None:
        if isinstance(target, ast.Name):
            if taint:
                self.tainted[target.id] = taint
            else:
                self.tainted.pop(target.id, None)
        elif isinstance(target, (ast.Tuple, ast.List)):
            # unpacking a container of traced values binds traced names
            elt = _TRACED if taint else _CLEAN
            for e in target.elts:
                self._bind(e, elt)
        elif isinstance(target, (ast.Subscript, ast.Attribute, ast.Starred)):
            self.expr(target)

    # -- expressions ---------------------------------------------------------
    def expr(self, e: Optional[ast.expr]) -> int:
        """Walk an expression, emitting findings; returns its taint level."""
        if e is None:
            return _CLEAN
        if isinstance(e, ast.Name):
            return self.tainted.get(e.id, _CLEAN)
        if isinstance(e, ast.Constant):
            if isinstance(e.value, int) and not isinstance(e.value, bool):
                if e.value > INT32_MAX or e.value < INT32_MIN:
                    self._emit(e, "int32-overflow",
                               f"int literal {e.value} exceeds int32 range "
                               "in traced code")
            return _CLEAN
        if isinstance(e, ast.Attribute):
            base = self.expr(e.value)
            if e.attr in _STATIC_ATTRS:
                return _CLEAN
            if e.attr in _TAINTED_ATTRS:
                return _TRACED
            return base
        if isinstance(e, ast.Call):
            return self._call(e)
        if isinstance(e, ast.IfExp):
            if self.expr(e.test) == _TRACED:
                self._emit(e, "traced-branch",
                           "conditional expression on a traced value")
            return max(self.expr(e.body), self.expr(e.orelse))
        if isinstance(e, ast.BinOp):
            return max(self.expr(e.left), self.expr(e.right))
        if isinstance(e, ast.UnaryOp):
            return self.expr(e.operand)
        if isinstance(e, ast.Compare):
            t = self.expr(e.left)
            for cmp in e.comparators:
                t = max(self.expr(cmp), t)
            # `x is None` / `in` on a traced operand is a host identity /
            # membership test over Python structure, not device compute
            if all(isinstance(op, (ast.Is, ast.IsNot, ast.In, ast.NotIn))
                   for op in e.ops):
                return _CLEAN
            return min(t, _TRACED)
        if isinstance(e, ast.BoolOp):
            t = _CLEAN
            for v in e.values:
                t = max(self.expr(v), t)
            return min(t, _TRACED)
        if isinstance(e, ast.Subscript):
            val = self.expr(e.value)
            sub = self.expr(e.slice)
            if val == _CONTAINER:
                # slicing a container keeps the container level; indexing
                # it yields one of its traced elements
                val = _CONTAINER if isinstance(e.slice, ast.Slice) \
                    else _TRACED
            return max(val, min(sub, _TRACED))
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            t = _CLEAN
            for elt in e.elts:
                t = max(self.expr(elt), t)
            return _CONTAINER if t else _CLEAN
        if isinstance(e, ast.Dict):
            t = _CLEAN
            for k in e.keys:
                if k is not None:
                    t = max(self.expr(k), t)
            for v in e.values:
                t = max(self.expr(v), t)
            return _CONTAINER if t else _CLEAN
        if isinstance(e, ast.Slice):
            t = self.expr(e.lower)
            t = max(self.expr(e.upper), t)
            return max(self.expr(e.step), t)
        if isinstance(e, ast.Starred):
            return self.expr(e.value)
        if isinstance(e, ast.Lambda):
            self.expr(e.body)
            return _CLEAN
        if isinstance(e, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                          ast.DictComp)):
            t = _CLEAN
            for gen in e.generators:
                gt = self.expr(gen.iter)
                self._bind(gen.target, _TRACED if gt else _CLEAN)
                for cond in gen.ifs:
                    self.expr(cond)
                t = max(gt, t)
            if isinstance(e, ast.DictComp):
                t = max(self.expr(e.key), t)
                t = max(self.expr(e.value), t)
            else:
                t = max(self.expr(e.elt), t)
            return _CONTAINER if t else _CLEAN
        # fallback: max over child expressions
        t = _CLEAN
        for child in ast.iter_child_nodes(e):
            if isinstance(child, ast.expr):
                t = max(self.expr(child), t)
        return t

    def _call(self, e: ast.Call) -> int:
        func_taint = _CLEAN
        if isinstance(e.func, (ast.Name, ast.Attribute)):
            # walking the func expr also handles taint of `x.sum` etc.
            func_taint = self.expr(e.func)
        arg_taints = [self.expr(a) for a in e.args]
        kw_taints = {kw.arg: self.expr(kw.value) for kw in e.keywords}
        any_arg = any(arg_taints) or any(kw_taints.values())

        term = _terminal(e.func)
        root = _root(e.func)

        # host syncs -----------------------------------------------------
        if term == "item":
            self._emit(e, "host-sync",
                       ".item() forces a device->host sync in traced code")
        elif root in _HOST_MODULES and any_arg:
            self._emit(e, "host-sync",
                       f"{root}.{term}() on a traced value forces a host "
                       "sync and escapes the tracer")
        elif isinstance(e.func, ast.Name) and e.func.id in (
                "float", "int", "bool") and any_arg:
            self._emit(e, "host-sync",
                       f"{e.func.id}() on a traced value forces a host sync")

        # non-static shapes ----------------------------------------------
        if term in _SHAPE_FNS:
            shape_args = []
            if e.args:
                shape_args.append(arg_taints[0])
            if "shape" in kw_taints:
                shape_args.append(kw_taints["shape"])
            if any(shape_args):
                self._emit(e, "nonstatic-shape",
                           f"{term}() shape derives from a traced value — "
                           "shapes must be static under jit")
        elif term == "reshape" and isinstance(e.func, ast.Attribute) \
                and _root(e.func) not in (_HOST_MODULES | _DEVICE_MODULES
                                          | {"jax"}) and any_arg:
            self._emit(e, "nonstatic-shape",
                       ".reshape() target derives from a traced value — "
                       "shapes must be static under jit")

        # result taint: only calls that provably build device values.
        # Other local helpers — even traced ones — return trace-static
        # metadata in this codebase (column tuples, bound flags), and
        # branching on their results is fine.
        if root in _DEVICE_MODULES:
            return _TRACED
        if term and (term.startswith("device_") or term.startswith("_device")):
            return _TRACED
        return min(func_taint, _TRACED)


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

_SUPPRESS_RE = re.compile(
    r"#\s*replint:\s*disable=([A-Za-z0-9_,-]+)\s*(?:--\s*(\S.*))?\s*$")


def _scan_suppressions(
    source: str, path: str,
) -> Tuple[Dict[int, Set[str]], List[LintFinding]]:
    """Map of line -> suppressed rules, plus bare-suppression findings.

    An inline directive covers its own line; a directive on a line of its
    own covers the line below it.
    """
    suppressed: Dict[int, Set[str]] = {}
    findings: List[LintFinding] = []
    for lineno, text in enumerate(source.splitlines(), start=1):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        justified = m.group(2) is not None
        if not justified:
            findings.append(LintFinding(
                path, lineno, m.start(), "bare-suppression",
                "suppression lacks a '-- <justification>' tail"))
        target = lineno + 1 if text.lstrip().startswith("#") else lineno
        suppressed.setdefault(target, set()).update(rules)
    return suppressed, findings


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def lint_source(source: str, path: str = "<string>") -> List[LintFinding]:
    """Lint one module's source text; returns findings sorted by location."""
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as exc:
        return [LintFinding(path, exc.lineno or 1, exc.offset or 0,
                            "syntax-error", f"cannot parse: {exc.msg}")]

    scan = _ModuleScan(path)
    scan.visit(tree)
    traced = scan.traced()

    findings: List[LintFinding] = list(scan.findings)
    for name in sorted(traced):
        node = scan.funcs.get(name)
        if node is None:
            continue  # seed referenced a name defined elsewhere
        checker = _TracedChecker(path, traced, findings)
        checker.run(node.body)

    suppressed, bare = _scan_suppressions(source, path)
    kept = [
        f for f in findings
        if not (f.rule in suppressed.get(f.line, ())
                and f.rule != "bare-suppression")
    ]
    kept.extend(bare)
    # nested traced defs are visited both standalone and through their
    # enclosing function — deduplicate by location+rule
    unique = {(f.line, f.col, f.rule): f for f in kept}
    return sorted(unique.values(), key=lambda f: (f.line, f.col, f.rule))


def lint_file(path) -> List[LintFinding]:
    p = Path(path)
    return lint_source(p.read_text(encoding="utf-8"), str(p))


def lint_paths(paths: Iterable) -> List[LintFinding]:
    """Lint files and/or directory trees (``*.py``, sorted, recursive)."""
    findings: List[LintFinding] = []
    for entry in paths:
        p = Path(entry)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            findings.extend(lint_file(f))
    return findings
