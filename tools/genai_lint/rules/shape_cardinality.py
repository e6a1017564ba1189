"""shape-cardinality: compiled-program call sites must round
request-varying sizes through a ladder helper.

Every distinct operand shape handed to a jitted program is its own XLA
executable (tens of seconds of compile on the layered path). The stack
therefore quantizes every request-varying dimension through a finite
ladder — power-of-two row rungs (``batcher.row_bucket``) and the
engine's ``ShapePlan`` (engine/scheduler/shapes.py: chunk-aligned
``prefill_bucket``, ``wave_pad``, ``chunk_rung``, power-of-two
``attention_window``) — so the warm
executable set is bounded. The pre-PR-5 embedder broke this by passing
raw ``len(texts)`` row counts to its jitted encoder: one executable per
distinct document-batch size, unbounded. This rule prevents the next
one.

Mechanics (intra-function taint, deliberately simple):

- **sources**: ``len(...)`` calls; a variable assigned an expression
  containing one becomes tainted, and taint propagates through
  arithmetic, ``min``/``max``/``sum``, container literals and ordinary
  calls (``np.zeros((n, d))`` with tainted ``n`` taints the array);
- **laundering**: a call whose function name carries a rounding-ladder
  word as a whole snake_case token (``bucket``, ``ladder``, ``rung``,
  ``pad``, ``pow2``, ``round``, ``window``, ``pages``, ``rows``) clears
  taint — these are the repo's quantizers, and new ones should follow
  the naming; an unlucky substring (``background``) does not launder;
- **sinks**: calls to compiled callables — a name or ``self.<attr>``
  assigned from ``jax.jit(...)``, a function decorated with ``jax.jit``
  (bare or via ``functools.partial``), or, by naming convention, any
  ``*_fn`` attribute — with a tainted argument.

Taint does not cross function boundaries: a helper returning a raw
``len()`` to its caller is invisible (name helpers after what they do —
if one rounds, the laundering list catches it; raw sizes usually appear
inline at the call site anyway).
"""
from __future__ import annotations

import ast
import re
from typing import List, Optional, Set

from tools.genai_lint.core import Finding, SourceRule

# Tokens match whole snake_case words only: `row_bucket`/`wave_pad`
# launder, but an unlucky substring (`round` inside `background`,
# `workaround`) must not.
LAUNDER_RE = re.compile(
    r"(?:^|_)(?:bucket|ladder|rung|pad|pow2|pow_two|round|window|pages|rows)"
    r"(?:_|$|\d)",
    re.IGNORECASE,
)


def _call_name(func: ast.AST) -> Optional[str]:
    """Trailing name of a callee ('self.shapes.wave_pad' -> 'wave_pad')."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _names_jit(node: ast.AST) -> bool:
    """Whether an expression names the jit transform itself
    (``jax.jit`` / ``jit``)."""
    return _call_name(node) == "jit" if isinstance(
        node, (ast.Name, ast.Attribute)
    ) else False


def _is_jit_product(node: ast.AST) -> bool:
    """Whether an expression evaluates to a compiled callable:
    ``jax.jit(...)`` or ``functools.partial(jax.jit, ...)``."""
    if not isinstance(node, ast.Call):
        return False
    name = _call_name(node.func)
    if name == "jit":
        return True
    if name == "partial" and node.args:
        first = node.args[0]
        return _names_jit(first) or _is_jit_product(first)
    return False


def _collect_compiled(tree: ast.AST) -> Set[str]:
    """Names and attribute names statically known to hold compiled
    callables: ``X = jax.jit(...)``, ``self.X = jax.jit(...)``, and
    defs decorated with ``jax.jit`` / ``functools.partial(jax.jit, ..)``."""
    compiled: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and _is_jit_product(node.value):
            for target in node.targets:
                if isinstance(target, ast.Name):
                    compiled.add(target.id)
                elif isinstance(target, ast.Attribute):
                    compiled.add(target.attr)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in node.decorator_list:
                if _names_jit(deco) or _is_jit_product(deco):
                    compiled.add(node.name)
    return compiled


class _Tainter:
    """Taint over names derived from raw ``len(...)``, learned from
    assignments in source order."""

    def __init__(self) -> None:
        self.tainted: Set[str] = set()

    def expr_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Call):
            name = _call_name(node.func)
            if name == "len":
                return True
            if name is not None and LAUNDER_RE.search(name):
                return False  # rounded through a ladder helper
            return any(self.expr_tainted(a) for a in node.args) or any(
                self.expr_tainted(kw.value) for kw in node.keywords
            )
        if isinstance(node, ast.BinOp):
            return self.expr_tainted(node.left) or self.expr_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.expr_tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.expr_tainted(v) for v in node.values)
        if isinstance(node, ast.IfExp):
            return self.expr_tainted(node.body) or self.expr_tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.expr_tainted(e) for e in node.elts)
        if isinstance(node, (ast.Subscript, ast.Starred)):
            return self.expr_tainted(node.value)
        if isinstance(node, ast.Compare):
            return self.expr_tainted(node.left) or any(
                self.expr_tainted(c) for c in node.comparators
            )
        return False

    def learn(self, stmt: ast.stmt) -> None:
        value = getattr(stmt, "value", None)
        if value is None:
            return
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            targets = [stmt.target]
        else:
            return
        tainted = self.expr_tainted(value)
        if isinstance(stmt, ast.AugAssign):
            # `n += 1` adjusts a size, it does not re-derive it: the
            # target keeps any taint it already carries.
            tainted = tainted or self.expr_tainted(stmt.target)
        for target in targets:
            # Only whole-name (re)bindings transfer shape taint: a
            # subscript store (`arr[i] = len(d)`) writes a VALUE into an
            # existing fixed-shape container without retyping its shape.
            elts = (
                target.elts
                if isinstance(target, (ast.Tuple, ast.List))
                else [target]
            )
            for elt in elts:
                if isinstance(elt, ast.Name):
                    if tainted:
                        self.tainted.add(elt.id)
                    else:
                        self.tainted.discard(elt.id)


class ShapeCardinalityRule(SourceRule):
    name = "shape-cardinality"
    description = (
        "compiled-program calls (jax.jit products, *_fn attributes) must "
        "not take values derived from raw len(...) — round through a "
        "bucket/ladder/pad helper first"
    )

    def check_file(
        self, path: str, source: str, tree: Optional[ast.AST]
    ) -> List[Finding]:
        if tree is None:
            return []
        compiled = _collect_compiled(tree)
        if not compiled and "_fn(" not in source:
            return []
        findings: List[Finding] = []

        def check_function(fn) -> None:
            # One pass in source order over every node in the function
            # (nested defs included — closures see outer taint): learn
            # assignments as they appear, check compiled calls against
            # the taint known at that point.
            nodes = sorted(
                ast.walk(fn),
                key=lambda n: (
                    getattr(n, "lineno", 0), getattr(n, "col_offset", 0)
                ),
            )
            tainter = _Tainter()
            for node in nodes:
                if isinstance(node, ast.stmt):
                    tainter.learn(node)
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node.func)
                if name is None or not (
                    name in compiled or name.endswith("_fn")
                ):
                    continue
                args = list(node.args) + [kw.value for kw in node.keywords]
                if any(tainter.expr_tainted(a) for a in args):
                    findings.append(Finding(
                        "shape-cardinality", path, node.lineno,
                        f"compiled call {name}() takes a value derived "
                        f"from len(...) without ladder rounding — every "
                        f"distinct size compiles a new executable",
                    ))

        # Check only outermost functions: nested defs are covered by the
        # enclosing function's walk (sharing its taint state).
        def outermost(node: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    check_function(child)
                else:
                    outermost(child)

        outermost(tree)
        return findings
