"""dispatch-readback: no blocking device syncs on the dispatch thread.

The engine dispatch loop's contract (llm_engine.py) is that it never
waits on the device or the host: it chains async device work and hands
result handles to the reader thread, whose whole job is the blocking
readback. A stray sync on the dispatch thread serializes every live
request behind one host round-trip, which is exactly the regression class the
decode_runahead pipeline exists to prevent.

Roots are marked in source — a trailing comment on the ``def`` line::

    def _loop(self) -> None:  # genai-lint: dispatch-root

The rule builds the intra-file call graph (``self.method()`` edges
within the class plus bare-name calls to module functions), walks
everything reachable from each root, and flags the blocking patterns:

- ``<expr>.item()`` and ``<expr>.block_until_ready()``;
- ``jax.device_get(...)``;
- ``np.asarray / np.array / np.atleast_1d`` applied to an existing
  array value (a bare name or attribute — calls/list literals build
  fresh host arrays and are not readbacks);
- ``float(...)`` / ``int(...)`` coercions of values following the
  engine's device-array naming convention (``*_dev`` names), the one
  case where a scalar coercion is statically known to sync.

``copy_to_host_async`` is explicitly NON-blocking: it starts the
device→host transfer and returns, which is precisely how the pipelined
paths overlap readbacks with compute — the dispatch thread calls it by
design, so it must never read as a sync (the allowlist is structural,
not a suppression).

The rule also emits a second finding kind, **coalescable-sync**: two
back-to-back sync-bearing statements (same thread, no statement — so
certainly no dispatch — between them) each pay a full device→host
round-trip where one packed array would pay one. Each such pair is a
finding on the second statement, suppressible under its own name —
this is how the engine's old twin spec-verify fetches (tokens at one
line, accepted counts on the next) would have been caught before they
shipped.

Legitimate sync points (the spec-verify proposer sync, the spec-block
fallback slab fetch) are allow-listed in place with a suppression
comment carrying the reason — the allow list lives next to the code it
excuses, not in the linter.

The rule runs in two passes. The per-file pass above is unchanged
(fixtures and explicit-path runs exercise it alone). On whole-repo
runs, a second **interprocedural** pass rides the shared project call
graph (tools/genai_lint/project.py): each dispatch root's reachability
now crosses module boundaries — ``module.func()`` through imports,
``self.attr.m()`` through inferred attribute types — so a sync buried
in a helper module (``DraftRuntime.propose``'s proposal-slab fetch two
modules from the loop) is finally visible. The cross-module pass
reports only functions OUTSIDE the root's own file (the per-file pass
owns those — no duplicate findings), and only in modules that import
``jax`` somewhere: a module that never touches jax holds no device
arrays, so its ``np.asarray`` calls are host-to-host copies, not
readbacks (this is the old "host-only modules" blind spot, kept as an
explicit boundary instead of an accident of scope).

Blind spots, by design: calls through dynamic attributes
(``self._extend_fn(...)``) dispatch compiled programs and are async —
they are not edges; nested defs and lambdas are assumed to run
off-thread (reader closures, ``Thread(target=...)`` workers), so
neither their syncs nor their calls are attributed to the enclosing
function; the project core's documented resolution limits (no
inheritance, no containers of callables) bound the cross-module pass.
"""
from __future__ import annotations

import ast
import pathlib
import re
from typing import Dict, List, Optional, Set

from tools.genai_lint.core import Finding, RepoRule, SourceRule, iter_comments

ROOT_MARKER_RE = re.compile(r"#\s*genai-lint:\s*dispatch-root\b")

_NP_SYNC_FNS = {"asarray", "array", "atleast_1d"}
_NP_MODULES = {"np", "numpy"}
# Non-blocking by contract: starts the device→host transfer and
# returns immediately. The pipelined engine paths call it ON the
# dispatch thread on purpose (overlap is the whole point), so it must
# never match a sync pattern regardless of what patterns grow here.
_NONBLOCKING_ATTRS = {"copy_to_host_async"}


def _qualname(cls: Optional[ast.ClassDef], fn) -> str:
    return f"{cls.name}.{fn.name}" if cls is not None else fn.name


def _collect_functions(tree: ast.AST):
    """(qualname -> def node, qualname -> class) for module functions
    and first-level methods."""
    fns: Dict[str, ast.AST] = {}
    owner: Dict[str, Optional[ast.ClassDef]] = {}
    for node in ast.iter_child_nodes(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns[node.name] = node
            owner[node.name] = None
        elif isinstance(node, ast.ClassDef):
            for item in ast.iter_child_nodes(node):
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = _qualname(node, item)
                    fns[q] = item
                    owner[q] = node
    return fns, owner


def _walk_same_thread(fn: ast.AST):
    """Walk a function's nodes WITHOUT descending into nested defs or
    lambdas — closures are handed to threads/executors/callbacks often
    enough that their bodies cannot be attributed to the enclosing
    thread (the same off-thread assumption lock-discipline makes)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _callees(fn: ast.AST, cls: Optional[ast.ClassDef]) -> Set[str]:
    """Qualified names this function may call within its own file:
    ``self.m()`` -> ``Class.m``; ``f()`` -> module function ``f``."""
    out: Set[str] = set()
    for node in _walk_same_thread(fn):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if (
            cls is not None
            and isinstance(func, ast.Attribute)
            and isinstance(func.value, ast.Name)
            and func.value.id == "self"
        ):
            out.add(f"{cls.name}.{func.attr}")
        elif isinstance(func, ast.Name):
            out.add(func.id)
    return out


def _is_dev_named(node: ast.AST) -> bool:
    """Whether an expression reads a ``*_dev``-named value (the engine's
    device-array naming convention), directly or through one subscript."""
    if isinstance(node, ast.Subscript):
        return _is_dev_named(node.value)
    if isinstance(node, ast.Attribute):
        return node.attr.endswith("_dev")
    if isinstance(node, ast.Name):
        return node.id.endswith("_dev")
    return False


def _is_array_ref(node: ast.AST) -> bool:
    """A Name/Attribute, or a subscript of one — ``np.asarray(slab[0])``
    slices a device array but still blocks on the same readback."""
    if isinstance(node, ast.Subscript):
        node = node.value
    return isinstance(node, (ast.Name, ast.Attribute))


def _sync_what(node: ast.Call) -> Optional[str]:
    """A short description of the blocking sync this call performs, or
    None when the call is not a (statically recognizable) sync."""
    func = node.func
    if isinstance(func, ast.Attribute):
        if func.attr in _NONBLOCKING_ATTRS:
            return None
        if func.attr == "item" and not node.args and not node.keywords:
            return ".item()"
        if func.attr == "block_until_ready":
            return ".block_until_ready()"
        if (
            func.attr == "device_get"
            and isinstance(func.value, ast.Name)
            and func.value.id == "jax"
        ):
            return "jax.device_get()"
        if (
            func.attr in _NP_SYNC_FNS
            and isinstance(func.value, ast.Name)
            and func.value.id in _NP_MODULES
            and node.args
            and _is_array_ref(node.args[0])
        ):
            return f"np.{func.attr}() on an existing array"
        return None
    if (
        isinstance(func, ast.Name)
        and func.id in ("float", "int")
        and node.args
        and _is_dev_named(node.args[0])
    ):
        return f"{func.id}() on a *_dev device array"
    return None


# Statement shapes a sync can hide in WITHOUT a dispatch possibly
# sitting between it and an adjacent statement's sync (compound
# statements may interleave dispatches inside their bodies, so they
# never join a coalescable pair).
_SIMPLE_STMTS = (ast.Expr, ast.Assign, ast.AnnAssign, ast.AugAssign,
                 ast.Return)


def _stmt_sync(stmt: ast.stmt):
    """The first blocking-sync call inside one SIMPLE statement (same
    off-thread discipline as _walk_same_thread), or None."""
    if not isinstance(stmt, _SIMPLE_STMTS):
        return None
    stack = [stmt]
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Call):
            what = _sync_what(node)
            if what is not None:
                return node, what
        stack.extend(ast.iter_child_nodes(node))
    return None


def _stmt_lists(fn: ast.AST):
    """Every same-thread statement list in the function (its body plus
    each compound statement's body/orelse/finalbody)."""
    for node in [fn, *_walk_same_thread(fn)]:
        for field in ("body", "orelse", "finalbody"):
            stmts = getattr(node, field, None)
            if (
                isinstance(stmts, list)
                and stmts
                and isinstance(stmts[0], ast.stmt)
            ):
                yield stmts


def _coalescable_findings(path: str, fn: ast.AST, root: str) -> List[Finding]:
    """Adjacent sync-bearing statements: each pays a device→host
    round-trip that one packed transfer would merge."""
    out: List[Finding] = []
    for stmts in _stmt_lists(fn):
        prev = None
        for stmt in stmts:
            cur = _stmt_sync(stmt)
            if cur is not None and prev is not None:
                node, what = cur
                _, prev_what = prev
                out.append(Finding(
                    "coalescable-sync", path, node.lineno,
                    f"{what} immediately follows another blocking sync "
                    f"({prev_what}) with no dispatch between them "
                    f"(reachable from dispatch root {root!r}); pack "
                    f"both results into one device array and pay ONE "
                    f"device→host transfer",
                ))
            prev = cur
    return out


def _sync_findings(path: str, fn: ast.AST, root: str) -> List[Finding]:
    out: List[Finding] = []
    for node in _walk_same_thread(fn):
        if not isinstance(node, ast.Call):
            continue
        what = _sync_what(node)
        if what is not None:
            out.append(Finding(
                "dispatch-readback", path, node.lineno,
                f"{what} blocks the dispatch thread on a device sync "
                f"(reachable from dispatch root {root!r}); move it to "
                f"the reader, or suppress with the reason this sync is "
                f"required",
            ))
    out.extend(_coalescable_findings(path, fn, root))
    return out


class DispatchReadbackRule(SourceRule, RepoRule):
    name = "dispatch-readback"
    description = (
        "blocking device syncs (.item(), np.asarray, block_until_ready, "
        "jax.device_get) in functions reachable from a "
        "`# genai-lint: dispatch-root` function — intra-file plus the "
        "cross-module call graph; copy_to_host_async is structurally "
        "non-blocking, and back-to-back syncs additionally emit a "
        "coalescable-sync finding"
    )

    def check_file(
        self, path: str, source: str, tree: Optional[ast.AST]
    ) -> List[Finding]:
        if tree is None or "dispatch-root" not in source:
            return []
        marker_lines = {
            lineno for lineno, comment in iter_comments(source)
            if ROOT_MARKER_RE.search(comment)
        }
        if not marker_lines:
            return []
        fns, owner = _collect_functions(tree)

        def header_lines(fn) -> range:
            # the `def` line through the line before the body — at
            # least the def line itself, so a single-line def whose
            # body shares the header line still matches
            return range(fn.lineno, max(fn.body[0].lineno, fn.lineno + 1))

        roots = [
            q for q, fn in fns.items()
            if any(ln in marker_lines for ln in header_lines(fn))
        ]
        # A marker that matches no tracked function (a typo'd placement,
        # or a nested def this rule's call graph doesn't cover) would
        # silently disable the lint — that is itself a finding.
        covered = {
            ln for fn in fns.values() for ln in header_lines(fn)
        }
        findings: List[Finding] = [
            Finding(
                "dispatch-readback", path, ln,
                "dispatch-root marker does not sit on a tracked function "
                "def header (module functions and first-level methods) — "
                "it marks nothing",
            )
            for ln in sorted(marker_lines - covered)
        ]
        # A function reachable from several roots reports each sync
        # ONCE, naming every root — so first collect root sets per
        # reachable function, then flag.
        reached_by: Dict[str, Set[str]] = {}
        for root in roots:
            seen: Set[str] = set()
            stack = [root]
            while stack:
                q = stack.pop()
                if q in seen or q not in fns:
                    continue
                seen.add(q)
                stack.extend(_callees(fns[q], owner[q]))
            for q in seen:
                reached_by.setdefault(q, set()).add(root)
        for q in sorted(reached_by):
            label = "/".join(sorted(reached_by[q]))
            findings.extend(_sync_findings(path, fns[q], label))
        return findings

    # ------------------------------------------------------------------ #
    # interprocedural pass (whole-repo runs)

    def _root_quals(self, index, root: pathlib.Path) -> List[str]:
        """Dispatch-root-marked functions, project-wide: same marker,
        matched against the project index's function headers."""
        from tools.genai_lint.core import load_source

        roots: List[str] = []
        for mod in index.modules.values():
            source, _, _ = load_source(root / mod.path)
            if not source or "dispatch-root" not in source:
                continue
            marker_lines = {
                lineno for lineno, comment in iter_comments(source)
                if ROOT_MARKER_RE.search(comment)
            }
            if not marker_lines:
                continue
            for fi in index.functions.values():
                if fi.module != mod.name:
                    continue
                fn = fi.node
                header = range(
                    fn.lineno, max(fn.body[0].lineno, fn.lineno + 1)
                )
                if any(ln in marker_lines for ln in header):
                    roots.append(fi.qual)
        return roots

    def check_repo(self, root: pathlib.Path) -> List[Finding]:
        from tools.genai_lint.project import get_index

        return self.check_index(get_index(root), root)

    def check_index(self, index, root: pathlib.Path) -> List[Finding]:
        roots = self._root_quals(index, root)
        if not roots:
            return []
        # A function reachable from several roots reports each sync
        # once, naming every root — same contract as the per-file pass.
        # Only CROSS-file functions are reported (the per-file pass owns
        # the root's own file), and only in jax-importing modules
        # (module docstring: no jax import = no device arrays).
        reached_by: Dict[str, Set[str]] = {}
        for root_qual in roots:
            root_path = index.functions[root_qual].path
            for q in index.reachable([root_qual]):
                fi = index.functions[q]
                if fi.path == root_path:
                    continue
                if not index.modules[fi.module].imports_jax:
                    continue
                reached_by.setdefault(q, set()).add(root_qual)
        findings: List[Finding] = []
        for q in sorted(reached_by):
            fi = index.functions[q]
            label = (
                "/".join(sorted(reached_by[q]))
                + " via the cross-module call graph"
            )
            findings.extend(_sync_findings(fi.path, fi.node, label))
        return findings
