"""warmup-coverage: every compiled program the compile watch registers
must be statically reachable from a warmup walker.

The serving stack's zero-hot-path-compile contract has two halves: the
runtime half (engine/compile_watch.py screams when a first-seen
signature lands after warmup) and this static half, which catches the
bug class BEFORE a TPU ever dispatches. PR 12's incident is the
motivating instance: the paged page-table scatter was registered with
``compile_watch.wrap("page_tables", ...)`` but no warmup path ever
dispatched it, so the first real admission wave of every size paid the
compile mid-serving — visible only because the runtime gate happened
to be watching.

Mechanics, on the shared project call graph (tools/genai_lint/
project.py):

- a **registration** is a call ``<expr>.wrap("name", ...)`` whose
  first argument is a string literal AND whose receiver chain names a
  compile watch (a ``compile_watch``-named segment:
  ``self._compile_watch.wrap``, a ``compile_watch`` parameter/module
  alias) — including through a local alias
  (``wrap = self._compile_watch.wrap; wrap("extend", ...)``), the
  engine's idiom. An unrelated ``textwrap.wrap("...")`` is not a
  registration. The storage target is the enclosing assignment
  (``self._extend_fn = wrap(...)`` registers attribute
  ``_extend_fn`` on the enclosing class).
- the **walkers** are every function named ``warmup`` or
  ``warmup_spec_shapes``, anywhere in
  the tree (``DraftRuntime.warmup`` counts exactly like
  ``LLMEngine.warmup``).
- coverage is judged **per registration site**: a site is covered
  when some function reachable from a walker calls its storage
  attribute on the SAME class (``self._tables_fn(...)`` inside
  ``warmup``), or — for a registration stored in a
  local — calls that local inside a reachable function. Neither an
  identically-named attribute of a different class nor a same-named
  program registered elsewhere counts: ``DraftRuntime._propose_fn``
  warming itself says nothing about an ``LLMEngine._propose_fn``, and a
  covered ``wrap("extend", ...)`` on one class never excuses an
  uncovered one on another.
- reachability follows the project core's edges and off-thread
  discipline; in particular the dispatch loop is NOT reachable from
  ``warmup()`` just because warmup submits requests the loop will
  serve — queue-mediated warming is real but dynamic, and sites that
  rely on it carry an in-place suppression saying so (the audit trail
  the PR 12 class needs).

A registration whose storage cannot be determined (the wrap result is
passed along rather than assigned) is reported too — an invisible
storage site is an unverifiable warmup contract.
"""
from __future__ import annotations

import ast
import pathlib
from typing import Dict, List, Optional, Set, Tuple

from tools.genai_lint.core import Finding, RepoRule
from tools.genai_lint.project import (
    FunctionInfo,
    ProjectIndex,
    get_index,
    walk_same_thread,
)

WARMUP_WALKERS = frozenset({"warmup", "warmup_spec_shapes"})


def _attr_target(node: ast.Assign) -> Optional[Tuple[str, str]]:
    """("self", attr) or ("local", name) for a single-target assign."""
    if len(node.targets) != 1:
        return None
    tgt = node.targets[0]
    if (
        isinstance(tgt, ast.Attribute)
        and isinstance(tgt.value, ast.Name)
        and tgt.value.id == "self"
    ):
        return ("self", tgt.attr)
    if isinstance(tgt, ast.Name):
        return ("local", tgt.id)
    return None


def _is_compile_watch_chain(node: ast.AST) -> bool:
    """Whether an attribute chain's segments name a compile watch
    (``self._compile_watch``, a ``compile_watch`` parameter, an
    imported ``compile_watch`` module) — the guard that keeps an
    unrelated ``textwrap.wrap("...")`` from reading as a program
    registration."""
    while isinstance(node, ast.Attribute):
        if "compile_watch" in node.attr:
            return True
        node = node.value
    return isinstance(node, ast.Name) and "compile_watch" in node.id


def _is_wrap_call(node: ast.Call, aliases: Set[str]) -> bool:
    func = node.func
    if (
        isinstance(func, ast.Attribute)
        and func.attr == "wrap"
        and _is_compile_watch_chain(func.value)
    ):
        return True
    return isinstance(func, ast.Name) and func.id in aliases


def _wrap_aliases(fn: ast.AST) -> Set[str]:
    """Locals assigned ``<compile_watch chain>.wrap`` (unparenthesized
    bound-method aliasing, the engine's
    ``wrap = self._compile_watch.wrap``)."""
    out: Set[str] = set()
    for node in walk_same_thread(fn):
        if (
            isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Attribute)
            and node.value.attr == "wrap"
            and _is_compile_watch_chain(node.value.value)
        ):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out.add(tgt.id)
    return out


class WarmupCoverageRule(RepoRule):
    name = "warmup-coverage"
    description = (
        "every program registered via compile_watch.wrap() is statically "
        "reachable from a warmup walker (warmup / warmup_spec_shapes) — "
        "the static half of the zero-hot-path-compile contract"
    )

    def check_repo(self, root: pathlib.Path) -> List[Finding]:
        return self.check_index(get_index(root), root)

    def check_index(
        self, index: ProjectIndex, root: pathlib.Path
    ) -> List[Finding]:
        # 1. registrations: program -> list of (FunctionInfo, call node,
        #    storage) — storage is ("self", attr) / ("local", name) /
        #    None (undetermined).
        regs: Dict[str, List[Tuple[FunctionInfo, ast.Call, Optional[Tuple[str, str]]]]] = {}
        for fi in index.functions.values():
            aliases = _wrap_aliases(fi.node)
            assigns: Dict[int, Tuple[ast.Assign, Optional[Tuple[str, str]]]] = {}
            for node in walk_same_thread(fi.node):
                if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Call
                ):
                    assigns[id(node.value)] = (node, _attr_target(node))
            for node in walk_same_thread(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                if not _is_wrap_call(node, aliases):
                    continue
                if not (
                    node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    continue
                program = node.args[0].value
                storage = None
                hit = assigns.get(id(node))
                if hit is not None:
                    storage = hit[1]
                regs.setdefault(program, []).append((fi, node, storage))
        if not regs:
            return []

        # 2. what the warmup walkers reach, and which attribute/local
        #    calls they make there.
        walkers = index.functions_named(set(WARMUP_WALKERS))
        reach = index.reachable([f.qual for f in walkers])
        covered_attrs: Set[Tuple[str, str]] = set()
        for q in reach:
            covered_attrs |= index.functions[q].attr_calls

        walker_label = "/".join(sorted(WARMUP_WALKERS))
        findings: List[Finding] = []
        # Coverage is judged PER SITE: a covered registration of the
        # same program name on another class/storage never excuses an
        # uncovered one (see the module docstring's cross-class
        # guarantee).
        for program in sorted(regs):
            for fi, node, storage in regs[program]:
                covered = False
                if storage is not None:
                    kind, name = storage
                    if kind == "self" and fi.cls is not None:
                        covered = (
                            f"{fi.module}:{fi.cls}", name
                        ) in covered_attrs
                    elif kind == "local":
                        covered = (
                            fi.qual in reach
                            and name in index.functions[fi.qual].name_calls
                        )
                if covered:
                    continue
                what = (
                    f"stored in {storage[1]!r}" if storage
                    else "with no visible storage target"
                )
                findings.append(Finding(
                    self.name, fi.path, node.lineno,
                    f"compiled program {program!r} (registered here, "
                    f"{what}) is not statically reachable from any warmup "
                    f"walker ({walker_label}) — its first dispatch will "
                    f"compile on the hot path (the PR 12 page-table "
                    f"class); dispatch it from a walker, or suppress with "
                    f"the reason it is warmed another way",
                ))
        return findings
