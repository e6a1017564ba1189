"""Project-wide symbol resolution and call graph for the flow rules.

The intra-file rules (dispatch-readback's original incarnation,
lock-discipline) deliberately stopped at file boundaries; PR 12's
compile-watch incident showed the contracts that actually break are the
CROSS-module ones — a program registered in one method and warmed (or
not) three calls away. This module gives the suite one shared
whole-tree view: module import resolution, per-function call summaries,
light attribute-type inference, and reachability — built once per run
over the same mtime-keyed AST cache the per-file rules parse through.

Resolution semantics (documented in docs/static_analysis.md; the rules
riding on this inherit them):

- **Edges followed**: bare-name calls to module functions and
  from-imports; ``self.method()`` within a class; ``module.func()`` /
  ``module.Class()`` through import aliases (function-level imports
  included — the engine imports lazily); ``ClassName(...)`` to
  ``__init__``; ``self.attr.m()`` and ``local.m()`` where the
  attribute/local's class is inferred (below).
- **Type inference**: an attribute assigned a direct constructor call
  (``self._prefix = prefix_cache_mod.PrefixCache(...)``) gets that
  class; a factory method whose returns are constructor calls
  propagates its class to ``self.x = self._build_...()`` call sites;
  a constructor parameter stored as ``self.attr = param`` picks up the
  classes of the arguments callers actually pass
  (``DraftModelProposer(self._draft)``). One candidate set per
  attribute — a union over every observed binding, never a guess.
- **Off-thread discipline**: nested ``def``s and ``lambda``s are NOT
  walked — closures are handed to threads/executors/callbacks often
  enough that neither their calls nor their bodies can be attributed
  to the enclosing function (the same assumption the intra-file rules
  make).
- **Blind spots, by design**: calls through function-valued attributes
  (``self._extend_fn(...)`` dispatches a compiled program — recorded
  as an *attribute-call event* for warmup-coverage, never an edge);
  inheritance (the tree's classes are flat); re-exported names;
  containers of callables.

Function qualnames are ``<dotted.module>:<Class>.<method>`` or
``<dotted.module>:<func>``.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.genai_lint.core import iter_py_files, load_source

_FUNC_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef)
_SAME_THREAD_SKIP = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def walk_same_thread(fn: ast.AST) -> Iterable[ast.AST]:
    """Walk a function's nodes WITHOUT descending into nested defs or
    lambdas (shared off-thread discipline — see module docstring)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, _SAME_THREAD_SKIP):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def module_name_for(rel: str) -> str:
    """Dotted module name for a repo-relative path
    (``a/b/c.py`` → ``a.b.c``, ``a/b/__init__.py`` → ``a.b``)."""
    parts = list(pathlib.PurePosixPath(rel.replace("\\", "/")).parts)
    if parts[-1] == "__init__.py":
        parts = parts[:-1]
    else:
        parts[-1] = parts[-1][:-3] if parts[-1].endswith(".py") else parts[-1]
    return ".".join(parts)


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """``a.b.c`` → ["a", "b", "c"]; None when the base isn't a Name."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


@dataclasses.dataclass
class FunctionInfo:
    qual: str
    module: str
    cls: Optional[str]  # bare class name, None for module functions
    name: str
    path: str  # index-root-relative path
    node: ast.AST
    callees: Set[str] = dataclasses.field(default_factory=set)
    #: (class_qual, attr) for every ``self.<attr>(...)`` call — the
    #: coverage events function-valued attributes produce.
    attr_calls: Set[Tuple[str, str]] = dataclasses.field(default_factory=set)
    #: bare names called (``wrap("p", ...)`` on a local alias).
    name_calls: Set[str] = dataclasses.field(default_factory=set)


@dataclasses.dataclass
class ClassInfo:
    qual: str  # "module:Class"
    module: str
    name: str
    node: ast.ClassDef
    methods: Dict[str, FunctionInfo] = dataclasses.field(default_factory=dict)
    #: attr -> candidate class quals
    attr_types: Dict[str, Set[str]] = dataclasses.field(default_factory=dict)
    #: __init__ param name -> attrs it is stored into (self.x = param)
    param_attrs: Dict[str, Set[str]] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class ModuleInfo:
    name: str
    path: str
    tree: ast.AST
    #: import alias -> dotted module ("np" -> "numpy")
    imports: Dict[str, str] = dataclasses.field(default_factory=dict)
    #: from-import alias -> (module, symbol)
    symbols: Dict[str, Tuple[str, str]] = dataclasses.field(default_factory=dict)
    functions: Dict[str, FunctionInfo] = dataclasses.field(default_factory=dict)
    classes: Dict[str, ClassInfo] = dataclasses.field(default_factory=dict)
    imports_jax: bool = False


class ProjectIndex:
    """The whole-tree view: modules, functions, classes, call edges."""

    def __init__(self) -> None:
        self.modules: Dict[str, ModuleInfo] = {}
        self.functions: Dict[str, FunctionInfo] = {}
        self.classes: Dict[str, ClassInfo] = {}

    # ------------------------------------------------------------------ #
    # construction

    @classmethod
    def build(
        cls,
        root: pathlib.Path,
        files: Optional[Sequence[pathlib.Path]] = None,
    ) -> "ProjectIndex":
        index = cls()
        for path in (files if files is not None else iter_py_files(root)):
            _, tree, _ = load_source(path)
            if tree is None:
                continue  # unparseable: the per-file pass reports it
            rel = (
                str(path.relative_to(root))
                if path.is_absolute() and path.is_relative_to(root)
                else str(path)
            )
            index._add_module(module_name_for(rel), rel, tree)
        index._infer_types()
        index._resolve_calls()
        return index

    def _add_module(self, name: str, rel: str, tree: ast.AST) -> None:
        mod = ModuleInfo(name=name, path=rel, tree=tree)
        # A package __init__ IS its own package (module_name_for maps
        # a/b/__init__.py to "a.b" already) — anchoring its relative
        # imports at the parent would resolve `from . import x` one
        # level too high and silently drop those call edges.
        if rel.replace("\\", "/").endswith("__init__.py"):
            package = name
        else:
            package = name.rpartition(".")[0]
        for node in ast.walk(tree):  # function-level imports included
            if isinstance(node, ast.Import):
                for alias in node.names:
                    target = alias.name
                    if target == "jax" or target.startswith("jax."):
                        mod.imports_jax = True
                    bound = alias.asname or target.split(".")[0]
                    # `import a.b.c` binds `a`; `import a.b.c as x` binds
                    # x to the full path
                    mod.imports[bound] = target if alias.asname else target.split(".")[0]
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:  # relative: resolve against this package
                    pkg_parts = package.split(".") if package else []
                    # level 1 = the module's own package; each extra
                    # level walks one package up
                    keep = len(pkg_parts) - (node.level - 1)
                    anchor = pkg_parts[:keep] if keep > 0 else []
                    base = ".".join(anchor + ([base] if base else []))
                if base == "jax" or base.startswith("jax."):
                    mod.imports_jax = True
                for alias in node.names:
                    bound = alias.asname or alias.name
                    # `from pkg import mod` may bind a submodule; record
                    # both readings and let resolution pick whichever
                    # exists in the index.
                    mod.symbols[bound] = (base, alias.name)
        for node in ast.iter_child_nodes(tree):
            if isinstance(node, _FUNC_DEFS):
                info = FunctionInfo(
                    qual=f"{name}:{node.name}", module=name, cls=None,
                    name=node.name, path=rel, node=node,
                )
                mod.functions[node.name] = info
                self.functions[info.qual] = info
            elif isinstance(node, ast.ClassDef):
                cinfo = ClassInfo(
                    qual=f"{name}:{node.name}", module=name,
                    name=node.name, node=node,
                )
                for item in ast.iter_child_nodes(node):
                    if isinstance(item, _FUNC_DEFS):
                        fi = FunctionInfo(
                            qual=f"{name}:{node.name}.{item.name}",
                            module=name, cls=node.name, name=item.name,
                            path=rel, node=item,
                        )
                        cinfo.methods[item.name] = fi
                        self.functions[fi.qual] = fi
                mod.classes[node.name] = cinfo
                self.classes[cinfo.qual] = cinfo
        self.modules[name] = mod

    # ------------------------------------------------------------------ #
    # symbol resolution helpers

    def _resolve_module(self, mod: ModuleInfo, alias: str) -> Optional[str]:
        """Dotted module an alias refers to, if it's in the index."""
        if alias in mod.imports:
            target = mod.imports[alias]
            if target in self.modules:
                return target
        if alias in mod.symbols:
            base, sym = mod.symbols[alias]
            # `from pkg import mod_name [as alias]`
            dotted = f"{base}.{sym}" if base else sym
            if dotted in self.modules:
                return dotted
        return None

    def _resolve_class_name(
        self, mod: ModuleInfo, name: str
    ) -> Optional[str]:
        """Class qual a bare name refers to in a module's namespace."""
        if name in mod.classes:
            return mod.classes[name].qual
        if name in mod.symbols:
            base, sym = mod.symbols[name]
            target = self.modules.get(base)
            if target is not None and sym in target.classes:
                return target.classes[sym].qual
        return None

    def _resolve_chain_callable(
        self, mod: ModuleInfo, parts: List[str]
    ) -> Tuple[Optional[str], Optional[str]]:
        """Resolve a dotted call chain rooted at a module alias to
        (function qual, None) or (None, class qual)."""
        target_mod = self._resolve_module(mod, parts[0])
        i = 1
        while (
            target_mod is not None
            and i < len(parts) - 1
            and f"{target_mod}.{parts[i]}" in self.modules
        ):
            target_mod = f"{target_mod}.{parts[i]}"
            i += 1
        if target_mod is None or i != len(parts) - 1:
            return None, None
        leaf = parts[i]
        target = self.modules[target_mod]
        if leaf in target.functions:
            return target.functions[leaf].qual, None
        if leaf in target.classes:
            return None, target.classes[leaf].qual
        return None, None

    def _expr_types(
        self,
        mod: ModuleInfo,
        cinfo: Optional[ClassInfo],
        locals_: Dict[str, Set[str]],
        expr: ast.AST,
        returns: Optional[Dict[str, Set[str]]] = None,
    ) -> Set[str]:
        """Candidate class quals for an expression: direct constructor
        calls, typed locals, typed self-attributes, and (when
        ``returns`` is supplied) factory-method calls."""
        if isinstance(expr, ast.Call):
            func = expr.func
            if isinstance(func, ast.Name):
                q = self._resolve_class_name(mod, func.id)
                return {q} if q else set()
            parts = _attr_chain(func)
            if parts is None:
                return set()
            if parts[0] == "self" and cinfo is not None and len(parts) == 2:
                # self._factory(...): one-step return inference
                if returns is not None:
                    return set(returns.get(f"{cinfo.qual}.{parts[1]}", ()))
                return set()
            _, class_qual = self._resolve_chain_callable(mod, parts)
            return {class_qual} if class_qual else set()
        if isinstance(expr, ast.Name):
            return set(locals_.get(expr.id, ()))
        parts = _attr_chain(expr)
        if (
            parts is not None
            and parts[0] == "self"
            and cinfo is not None
            and len(parts) == 2
        ):
            return set(cinfo.attr_types.get(parts[1], ()))
        return set()

    # ------------------------------------------------------------------ #
    # type inference

    def _infer_types(self) -> None:
        # Pass 0: factory returns — method -> classes its `return
        # Ctor(...)` statements build (no transitive chaining).
        factory_returns: Dict[str, Set[str]] = {}
        for fi in self.functions.values():
            mod = self.modules[fi.module]
            out: Set[str] = set()
            for node in walk_same_thread(fi.node):
                if isinstance(node, ast.Return) and node.value is not None:
                    out |= self._expr_types(mod, None, {}, node.value)
            if out:
                factory_returns[fi.qual] = out

        # Pass 1: self.attr = <typed expr> within each class, plus
        # self.attr = <param> pending bindings for pass 2.
        for cinfo in self.classes.values():
            mod = self.modules[cinfo.module]
            for fi in cinfo.methods.values():
                params = {
                    a.arg for a in (
                        fi.node.args.posonlyargs + fi.node.args.args
                        + fi.node.args.kwonlyargs
                    )
                }
                for node in walk_same_thread(fi.node):
                    if not isinstance(node, ast.Assign):
                        continue
                    for tgt in node.targets:
                        parts = _attr_chain(tgt)
                        if (
                            parts is None or len(parts) != 2
                            or parts[0] != "self"
                        ):
                            continue
                        attr = parts[1]
                        if (
                            isinstance(node.value, ast.Name)
                            and node.value.id in params
                        ):
                            cinfo.param_attrs.setdefault(
                                node.value.id, set()
                            ).add(attr)
                            continue
                        types = self._expr_types(
                            mod, cinfo, {}, node.value,
                            returns=factory_returns,
                        )
                        if types:
                            cinfo.attr_types.setdefault(attr, set()).update(
                                types
                            )

        # Pass 2: constructor-parameter propagation — a ctor call whose
        # argument types are known binds the receiving class's
        # param-stored attributes (DraftModelProposer(self._draft)).
        for fi in self.functions.values():
            mod = self.modules[fi.module]
            cinfo = self.classes.get(f"{fi.module}:{fi.cls}") if fi.cls else None
            for node in walk_same_thread(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                ctor: Optional[str] = None
                if isinstance(node.func, ast.Name):
                    ctor = self._resolve_class_name(mod, node.func.id)
                else:
                    parts = _attr_chain(node.func)
                    if parts is not None and parts[0] != "self":
                        _, ctor = self._resolve_chain_callable(mod, parts)
                if ctor is None:
                    continue
                target = self.classes[ctor]
                init = target.methods.get("__init__")
                if init is None or not target.param_attrs:
                    continue
                pos = [
                    a.arg for a in (
                        init.node.args.posonlyargs + init.node.args.args
                    )
                ][1:]  # drop self
                bindings: List[Tuple[str, ast.AST]] = []
                bindings += list(zip(pos, node.args))
                bindings += [
                    (kw.arg, kw.value) for kw in node.keywords if kw.arg
                ]
                for pname, arg in bindings:
                    attrs = target.param_attrs.get(pname)
                    if not attrs:
                        continue
                    types = self._expr_types(mod, cinfo, {}, arg)
                    if not types:
                        continue
                    for attr in attrs:
                        target.attr_types.setdefault(attr, set()).update(
                            types
                        )

    # ------------------------------------------------------------------ #
    # call edges

    def _function_locals(
        self, mod: ModuleInfo, cinfo: Optional[ClassInfo], fn: ast.AST
    ) -> Dict[str, Set[str]]:
        """name -> candidate class quals for locals assigned a typed
        expression anywhere in the function (order-insensitive union —
        good enough for edge discovery, documented as such)."""
        locals_: Dict[str, Set[str]] = {}
        for node in walk_same_thread(fn):
            if not isinstance(node, ast.Assign):
                continue
            for tgt in node.targets:
                if not isinstance(tgt, ast.Name):
                    continue
                types = self._expr_types(mod, cinfo, {}, node.value)
                if (
                    not types
                    and isinstance(node.value, ast.Attribute)
                ):
                    parts = _attr_chain(node.value)
                    if (
                        parts is not None and parts[0] == "self"
                        and cinfo is not None and len(parts) == 2
                    ):
                        types = set(cinfo.attr_types.get(parts[1], ()))
                if types:
                    locals_.setdefault(tgt.id, set()).update(types)
        return locals_

    def _resolve_calls(self) -> None:
        for fi in self.functions.values():
            mod = self.modules[fi.module]
            cinfo = (
                self.classes.get(f"{fi.module}:{fi.cls}") if fi.cls else None
            )
            locals_ = self._function_locals(mod, cinfo, fi.node)
            for node in walk_same_thread(fi.node):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                if isinstance(func, ast.Name):
                    fi.name_calls.add(func.id)
                    if func.id in mod.functions:
                        fi.callees.add(mod.functions[func.id].qual)
                        continue
                    if func.id in mod.symbols:
                        base, sym = mod.symbols[func.id]
                        target = self.modules.get(base)
                        if target is not None and sym in target.functions:
                            fi.callees.add(target.functions[sym].qual)
                            continue
                    class_qual = self._resolve_class_name(mod, func.id)
                    if class_qual:
                        init = self.classes[class_qual].methods.get("__init__")
                        if init is not None:
                            fi.callees.add(init.qual)
                    continue
                parts = _attr_chain(func)
                if parts is None:
                    continue
                if parts[0] == "self" and cinfo is not None:
                    if len(parts) == 2:
                        fi.attr_calls.add((cinfo.qual, parts[1]))
                        if parts[1] in cinfo.methods:
                            fi.callees.add(cinfo.methods[parts[1]].qual)
                        continue
                    if len(parts) == 3:
                        for tq in cinfo.attr_types.get(parts[1], ()):
                            m = self.classes[tq].methods.get(parts[2])
                            if m is not None:
                                fi.callees.add(m.qual)
                        continue
                    continue
                if len(parts) == 2 and parts[0] in locals_:
                    for tq in locals_[parts[0]]:
                        m = self.classes[tq].methods.get(parts[1])
                        if m is not None:
                            fi.callees.add(m.qual)
                    continue
                fn_qual, class_qual = self._resolve_chain_callable(mod, parts)
                if fn_qual:
                    fi.callees.add(fn_qual)
                elif class_qual:
                    init = self.classes[class_qual].methods.get("__init__")
                    if init is not None:
                        fi.callees.add(init.qual)

    # ------------------------------------------------------------------ #
    # queries

    def functions_named(self, names: Set[str]) -> List[FunctionInfo]:
        return [f for f in self.functions.values() if f.name in names]

    def reachable(self, roots: Iterable[str]) -> Set[str]:
        """Every function qual reachable from the given root quals
        (roots included when they exist in the index)."""
        seen: Set[str] = set()
        stack = [q for q in roots if q in self.functions]
        while stack:
            q = stack.pop()
            if q in seen:
                continue
            seen.add(q)
            stack.extend(self.functions[q].callees - seen)
        return seen


# --------------------------------------------------------------------------- #
# Per-run memoization: the three project rules in one suite run share a
# single index (one parse + one summary pass), invalidated when any
# indexed file's mtime/size changes.

_INDEX_CACHE: Dict[str, Tuple[Tuple[Tuple[str, int, int], ...], ProjectIndex]] = {}


def get_index(root: pathlib.Path) -> ProjectIndex:
    key = str(root.resolve())
    files = list(iter_py_files(root))
    stamp: List[Tuple[str, int, int]] = []
    for f in files:
        try:
            st = f.stat()
            stamp.append((str(f), st.st_mtime_ns, st.st_size))
        except OSError:
            stamp.append((str(f), -1, -1))
    frozen = tuple(stamp)
    hit = _INDEX_CACHE.get(key)
    if hit is not None and hit[0] == frozen:
        return hit[1]
    index = ProjectIndex.build(root, files)
    _INDEX_CACHE[key] = (frozen, index)
    return index
