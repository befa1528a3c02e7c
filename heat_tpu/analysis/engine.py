"""Shared AST infrastructure for the invariant checker.

One parse per module, one index pass, then every rule family works off the
same :class:`ModuleIndex`: parent links for ancestor queries (is this write
inside a ``with _lock`` block? is this call under an ``if diagnostics._enabled``
guard?), import-alias maps for cross-module call resolution, a per-module
function table, and the *traced-body* set — the functions statically reachable
from jit/shard_map/eval_shape closures, which the trace-purity rules police.

Everything here is stdlib-only: the checker runs as a separate process and
must never pull the JAX backend (or anything else heavy) into itself.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, Iterable, List, Optional, Set, Tuple

# ---------------------------------------------------------------------------
# findings


class Finding:
    """One rule violation: ``rule`` id, repo-relative ``path``, 1-based
    ``line``, human ``message``, and the stripped source ``snippet`` (the
    stable half of a baseline entry — line numbers drift, source lines
    rarely do)."""

    __slots__ = ("rule", "path", "line", "message", "snippet")

    def __init__(self, rule: str, path: str, line: int, message: str, snippet: str = ""):
        self.rule = rule
        self.path = path
        self.line = line
        self.message = message
        self.snippet = snippet

    def key(self) -> Tuple[str, str, str]:
        """The baseline identity: line numbers are excluded so a finding does
        not go stale when unrelated code above it moves."""
        return (self.rule, self.path, self.snippet)

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "snippet": self.snippet,
        }


# ---------------------------------------------------------------------------
# module discovery + index

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)

# Calls that start a trace: a function (or lambda) passed to one of these has
# its body staged by JAX — the trace-purity rules apply to everything
# statically reachable from it. (jax.lax primitives that only *work* inside a
# trace — collectives, axis_index — additionally self-seed the set below.)
TRACE_ENTRIES: Set[Tuple[str, ...]] = {
    ("jax", "jit"),
    ("jax", "vmap"),
    ("jax", "pmap"),
    ("jax", "eval_shape"),
    ("jax", "shard_map"),
    ("jax", "checkpoint"),
    ("jax", "lax", "scan"),
    ("jax", "lax", "while_loop"),
    ("jax", "lax", "fori_loop"),
    ("jax", "lax", "cond"),
    ("jax", "lax", "map"),
    ("jax", "lax", "associative_scan"),
    ("shard_map",),
    ("pallas_call",),
    ("pl", "pallas_call"),
}

# jax.lax primitives that are only legal inside a mesh trace: any function
# that calls one is necessarily a traced body even when the checker cannot see
# who traces it (e.g. an implementation method passed through a dispatcher).
TRACE_ONLY_PRIMITIVES: Set[str] = {
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "pshuffle", "psum_scatter", "ragged_all_to_all", "axis_index", "pcast",
}


def dotted_chain(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """``jax.lax.psum`` -> ("jax", "lax", "psum"); None for non-name chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


class ModuleIndex:
    """Everything the rules need to know about one parsed module."""

    def __init__(self, name: str, path: str, rel_path: str, source: str):
        self.name = name
        self.path = path
        self.rel_path = rel_path
        self.is_package = os.path.basename(path) == "__init__.py"
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.module_aliases: Dict[str, str] = {}   # local name -> dotted module
        self.func_imports: Dict[str, Tuple[str, str]] = {}  # name -> (module, attr)
        self.functions: Dict[str, List[ast.AST]] = {}       # bare name -> defs
        self.toplevel_names: Set[str] = set()
        self.toplevel_containers: Set[str] = set()
        self.toplevel_aliases: Dict[str, Tuple[str, str]] = {}  # x = mod.attr
        self.class_of: Dict[ast.AST, Optional[str]] = {}    # def -> enclosing class
        self._annotate_parents()
        self._index()

    # -- structure -----------------------------------------------------------
    def _annotate_parents(self) -> None:
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                child._ht_parent = node  # type: ignore[attr-defined]

    def parent(self, node: ast.AST) -> Optional[ast.AST]:
        return getattr(node, "_ht_parent", None)

    def ancestors(self, node: ast.AST) -> Iterable[ast.AST]:
        cur = self.parent(node)
        while cur is not None:
            yield cur
            cur = self.parent(cur)

    def enclosing_function(self, node: ast.AST) -> Optional[ast.AST]:
        for anc in self.ancestors(node):
            if isinstance(anc, _FUNC_NODES):
                return anc
        return None

    def snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def finding(self, rule: str, node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 0)
        return Finding(rule, self.rel_path, line, message, self.snippet(line))

    # -- index pass ----------------------------------------------------------
    def _index(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, _FUNC_NODES):
                self.functions.setdefault(node.name, []).append(node)
                cls = None
                for anc in self.ancestors(node):
                    if isinstance(anc, ast.ClassDef):
                        cls = anc.name
                        break
                    if isinstance(anc, _FUNC_NODES):
                        break
                self.class_of[node] = cls
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    self.module_aliases[alias.asname or alias.name.split(".")[0]] = (
                        alias.name if alias.asname else alias.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom):
                base = self._resolve_from(node)
                for alias in node.names:
                    local = alias.asname or alias.name
                    if base is None:
                        continue
                    # `from x import y` may bind a submodule OR a function; we
                    # record both interpretations and let resolution try each.
                    self.module_aliases.setdefault(local, f"{base}.{alias.name}")
                    self.func_imports[local] = (base, alias.name)
        for stmt in self.tree.body:
            self._index_toplevel(stmt)

    def _resolve_from(self, node: ast.ImportFrom) -> Optional[str]:
        if node.level == 0:
            return node.module
        parts = self.name.split(".")
        # for a plain module, level 1 is the containing package; for a
        # package's __init__, level 1 is the package itself
        drop = node.level - 1 if self.is_package else node.level
        base_parts = parts[: len(parts) - drop] if drop else parts
        if node.module:
            base_parts += node.module.split(".")
        return ".".join(base_parts) if base_parts else None

    def _index_toplevel(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
            value = getattr(stmt, "value", None)
            for tgt in targets:
                if not isinstance(tgt, ast.Name):
                    continue
                self.toplevel_names.add(tgt.id)
                if value is not None and _is_container_ctor(value):
                    self.toplevel_containers.add(tgt.id)
                if isinstance(value, ast.Attribute):
                    chain = dotted_chain(value)
                    if chain and len(chain) == 2:
                        self.toplevel_aliases[tgt.id] = (chain[0], chain[1])
        elif isinstance(stmt, (ast.If, ast.Try, ast.With)):
            for sub in ast.iter_child_nodes(stmt):
                if isinstance(sub, ast.stmt):
                    self._index_toplevel(sub)


def _is_container_ctor(node: ast.expr) -> bool:
    if isinstance(node, (ast.Dict, ast.List, ast.Set, ast.ListComp, ast.DictComp)):
        return True
    if isinstance(node, ast.Call):
        chain = dotted_chain(node.func)
        if chain and chain[-1] in {
            "dict", "list", "set", "deque", "OrderedDict", "defaultdict", "Counter",
        }:
            return True
    return False


# ---------------------------------------------------------------------------
# universe


class Universe:
    """Every parsed module of the package, with cross-module call resolution
    and the traced-body set."""

    def __init__(self, package_root: str):
        self.package_root = os.path.abspath(package_root)
        self.repo_root = os.path.dirname(self.package_root)
        self.modules: Dict[str, ModuleIndex] = {}
        for path in sorted(self._iter_py_files()):
            rel = os.path.relpath(path, self.repo_root).replace(os.sep, "/")
            name = rel[:-3].replace("/", ".")
            if name.endswith(".__init__"):
                name = name[: -len(".__init__")]
            self._load(name, path, rel)
        self.traced: Dict[str, Set[ast.AST]] = {}
        self._build_traced_sets()

    def _iter_py_files(self):
        for dirpath, dirnames, filenames in os.walk(self.package_root):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)

    def _load(self, name: str, path: str, rel: str) -> None:
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
        self.modules[name] = ModuleIndex(name, path, rel, source)

    # -- call resolution -----------------------------------------------------
    def resolve_call(self, mod: ModuleIndex, call: ast.Call) -> List[Tuple[ModuleIndex, ast.AST]]:
        """Resolve a call to candidate function defs — same-module names,
        ``module_alias.fn`` attributes into sibling package modules, and
        ``self.method`` within the enclosing class. Unresolvable calls return
        [] (the walk is deliberately conservative)."""
        func = call.func
        if isinstance(func, ast.Name):
            name = func.id
            if name in mod.functions:
                return [(mod, d) for d in mod.functions[name]]
            target = mod.func_imports.get(name)
            if target:
                return self._resolve_in_module(target[0], target[1])
            return []
        if isinstance(func, ast.Attribute):
            chain = dotted_chain(func)
            if chain is None:
                if isinstance(func.value, ast.Name) and func.value.id == "self":
                    return [(mod, d) for d in mod.functions.get(func.attr, [])]
                return []
            if chain[0] == "self":
                return [(mod, d) for d in mod.functions.get(chain[-1], [])]
            alias = mod.module_aliases.get(chain[0])
            if alias and len(chain) == 2:
                return self._resolve_in_module(alias, chain[1])
        return []

    def _resolve_in_module(self, modname: str, attr: str, depth: int = 0
                           ) -> List[Tuple[ModuleIndex, ast.AST]]:
        target = self.modules.get(modname)
        if target is None or depth > 2:
            return []
        if attr in target.functions:
            return [(target, d) for d in target.functions[attr]]
        reexport = target.func_imports.get(attr)
        if reexport:
            return self._resolve_in_module(reexport[0], reexport[1], depth + 1)
        alias = target.toplevel_aliases.get(attr)
        if alias:
            inner = target.module_aliases.get(alias[0])
            if inner:
                return self._resolve_in_module(inner, alias[1], depth + 1)
        return []

    # -- traced-body discovery ----------------------------------------------
    def _build_traced_sets(self) -> None:
        # The stdlib-only telemetry modules are a hard boundary: they import
        # no jax, so nothing inside them can contribute operations to a trace
        # — their internals are host-side by construction (and separately
        # policed by the import-contract rules). Without the cut, the
        # trace-time telemetry hooks (documented: collectives record at trace
        # time) would drag the whole diagnostics/resilience machinery into
        # the traced set and drown the purity rules in noise.
        from .rules_imports import STDLIB_ONLY

        roots: List[Tuple[ModuleIndex, ast.AST]] = []
        for mod in self.modules.values():
            roots.extend(self._module_roots(mod))
        seen: Set[Tuple[str, int]] = set()
        queue = list(roots)
        while queue:
            mod, fn = queue.pop()
            if mod.name in STDLIB_ONLY:
                continue
            key = (mod.name, id(fn))
            if key in seen:
                continue
            seen.add(key)
            self.traced.setdefault(mod.name, set()).add(fn)
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    for tmod, tfn in self.resolve_call(mod, node):
                        queue.append((tmod, tfn))

    def _module_roots(self, mod: ModuleIndex) -> List[Tuple[ModuleIndex, ast.AST]]:
        roots: List[Tuple[ModuleIndex, ast.AST]] = []

        def local_def(name_node: ast.expr) -> Optional[ast.AST]:
            if isinstance(name_node, ast.Name) and name_node.id in mod.functions:
                return mod.functions[name_node.id][0]
            return None

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                chain = dotted_chain(node.func)
                if chain in TRACE_ENTRIES or (
                    chain and len(chain) > 1 and chain[-2:] in {c[-2:] for c in TRACE_ENTRIES if len(c) >= 2}
                    and chain[0] in mod.module_aliases
                ):
                    for arg in list(node.args) + [kw.value for kw in node.keywords]:
                        fn = local_def(arg)
                        if fn is not None:
                            roots.append((mod, fn))
                        elif isinstance(arg, ast.Lambda):
                            roots.append((mod, arg))
            elif isinstance(node, _FUNC_NODES):
                # lookup()-protocol convention: functions RETURNED by a `build`
                # callback are the traced program body (the executor jits the
                # first tuple element); and any function calling a trace-only
                # jax.lax primitive is a traced body by construction.
                if node.name == "build":
                    for sub in ast.walk(node):
                        if isinstance(sub, ast.Return) and sub.value is not None:
                            cand = sub.value
                            if isinstance(cand, ast.Tuple) and cand.elts:
                                cand = cand.elts[0]
                            fn = local_def(cand)
                            if fn is not None:
                                roots.append((mod, fn))
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call):
                        chain = dotted_chain(sub.func)
                        if (
                            chain
                            and len(chain) >= 2
                            and chain[-2] == "lax"
                            and chain[-1] in TRACE_ONLY_PRIMITIVES
                            # attribute the seed to the INNERMOST enclosing
                            # function — an outer host-side orchestrator that
                            # merely defines a traced closure is not traced
                            and mod.enclosing_function(sub) is node
                        ):
                            roots.append((mod, node))
                            break
        return roots

    def is_traced(self, mod: ModuleIndex, fn: ast.AST) -> bool:
        return fn in self.traced.get(mod.name, ())


# ---------------------------------------------------------------------------
# stdlib classification (for the import-contract rules)

_STDLIB = set(getattr(sys, "stdlib_module_names", ())) | {"__future__"}


def is_stdlib(module: Optional[str]) -> bool:
    if not module:
        return False
    return module.split(".")[0] in _STDLIB


# ---------------------------------------------------------------------------
# orchestration


def run_analysis(package_root: Optional[str] = None) -> Tuple[List[Finding], "object"]:
    """Run every rule family over the package. Returns ``(findings, universe)``
    — findings are pragma-filtered and sorted, with pragma misuse (missing
    reason, unknown rule, unused pragma) appended as findings of their own."""
    from . import pragmas, rules

    if package_root is None:
        package_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    uni = Universe(package_root)
    raw: List[Finding] = []
    for rule_fn in rules.RULE_RUNNERS:
        raw.extend(rule_fn(uni))
    pragma_table = {name: pragmas.collect(mod) for name, mod in uni.modules.items()}
    kept: List[Finding] = []
    for f in raw:
        mod = next((m for m in uni.modules.values() if m.rel_path == f.path), None)
        if mod is not None and pragmas.suppressed(pragma_table[mod.name], f):
            continue
        kept.append(f)
    for name, table in pragma_table.items():
        kept.extend(pragmas.misuse_findings(uni.modules[name], table))
    kept.sort(key=lambda f: (f.path, f.line, f.rule))
    return kept, uni
