"""Import-contract rules.

JAX-free tooling (the telemetry merge, the ops exporter/parser, this checker)
loads the telemetry stack by file path, so ``diagnostics`` / ``profiler`` /
``resilience`` / ``_scheduler`` commit (in their module docstrings) to
importing only the stdlib at module level. ``import-nonstdlib`` enforces that
statically; ``tests/test_analysis.py`` proves it dynamically with a
``sys.meta_path`` hook. Relative imports *within* the stdlib-only set are
fine (``resilience`` imports ``diagnostics``); anything else — ``jax``,
``numpy``, the package itself — at module level is an error. Imports inside
function bodies are the sanctioned lazy form and are not flagged (unless the
function is a traced body — that is ``trace-lazy-import``'s job).

``import-backend-touch`` guards the order of the package bring-up
(``core/_bootstrap.py``): ``heat_tpu.core`` calls ``_bootstrap.run()`` before
it imports anything else, and no module that loads before that call has
joined ``jax.distributed`` (``_bootstrap`` and what it imports at module
level) may create the XLA backend at module level. The runtime twin in
``tests/test_analysis.py`` imports the package with JAX's backend factory
patched to raise until the distributed client exists.
"""

from __future__ import annotations

import ast
from typing import List, Set

from .engine import Finding, ModuleIndex, Universe, dotted_chain, is_stdlib

# The stdlib-only-at-load set (module docstrings state the contract).
STDLIB_ONLY: Set[str] = {
    "heat_tpu.core.diagnostics",
    "heat_tpu.core.profiler",
    "heat_tpu.core.resilience",
    "heat_tpu.core._scheduler",
    "heat_tpu.core.telemetry",  # merge must run in jax-free tooling
    "heat_tpu.core.supervision",  # _scheduler imports it; jax only lazily
    "heat_tpu.core.ops",  # exporter/parser must run jax-free; executor lazily
    "heat_tpu.core.forensics",  # record store reads shards jax-free too
    "heat_tpu.analysis",  # the checker polices itself: it must stay light
}
_ANALYSIS_PREFIX = "heat_tpu.analysis"


def _in_contract(name: str) -> bool:
    return name in STDLIB_ONLY or name.startswith(_ANALYSIS_PREFIX)


def _toplevel_imports(mod: ModuleIndex):
    """Module-level import statements, descending into top-level If/Try
    (conditional imports still run at load) but not into functions."""
    stack = list(mod.tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            yield node
        elif isinstance(node, (ast.If, ast.Try, ast.With)):
            skip = False
            if isinstance(node, ast.If):
                t = node.test
                if isinstance(t, ast.Name) and t.id == "TYPE_CHECKING":
                    skip = True  # never executes at runtime
                if isinstance(t, ast.Attribute) and t.attr == "TYPE_CHECKING":
                    skip = True
            if not skip:
                for sub in ast.iter_child_nodes(node):
                    if isinstance(sub, ast.stmt):
                        stack.append(sub)


def run(uni: Universe) -> List[Finding]:
    out: List[Finding] = _run_bring_up(uni)
    for name in sorted(STDLIB_ONLY | {
        m for m in uni.modules if m.startswith(_ANALYSIS_PREFIX)
    }):
        mod = uni.modules.get(name)
        if mod is None:
            continue
        for node in _toplevel_imports(mod):
            out.extend(_check_import(uni, mod, node))
    return out


def _check_import(uni: Universe, mod: ModuleIndex, node: ast.AST) -> List[Finding]:
    out: List[Finding] = []
    if isinstance(node, ast.Import):
        for alias in node.names:
            if not is_stdlib(alias.name):
                out.append(mod.finding(
                    "import-nonstdlib", node,
                    f"{mod.name} is stdlib-only at module load but imports "
                    f"{alias.name!r} at top level",
                ))
    elif isinstance(node, ast.ImportFrom):
        target = mod._resolve_from(node)
        if target is None:
            return out
        if is_stdlib(target):
            return out
        if node.level > 0:
            # relative import: allowed when every imported name stays inside
            # the stdlib-only set (the bootstrap's diagnostics/resilience web)
            ok = _in_contract(target) or all(
                _in_contract(f"{target}.{alias.name}") for alias in node.names
            )
            if ok:
                return out
        out.append(mod.finding(
            "import-nonstdlib", node,
            f"{mod.name} is stdlib-only at module load but imports "
            f"{target!r} at top level",
        ))
    return out


_BOOTSTRAP = "heat_tpu.core._bootstrap"
# jax.<chain>() calls that create the XLA backend (or must come before it)
_BACKEND_CALLS = {
    ("devices",), ("default_backend",), ("local_devices",), ("device_count",),
    ("local_device_count",), ("process_count",), ("process_index",),
    ("distributed", "initialize"),
}


def _load_time_calls(mod: ModuleIndex):
    """Call nodes that run when the module loads (function bodies excluded)."""
    stack = list(mod.tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(node, ast.Call):
            yield node
        stack.extend(ast.iter_child_nodes(node))


def _run_bring_up(uni: Universe) -> List[Finding]:
    core = uni.modules.get("heat_tpu.core")
    if core is None or _BOOTSTRAP not in uni.modules:
        return []
    out: List[Finding] = []
    body = [s for s in core.tree.body
            if not (isinstance(s, ast.Expr) and isinstance(s.value, ast.Constant))]
    head = body[:2] + [None, None]
    call = getattr(head[1], "value", None)
    if not (isinstance(head[0], ast.ImportFrom)
            and [a.name for a in head[0].names] == ["_bootstrap"]
            and isinstance(call, ast.Call)
            and dotted_chain(call.func) == ("_bootstrap", "run")):
        out.append(core.finding(
            "import-backend-touch", head[0] or core.tree,
            "heat_tpu.core must import _bootstrap and call _bootstrap.run() "
            "before it imports anything else",
        ))
    early, todo = {"heat_tpu", "heat_tpu.core"}, [_BOOTSTRAP]
    while todo:  # what _bootstrap pulls in at module level loads before run()
        name = todo.pop()
        if name in early or name not in uni.modules:
            continue
        early.add(name)
        for node in _toplevel_imports(uni.modules[name]):
            if isinstance(node, ast.Import):
                todo.extend(alias.name for alias in node.names)
            elif (target := uni.modules[name]._resolve_from(node)) is not None:
                todo.append(target)
                todo.extend(f"{target}.{alias.name}" for alias in node.names)
    for name in sorted(early & set(uni.modules)):
        mod = uni.modules[name]
        for call in _load_time_calls(mod):
            chain = dotted_chain(call.func)
            if (chain and mod.module_aliases.get(chain[0]) == "jax"
                    and chain[1:] in _BACKEND_CALLS):
                out.append(mod.finding(
                    "import-backend-touch", call,
                    f"{name} loads before the bring-up has joined "
                    f"jax.distributed but calls {'.'.join(chain)}() at module level",
                ))
    return out
