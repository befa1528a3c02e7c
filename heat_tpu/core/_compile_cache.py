"""Persistent per-signature compile cache + AOT warmup (cold-start elimination).

A fresh serving process used to pay full trace + XLA-compile cost for every
signature on its first request — multi-second p99 for the first minutes after
every restart, exactly what PR 14's elastic restarts made routine.  This
module closes that gap with two cooperating layers (ISSUE 15):

1. **Persistent signature cache** (``HEAT_TPU_EXEC_CACHE=<dir>``): a JSON
   index (``index.json``, schema ``heat-tpu-compile-cache/1``) plus a
   content-addressed blob directory (``blobs/<sha256>.bin``) — the
   ``dispatch_baseline.json`` pattern.  Each entry maps a **signature
   fingerprint** (the sha256 of the signature's canonical JSON *replay spec*
   — op names, avals, splits, kwargs, mesh shape: everything
   process-portable, nothing identity-keyed) to the spec itself and,
   when the backend supports executable serialization, a serialized
   compiled artifact produced via the ``jax.stages`` AOT path
   (``jit(...).lower(...).compile()`` → ``serialize_executable.serialize``).
   With the cache armed, a :class:`~._executor._Program`'s first call
   consults :func:`load_program`: a fingerprint-matched artifact is
   deserialized and installed in place of the jit build — zero trace, zero
   XLA compile.  Every write goes through ``resilience.atomic_write``;
   every read re-verifies the blob against its content address and any
   mismatch (truncation, bit-rot, unpicklable payload, backend refusal) is
   a **typed rejection** — a :class:`CompileCacheCorrupt` recorded on the
   always-on resilience event stream (kind ``cache-corrupt``) and counted,
   after which the executor simply recompiles.  A corrupt cache can slow a
   boot down; it can never break one.

2. **AOT warmup** (``ht.executor_warmup(path)``): replays the recorded
   top-K signature specs — ordered by (hits desc, label asc), the same
   deterministic order ``executor_stats(top=N)`` reports — through the real
   dispatch layer at boot: staged ``l``/``r``/``c`` specs re-enter their
   wrappers over zeros arrays of the recorded layout, fused-graph specs
   rebuild an identically-shaped :class:`~._executor.Deferred` graph
   (resolving the same ``jax.numpy`` objects by name, pinning the recorded
   emission set with warmup holders) and force it.  Because replay drives
   the PUBLIC dispatch path, the executor's signature table ends up keyed
   exactly as live traffic will key it — warmed programs are replay hits
   from the first request.  Each replayed compile either loads its artifact
   (layer 1) or recompiles; the recompiles hit JAX's persistent
   compilation cache (below).  ``ht.executor_save_warmup(path)``
   records the manifest (and artifacts) from a warm process.

**JAX's own persistent compilation cache** is always on and is placed once,
by the package bring-up (``_bootstrap.place_jax_cache``).  JAX's own
size/time thresholds decide what persists.  ``HEAT_TPU_EXEC_CACHE`` is
memoised at import; :func:`reload` (called from ``ht.reload_env_knobs`` /
``clear_executor_cache``) is the documented re-read point for in-process
flips.

Observability: ``executor.aot_load`` / ``executor.cache_reject`` /
``warmup.replayed`` / ``warmup.failed`` diagnostics counters, fallback
events at sites ``executor.compile_cache`` / ``executor.warmup``, and
``executor.warmup``/``executor.compile_cache`` resilience events
(``warmup-complete`` / ``cache-corrupt``) on the always-on stream — see
doc/source/observability.rst.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import threading
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental import serialize_executable as _se

from . import diagnostics, resilience

__all__ = [
    "CompileCacheCorrupt", "armed", "cache_dir", "reload",
    "load_program", "executor_save_warmup", "executor_warmup",
]

SCHEMA = "heat-tpu-compile-cache/1"

#: default number of top signatures saved/replayed when the caller gives none
DEFAULT_TOP = 32


class CompileCacheCorrupt(RuntimeError):
    """A persistent-cache artifact failed verification (truncated blob, hash
    mismatch, unpicklable payload, undeserializable executable) or the index
    itself is unreadable.  Never propagates out of a dispatch: the loader
    records it (resilience event kind ``cache-corrupt`` + an
    ``executor.compile_cache`` fallback) and the executor recompiles."""


# ---------------------------------------------------------------------------
# memoised knobs.  Thread-safety: _dir / the in-memory index mutate under
# _lock; reload() is the documented re-read point (ht.reload_env_knobs).
_lock = threading.Lock()
_dir: Optional[str] = None
_index: Optional[Dict[str, Any]] = None   # fingerprint -> entry (lazy-loaded)
_index_rejected = False                   # corrupt index: stop retrying reads


def reload() -> None:
    """Re-read ``HEAT_TPU_EXEC_CACHE`` from the environment (the documented
    re-read point — wired into ``ht.reload_env_knobs``).  Changing the cache
    directory drops the in-memory index so the next lookup reads the new
    location."""
    global _dir, _index, _index_rejected
    with _lock:
        new = os.environ.get("HEAT_TPU_EXEC_CACHE") or None
        if new != _dir:
            _dir = new
            _index = None
            _index_rejected = False


def armed() -> bool:
    """Whether the persistent signature cache is on (``HEAT_TPU_EXEC_CACHE``)."""
    return _dir is not None


def cache_dir() -> Optional[str]:
    return _dir


def fingerprint(spec: dict) -> str:
    """The content fingerprint of a replay spec: sha256 over its canonical
    JSON.  Process-portable by construction — specs carry names, avals and
    mesh shape, never object identities — so two processes running the same
    workload on the same topology compute the same fingerprint."""
    blob = json.dumps(spec, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


def _reject(detail: str, *, fingerprint_: str = "") -> None:
    """Record one typed cache rejection (corruption is never silent and never
    fatal: the caller recompiles)."""
    exc = CompileCacheCorrupt(detail)
    diagnostics.record_resilience_event(
        "executor.compile_cache", "cache-corrupt",
        f"{type(exc).__name__}: {detail}"
        + (f" (fingerprint {fingerprint_[:12]})" if fingerprint_ else ""),
    )
    if diagnostics._enabled:
        diagnostics.counter("executor.cache_reject")
        diagnostics.record_fallback(
            "executor.compile_cache", f"{type(exc).__name__}: {detail}"
        )


def _index_path(base: Optional[str] = None) -> str:
    return os.path.join(base or _dir, "index.json")


def _blob_path(sha: str, base: Optional[str] = None) -> str:
    return os.path.join(base or _dir, "blobs", f"{sha}.bin")


def _load_index_locked() -> Dict[str, Any]:
    """The fingerprint -> entry map, read once per directory. A corrupt index
    is a typed rejection and reads as empty (recompiles, never breaks)."""
    global _index, _index_rejected
    if _index is not None:
        return _index
    path = _index_path()
    entries: Dict[str, Any] = {}
    if os.path.exists(path) and not _index_rejected:
        try:
            with open(path) as f:
                doc = json.load(f)
            if doc.get("schema") != SCHEMA:
                raise CompileCacheCorrupt(
                    f"unexpected schema {doc.get('schema')!r} in {path}"
                )
            entries = dict(doc.get("entries") or {})
        except (OSError, ValueError, CompileCacheCorrupt) as exc:
            _index_rejected = True
            _reject(f"unreadable index {path}: {type(exc).__name__}: {exc}")
            entries = {}
    _index = entries
    return entries


def _read_index(base: Optional[str]) -> Dict[str, Any]:
    """Read an index for an explicit ``base`` dir (save/warmup paths that may
    differ from the armed knob).  Typed-rejects corrupt files as empty."""
    if base is None or base == _dir:
        with _lock:
            return dict(_load_index_locked())
    path = _index_path(base)
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != SCHEMA:
            raise CompileCacheCorrupt(f"unexpected schema in {path}")
        return dict(doc.get("entries") or {})
    except (OSError, ValueError, CompileCacheCorrupt) as exc:
        _reject(f"unreadable index {path}: {type(exc).__name__}: {exc}")
        return {}


def _write_index(base: str, entries: Dict[str, Any]) -> None:
    payload = json.dumps(
        {"schema": SCHEMA, "entries": entries}, indent=1, sort_keys=True
    )
    os.makedirs(base, exist_ok=True)

    def writer(tmp: str) -> None:
        with open(tmp, "w") as f:
            f.write(payload)

    resilience.atomic_write(_index_path(base), writer,
                            site="executor.compile_cache")
    with _lock:
        global _index
        if base == _dir:
            _index = dict(entries)


# ---------------------------------------------------------------------------
# artifact load (the _Program first-call hook)


def load_program(prog) -> Optional[Any]:
    """A deserialized compiled executable for ``prog``'s fingerprint, or None
    (miss / unsupported / typed-rejected corruption — the caller jit-builds
    as usual).  Called by ``_Program.__call__`` under the executor lock on
    the FIRST call of the plain variant only; replays never touch this."""
    if _dir is None:
        return None
    spec = prog.spec
    if spec is None:
        return None
    fp = prog.fingerprint
    if fp is None:
        fp = prog.fingerprint = fingerprint(spec)
    with _lock:
        entry = _load_index_locked().get(fp)
    if not entry:
        return None
    sha = entry.get("blob")
    if not sha:
        return None
    path = _blob_path(sha)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError as exc:
        _reject(f"artifact unreadable: {type(exc).__name__}: {exc}",
                fingerprint_=fp)
        return None
    if hashlib.sha256(blob).hexdigest() != sha:
        # content-address mismatch: truncated or bit-rotted blob
        _reject(
            f"artifact {os.path.basename(path)} fails its content address "
            f"({len(blob)} bytes on disk)", fingerprint_=fp,
        )
        with _lock:
            if _index is not None:
                _index.pop(fp, None)  # stop re-reading the corpse this process
        return None
    try:
        payload, in_tree, out_tree = pickle.loads(blob)
    except Exception as exc:  # ht: ignore[silent-except] -- typed rejection, not a swallow: _reject records a cache-corrupt resilience event + an executor.compile_cache fallback, and the caller recompiles
        # content verified but unpicklable: written-corrupt. Typed rejection.
        _reject(f"artifact unpicklable: {type(exc).__name__}: {exc}",
                fingerprint_=fp)
        with _lock:
            if _index is not None:
                _index.pop(fp, None)
        return None
    try:
        loaded = _se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception as exc:
        # the artifact is INTACT but this backend/topology cannot reload it
        # (XLA CPU cannot relocate jit fusion symbols across processes;
        # version/topology skew does the same on device backends): not
        # corruption — recorded as its own kind, recompiled via the normal
        # build (which JAX's persistent compilation cache accelerates)
        diagnostics.record_resilience_event(
            "executor.compile_cache", "artifact-incompatible",
            f"{type(exc).__name__}: {exc} (fingerprint {fp[:12]})",
        )
        if diagnostics._enabled:
            diagnostics.counter("executor.artifact_incompatible")
            diagnostics.record_fallback(
                "executor.compile_cache",
                f"artifact incompatible: {type(exc).__name__}: {exc}",
            )
        with _lock:
            if _index is not None:
                _index.pop(fp, None)
        return None
    if diagnostics._enabled:
        diagnostics.counter("executor.aot_load")
    return loaded


# ---------------------------------------------------------------------------
# save (warm process -> manifest + artifacts)


def executor_save_warmup(path: Optional[str] = None, top: int = DEFAULT_TOP,
                         aot: bool = True) -> dict:
    """Record the executor's hottest signatures into a persistent warmup
    manifest at ``path`` (default: the armed ``HEAT_TPU_EXEC_CACHE`` dir).

    Signatures are ordered by (hits desc, label asc) — the
    ``executor_stats(top=N)`` order — and only portably-describable ones
    (``_Program.spec`` is not None) are saved.  With ``aot`` (and a backend
    that supports executable serialization) each saved program is also
    AOT-lowered from its recorded arg specs (shardings included), compiled,
    serialized, and stored content-addressed under ``blobs/`` — the artifact
    :func:`load_program` swaps in for the jit build on the next boot.
    Re-lowering happens here, OFF the dispatch path, so steady-state replay
    performance never pays for artifact production.  Returns
    ``{"saved", "artifacts", "skipped", "path"}``."""
    from . import _executor

    base = path or _dir
    if base is None:
        raise ValueError(
            "executor_save_warmup needs a path (or HEAT_TPU_EXEC_CACHE set)"
        )
    with _executor._lock:
        progs = [
            entry for entry in _executor._programs.values()
            if entry is not _executor.UNSUPPORTED
        ]
    progs.sort(key=lambda e: (-e.hits, e.label or ""))
    entries = _read_index(base)
    saved = artifacts = skipped = 0
    for prog in progs:
        if saved >= max(1, top):
            break
        spec = prog.spec
        if spec is None:
            skipped += 1
            continue
        fp = prog.fingerprint or fingerprint(spec)
        prog.fingerprint = fp
        entry = {"label": prog.label, "hits": prog.hits, "spec": spec}
        prior = entries.get(fp)
        if prior and prior.get("blob"):
            entry["blob"] = prior["blob"]  # artifact already on disk
            entry["nbytes"] = prior.get("nbytes")
        elif aot and prog._plain is not None \
                and prog.arg_specs is not None and not prog.aot_loaded:
            try:
                compiled = prog._plain.lower(*prog.arg_specs).compile()
                payload, in_tree, out_tree = _se.serialize(compiled)
                blob = pickle.dumps((payload, in_tree, out_tree))
                sha = hashlib.sha256(blob).hexdigest()
                bpath = _blob_path(sha, base)
                os.makedirs(os.path.dirname(bpath), exist_ok=True)

                def writer(tmp: str, data: bytes = blob) -> None:
                    with open(tmp, "wb") as f:
                        f.write(data)

                resilience.atomic_write(bpath, writer,
                                        site="executor.compile_cache")
                entry["blob"] = sha
                entry["nbytes"] = len(blob)
                artifacts += 1
            except Exception as exc:
                # artifact production is best-effort: the spec-replay tier
                # still covers this signature at boot — counted, not fatal
                if diagnostics._enabled:
                    diagnostics.record_fallback(
                        "executor.compile_cache",
                        f"serialize {prog.label}: {type(exc).__name__}: {exc}",
                    )
        entries[fp] = entry
        saved += 1
    _write_index(base, entries)
    diagnostics.record_resilience_event(
        "executor.warmup", "warmup-saved",
        f"{saved} signatures ({artifacts} artifacts) -> {base}",
    )
    return {"saved": saved, "artifacts": artifacts, "skipped": skipped,
            "path": base}


# ---------------------------------------------------------------------------
# warmup (fresh process -> compiled programs before the first request)


class _WarmupHolder:
    """Stand-in DNDarray wrapper pinning a rebuilt node's recorded emission
    (``_linearise`` checks ``holder._payload is node`` through the weakref)."""

    __slots__ = ("_payload", "__weakref__")


def _np_scalar(entry: dict):
    if "np" in entry:
        return np.dtype(entry["np"]).type(entry["scalar"])
    return entry["scalar"]


def _zeros_dnd(gshape, split, np_dtype_str):
    """A balanced zeros DNDarray of the recorded layout (the physical shape a
    fresh process derives for (gshape, split) — checked by callers against
    the recorded one)."""
    from . import factories, types

    return factories.zeros(
        tuple(gshape),
        dtype=types.canonical_heat_type(np.dtype(np_dtype_str)),
        split=split,
    )


def _resolve_op(name: str):
    op = getattr(jnp, name, None)
    if op is None:
        raise CompileCacheCorrupt(f"spec op {name!r} is not a jax.numpy name")
    return op


def _replay_staged(spec: dict) -> bool:
    """Re-dispatch one staged ``l``/``r``/``c``/``mm`` signature through its
    real wrapper over a zeros array of the recorded layout — the executor's
    table ends up keyed exactly as live traffic keys it."""
    from . import _operations

    if spec["family"] == "mm":
        # comm-plan contraction / resplit programs (linalg/comm_plan.py)
        from .linalg import comm_plan

        return comm_plan.replay_warmup(spec, _zeros_dnd)
    op = _resolve_op(spec["op"])
    x = _zeros_dnd(spec["gshape"], spec["split"], spec["dtype"])
    if list(x.parray.shape) != list(spec["phys"]):
        # a different device count pads differently: this spec does not
        # describe a signature THIS process can ever hit
        return False
    kwargs = dict(spec.get("kwargs") or {})
    family = spec["family"]
    if family == "l":
        res = _operations._local_jit(op, x, None, kwargs)
    elif family == "r":
        axis = spec.get("axis")
        axis = tuple(axis) if isinstance(axis, list) else axis
        res = _operations._reduce_jit(
            op, x, axis, spec.get("out_split"), None,
            bool(spec.get("keepdims")), kwargs,
        )
    elif family == "c":
        axis = spec.get("axis")
        target = spec.get("target")
        res = _operations._cum_jit(
            op, x, axis, None,
            np.dtype(target) if target else None, kwargs,
        )
    else:
        raise CompileCacheCorrupt(f"unknown staged family {family!r}")
    return res is not NotImplemented


def _replay_defer(spec: dict) -> bool:
    """Rebuild the recorded fused-graph shape node by node (same jnp ops,
    same sharing structure, same emission set — pinned by warmup holders)
    and force it, compiling or artifact-loading the identical program."""
    from . import _executor

    gshape = tuple(spec["gshape"])
    split = spec["split"]
    leaf_vals = []
    comm = None
    for lf in spec["leaves"]:
        if "shape" in lf:
            d = _zeros_dnd(gshape, split, lf["dtype"])
            if list(d.parray.shape) != list(lf["shape"]):
                return False  # different topology pads differently
            comm = d.comm
            leaf_vals.append(d.parray)
        else:
            leaf_vals.append(_np_scalar(lf))
    if comm is None or not spec["entries"]:
        return False
    nodes: list = []
    for e in spec["entries"]:
        operands = []
        for kind, idx in e["refs"]:
            if kind == "L":
                v = leaf_vals[idx]
                operands.append(
                    ("a", v) if isinstance(v, jax.Array) else ("s", v)
                )
            else:
                operands.append(("d", nodes[idx]))
        node = _executor.defer_node(
            _resolve_op(e["op"]), dict(e.get("kwargs") or {}), operands,
            gshape, split, comm,
        )
        if node is _executor.UNSUPPORTED:
            return False
        nodes.append(node)
    holders = []
    for i in spec["out_idxs"]:
        holder = _WarmupHolder()
        holder._payload = nodes[i]
        _executor.note_wrapped(nodes[i], holder)
        holders.append(holder)
    roots = tuple(nodes[i] for i in spec["root_idxs"])
    keep = [nodes[i] for i in spec["out_idxs"]]
    # drop every other NODE reference: interior emission is refcount-driven,
    # and a stray list would make the rebuilt plan emit MORE than the
    # recorded set (a different signature than traffic will ever look up).
    # leaf_vals stays ALIVE through the force — a sole-reader zeros leaf
    # would otherwise be donated, and a donating first call compiles the
    # donate variant instead of consulting the artifact cache.
    del nodes, node, operands
    for r in roots:
        r.force()
    del keep, holders, leaf_vals
    return True


def executor_warmup(path: Optional[str] = None, top: Optional[int] = None) -> dict:
    """AOT warmup: replay the manifest at ``path`` (default: the armed
    ``HEAT_TPU_EXEC_CACHE`` dir) so a fresh process compiles — or
    artifact-loads — its serving signatures BEFORE the first request.

    Entries replay in (hits desc, label asc) order, ``top`` limiting how
    many (None = all recorded).  Each replay drives the real dispatch layer,
    so the signature table is keyed exactly as live traffic keys it; a
    replay that cannot reproduce its signature on this topology (different
    device count, missing op) is counted and skipped, never fatal.  Returns
    ``{"replayed", "aot_loaded", "failed", "skipped", "path"}`` and records
    a ``warmup-complete`` resilience event with the same numbers."""
    base = path or _dir
    if base is None:
        raise ValueError(
            "executor_warmup needs a path (or HEAT_TPU_EXEC_CACHE set)"
        )
    entries = _read_index(base)
    ordered = sorted(
        entries.values(),
        key=lambda e: (-int(e.get("hits", 0)), str(e.get("label") or "")),
    )
    if top is not None:
        ordered = ordered[: max(0, top)]
    replayed = failed = skipped = 0
    aot_before = _aot_load_count()
    for entry in ordered:
        spec = entry.get("spec")
        if not isinstance(spec, dict):
            skipped += 1
            continue
        try:
            if spec.get("family") == "defer":
                ok = _replay_defer(spec)
            else:
                ok = _replay_staged(spec)
        except Exception as exc:
            failed += 1
            if diagnostics._enabled:
                diagnostics.counter("warmup.failed")
            diagnostics.record_fallback(
                "executor.warmup",
                f"{entry.get('label')}: {type(exc).__name__}: {exc}",
            )
            continue
        if ok:
            replayed += 1
            if diagnostics._enabled:
                diagnostics.counter("warmup.replayed")
        else:
            skipped += 1
    aot_loaded = _aot_load_count() - aot_before
    diagnostics.record_resilience_event(
        "executor.warmup", "warmup-complete",
        f"replayed={replayed} aot_loaded={aot_loaded} failed={failed} "
        f"skipped={skipped} path={base}",
    )
    return {"replayed": replayed, "aot_loaded": aot_loaded, "failed": failed,
            "skipped": skipped, "path": base}


def _aot_load_count() -> int:
    """Programs whose plain variant came from a deserialized artifact."""
    from . import _executor

    with _executor._lock:
        return sum(
            1 for e in _executor._programs.values()
            if e is not _executor.UNSUPPORTED and e.aot_loaded
        )


# memoise the knobs at import (a fresh process needs nothing extra; in-process
# flips re-read through reload(), wired into ht.reload_env_knobs)
reload()
