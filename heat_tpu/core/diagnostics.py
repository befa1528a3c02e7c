"""``ht.diagnostics`` — framework-wide tracing, metrics, and backend-health telemetry.

The framework has three hot subsystems whose behavior is otherwise invisible at
runtime: the signature-cached dispatch executor (:mod:`_executor`), the L0
collective layer (:class:`communication.MeshCommunication`), and the accelerator
backend's health. Heat's MPI lineage leans on external tools (mpiP, Score-P) for
this; the TPU-native stack carries its own instrumentation so device traces and
run artifacts explain themselves. This module is the registry those hooks
report into:

- **Counters & spans** — :func:`counter` named tallies; :func:`span`, the
  program's ONE host-span primitive: a ``jax.profiler.TraceAnnotation``
  ``ht.<name>`` on the profiler trace's own clock (beside the device's
  ``XLA Ops`` line), a per-thread stack that gives every span its parent and
  its self time, per-name aggregates (count / inclusive / self / max seconds,
  also flat in ``report()["counters"]`` as ``span_n.`` / ``span_s.`` /
  ``span_self_s.<name>``), and backend compiles attributed to the innermost
  open span (``compile_n.`` / ``compile_s.<span>``).
- **Compile accounting** — what ``jax.monitoring`` tells of tracing, lowering,
  backend compiles and the persistent cache, kept while enabled: the flat
  ``jit.*`` counters and ``report()["programs"]``, one entry a ``fun_name``
  (:func:`_on_duration`, :func:`_on_event`, :func:`_on_stage_begins`).
- **The start-up record** — ``report()["startup"]``: the phases of ``import
  heat_tpu`` (:func:`startup`), written once a process and always on.
- **Collective telemetry** — every ``MeshCommunication`` collective (``psum`` …
  ``scatter``, plus ``shard`` and ``_pad_reshard``) records (op name, mesh axis,
  participant count, logical bytes moved). Collectives called inside a traced
  program (``shard_map`` / ``jit`` bodies) are recorded **at trace time**:
  replays of a cached executable do not re-execute the Python hook, so a count
  of 1 means "one traced occurrence", not "one device execution". Nested
  convenience collectives record both layers (``scan`` also records its inner
  ``exscan``; ``scatter`` its inner ``broadcast``).
- **Executor telemetry** — per-signature compile wall time, and miss events
  annotated with the *reason*: which signature component (operand aval, split,
  kwargs, mesh, …) changed versus the nearest cached key.
- **Result-cache counters** (``HEAT_TPU_RESULT_CACHE=1``; see
  :mod:`_result_cache`) — ``executor.result_cache_hit`` /
  ``executor.result_cache_store`` / ``executor.result_cache_invalidation`` /
  ``executor.result_cache_reject`` ride :func:`counter`; a poisoned entry is
  additionally a typed ``cache-corrupt`` resilience event through
  :func:`record_resilience_event`, the same contract as the compile cache.
- **Padded-layout waste gauges** — the dispatch wrappers record the pad
  fraction ``(physical - logical) / physical`` of every padded ``(gshape,
  split)`` family they dispatch on.
- **Backend-health events** — timestamped backend up/down *transitions*
  (:func:`record_backend_event`), fed by whatever probes the backend.
- **Provider sections** — :func:`register_provider` attaches named report
  sections computed at :func:`report` time; the executor, resilience,
  supervision and the live operations plane (:mod:`ops` — whose ``slo-burn``
  alert transitions also arrive as typed events through
  :func:`record_resilience_event`) all report through this hook.

Zero-cost contract
------------------
When disabled (the default) the hooks are a single module-attribute read and a
branch not taken, and nothing is ever injected into traced program bodies —
compiled HLO is byte-identical to an uninstrumented build
(``tests/test_diagnostics.py::TestZeroOverheadContract``). Backend-health
events are the one always-on stream: they are only produced by explicit probe
calls, never on a compute path. The start-up record is always on for the same
reason: a dozen ``time.perf_counter()`` reads while the package is imported,
none afterwards, and ``enable()`` can only be called after the import it measures.

Env knobs (read once at import)
-------------------------------
- ``HEAT_TPU_METRICS=1``   — start with metrics collection enabled.
- ``HEAT_TPU_TRACE=1``     — start with tracing enabled. Trace-time meaning
  only: ``jax.named_scope`` framework-level op names compiled into program
  metadata (visible in XLA device traces / HLO dumps). Host spans are
  :func:`span`'s, under ``HEAT_TPU_METRICS``. Programs cached before the flag
  flips keep their old annotations — ``clear_executor_cache()`` forces a
  re-trace.
- ``HEAT_TPU_DIAG_DUMP=path`` — dump the full JSON report to ``path`` at
  interpreter exit (the CI tier-1 artifact).
- ``HEAT_TPU_DIAG_LOG=path``  — append backend-health transitions to ``path``
  as JSON lines (survives the process).

This module deliberately imports only the stdlib at top level (the
import-contract rule of ``ht.analysis``), so tooling can load it by file path
without JAX; :func:`span` imports ``jax.profiler`` / ``jax.monitoring`` at the
first span entered while enabled (without JAX it aggregates and annotates
nothing).

Thread-safety (audited for the multi-threaded serving harness)
--------------------------------------------------------------
Every mutation of the shared registries — counters, spans, collective and
pad-waste aggregates, the bounded event deques, the backend-state transition
check, provider registration — runs under the one module ``_lock``, and
:func:`report`/:func:`reset` snapshot/clear under the same lock, so counts
are EXACT under concurrent requests (``tests/test_diagnostics.py::
TestThreadSafety`` hammers this). The deliberate exceptions, relaxed rather
than locked:

- the ``_enabled`` / ``_tracing`` switches are bare module attributes: hot
  paths read them un-locked (the zero-cost contract), so a concurrent
  ``enable()``/``disable()`` takes effect on other threads at their next
  hook — no torn state is possible (bool writes are atomic), only a few
  events either side of the flip may or may not be collected;
- the ``HEAT_TPU_DIAG_LOG`` file append in :func:`record_backend_event` runs
  OUTSIDE the lock (a slow disk must not stall telemetry); interleaved lines
  from two processes are whole-line atomic on POSIX appends of this size;
- the late-bound collaborator hooks (``_atomic_writer``, ``_resilience_tee``,
  ``_fallback_tee``, ``_request_id``, and ``_annotation``, which :func:`span`
  binds at first use) are written exactly once and read bare afterwards; the
  stack of open spans is per thread (``_open``) and needs no lock; tee invocations happen OUTSIDE ``_lock`` so the
  flight-recorder ring's lock stays strictly below this one;
- the executor's ``_stats`` tallies (in :mod:`_executor`) are PER-THREAD
  accumulator cells merged at report time: increments stay lock-free on the
  hot paths (``retraces`` inside a traced body, the memo-hit
  ``reexec_avoided`` fast path, the scheduler thread's execution tallies)
  yet counts are EXACT — the async dispatch scheduler made the old
  relaxed-racing-``+=`` undercount a real risk instead of a curiosity. The
  signature table itself and every decision made from it are fully
  lock-protected.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional

__all__ = [
    "enable",
    "disable",
    "enabled",
    "tracing",
    "reset",
    "report",
    "dump",
    "span",
    "NO_SPAN",
    "startup",
    "counter",
    "record_collective",
    "record_compile",
    "record_dispatch_event",
    "record_fallback",
    "record_resilience_event",
    "record_pad_waste",
    "record_backend_event",
    "register_provider",
]

SCHEMA = "heat-tpu-diagnostics/1"

# Hot-path hooks read these module attributes directly (`diagnostics._enabled`):
# one attribute load + branch when off — the zero-cost-when-disabled contract.
_enabled: bool = False
_tracing: bool = False

_lock = threading.RLock()

# Bounded event streams: telemetry must never become the memory leak it exists
# to find. Aggregates (counters/spans/collectives/pad gauges) are dicts keyed by
# identity and stay small; raw event streams evict OLDEST on overflow (deque
# maxlen) so the report always holds the most recent tail of the run.
_MAX_EVENTS = 10_000

_counters: Dict[str, float] = {}
_spans: Dict[str, Dict[str, float]] = {}
_collectives: Dict[Any, Dict[str, int]] = {}
_pad_gauges: Dict[Any, Dict[str, Any]] = {}
_compile_events: "deque[dict]" = deque(maxlen=_MAX_EVENTS)
_dispatch_events: "deque[dict]" = deque(maxlen=_MAX_EVENTS)
_fallback_events: "deque[dict]" = deque(maxlen=_MAX_EVENTS)
_resilience_events: "deque[dict]" = deque(maxlen=_MAX_EVENTS)
_backend_events: "deque[dict]" = deque(maxlen=_MAX_EVENTS)
_backend_state: Optional[bool] = None

# Subsystems register report sections lazily (the executor registers its
# ``executor_stats`` here) so this module never imports the package — it must
# stay loadable standalone, without JAX.
_providers: Dict[str, Callable[[], Any]] = {}

# Late-bound collaborators, installed by modules this one must not import
# (each would be a cycle — resilience and telemetry both import diagnostics).
# All three are written once at their owner's import and read bare afterwards
# (relaxed, like the switches): ``_atomic_writer`` is
# ``resilience.atomic_write`` so :func:`dump` commits whole artifacts;
# ``_resilience_tee`` / ``_fallback_tee`` are ``telemetry.flight_record``
# adapters so every failure-path event also lands in the flight-recorder ring
# (and can trigger its automatic post-mortem dump); ``_forensics_tee`` is the
# forensics event adapter so typed failures also land on the active request's
# critical path. Tees are invoked OUTSIDE ``_lock`` — the flight ring and the
# forensics store have their own locks and must stay leaves.
_atomic_writer: Optional[Callable[..., Any]] = None
_resilience_tee: Optional[Callable[[str, str, str], None]] = None
_fallback_tee: Optional[Callable[[str, str], None]] = None
_forensics_tee: Optional[Callable[[str, str, str], None]] = None
# ``_request_id`` is ``profiler.current_request`` (set at the profiler's
# import): the ambient request id a span's annotation carries as ``req=``.
_request_id: Optional[Callable[[], Optional[int]]] = None
# ``jax.profiler.TraceAnnotation`` once :func:`_bind_jax` has run (``False``
# where JAX cannot be imported), the tracer type that marks an operand met while
# tracing, and the open spans of each thread, innermost last.
_annotation: Any = None
_tracer: Any = ()  # jax.core.Tracer, bound with it
_open = threading.local()

# What ``jax.monitoring`` emits where JAX traces, lowers, compiles or reads its
# persistent cache (jax 0.9: ``dispatch.py``, ``compiler.py``), and nowhere else: a
# cached dispatch fires none of them. The first three carry ``fun_name=``.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _COMPILE_EVENT: "backend",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_read_s",
    "/jax/compilation_cache/compile_time_saved_sec": "jit.cache_saved_s",
}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_JIT_COUNTERS = ("jit.trace_s", "jit.lower_s", "jit.backend_s", "jit.backend_n",
                 "jit.cache_hit_n", "jit.cache_miss_n", "jit.cache_read_s",
                 "jit.cache_saved_s")
# ``report()["programs"]``: one entry a ``fun_name``, the names past the bound
# summed under ``other``.
_MAX_PROGRAMS = 256
_programs: Dict[str, Dict[str, float]] = {}


def _utcnow(at: Optional[float] = None) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(at))


# ------------------------------------------------------------------ switches
def enable(trace: Optional[bool] = None) -> None:
    """Turn on metrics collection and host spans; ``trace=True`` additionally
    turns on the trace-time ``jax.named_scope`` names (``trace=False`` turns
    them off, ``None`` leaves them as-is).

    Tracing affects programs at *trace* time: executables cached while tracing
    was off keep their unannotated HLO until ``clear_executor_cache()``."""
    global _enabled, _tracing
    _enabled = True
    if trace is not None:
        _tracing = bool(trace)
    if _annotation is None and "jax" in sys.modules:
        _bind_jax()  # compiles are attributed from here on, also outside any span


def disable(trace: Optional[bool] = None) -> None:
    """Stop collecting metrics (collected data is kept — :func:`report` still
    works; :func:`reset` clears it). ``trace`` as in :func:`enable`, default
    turns tracing off too."""
    global _enabled, _tracing
    _enabled = False
    _tracing = bool(trace) if trace is not None else False


def enabled() -> bool:
    """Whether metrics collection is currently on."""
    return _enabled


def tracing() -> bool:
    """Whether trace-time ``jax.named_scope`` names are compiled into programs."""
    return _tracing


def reset() -> None:
    """Drop every collected datum (counters, spans, programs, collectives, pad
    gauges, compile/dispatch/backend events). The enabled/tracing switches, the
    last-known backend state and the start-up record are kept."""
    with _lock:
        _counters.clear()
        _programs.clear()
        _spans.clear()
        _collectives.clear()
        _pad_gauges.clear()
        _compile_events.clear()
        _dispatch_events.clear()
        _fallback_events.clear()
        _resilience_events.clear()
        _backend_events.clear()


def register_provider(name: str, fn: Callable[[], Any]) -> None:
    """Attach a named report section computed at :func:`report` time (the
    executor registers its stats here; avoids an import cycle and keeps this
    module standalone-loadable)."""
    with _lock:
        _providers[name] = fn


# ------------------------------------------------------------------ start-up record
def _process_start() -> Optional[float]:
    """The ``time.perf_counter()`` reading that this process's start corresponds
    to: its ``starttime`` (``/proc/self/stat``, ticks since boot) against
    ``CLOCK_BOOTTIME``. ``None`` where there is no ``/proc``."""
    try:
        with open("/proc/self/stat", "rb") as f:
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter() - age


# ``start_s`` counts from process start, or from this module's load where that
# is not known. Written while the package is imported, by the importing thread
# alone; :func:`startup_imported` closes it.
_T_START = _process_start()
_T_ZERO = time.perf_counter() if _T_START is None else _T_START
_startup: Dict[str, Any] = {
    "perf_counter_at_start": _T_START,
    "wall_start": _utcnow(time.time() - (time.perf_counter() - _T_ZERO)),
}
_startup_open = True


class _Phase:
    """One phase of the start-up record (see :func:`startup`)."""

    __slots__ = ("name", "fields", "t0")

    def __init__(self, name: str, fields: Dict[str, Any]):
        self.name = name
        self.fields = fields

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self.t0
        _startup[self.name] = {"start_s": self.t0 - _T_ZERO, "seconds": seconds,
                               **self.fields}
        return False


def startup(name: str, **fields: Any):
    """One phase of ``import heat_tpu``: ``with diagnostics.startup("bootstrap.world"):``
    writes ``report()["startup"][name]`` = ``start_s`` (seconds since process
    start), ``seconds`` and ``fields``. Always on, whatever ``HEAT_TPU_METRICS``
    says: two clock reads a phase. Once the package is imported the record is
    closed and this is the shared no-op, so that a later ``build_world()`` (an
    elastic restart) leaves the record of the process's start alone."""
    return _Phase(name, fields) if _startup_open else NO_SPAN


def startup_imported(first: float) -> None:
    """The last statement of ``heat_tpu/__init__.py``, given the
    ``time.perf_counter()`` reading of its first: writes ``before_import``
    (process start to that first statement: the interpreter and whatever the
    program imported and started before ``heat_tpu``; ``None`` where process
    start is not known) and the totals ``import_s`` (first to last statement)
    and ``bootstrap_s`` (``_bootstrap.run()`` whole), and closes the record."""
    global _startup_open
    if not _startup_open:
        return
    _startup["before_import"] = None if _T_START is None else {
        "start_s": 0.0, "seconds": first - _T_START}
    _startup["import_s"] = time.perf_counter() - first
    _startup["bootstrap_s"] = _startup.pop("bootstrap", {}).get("seconds")
    _startup_open = False


# ------------------------------------------------------------------ primitives
def counter(name: str, value: float = 1) -> None:
    """Add ``value`` to the named counter (no-op while disabled)."""
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + value


NO_SPAN = contextlib.nullcontext()  # what :func:`span` is while disabled, shared


def _bind_jax() -> None:
    """First enabled span: bind ``jax.profiler.TraceAnnotation`` and register
    the listeners, one of a kind: durations, events, and the scalar JAX records
    where a compile stage begins (JAX has no public way to take a listener off
    again, so they stay and read ``_enabled`` themselves)."""
    global _annotation, _tracer
    with _lock:
        if _annotation is None:
            try:
                import jax.monitoring
                import jax.profiler
            except ImportError:  # standalone tooling without JAX
                _annotation = False
            else:
                jax.monitoring.register_event_duration_secs_listener(_on_duration)
                jax.monitoring.register_event_listener(_on_event)
                jax.monitoring.register_scalar_listener(_on_stage_begins)
                _tracer = jax.core.Tracer
                _annotation = jax.profiler.TraceAnnotation


def _program_locked(fun_name: str) -> Dict[str, float]:
    # callers hold _lock; ``jit(f)`` / ``pmap(f)`` (lowering, backend) is ``f`` (trace)
    if fun_name.endswith(")") and "(" in fun_name:
        fun_name = fun_name[fun_name.index("(") + 1:-1]
    entry = _programs.get(fun_name)
    if entry is None:
        if len(_programs) >= _MAX_PROGRAMS:
            fun_name = "other"
        entry = _programs.setdefault(fun_name, {
            "trace_n": 0, "trace_s": 0.0, "lower_s": 0.0, "backend_n": 0,
            "backend_s": 0.0, "cache_hit_n": 0, "cache_miss_n": 0})
    return entry


def _on_stage_begins(event: str, _value: float, **_kw) -> None:
    """JAX records a stage's start time as a scalar under the stage's own event
    name: from here to its duration event, what ends on this thread lies inside it.
    Kept whatever the switch says, so that the stack of open stages stays true."""
    if event in _STAGES:
        inside = getattr(_open, "stages", None)
        if inside is None:
            inside = _open.stages = []
        inside.append(0.0)


def _on_duration(event: str, seconds: float, fun_name: str = "?", **_kw) -> None:
    """What JAX spent where it traced, lowered, compiled or read its cache.

    A program's entry keeps each stage as JAX reports it, what the stage
    encloses included; the flat ``jit.trace_s`` / ``jit.lower_s`` /
    ``jit.backend_s`` count every second once: a stage that lay inside another on
    its thread (a jitted ``jnp`` function traced while its caller is) is taken off
    the one that encloses it, so the three add up to the time the thread spent.
    ``jit.backend_s`` includes ``jit.cache_read_s``, as JAX's event does. Each
    backend compile also lands on the innermost span open on its thread (``none``
    outside every span), and settles the cache requests that its thread made
    inside it (:func:`_on_event`)."""
    stage = _STAGES.get(event)
    if stage is not None:
        open_stages = getattr(_open, "stages", None)
        inside = open_stages.pop() if open_stages else 0.0
        if open_stages:
            open_stages[-1] += seconds
    if not _enabled:
        return
    if stage is None:
        flat = _CACHE_SECONDS.get(event)
        if flat is not None:
            with _lock:
                _counters[flat] = _counters.get(flat, 0) + seconds
        return
    add = {f"jit.{stage}_s": max(0.0, seconds - inside)}
    with _lock:
        entry = _program_locked(fun_name)
        entry[f"{stage}_s"] += seconds
        if stage == "trace":
            entry["trace_n"] += 1
        elif stage == "backend":
            requests, hits = getattr(_open, "cache", (0, 0))
            _open.cache = (0, 0)
            entry["backend_n"] += 1
            entry["cache_hit_n"] += hits
            entry["cache_miss_n"] += requests - hits
            spans = getattr(_open, "stack", None)
            name = spans[-1].name if spans else "none"
            add.update({"jit.backend_n": 1, "jit.cache_hit_n": hits,
                        "jit.cache_miss_n": requests - hits,
                        f"compile_n.{name}": 1, f"compile_s.{name}": seconds})
        for key, value in add.items():
            _counters[key] = _counters.get(key, 0) + value


def _on_event(event: str, **_kw) -> None:
    """A compile that asked the persistent cache, and one that was answered from
    it, noted on their thread until the backend-compile event that encloses them
    (:func:`_on_duration`). A miss is a request that was not a hit: JAX's own
    ``/cache_misses`` fires only where it writes the entry, which its size and
    time thresholds decide."""
    if not _enabled or event not in (_CACHE_REQUEST, _CACHE_HIT):
        return
    requests, hits = getattr(_open, "cache", (0, 0))
    _open.cache = (requests + 1, hits) if event == _CACHE_REQUEST else (requests, hits + 1)


class _Span:
    """One open host span (see :func:`span`)."""

    __slots__ = ("name", "label", "t0", "child_s", "ann")

    def __init__(self, name: str, label: str):
        self.name = name
        self.label = label
        self.child_s = 0.0
        self.ann = None

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        stack.append(self)
        if _annotation:  # bound by span(); False without JAX
            rid = _request_id() if _request_id is not None else None
            self.ann = (_annotation(f"ht.{self.label}") if rid is None
                        else _annotation(f"ht.{self.label}", req=rid))
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        stack = _open.stack
        if stack[-1] is self:
            stack.pop()
        else:  # closed out of order (a generator resumed late)
            stack.remove(self)
        if stack:
            stack[-1].child_s += dt
        name = self.name
        with _lock:
            agg = _spans.get(name)
            if agg is None:
                agg = _spans[name] = {"count": 0, "total_s": 0.0, "self_s": 0.0,
                                      "max_s": 0.0}
            agg["count"] += 1
            agg["total_s"] += dt
            agg["self_s"] += max(0.0, dt - self.child_s)
            agg["max_s"] = max(agg["max_s"], dt)
        return False


def span(name: str, operand: Any = None, label: Optional[str] = None):
    """The program's one host span: ``with diagnostics.span("cluster.predict"):``.

    Disabled (the default) this is one attribute read and a shared no-op.
    Enabled, the block (a) is a ``jax.profiler.TraceAnnotation`` named
    ``ht.<label or name>`` carrying the ambient request id as ``req=``, so it
    lies in the profiler's own trace on the clock of the device's operations;
    (b) knows its parent, the span open on this thread when it was entered,
    and so its self time: its duration less what its child spans cover; (c) is
    aggregated under ``name``: ``report()["spans"][name]`` holds count /
    total_s / self_s / max_s, and ``report()["counters"]`` the same flat, as
    ``span_n.<name>`` / ``span_s.<name>`` / ``span_self_s.<name>``, so that a
    reader can take window deltas. Backend compiles inside the block count
    towards ``compile_n.<name>`` / ``compile_s.<name>`` of the innermost span.

    Host side only, never inside a traced body. An entry point that can also
    be reached while tracing (``Module.__call__`` under a jitted training step)
    passes the array it was given as ``operand`` (a DNDarray is looked through
    to its ``larray``): a tracer there opens no span. Call sites on hot paths
    gate on ``diagnostics._enabled`` themselves and enter :data:`NO_SPAN`
    otherwise."""
    if not _enabled:
        return NO_SPAN
    if _annotation is None:
        _bind_jax()
    if operand is not None and isinstance(getattr(operand, "larray", operand), _tracer):
        return NO_SPAN
    return _Span(name, label or name)


def record_collective(op: str, axis: Any, participants: int, nbytes: int) -> None:
    """Count one (traced) collective: ``nbytes`` is the *logical* payload —
    per-participant payload bytes × participants for the symmetric collectives,
    the logical array size for layout ops (``shard`` / ``_pad_reshard``)."""
    if not _enabled:
        return
    key = (op, str(axis), int(participants))
    with _lock:
        agg = _collectives.get(key)
        if agg is None:
            agg = _collectives[key] = {"count": 0, "bytes": 0}
        agg["count"] += 1
        agg["bytes"] += int(nbytes)


def record_compile(label: str, seconds: float) -> None:
    """One executor program compile: signature label + wall seconds (first-call
    wall time — trace + XLA compile + the first execution)."""
    if not _enabled:
        return
    rec = {"t": _utcnow(), "label": label, "seconds": round(float(seconds), 6)}
    with _lock:
        _compile_events.append(rec)


def record_dispatch_event(kind: str, label: str, reason: str) -> None:
    """An executor cache event worth explaining — currently ``miss`` with the
    signature component(s) that changed vs. the nearest cached key."""
    if not _enabled:
        return
    rec = {"t": _utcnow(), "kind": kind, "label": label, "reason": reason}
    with _lock:
        _dispatch_events.append(rec)


def record_fallback(site: str, reason: str) -> None:
    """One eager-path fallback that used to be a silent ``except Exception``:
    counted per site (``fallback.<site>``) and recorded with its reason
    (exception type + op label), so a workload that quietly lost its staged
    programs is visible in the report instead of just slow."""
    if not _enabled:
        return
    rec = {"t": _utcnow(), "site": site, "reason": str(reason)}
    with _lock:
        _counters[f"fallback.{site}"] = _counters.get(f"fallback.{site}", 0) + 1
        _fallback_events.append(rec)
    tee = _fallback_tee
    if tee is not None:
        tee(site, rec["reason"])


def record_resilience_event(site: str, kind: str, detail: str = "") -> None:
    """A resilience-subsystem event: policy ``retry``/``exhausted``, circuit
    ``breaker`` transitions, injected ``fault`` firings, executor ``fallback``
    and quarantine decisions. Always on (not gated by :func:`enabled`), like
    backend-health events: these come from explicit failure-path machinery,
    never from a hot compute path, and a failure must stay attributable
    even when metrics were off."""
    rec = {"t": _utcnow(), "site": site, "kind": kind, "detail": str(detail)}
    with _lock:
        _resilience_events.append(rec)
    tee = _resilience_tee
    if tee is not None:
        tee(site, kind, rec["detail"])
    ftee = _forensics_tee
    if ftee is not None:
        ftee(site, kind, rec["detail"])


def record_pad_waste(gshape, split: int, padded_dim: int) -> None:
    """Gauge the padded-layout waste of one dispatched op's ``(gshape, split)``
    family: pad fraction ``(padded - n) / padded`` of the split dimension."""
    if not _enabled:
        return
    gshape = tuple(int(s) for s in gshape)
    n = gshape[split]
    padded_dim = int(padded_dim)
    frac = (padded_dim - n) / padded_dim if padded_dim else 0.0
    key = (gshape, int(split), padded_dim)
    with _lock:
        agg = _pad_gauges.get(key)
        if agg is None:
            agg = _pad_gauges[key] = {"pad_fraction": round(frac, 6), "observations": 0}
        agg["observations"] += 1


# ------------------------------------------------------------------ backend health
def record_backend_event(up: bool, detail: str = "") -> dict:
    """Record an accelerator-backend probe result. Only *transitions* (and the
    first probe) enter the event stream and the ``HEAT_TPU_DIAG_LOG`` file —
    steady-state probes just confirm the known state. Always on (not gated by
    :func:`enabled`): health events come from explicit probes, never from a
    compute path."""
    global _backend_state
    up = bool(up)
    rec = {"t": _utcnow(), "up": up, "detail": str(detail)}
    with _lock:
        transition = _backend_state is None or _backend_state != up
        _backend_state = up
        if transition:
            _backend_events.append(rec)
    if transition:
        path = os.environ.get("HEAT_TPU_DIAG_LOG")
        if path:
            try:
                with open(path, "a") as f:
                    f.write(json.dumps({"backend": rec}) + "\n")
            except OSError:
                pass
    rec = dict(rec)
    rec["transition"] = transition
    return rec


# ------------------------------------------------------------------ reporting
def _flat_counters_locked() -> Dict[str, float]:
    # callers hold _lock; the field leads the name so that a prefix selects one field
    flat = dict(_counters)
    if _enabled:  # a reader tells "none" from "not counted"
        for name in _JIT_COUNTERS:
            flat.setdefault(name, 0)
    for name, agg in _spans.items():
        flat[f"span_n.{name}"] = agg["count"]
        flat[f"span_s.{name}"] = agg["total_s"]
        flat[f"span_self_s.{name}"] = agg["self_s"]
    return flat


def report() -> dict:
    """The full structured snapshot — the JSON schema documented in
    ``doc/source/observability.rst``."""
    with _lock:
        rep = {
            "schema": SCHEMA,
            "generated_at": _utcnow(),
            "enabled": _enabled,
            "tracing": _tracing,
            "startup": {k: dict(v) if isinstance(v, dict) else v
                        for k, v in _startup.items()},
            "counters": _flat_counters_locked(),
            "spans": {k: dict(v) for k, v in _spans.items()},
            "programs": {k: dict(v) for k, v in _programs.items()},
            "collectives": [
                {
                    "op": op,
                    "axis": axis,
                    "participants": participants,
                    "count": agg["count"],
                    "bytes": agg["bytes"],
                }
                for (op, axis, participants), agg in sorted(_collectives.items())
            ],
            "pad_waste": [
                {
                    "gshape": list(gshape),
                    "split": split,
                    "physical_dim": padded,
                    "logical_dim": gshape[split],
                    "pad_fraction": agg["pad_fraction"],
                    "observations": agg["observations"],
                }
                for (gshape, split, padded), agg in sorted(_pad_gauges.items())
            ],
            "compile_events": list(_compile_events),
            "dispatch_events": list(_dispatch_events),
            "fallback_events": list(_fallback_events),
            "resilience_events": list(_resilience_events),
            "backend_events": list(_backend_events),
        }
    with _lock:
        providers = list(_providers.items())
    for name, provider in providers:
        try:
            rep[name] = provider()
        except Exception as exc:  # ht: ignore[silent-except] -- not silent: the error lands in the report payload itself; a broken provider must not kill the report
            rep[name] = {"error": repr(exc)}
    return rep


def dump(path: str) -> None:
    """Write :func:`report` as JSON to ``path``.

    Routed through ``resilience.atomic_write`` (site ``diagnostics.dump``)
    when the resilience module has installed itself: a crash mid-dump leaves
    the previous artifact (or nothing), never a torn half-JSON — merged
    telemetry reads these artifacts back, so partial writes must be
    impossible, not just unlikely."""
    payload = report()

    def _write(target: str) -> None:
        with open(target, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")

    writer = _atomic_writer
    if writer is not None:
        writer(path, _write, site="diagnostics.dump")
    else:  # standalone load before resilience exists: plain write
        _write(path)


# ------------------------------------------------------------------ env bootstrap
if os.environ.get("HEAT_TPU_METRICS") == "1":
    _enabled = True
if os.environ.get("HEAT_TPU_TRACE") == "1":
    _tracing = True

# Only the PACKAGE instance registers the exit dump: a standalone file-path
# load (no parent package, __package__ falsy) is a second module instance, and
# atexit's LIFO order would let its near-empty report overwrite the package
# instance's full one.
_dump_path = os.environ.get("HEAT_TPU_DIAG_DUMP")
if _dump_path and __package__:

    @atexit.register
    def _dump_at_exit(path: str = _dump_path) -> None:  # pragma: no cover - exit hook
        try:
            dump(path)
        except Exception:  # ht: ignore[silent-except] -- atexit hook: raising here would mask the process's real exit status
            pass
