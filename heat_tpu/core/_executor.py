"""Signature-cached jit executor for the eager dispatch layer.

The four dispatch wrappers in :mod:`_operations` (``binary_op`` / ``local_op`` /
``reduce_op`` / ``cum_op``) historically issued their compute, pad re-mask
(``_zero_pads``), dtype cast and ``comm.shard`` epilogues as *separate* eager XLA
executions, so the per-op Python + dispatch latency dominated any small-op
workload. This module lets each
framework-level op resolve to an **abstract signature** and replay a
``jax.jit``-compiled program for it:

- The signature key is (operation identity, operand avals with weak-type
  normalisation for scalars, operand logical extents/padded-ness, splits and the
  out split, ``fn_kwargs``, ``out=``/``where=`` presence, the communicator's
  mesh). Everything the traced program closes over statically is in the key.
- On miss the wrapper builds the *whole* chain — compute → pad re-mask → dtype
  cast → physical pad — as one traced body, jitted with the explicit
  ``NamedSharding`` output spec from :mod:`communication`, so the mask and cast
  genuinely fuse into the producing op and the shard constraint costs no extra
  execution. On hit the call goes straight through jax's C++ dispatch fast path.
- ``out=`` programs take the destination buffer as their trailing argument and
  can be compiled with ``donate_argnums`` on it, so in-place-style updates stop
  allocating a second full shard (see :func:`sanitation.sanitize_donation` for
  the aliasing-safety contract).

A signature that the executor cannot stage (unhashable kwargs, shapes the padded
plans reject, …) is cached as *unsupported* so the wrapper falls back to the
eager path without re-deriving the decision.

**Real fusion — the deferred expression graph.** One XLA execution per
framework op still pays the backend's per-execution floor 64 times on a 64-op
chain, so supported elementwise ops (binary/local, no ``out=``/``where=``,
layout-aligned operands) do not execute at all at call time: they return a
:class:`Deferred` node recording (operation, operands) plus the result aval
resolved through a cached ``jax.eval_shape``. The first access to the result's
physical value (``DNDarray.parray``) **forces** the node: the whole reachable
graph is linearised, keyed by its structural signature (per-node op identity +
leaf avals + sharing pattern), and compiled/replayed as ONE program through the
same signature cache — a 64-op chain becomes one XLA executable per distinct
chain shape. Interior nodes of a fused graph skip the pad re-mask (pad slots
may hold garbage mid-program); every *materialised* value is re-masked by its
root program, so the clean-pad invariant still holds for anything observable.

**Multi-output fused programs.** A fan-out graph (``t = a + b; u = t * 2;
v = t * 3``) must not re-execute ``t``'s subchain inside every consumer's
program, so :func:`_force_graph` promotes *interior* nodes to extra program
outputs when their value has a future: a node referenced by more than one plan
entry, still wrapped by a live ``DNDarray`` (the weakref registry
:func:`note_wrapped` populates at wrap time), or held by a deferred graph
outside this plan (a refcount check). Every emitted value is pad re-masked by
the program and **memoised** into ``Deferred.value``, so forcing ``u`` also
materialises ``t``, and forcing ``v`` replays a trivial one-op program over the
cached leaf. Three more things ride the same linearisation:

- **structural CSE** — plan entries are keyed by ``(op identity, kwargs sig,
  operand refs)`` rather than node identity, so separately-built identical
  subexpressions collapse to one slot in the program (and one output slot when
  memoised);
- **leaf donation** — a leaf ``jax.Array`` whose only remaining readers are
  this program's plan entries (``sanitation.sanitize_leaf_donation``, the
  fused-graph form of the ``out=`` donation contract) is passed through
  ``donate_argnums``, so pipeline-style ``x = f(x)`` workloads stop holding
  two full generations of shards;
- nothing-shared graphs emit exactly one output through the same code path,
  so single-consumer chains compile byte-identical HLO to the single-output
  executor.

**Async multi-tenant dispatch.** Forces used to run entirely under the global
executor lock — linearisation, donation decisions, AND the program call — so
concurrent serving requests serialised on every force. With
``HEAT_TPU_ASYNC_DISPATCH`` (default on, ``=0`` restores the serialized path
bit-for-bit) a force only *plans* under the lock: the graph is linearised, the
donation/emission decisions are made, every emitted node's ``Deferred.value``
is filled with a :class:`~._scheduler.PendingValue` dispatch-done future, and
the buffers the call will touch are claimed in the per-buffer ownership
registry (donation epochs — the narrow thing the global lock actually
protected). The *execution* then happens outside the lock: inline on the
submitting thread when nobody else is dispatching, or parked in the
:class:`~._scheduler.DispatchScheduler`'s bounded per-tenant queue, where a
scheduler thread drains it round-robin across request tags and **batches**
concurrent same-signature forces into one ``jax.vmap``-derived program variant
(:meth:`_Program.call_batched`). A full queue is backpressure: the submitter
retries under the ``executor.queue`` ``ht.resilience`` policy and, exhausted,
runs inline — work is never dropped. Failures inside a queued execution take
the same :func:`fallback_after_failure` + ``replay_eager`` path as the
serialized executor, so chaos plans cannot lose data by firing mid-queue.

**Request lifecycle (ISSUE 10).** A force can carry a wall-clock **deadline**:
``profiler.request(tag, deadline_s=...)`` arms it in the request's contextvar
scope, every :class:`Deferred` captures it at defer time (exactly like
``Deferred.req``), and the earliest deadline over a force's roots rides the
:class:`_ForcePlan` and the queued :class:`~._scheduler.WorkItem`. The
executor then refuses to spend capacity on work that can no longer meet it,
at every checkpoint that is safe to interrupt — **admission** (a force whose
deadline already passed raises a typed ``ht.resilience.DeadlineExceeded``
before planning; with ``HEAT_TPU_SHED=1``, SLO-aware admission control also
sheds work whose per-signature service-time EWMA — ``_Program.ewma_s``, the
same quantity the profiler's ``service.<label>`` histograms record — cannot
fit in the remaining budget), **pre-dispatch** (the scheduler cancels expired
queued items and excludes expired peers from batch formation), and **between
ops of the eager replay** (:func:`_plan_replay_eager` checks the deadline per
plan entry). ``HEAT_TPU_SHED=1`` additionally turns queue-full backpressure
exhaustion into a typed ``Shed`` for deadline-bearing requests instead of
inline execution, so overload sheds infeasible work rather than serialising
everyone behind it. Lifecycle verbs live on the scheduler
(``cancel(tag)`` / ``drain(timeout)`` / ``reopen()``), an atexit drain
guarantees interpreter shutdown fulfils every outstanding ``PendingValue``
with a value or a typed error, and every shed/cancel/expiry lands in the
scheduler's lifecycle ledger (``executor_stats()``, diagnostics counters,
and the profiler's ``lifecycle.<kind>`` Perfetto counter tracks). With no
deadline armed, every checkpoint is a single attribute read — the
deadline-off dispatch ops/s and HLO-parity gates keep enforcing that.

Escape hatch: ``HEAT_TPU_EAGER_DISPATCH=1`` disables the executor entirely and
restores the fully eager dispatch path for debugging. Introspection:
:func:`executor_stats` (hits / misses / retraces / cache size / queue + batch
telemetry) backs the tests and the ``benchmarks/cb/dispatch.py``
microbenchmark.
"""

from __future__ import annotations

import atexit
import os
import sys
import threading
import time
import weakref
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import (
    _compile_cache, _result_cache, _scheduler, diagnostics, forensics, ops,
    profiler, resilience, supervision,
)
from ._compile_cache import executor_save_warmup, executor_warmup
from ._scheduler import PendingValue

__all__ = [
    "executor_stats",
    "reset_executor_stats",
    "clear_executor_cache",
    "reload_env_knobs",
    "executor_enabled",
    "async_dispatch_enabled",
    "executor_warmup",
    "executor_save_warmup",
    "rebuild_scheduler",
]

# Retrace-storm guard: per-call lambdas (now hoisted where we control them) or
# genuinely polymorphic workloads must not grow the program table without bound.
_MAX_PROGRAMS = 1024

# Per-program cap on distinct leaf-donation jit variants: each distinct
# donate_argnums tuple is a separate XLA compile, and a workload whose
# donation mask churns call-to-call would otherwise compile without bound.
_MAX_DONATE_VARIANTS = 4

UNSUPPORTED = object()
"""Sentinel a ``build`` callback returns (and the cache stores) for signatures the
executor cannot stage; the wrapper takes the eager path."""


# Telemetry tallies. These used to be one shared object with RELAXED racing
# `+=` on a few hot paths (a racing increment could undercount) — acceptable
# when the only concurrency was test threads, wrong for a scheduler that
# executes forces on worker + scheduler threads all day. They are now
# PER-THREAD accumulator cells merged at report time: every `_stats.field += n`
# lands in the calling thread's private cell (no lock, no race, exact), and
# `executor_stats()` sums the cells. Cells of finished threads are folded into
# a retired cell so thread churn cannot grow the registry without bound.
_STAT_FIELDS = (
    "hits", "misses", "retraces",
    # multi-output fused-graph telemetry (see the force paths)
    "interior_outputs", "reexec_avoided", "reexecuted",
    "cse_hits", "donated_bytes",
    # failure hardening: compiled programs whose compile/execute failed and
    # whose call fell back to the eager path (see fallback_after_failure)
    "eager_fallbacks",
    # async executor telemetry: wall nanoseconds threads spent BLOCKED on the
    # executor lock, and leaf donations refused by the per-buffer ownership
    # registry (an in-flight reader or a standing claim held the buffer)
    "lock_wait_ns", "donation_refusals",
)
_STAT_FIELD_SET = frozenset(_STAT_FIELDS)


class _StatsCell:
    __slots__ = _STAT_FIELDS + ("_thread",)

    def __init__(self):
        for field in _STAT_FIELDS:
            setattr(self, field, 0)
        self._thread = weakref.ref(threading.current_thread())


class _Stats:
    """Per-thread stat cells behind the familiar ``_stats.field += n`` shape.

    Attribute reads/writes of a stat field resolve to the calling thread's
    cell (created on first touch), so increments are exact without any lock.
    :meth:`totals` merges every cell (minus the reset baseline); dead threads'
    cells are folded into ``_retired`` during the merge."""

    def __init__(self):
        object.__setattr__(self, "_local", threading.local())
        object.__setattr__(self, "_cells", [])
        object.__setattr__(self, "_cells_lock", threading.Lock())
        object.__setattr__(self, "_retired", {f: 0 for f in _STAT_FIELDS})
        object.__setattr__(self, "_base", {f: 0 for f in _STAT_FIELDS})

    def _cell(self) -> _StatsCell:
        cell = getattr(self._local, "cell", None)
        if cell is None:
            cell = _StatsCell()
            with self._cells_lock:
                self._cells.append(cell)
            self._local.cell = cell
        return cell

    def __getattr__(self, name):
        if name in _STAT_FIELD_SET:
            return getattr(self._cell(), name)
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if name in _STAT_FIELD_SET:
            setattr(self._cell(), name, value)
        else:
            object.__setattr__(self, name, value)

    def _raw_totals_locked(self) -> dict:
        live = []
        for cell in self._cells:
            th = cell._thread()
            if th is None or not th.is_alive():
                # the owning thread can no longer increment: fold and drop
                for f in _STAT_FIELDS:
                    self._retired[f] += getattr(cell, f)
            else:
                live.append(cell)
        self._cells[:] = live
        totals = dict(self._retired)
        for cell in live:
            for f in _STAT_FIELDS:
                totals[f] += getattr(cell, f)
        return totals

    def totals(self) -> dict:
        with self._cells_lock:
            raw = self._raw_totals_locked()
        return {f: raw[f] - self._base[f] for f in _STAT_FIELDS}

    def total(self, name: str) -> int:
        return self.totals()[name]

    def reset(self) -> None:
        # a baseline snapshot, not a zeroing write: concurrent increments on
        # other threads are never lost, they just count toward the next window
        with self._cells_lock:
            raw = self._raw_totals_locked()
            self._base.update(raw)


_stats = _Stats()
_programs: "OrderedDict[Any, Any]" = OrderedDict()
_lock = threading.RLock()


def _lock_acquire() -> None:
    """Acquire the executor lock, charging any blocked wait to the calling
    thread's ``lock_wait_ns`` tally (the uncontended path is one try-acquire)."""
    if _lock.acquire(blocking=False):
        return
    t0 = time.perf_counter_ns()
    _lock.acquire()
    _stats.lock_wait_ns += time.perf_counter_ns() - t0


class _TimedLock:
    """``with _tlock:`` — the executor lock with contention accounting."""

    __slots__ = ()

    def __enter__(self):
        _lock_acquire()

    def __exit__(self, *exc):
        _lock.release()


_tlock = _TimedLock()

# Warm-up counts for signatures seen but not yet compiled (jit threshold > 1).
_seen: Dict[Any, int] = {}
_MAX_SEEN = 8192


# ----------------------------------------------------------------- env knobs
# The dispatch knobs used to be re-read from os.environ on every call —
# async_dispatch_enabled() per force, executor_enabled() per op (twice for
# binary ops), batch_max() per queued submit. Each read is cheap, but the hot
# dispatch path paid them millions of times for values that change a handful
# of times per process. They are now MEMOISED: parsed once at import, and
# re-read only at the two documented re-read points —
#
#   * reload_env_knobs()      — the explicit API; call it after mutating
#     os.environ in-process (tests, benchmarks, the serving async-gate);
#   * clear_executor_cache()  — dropping every cached program is the natural
#     moment to re-honour the environment that shapes new ones.
#
# A fresh process always re-reads at import, so subprocess-armed knobs need
# nothing extra.


class _EnvKnobs:
    __slots__ = (
        "eager_dispatch", "async_dispatch", "jit_threshold",
        "queue_bound", "batch_max", "quarantine_after", "shed",
        "sched_shards", "batch_window_s", "exec_cache", "linalg_plan",
    )

    def reload(self) -> None:
        def _int(name: str, default: int) -> int:
            try:
                return max(1, int(os.environ.get(name, str(default))))
            except ValueError:
                return default

        self.eager_dispatch = os.environ.get("HEAT_TPU_EAGER_DISPATCH") == "1"
        self.async_dispatch = os.environ.get("HEAT_TPU_ASYNC_DISPATCH", "1") != "0"
        self.jit_threshold = _int("HEAT_TPU_JIT_THRESHOLD", 1)
        self.queue_bound = _int("HEAT_TPU_DISPATCH_QUEUE", 256)
        self.batch_max = _int("HEAT_TPU_BATCH_MAX", 8)
        self.quarantine_after = _int("HEAT_TPU_QUARANTINE_AFTER", 3)
        self.shed = os.environ.get("HEAT_TPU_SHED") == "1"
        # scheduler shard count (ISSUE 15): applied when the scheduler is
        # CONSTRUCTED — an in-process change needs rebuild_scheduler()
        self.sched_shards = _int(
            "HEAT_TPU_SCHED_SHARDS", min(4, os.cpu_count() or 1)
        )
        # adaptive batch window in µs (0 = no holds, the pre-window scheduler)
        try:
            self.batch_window_s = max(
                0.0, int(os.environ.get("HEAT_TPU_BATCH_WINDOW_US", "0")) * 1e-6
            )
        except ValueError:
            self.batch_window_s = 0.0
        # persistent per-signature compile-cache directory (None = off)
        self.exec_cache = os.environ.get("HEAT_TPU_EXEC_CACHE") or None
        # communication plan for distributed contractions (linalg/comm_plan.py)
        plan = os.environ.get("HEAT_TPU_LINALG_PLAN", "auto").strip().lower()
        self.linalg_plan = plan if plan in ("auto", "xla", "ring", "rs") else "auto"


_knobs = _EnvKnobs()
_knobs.reload()


def reload_env_knobs() -> None:
    """Re-read every memoised ``HEAT_TPU_*`` dispatch knob from ``os.environ``.

    The knobs (``HEAT_TPU_EAGER_DISPATCH`` / ``ASYNC_DISPATCH`` /
    ``JIT_THRESHOLD`` / ``DISPATCH_QUEUE`` / ``BATCH_MAX`` /
    ``QUARANTINE_AFTER`` / ``SHED``) are parsed once at import and memoised off the hot
    dispatch path; in-process environment mutations take effect at the next
    call to this function (or to :func:`clear_executor_cache`, which re-reads
    as part of dropping the program table). The supervision plane's memoised
    knobs (``HEAT_TPU_SUPERVISION`` / ``PEER_TIMEOUT_S`` /
    ``COLLECTIVE_TIMEOUT_S`` / ``COORD_TIMEOUT_MS``) and the signature-cache
    knob (``HEAT_TPU_EXEC_CACHE``) re-read here
    too, so one call covers the whole framework. ``HEAT_TPU_SCHED_SHARDS`` is
    re-read but only applied when the scheduler is (re)constructed — see
    :func:`rebuild_scheduler`. The result-memoization knobs
    (``HEAT_TPU_RESULT_CACHE`` / ``HEAT_TPU_RESULT_CACHE_BYTES``) re-read
    here as well — see :mod:`._result_cache`. The live-operations knobs
    (``HEAT_TPU_OPS*``) re-read here too — see :mod:`.ops` — as do the
    request-forensics knobs (``HEAT_TPU_FORENSICS*``) — see
    :mod:`.forensics`. The communication-plan knob for distributed
    contractions (``HEAT_TPU_LINALG_PLAN``) re-reads here too — see
    :func:`linalg_plan` and :mod:`.linalg.comm_plan`."""
    _knobs.reload()
    supervision.reload_env_knobs()
    _compile_cache.reload()
    _result_cache.reload()
    ops.reload()
    forensics.reload()


def jit_threshold() -> int:
    """How many sightings of a signature before the executor compiles it.

    ``HEAT_TPU_JIT_THRESHOLD=1`` (the default) compiles on first miss — every
    structurally-identical later call is pure replay. Values >1 let the first
    ``N-1`` sightings take the original eager path and only compile signatures
    that prove hot: the right trade for signature-diverse workloads (test
    suites, exploratory sessions) where most programs would compile once and
    never replay. Memoised; see :func:`reload_env_knobs` for the re-read
    contract."""
    return _knobs.jit_threshold


def linalg_plan() -> str:
    """The communication plan for distributed contractions
    (``HEAT_TPU_LINALG_PLAN``): ``auto`` (default — the cost model in
    :mod:`.linalg.comm_plan` picks per call), ``xla`` (always the XLA-SPMD
    default, also disabling the all_to_all resplit path), ``ring`` (force the
    ring collective matmul where eligible), or ``rs`` (force the
    reduce-scatter contraction — note this changes the result's split from
    ``None`` to ``0``). Unknown values fall back to ``auto``. Memoised; see
    :func:`reload_env_knobs` for the re-read contract."""
    return _knobs.linalg_plan


_single_controller: Optional[bool] = None


def executor_enabled() -> bool:
    """Whether dispatch should route through the cached-program executor.

    ``HEAT_TPU_EAGER_DISPATCH=1`` is the debugging escape hatch (memoised —
    call :func:`reload_env_knobs` after flipping it in-process);
    multi-controller processes always take the eager path — its ``comm.shard``
    has the per-process shard-population logic the staged programs do not
    replicate. The process count is resolved once (it cannot change after
    backend initialisation, and dispatch calls this per op — twice for binary
    ops — so the xla_bridge round-trip matters)."""
    global _single_controller
    if _knobs.eager_dispatch:
        return False
    if _single_controller is None:
        _single_controller = jax.process_count() == 1
    return _single_controller


def async_dispatch_enabled() -> bool:
    """Whether deferred-graph forces take the async scheduler path.

    ``HEAT_TPU_ASYNC_DISPATCH=0`` restores the fully lock-serialized force
    (plan AND program call under the executor lock, direct memoisation — the
    pre-scheduler executor, bit for bit). Memoised off the per-force hot path;
    tests and the serving async-gate flip it in-process via
    :func:`reload_env_knobs`."""
    return _knobs.async_dispatch


def queue_bound() -> int:
    """Dispatch-queue capacity (``HEAT_TPU_DISPATCH_QUEUE``, default 256).
    A submit against a full queue is backpressure: retried under the
    ``executor.queue`` resilience policy, then executed inline. Memoised; see
    :func:`reload_env_knobs`."""
    return _knobs.queue_bound


def batch_max() -> int:
    """Cross-request batching width cap (``HEAT_TPU_BATCH_MAX``, default 8;
    ``1`` disables batching). Widths are bucketed to powers of two up to this
    cap so each program compiles a bounded set of batched variants. Memoised;
    see :func:`reload_env_knobs`."""
    return _knobs.batch_max


def shed_enabled() -> bool:
    """Whether load-shedding admission control is on (``HEAT_TPU_SHED=1``).
    Shedding only changes behaviour for DEADLINE-bearing requests: infeasible
    work (service-time EWMA past the remaining budget) and queue-full
    backpressure exhaustion deliver a typed ``ht.resilience.Shed`` instead of
    executing; requests without a deadline are never shed. Memoised; see
    :func:`reload_env_knobs`."""
    return _knobs.shed


def sched_shards() -> int:
    """Dispatch-scheduler shard count (``HEAT_TPU_SCHED_SHARDS``, default
    ``min(4, cores)``; ``1`` reproduces the single-queue scheduler exactly).
    Memoised, and applied when the scheduler singleton is CONSTRUCTED — an
    in-process change needs :func:`rebuild_scheduler` (benchmarks/tests) or a
    fresh process; :func:`reload_env_knobs` alone only updates the value the
    next construction will read."""
    return _knobs.sched_shards


def batch_window_s() -> float:
    """Adaptive batch-window cap in SECONDS (``HEAT_TPU_BATCH_WINDOW_US``,
    default 0 = no holds — today's dispatch timing exactly). When positive, a
    shard that popped a batchable item below the batch cap may hold it up to
    this long (EWMA-tuned down, bounded by deadline headroom) so concurrent
    same-signature requests widen the batch. Memoised; see
    :func:`reload_env_knobs`."""
    return _knobs.batch_window_s


# ------------------------------------------------------- per-buffer ownership
# Donation epochs: the narrow invariant the global force lock actually
# protected is "a buffer donated to one program call is never an operand of a
# concurrent call". With execution moved outside the lock, that invariant
# lives here instead: a planned call REGISTERS its leaf buffers (reads) and
# CLAIMS its donation candidates under _own_lock before the executor lock is
# released; a claim is refused — the call simply runs undonated, donation is
# an optimisation, never a dependency — when any other in-flight call still
# reads the buffer or holds a standing claim. Non-donating forces only touch
# this tiny lock for the register/release pair and never contend on donation.

_own_lock = threading.Lock()
_inflight_reads: Dict[int, int] = {}   # id(jax.Array) -> in-flight reading calls
_donation_claims: Dict[int, int] = {}  # id(jax.Array) -> claim epoch
_donation_epoch = 0


def _acquire_buffers(read_leaves, donate_leaves):
    """Register one planned call's buffer ownership. Returns the subset of
    ``donate_leaves`` whose claims were GRANTED (the rest count as
    ``donation_refusals`` and run undonated). Call :func:`_release_buffers`
    with the same lists when the call completes."""
    global _donation_epoch
    granted = []
    with _own_lock:
        _donation_epoch += 1
        for leaf in donate_leaves:
            i = id(leaf)
            if _inflight_reads.get(i) or i in _donation_claims:
                _stats.donation_refusals += 1
                read_leaves.append(leaf)  # demoted to a plain read
            else:
                _donation_claims[i] = _donation_epoch
                granted.append(leaf)
        for leaf in read_leaves:
            i = id(leaf)
            _inflight_reads[i] = _inflight_reads.get(i, 0) + 1
    if diagnostics._enabled and len(granted) != len(donate_leaves):
        diagnostics.counter(
            "executor.donation_refused", len(donate_leaves) - len(granted)
        )
    if granted and _result_cache._enabled:
        # the donation-epoch bump doubles as result-cache invalidation: every
        # entry whose inputs or outputs alias a granted buffer is dropped
        # BEFORE the donating call can consume it (a late racer is caught by
        # the deleted-buffer re-check at hit time — never served)
        _result_cache.note_donation([id(v) for v in granted])
    return granted


def _release_buffers(read_leaves, granted) -> None:
    with _own_lock:
        for leaf in read_leaves:
            i = id(leaf)
            n = _inflight_reads.get(i, 0) - 1
            if n > 0:
                _inflight_reads[i] = n
            else:
                _inflight_reads.pop(i, None)
        for leaf in granted:
            _donation_claims.pop(id(leaf), None)


# ------------------------------------------------------------ dispatch queue
_dispatch_scheduler: Optional[_scheduler.DispatchScheduler] = None


def _get_scheduler() -> _scheduler.DispatchScheduler:
    global _dispatch_scheduler
    sched = _dispatch_scheduler
    if sched is None:
        with _lock:
            sched = _dispatch_scheduler
            if sched is None:
                sched = _scheduler.DispatchScheduler(
                    _execute_batch, shards=_knobs.sched_shards
                )
                _dispatch_scheduler = sched
    return sched


def rebuild_scheduler() -> _scheduler.DispatchScheduler:
    """Tear the scheduler singleton down and rebuild it with the CURRENT
    memoised knobs (``HEAT_TPU_SCHED_SHARDS`` is applied at construction).

    For benchmarks and tests that compare shard counts in one process
    (``benchmarks/serving/shard_gate.py``): the old scheduler is drained
    first — every outstanding future settles with a value or a typed error —
    and the replacement starts fresh (telemetry zeroed). Not a hot path."""
    global _dispatch_scheduler
    old = _dispatch_scheduler
    if old is not None:
        try:
            old.drain(timeout=30.0)
        except resilience.DrainTimeout:
            # leftovers were already shed with typed errors; the rebuild
            # proceeds — nothing can strand on the abandoned scheduler
            pass
    with _lock:
        _dispatch_scheduler = _scheduler.DispatchScheduler(
            _execute_batch, shards=_knobs.sched_shards
        )
        sched = _dispatch_scheduler
    return sched


#: hot signatures carried in the pressure block (bounded: the block rides in
#: every ops sample and cluster beat, so it must stay compact)
_PRESSURE_TOP_SIGNATURES = 8


def _pressure_block(per_shard: Sequence[dict]) -> dict:
    """The autoscaler-facing pressure contract (``executor_stats()
    ["pressure"]``): per-shard queue-depth / shed-rate / submit-gap EWMAs plus
    the service-time EWMA of the hottest compiled signatures.

    Lock policy — exact vs relaxed, spelled out because the two halves
    deliberately differ:

    * The per-shard EWMAs are **exact at copy time**: each shard's cells are
      read under its own ``_cv`` by ``snapshot_locked_copy`` (the same fold
      every other scheduler stat takes), so a shard's depth/shed/gap triple
      is internally consistent, though shards are sampled at slightly
      different instants.
    * The per-signature ``service_ewma_s`` values are **deliberately
      relaxed**: ``_Program.ewma_s`` is a last-writer-wins cell updated by
      whichever thread replays the program (admission feasibility checks read
      it bare the same way). Only the program-table *iteration* is under
      ``_lock``; the EWMA reads are bare — a torn read is impossible for a
      Python float reference, and a stale one is exactly as stale as the
      admission controller already tolerates."""
    pressure_shards = [
        {
            "shard": i,
            "queue_depth": snap["queue_depth"],
            "depth_ewma": round(snap["depth_ewma"], 6),
            "shed_rate_ewma": round(snap["shed_rate_ewma"], 6),
            "gap_ewma_s": round(snap["gap_ewma_s"], 9),
        }
        for i, snap in enumerate(per_shard)
    ]
    with _lock:
        entries = [
            (entry.label or _key_label(key), entry.hits, entry.ewma_s)
            for key, entry in _programs.items()
            if entry is not UNSUPPORTED
        ]
    entries.sort(key=lambda e: (-e[1], e[0]))
    service = {
        label: round(ewma, 9)
        for label, hits, ewma in entries[:_PRESSURE_TOP_SIGNATURES]
        if ewma > 0.0
    }
    return {"per_shard": pressure_shards, "service_ewma_s": service}


def executor_stats(top: int = 0) -> dict:
    """Cache introspection: ``hits`` / ``misses`` (signature-table lookups),
    ``retraces`` (times a program body was actually traced — 0 between two
    identical calls means the replay was pure cache), and ``programs`` (table
    size, unsupported-signature entries included).

    Multi-output fused-graph counters (all global tallies since the last
    :func:`reset_executor_stats`, maintained by the deferred-graph force):

    - ``interior_outputs`` — interior (non-root) values a forced graph emitted
      as extra program outputs and memoised into their ``Deferred`` nodes:
      nodes shared by several plan entries, still wrapped by a live
      ``DNDarray``, or referenced by a deferred graph outside the plan.
    - ``reexec_avoided`` — re-executions of a whole subchain that the
      memoisation made unnecessary: a force that consumed a previously
      memoised interior value as a plain leaf, or a ``.parray`` read satisfied
      straight from ``Deferred.value`` without building a program at all.
    - ``reexecuted`` — plan entries whose node had ALREADY been executed
      inside an earlier program but was not memoised, so its subchain ran
      again. Structurally this should stay 0; the ``fanout`` dispatch
      benchmark gates on it.
    - ``cse_hits`` — structural-CSE collapses during linearisation: a
      separately-built node whose ``(op, kwargs, operand refs)`` matched an
      existing plan entry and took its slot instead of adding one.
    - ``donated_bytes`` — physical bytes of leaf buffers donated to fused
      programs (``donate_argnums``; see ``sanitation.sanitize_leaf_donation``).

    Failure-hardening counters (see :func:`fallback_after_failure`):

    - ``eager_fallbacks`` — compiled-program calls whose compile or execution
      failed and whose dispatch fell back to the eager path (same math, no
      user-visible data loss).
    - ``quarantined`` — labels of signatures evicted to the permanent eager
      path after repeated failures, each mapped to the explained reason
      (phase, failure count, exception).

    Async-scheduler counters (all since the last reset; see
    :mod:`._scheduler` and ``doc/source/performance.rst``):

    - ``queue_depth_peak`` — deepest the bounded dispatch queue has been.
    - ``batched_requests`` — forces that rode a cross-request batched
      execution (one ``jax.vmap``-derived program call for N requests).
    - ``batch_width_hist`` — ``{width: count}`` of batched executions.
    - ``lock_wait_ns`` — wall nanoseconds threads spent blocked acquiring the
      executor lock (the contention the async path exists to remove).
    - ``donation_refusals`` — leaf donations the per-buffer ownership registry
      refused because another in-flight call still owned the buffer.

    Sharded-scheduler counters (ISSUE 15; every scheduler tally lives in
    per-shard cells folded exactly at report — see ``_scheduler``):

    - ``sched_shards`` / ``per_shard`` — the constructed shard count and one
      telemetry snapshot per shard (``queue_depth_peak`` at top level is the
      SUM of per-shard peaks; each shard's own peak is in ``per_shard``).
    - ``stolen_batch_items`` — batchable items pulled from other shards'
      queues by cross-shard work-stealing.
    - ``window_holds`` / ``window_widened`` / ``window_hold_ns`` — adaptive
      batch-window activity (``HEAT_TPU_BATCH_WINDOW_US``).
    - ``pressure`` — the autoscaler-facing live-pressure contract (ISSUE 18;
      consumed by :mod:`.ops` but useful with the ops plane off): per-shard
      queue-depth / shed-rate / submit-gap EWMAs plus the service-time EWMA
      per hot signature — see :func:`_pressure_block` for the exact-vs-relaxed
      lock policy.

    Cross-request result cache (``HEAT_TPU_RESULT_CACHE=1``; see
    :mod:`._result_cache` and ``doc/source/performance.rst``):

    - ``cache_hits`` / ``cache_misses`` — result-cache consults that served a
      validated memoised value vs. fell through to execution.
    - ``cache_bytes_saved`` — result-buffer bytes served without executing.
    - ``cache_invalidations`` — entries dropped by generation bumps
      (``swap_state``, batch rotation) or donation-epoch bumps.
    - ``result_cache`` — the full per-shard block (occupancy, stores,
      evictions, replications, typed ``cache-corrupt`` rejects).

    Request-lifecycle ledger (ISSUE 10; every shed/cancel/expiry is counted —
    nothing is silently dropped):

    - ``expired_requests`` — forces refused at admission, cancelled
      pre-dispatch, or interrupted between replay ops because their wall-clock
      deadline had passed (typed ``DeadlineExceeded`` delivered).
    - ``shed_requests`` — deadline-bearing forces rejected by
      ``HEAT_TPU_SHED=1`` admission control (infeasible per the service-time
      EWMA, or queue-full through backpressure) with a typed ``Shed``; also
      items shed by a timed-out ``drain``.
    - ``cancelled_requests`` — queued items cancelled by
      ``DispatchScheduler.cancel(tag)`` (typed ``RequestCancelled``).
    - ``drain_rejects`` / ``draining`` — submits refused because admission is
      closed, and whether it currently is.
    - ``lifecycle_by_tenant`` — the same ledger broken down by request tag.

    ``top > 0`` adds ``top_signatures``: the N hottest compiled programs by
    lifetime replay count, each as ``{"label", "hits", "compile_s"}`` —
    ``label`` names the dispatch family and operation (``"defer:add..add[64]"``,
    ``"r:sum"``), ``hits`` counts replays since the program was compiled (NOT
    reset by :func:`reset_executor_stats` — they live with the program), and
    ``compile_s`` is the first-call wall time (trace + XLA compile + first
    execution)."""
    totals = _stats.totals()
    stats = {
        "hits": totals["hits"],
        "misses": totals["misses"],
        "retraces": totals["retraces"],
        "programs": len(_programs),
        "interior_outputs": totals["interior_outputs"],
        "reexec_avoided": totals["reexec_avoided"],
        "reexecuted": totals["reexecuted"],
        "cse_hits": totals["cse_hits"],
        "donated_bytes": totals["donated_bytes"],
        "eager_fallbacks": totals["eager_fallbacks"],
        "lock_wait_ns": totals["lock_wait_ns"],
        "donation_refusals": totals["donation_refusals"],
    }
    sched = _dispatch_scheduler
    if sched is not None:
        sstats = sched.stats()
        stats["queue_depth_peak"] = sstats["queue_depth_peak"]
        stats["batched_requests"] = sstats["batched_requests"]
        stats["batch_width_hist"] = sstats["batch_width_hist"]
        stats["queue_full_events"] = sstats["queue_full_events"]
        stats["inline_dispatches"] = sstats["inline_runs"]
        stats["queued_dispatches"] = sstats["submitted"]
        stats["shed_requests"] = sstats["lifecycle"]["shed"]
        stats["expired_requests"] = sstats["lifecycle"]["deadline_expired"]
        stats["cancelled_requests"] = sstats["lifecycle"]["cancelled"]
        stats["drain_rejects"] = sstats["drain_rejects"]
        stats["draining"] = sstats["draining"]
        stats["lifecycle_by_tenant"] = sstats["tenant_lifecycle"]
        stats["sched_shards"] = sstats["shards"]
        stats["per_shard"] = sstats["per_shard"]
        stats["stolen_batch_items"] = sstats["stolen_batch_items"]
        stats["window_holds"] = sstats["window_holds"]
        stats["window_widened"] = sstats["window_widened"]
        stats["window_hold_ns"] = sstats["window_hold_ns"]
        stats["pressure"] = _pressure_block(sstats["per_shard"])
    else:
        stats["queue_depth_peak"] = 0
        stats["batched_requests"] = 0
        stats["batch_width_hist"] = {}
        stats["queue_full_events"] = 0
        stats["inline_dispatches"] = 0
        stats["queued_dispatches"] = 0
        stats["shed_requests"] = 0
        stats["expired_requests"] = 0
        stats["cancelled_requests"] = 0
        stats["drain_rejects"] = 0
        stats["draining"] = False
        stats["lifecycle_by_tenant"] = {}
        stats["sched_shards"] = _knobs.sched_shards
        stats["per_shard"] = []
        stats["stolen_batch_items"] = 0
        stats["window_holds"] = 0
        stats["window_widened"] = 0
        stats["window_hold_ns"] = 0
        stats["pressure"] = _pressure_block([])
    rc = _result_cache.stats()
    stats["result_cache"] = rc
    stats["cache_hits"] = rc["hits"]
    stats["cache_misses"] = rc["misses"]
    stats["cache_bytes_saved"] = rc["bytes_saved"]
    stats["cache_invalidations"] = rc["invalidations"]
    # per-tenant cost meters (forensics plane): empty dict until armed; the
    # fold over tenants reconciles exactly with forensics.totals()
    stats["tenant_cost"] = forensics.tenant_cost()
    with _lock:
        stats["quarantined"] = dict(_quarantined)
    if top > 0:
        with _lock:
            progs = [
                (key, entry)
                for key, entry in _programs.items()
                if entry is not UNSUPPORTED
            ]
        # deterministic tie order (ISSUE 15 satellite): equal-hit signatures
        # used to come back in dict-insertion order, making warmup top-K
        # selection and test assertions depend on dispatch history
        progs.sort(
            key=lambda item: (
                -item[1].hits, item[1].label or _key_label(item[0])
            )
        )
        stats["top_signatures"] = [
            {
                "label": entry.label or _key_label(key),
                "hits": entry.hits,
                "compile_s": round(entry.compile_s, 6),
            }
            for key, entry in progs[:top]
        ]
    return stats


def reset_executor_stats() -> None:
    """Zero the GLOBAL counters (``hits`` / ``misses`` / ``retraces``, the
    multi-output fused-graph tallies ``interior_outputs`` / ``reexec_avoided``
    / ``reexecuted`` / ``cse_hits`` / ``donated_bytes``, and the async
    scheduler/lock telemetry). The program table is kept, and so are the
    per-signature lifetime tallies behind ``executor_stats(top=N)`` — those
    are properties of the cached programs and only drop with them
    (:func:`clear_executor_cache`)."""
    _stats.reset()
    sched = _dispatch_scheduler
    if sched is not None:
        sched.reset_stats()
    _result_cache.reset_stats()


def clear_executor_cache() -> None:
    """Drop every cached program (plus warm-up counts and result-aval cache)
    AND the cross-request result cache (:mod:`._result_cache` — every
    memoised result is gone, so the first post-clear read of any key is a
    guaranteed recompute, never a stale hit), AND reset all statistics: the
    global ``hits`` / ``misses`` / ``retraces`` counters are zeroed, and the
    per-signature breakdown of ``executor_stats(top=N)`` empties because the
    programs carrying those tallies are gone. After this call
    ``executor_stats()`` reports all zeros and the next dispatch of any
    signature recompiles (a counted retrace).
    Also one of the two documented re-read points for the memoised
    ``HEAT_TPU_*`` dispatch knobs (:func:`reload_env_knobs`)."""
    with _lock:
        _programs.clear()
        _seen.clear()
        _quarantined.clear()
    with _aval_lock:
        _aval_cache.clear()
    _result_cache.clear()
    reset_executor_stats()
    reload_env_knobs()


# ------------------------------------------------------------------ diagnostics glue
# Signature keys are positional tuples; these name the positions per dispatch
# family so a cache miss can be *explained* — which component changed vs. the
# nearest cached key (diagnostics.record_dispatch_event). Keys are built in
# _operations (b.pad/b.log/l/r/c) and _force below (defer).
_KEY_COMPONENTS: Dict[str, Tuple[str, ...]] = {
    "b.pad": ("family", "operation", "kwargs", "out_shape", "out_split", "mesh",
              "operand_avals"),
    "b.log": ("family", "operation", "kwargs", "out_shape", "out_split", "mesh",
              "operand_avals", "where", "out"),
    "l": ("family", "operation", "kwargs", "operand_aval", "gshape", "split",
          "mesh", "out"),
    "r": ("family", "operation", "kwargs", "operand_aval", "gshape", "split",
          "axis", "keepdims", "mesh", "out"),
    "c": ("family", "operation", "kwargs", "operand_aval", "gshape", "split",
          "axis", "accum_dtype", "mesh", "out"),
    "defer": ("family", "mesh", "gshape", "split", "graph", "outputs"),
}


def _op_label(operation) -> str:
    name = getattr(operation, "__name__", None)
    return name if name else repr(operation)


def _key_label(key) -> str:
    """A compact human label for a signature key: dispatch family + op name
    (``"r:sum"``). Fused-graph (``"defer"``) keys carry opaque ``id(op)``
    tokens, so their readable label (``"defer:add..mul[64]"``) is always
    passed explicitly to :func:`lookup` by the force — this fallback only
    reports the plan length."""
    if not isinstance(key, tuple) or not key:
        return repr(key)
    tag = key[0]
    if tag == "defer" and len(key) >= 5 and isinstance(key[4], tuple):
        return f"defer:[{len(key[4])}]"
    if tag in _KEY_COMPONENTS and len(key) >= 2:
        return f"{tag}:{_op_label(key[1])}"
    return repr(tag)


def _miss_reason(key) -> str:
    """Explain a cache miss: diff ``key`` against the nearest cached key of the
    same dispatch family and name the signature component(s) that changed.
    Only called when diagnostics are enabled (it scans the table)."""
    if not isinstance(key, tuple) or not key:
        return "uncategorised signature"
    n = _seen.get(key)
    if n is not None:
        # the signature is known but still warming up (jit threshold > 1):
        # the repeat count, not a key diff, is the whole explanation
        return f"warm-up (seen {n + 1} of threshold {jit_threshold()})"
    tag = key[0]
    names = _KEY_COMPONENTS.get(tag)
    best_diff: Optional[Tuple[int, ...]] = None
    # newest-first, bounded: the nearest key is almost always a recent one, and
    # a miss-dominated workload (the test suite's profile) must not pay a full
    # 1024-key × deep-tuple comparison under _lock per miss — the cap bounds
    # the WALK itself, not just the same-family comparisons
    scanned = 0
    for cached in reversed(_programs):
        scanned += 1
        if scanned > 256:
            break
        if not isinstance(cached, tuple) or len(cached) != len(key) or cached[0] != tag:
            continue
        diff = tuple(i for i in range(1, len(key)) if cached[i] != key[i])
        if best_diff is None or len(diff) < len(best_diff):
            best_diff = diff
            if len(diff) <= 1:
                break
    if best_diff is None:
        return f"first {tag!r} signature seen"
    if not best_diff:
        return "evicted signature recompiled"  # identical key no longer cached
    if names:
        changed = ", ".join(names[i] if i < len(names) else f"component[{i}]"
                            for i in best_diff)
    else:
        changed = ", ".join(f"component[{i}]" for i in best_diff)
    return f"changed vs nearest cached signature: {changed}"


def kwargs_sig(kwargs: dict):
    """A hashable signature of an op's ``fn_kwargs``, or :data:`UNSUPPORTED` when
    a value cannot be hashed (array-valued kwargs etc. stay eager)."""
    if not kwargs:
        return ()
    try:
        items = tuple(sorted(kwargs.items()))
        hash(items)
    except TypeError:
        return UNSUPPORTED
    return items


def operand_sig(x):
    """The abstract signature of one program operand.

    Arrays key on (shape, dtype) — their aval; jax's own dispatch re-keys on the
    concrete layout, so a layout change surfaces as a counted retrace rather than
    a wrong program. Scalars key on their *type* with weak-type normalisation:
    two Python floats share a program, a np.float32 scalar gets its own (their
    promotion semantics differ)."""
    if isinstance(x, jax.Array):
        return (x.shape, x.dtype)
    if isinstance(x, PendingValue):
        # a dispatch-done future from an in-flight async force: signatures key
        # on its (known) physical aval exactly like the concrete array it
        # resolves to, so the program replays regardless of arrival order
        return (x.shape, x.dtype)
    if isinstance(x, np.ndarray):
        return (x.shape, x.dtype, "np")
    if isinstance(x, (np.number, np.bool_)):
        return ("s", x.dtype)
    return ("s", type(x).__name__)


def op_sig(operation: Callable):
    """``operation`` itself when hashable (jnp functions — program identity), else
    :data:`UNSUPPORTED`."""
    try:
        hash(operation)
    except TypeError:
        return UNSUPPORTED
    return operation


class _Program:
    """One compiled dispatch program: a traced body plus its jit configuration.

    ``donate_index`` names the trailing ``out=`` buffer argument; the donating
    and non-donating variants are jitted lazily because donation safety is a
    per-call property of the destination buffer (see
    ``sanitation.sanitize_donation``), not of the signature. Fused deferred
    graphs instead donate *leaf* arguments — ``donate_leaves`` is a tuple of
    argument positions, and each distinct tuple gets its own lazily-jitted
    variant (capped at :data:`_MAX_DONATE_VARIANTS`; past the cap the call
    simply runs undonated — donation is an optimisation, never a dependency).

    Telemetry carried per program (all first-call or per-hit trivia — nothing
    on the replay hot path beyond an integer increment in :func:`lookup`):
    ``label`` (human signature name), ``hits`` (lifetime replays), ``compile_s``
    (first-call wall time per jit variant, summed), ``arg_specs`` (the abstract
    argument signature of the first call — lets tests and tools re-lower the
    exact executable for HLO inspection)."""

    __slots__ = (
        "body", "out_shardings", "donate_index", "meta",
        "label", "hits", "compile_s", "arg_specs", "_plain", "_donating",
        "_variants", "_batched", "failures", "proven", "ewma_s",
        "spec", "fingerprint", "aot_loaded", "flops",
    )

    def __init__(self, body, out_shardings, donate_index, meta):
        self.body = body
        self.out_shardings = out_shardings
        self.donate_index = donate_index
        self.meta = meta
        self.label = None
        self.hits = 0
        self.compile_s = 0.0
        self.arg_specs = None
        self._plain = None
        self._donating = None
        self._variants = None
        self._batched = None  # width -> jitted vmap variant (cross-request batching)
        self.failures = 0   # compile/execute failures (fallback_after_failure)
        self.proven = False  # at least one call of any variant has succeeded
        # Persistent compile cache (ISSUE 15): ``spec`` is the JSON-able
        # replay description the miss site captured (None when the signature
        # cannot be described portably — ``out=`` donation, unhashable
        # kwargs, pending leaves), ``fingerprint`` its content hash (computed
        # lazily), ``aot_loaded`` whether the plain variant came from a
        # deserialized cached executable instead of a fresh trace+compile.
        self.spec = None
        self.fingerprint = None
        self.aot_loaded = False
        # per-signature FLOPs estimate (XLA cost analysis), memoised by
        # _program_flops while the forensics plane is armed; None = unknown
        self.flops = None
        # Service-time EWMA over REPLAY dispatches (first calls are compile
        # time, not service time), the estimate behind HEAT_TPU_SHED admission
        # control. It measures host-side DISPATCH wall time — jax calls return
        # once dispatched, before device execution finishes — so for
        # device-bound programs it is a LOWER bound on true service time and
        # the admission check is conservative: it can under-shed (wall-clock
        # expiry still catches that work late), never reject feasible work.
        # Whether dispatch time is the bulk of service time on a directly
        # attached chip is not measured (ROADMAP follow-up). Deliberately
        # relaxed (last-writer-wins float; a lost update nudges the estimate
        # by one sample) — the same quantity lands in the profiler's
        # `service.<label>` histograms when it is collecting.
        self.ewma_s = 0.0

    def _traced(self):
        body = self.body
        label = self.label

        def counted(*args):
            _stats.retraces += 1
            if diagnostics._tracing:
                # trace-time gate: framework-level op names compiled into HLO
                # metadata (device traces show them); OFF injects nothing, so
                # the executable is byte-identical to an uninstrumented build
                with jax.named_scope(f"ht.{label or 'dispatch'}"):
                    return body(*args)
            return body(*args)

        return counted

    def _lifecycle_check(self) -> None:
        """Admission checkpoint for STAGED dispatches — the one-op programs
        the four dispatch wrappers call directly, which never pass through the
        deferred force's plan admission. Host-side attr reads only (nothing
        enters the traced body): an ambient deadline that has already passed
        raises a typed ``DeadlineExceeded`` before any dispatch, and with
        ``HEAT_TPU_SHED=1`` a budget the service-time EWMA cannot fit raises
        ``Shed`` — both travel through :func:`fallback_after_failure`, which
        counts them and tells the wrapper to re-raise rather than replay
        (executing over-deadline work late is what the deadline prevents)."""
        dl = profiler.current_deadline()
        if dl is None:
            return
        now = time.monotonic()
        if now >= dl:
            if forensics._enabled:
                forensics.note_admission("staged", "deadline-expired", dl - now)
            raise resilience.DeadlineExceeded(
                f"deadline passed before dispatch ({self.label or 'program'})"
            )
        if _knobs.shed and self.ewma_s > 0.0 and now + self.ewma_s >= dl:
            if forensics._enabled:
                forensics.note_admission("staged", "shed", dl - now)
            raise resilience.Shed(
                f"admission control: estimated service time "
                f"{self.ewma_s * 1e3:.2f} ms exceeds the remaining deadline "
                f"budget ({self.label or 'program'})"
            )
        if forensics._enabled:
            forensics.note_admission("staged", "admitted", dl - now)

    def __call__(self, *args, donate: bool = False, donate_leaves: Tuple[int, ...] = ()):
        if profiler._deadline_seen:
            # one module-attribute read in processes that never arm a deadline
            self._lifecycle_check()
        if resilience._armed:
            # every program call is one countable "executor.execute" event; the
            # fault fires BEFORE any dispatch, so argument buffers (including
            # donation candidates) are still intact when the caller falls back
            resilience.maybe_fault("executor.execute")
        donating = donate and self.donate_index is not None
        rkey = None
        if (
            _result_cache._enabled
            and not donating
            and not donate_leaves
            and self.donate_index is None
        ):
            # cross-request result memoization (HEAT_TPU_RESULT_CACHE=1): the
            # plain variant of a deterministic program is a pure function of
            # (fingerprint, input digest) — a validated hit IS the execution.
            # Donation-bearing variants never consult or fill (their inputs
            # die in the call); expired deadlines raised above, before this.
            rkey, rwhy = _result_key_explained(self, args)
            if rkey is not None:
                cached = _result_cache.lookup(rkey, _tenant_or_none())
                if cached is not _result_cache.MISS:
                    if forensics._enabled:
                        forensics.note_result_cache(
                            "hit", nbytes=_result_cache.result_nbytes(cached)
                        )
                    return cached
                if forensics._enabled:
                    forensics.note_result_cache("miss")
            elif forensics._enabled:
                # the *reason* the consult was skipped is forensic signal: a
                # tenant whose tail is all bypasses is paying for rng labels
                # or undigestable operands, not for cold caches
                forensics.note_result_cache("bypass", rwhy)
        elif forensics._enabled:
            forensics.note_result_cache(
                "bypass",
                "cache-off" if not _result_cache._enabled else "donation",
            )
        if donate_leaves:
            variants = self._variants
            if (
                variants is not None
                and donate_leaves not in variants
                and len(variants) >= _MAX_DONATE_VARIANTS
            ):
                donate_leaves = ()  # variant table full: run undonated
        if donate_leaves:
            fn = None if self._variants is None else self._variants.get(donate_leaves)
        else:
            fn = self._donating if donating else self._plain
        first = fn is None
        if first:
            # build the jit variant under the executor lock: two threads racing
            # the first call of one program must share ONE jit object (else both
            # trace — double-counted retraces/compile events, wasted compile)
            with _tlock:
                if donate_leaves:
                    if self._variants is None:
                        self._variants = {}
                    fn = self._variants.get(donate_leaves)
                    if fn is None and len(self._variants) >= _MAX_DONATE_VARIANTS:
                        # cap re-checked under the lock: first calls racing on
                        # distinct masks must not grow the table past the
                        # bound — this call just runs undonated instead
                        donate_leaves = ()
                        fn = self._plain
                else:
                    fn = self._donating if donating else self._plain
                first = fn is None
                if first and resilience._armed:
                    # a jit variant is about to be built: the deterministic
                    # hook for injected COMPILE failures (real ones surface
                    # from the first fn(*args) below — both land in the same
                    # except/fallback path at the call site)
                    resilience.maybe_fault("executor.compile")
                if first and donate_leaves:
                    # fused-graph leaf donation: every donated leaf is a real
                    # program operand, so no keep_unused is needed
                    fn = self._variants[donate_leaves] = jax.jit(
                        self._traced(),
                        out_shardings=self.out_shardings,
                        donate_argnums=donate_leaves,
                    )
                elif first and donating:
                    # keep_unused: a plain out= overwrite never reads the
                    # destination buffer, and jit would otherwise prune the
                    # argument and lose the input/output aliasing the donation
                    # exists for
                    fn = self._donating = jax.jit(
                        self._traced(),
                        out_shardings=self.out_shardings,
                        donate_argnums=(self.donate_index,),
                        keep_unused=True,
                    )
                elif first:
                    if (
                        self.donate_index is None
                        and _compile_cache.armed()
                    ):
                        # persistent compile cache: a fingerprint-matched
                        # serialized executable replaces trace + XLA compile
                        # entirely (cold-start elimination); corruption is a
                        # typed rejection inside load_program, and a miss
                        # falls through to the normal jit build below
                        fn = _compile_cache.load_program(self)
                        if fn is not None:
                            self._plain = fn
                            self.aot_loaded = True
                    if fn is None:
                        fn = self._plain = jax.jit(
                            self._traced(),
                            out_shardings=self.out_shardings,
                            keep_unused=self.donate_index is not None,
                        )
                if self.arg_specs is None:
                    # shardings ride the specs so AOT lowering (the compile
                    # cache's save path) compiles for the exact committed
                    # input layouts the replay path dispatches with
                    self.arg_specs = tuple(
                        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                        if isinstance(a, jax.Array) else a
                        for a in args
                    )
        t0 = time.perf_counter()
        if profiler._active:
            # host-side timing only (never inside the traced body — the HLO
            # parity contract): the first call spans trace + XLA compile +
            # first execution, replays span C++ dispatch
            # (with diagnostics on, the scope is also the host span
            # ``compile`` / ``execute`` in the profiler's own trace)
            with profiler.scope("compile" if first else "execute",
                                self.label or "program"):
                out = fn(*args)
        else:
            out = fn(*args)
        dt = time.perf_counter() - t0
        if first:
            self.compile_s += dt
            if diagnostics._enabled:
                diagnostics.record_compile(self.label or "program", dt)
            if forensics._enabled:
                forensics.note_program(self.label or "program", dt, "compile")
                forensics.note_compile_cache(
                    "aot-load" if self.aot_loaded
                    else ("miss" if _compile_cache.armed() else "off")
                )
        else:
            self._note_service(dt)
            if forensics._enabled:
                forensics.note_program(self.label or "program", dt, "execute",
                                       flops=_program_flops(self))
        self.proven = True
        if rkey is not None:
            # memoised only after a SUCCESSFUL plain-path execution; the
            # entry's strong reference keeps refcount sanitation from ever
            # proving sole ownership of a buffer the cache still serves
            _result_cache.store(rkey, out, _tenant_or_none())
        return out

    def _note_service(self, dt: float, items: int = 1) -> None:
        """Fold one replay's wall time into the service-time EWMA (relaxed
        write — see the ``ewma_s`` comment) and, when the profiler is
        collecting, into the ``service.<label>`` histogram it feeds."""
        per = dt / items
        prev = self.ewma_s
        self.ewma_s = per if prev <= 0.0 else prev + 0.25 * (per - prev)
        if profiler._active:
            profiler.observe(f"service.{self.label or 'program'}", per)

    def call_batched(self, width: int, array_pos: Tuple[int, ...],
                     scalar_pos: Tuple[int, ...], flat_arrays: Sequence,
                     scalars: Sequence) -> Tuple:
        """Run ``width`` same-signature calls as ONE batched program.

        The batched variant stacks each leaf position's ``width`` buffers
        inside the traced body (no eager per-leaf stack dispatch), maps the
        original program body over the stacked leading axis with ``jax.vmap``
        — deferred-graph bodies are strictly elementwise, so every lane
        computes bit-identically to its single-item call — and returns the
        un-stacked per-item outputs as separate, per-item-sharded results.
        ``flat_arrays`` is item-major (item0's arrays, item1's, …); ``scalars``
        are the scalar leaves shared by every item in the group (identity is
        part of the batch key). Returns a flat tuple, item-major, ``n_outs``
        entries per item. Variants are cached per width; widths are bucketed
        to powers of two by the scheduler, so the set stays bounded."""
        fn = None if self._batched is None else self._batched.get(width)
        first = fn is None
        if first:
            with _tlock:
                if self._batched is None:
                    self._batched = {}
                fn = self._batched.get(width)
                first = fn is None
                if first and resilience._armed:
                    resilience.maybe_fault("executor.compile")
                if first:
                    body = self._traced()
                    n_arr = len(array_pos)

                    def batched_body(*flat):
                        arrs = flat[: width * n_arr]
                        scal = flat[width * n_arr:]

                        def one(*xs):
                            argv = [None] * (len(array_pos) + len(scalar_pos))
                            for k, j in enumerate(array_pos):
                                argv[j] = xs[k]
                            for k, j in enumerate(scalar_pos):
                                argv[j] = scal[k]
                            return body(*argv)

                        stacked = tuple(
                            jnp.stack([arrs[i * n_arr + k] for i in range(width)])
                            for k in range(n_arr)
                        )
                        outs = jax.vmap(one)(*stacked)
                        if not isinstance(outs, tuple):
                            outs = (outs,)
                        return tuple(o[i] for i in range(width) for o in outs)

                    inner = (
                        self.out_shardings
                        if isinstance(self.out_shardings, tuple)
                        else (self.out_shardings,)
                    )
                    fn = self._batched[width] = jax.jit(
                        batched_body, out_shardings=inner * width
                    )
        if resilience._armed:
            resilience.maybe_fault("executor.execute")
        args = tuple(flat_arrays) + tuple(scalars)
        label = f"{self.label or 'program'}[x{width}]"
        t0 = time.perf_counter()
        if profiler._active:
            with profiler.scope("compile" if first else "execute", label):
                out = fn(*args)
        else:
            out = fn(*args)
        dt = time.perf_counter() - t0
        if first:
            self.compile_s += dt
            if diagnostics._enabled:
                diagnostics.record_compile(label, dt)
        else:
            # per-item service time: a width-N batch serves N requests in dt
            self._note_service(dt, items=width)
        self.proven = True
        return out


def _result_key_explained(
    prog: "_Program", args
) -> Tuple[Optional[Tuple[str, Tuple]], Optional[str]]:
    """The result-cache key ``(fingerprint, input digest)`` for a plain call
    of ``prog`` over ``args``, or ``(None, reason)`` when the call is
    uncacheable: ``no-replay-spec`` (warmup gap / out=-aliasing signature),
    ``rng-label`` (an RNG-consuming label), or ``undigestable-operand``
    (large unregistered arrays, pending async values) — see ``_result_cache``
    for the documented bypass contract. The reason string is the forensic
    record's bypass label.  The fingerprint is the compile cache's (sha256 of
    the canonical replay spec), memoised on the program."""
    spec = prog.spec
    if spec is None:
        return None, "no-replay-spec"
    if _result_cache.uncacheable_label(prog.label):
        return None, "rng-label"
    digest = _result_cache.digest_args(args)
    if digest is None:
        return None, "undigestable-operand"
    fp = prog.fingerprint
    if fp is None:
        fp = prog.fingerprint = _compile_cache.fingerprint(spec)
    return (fp, digest), None


def _result_key(prog: "_Program", args) -> Optional[Tuple[str, Tuple]]:
    """See :func:`_result_key_explained` (this is its key half — callers that
    do not record bypass reasons)."""
    return _result_key_explained(prog, args)[0]


def _program_flops(prog: "_Program") -> float:
    """Per-signature FLOPs estimate from XLA's compiled cost analysis,
    memoised on the program — computed at most once per signature, and only
    reached while the forensics plane is armed (the cost-metering feed).
    Returns 0.0 (memoised) when the executable cannot be re-lowered or the
    backend offers no cost model; 0.0 un-memoised when the plain variant or
    arg specs have not materialised yet (a later call may fill them)."""
    flops = prog.flops
    if flops is not None:
        return flops
    if prog._plain is None or prog.arg_specs is None:
        return 0.0
    try:
        cost = prog._plain.lower(*prog.arg_specs).compile().cost_analysis()
    except Exception as exc:
        diagnostics.record_fallback(
            "executor.cost_analysis",
            f"{type(exc).__name__}: {prog.label or 'program'}",
        )
        prog.flops = 0.0
        return 0.0
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0)) if isinstance(cost, dict) else 0.0
    prog.flops = flops
    return flops


def lookup(key, build: Callable[[], Any], label: Optional[str] = None,
           spec: Optional[Callable[[], Optional[dict]]] = None) -> Optional[_Program]:
    """The cached :class:`_Program` for ``key``, building it on miss.

    ``build()`` returns either ``(body, out_shardings, donate_index, meta)`` or
    :data:`UNSUPPORTED`; both results are cached, so an eager-only signature is
    rejected in O(1) on every later call. Returns ``None`` for unsupported.
    ``label`` overrides the derived :func:`_key_label` — callers whose keys
    carry opaque id tokens (the deferred-graph force) pass a readable one.
    ``spec`` (a zero-arg callable, evaluated ONLY on a successful build — hits
    never pay for it) returns the JSON-able replay description behind the
    persistent compile cache and AOT warmup (``_compile_cache``), or None for
    signatures that cannot be replayed portably."""
    # the whole lookup holds the lock: signature keys hash Python-level objects
    # (the Mesh), so even the read path could yield the GIL mid-mutation of the
    # shared OrderedDict; an uncontended RLock costs ~100 ns against a ~40 µs
    # replay, and compiles were already serialised. Timed: blocked waits land
    # in the lock_wait_ns tally.
    with _tlock:
        entry = _programs.get(key)
        if entry is not None:
            _stats.hits += 1
            if entry is not UNSUPPORTED:
                entry.hits += 1  # lifetime per-signature tally (executor_stats top=N)
            _programs.move_to_end(key)  # eviction is LRU, not FIFO: hits refresh
            return None if entry is UNSUPPORTED else entry
        if diagnostics._enabled:
            # explain the miss BEFORE the table mutates: which signature
            # component changed vs. the nearest cached key of the same family
            diagnostics.record_dispatch_event(
                "miss", label or _key_label(key), _miss_reason(key)
            )
        threshold = jit_threshold()
        if threshold > 1:
            n = _seen.get(key, 0) + 1
            if n < threshold:
                # still warming up: the caller takes the eager path; only a
                # signature seen `threshold` times earns a compile
                if len(_seen) >= _MAX_SEEN:
                    # evict the least-recently-SEEN half, not everything: a hot
                    # signature one sighting from its compile must not restart
                    # at zero every time a signature-churning workload fills
                    # the table (the pop below keeps re-seen keys at the end)
                    for stale in list(_seen)[: _MAX_SEEN // 2]:
                        del _seen[stale]
                _seen.pop(key, None)  # re-insert at the end: recency order
                _seen[key] = n
                _stats.misses += 1
                return None
            _seen.pop(key, None)
        built = build()
        if built is UNSUPPORTED:
            entry = UNSUPPORTED
        else:
            entry = _Program(*built)
            entry.label = label or _key_label(key)
            if spec is not None:
                try:
                    entry.spec = spec()
                except Exception as exc:
                    # a spec that cannot be described is a warmup gap, never
                    # a dispatch failure — counted, program still compiles
                    entry.spec = None
                    if diagnostics._enabled:
                        diagnostics.record_fallback(
                            "executor.warmup_spec",
                            f"{entry.label}: {type(exc).__name__}: {exc}",
                        )
        while len(_programs) >= _MAX_PROGRAMS:
            _programs.popitem(last=False)
        _programs[key] = entry
        _stats.misses += 1
        return None if entry is UNSUPPORTED else entry


# ------------------------------------------------------------- failure hardening
# A compiled program whose compile or execution fails must not take the user's
# computation down with it: the dispatch wrappers and the fused-graph force
# catch the failure, count it, and replay the SAME math on the eager path (the
# original dispatch code, which never left). A signature that keeps failing is
# quarantined — its table entry becomes UNSUPPORTED, so every later dispatch
# takes the eager path in O(1) — with the reason kept for executor_stats().

_quarantined: "OrderedDict[str, str]" = OrderedDict()
_MAX_QUARANTINED = 64


def quarantine_threshold() -> int:
    """Failures of one signature before it is quarantined to the eager path
    (``HEAT_TPU_QUARANTINE_AFTER``, default 3). Memoised with the other
    dispatch knobs; see :func:`reload_env_knobs`."""
    return _knobs.quarantine_after


def fallback_after_failure(key, prog: "_Program", exc: BaseException,
                           donated: Sequence = ()) -> bool:
    """Account one compiled-program failure and decide whether the eager path
    may safely re-run the op.

    Returns False — the caller must re-raise — in two cases: a
    request-lifecycle rejection (``DeadlineExceeded`` / ``Shed``, counted in
    the scheduler's lifecycle ledger — the signature is healthy, the REQUEST
    ran out of budget, so there is no quarantine and no replay: executing
    over-deadline work late is exactly what the deadline prevents), or a
    buffer donated to the failed call already invalidated by XLA (replaying
    would read garbage; the donation contract holds every leaf reference
    until the call succeeds, so this only happens when a failure strikes
    *after* dispatch consumed the buffer). Otherwise the failure is counted
    (``eager_fallbacks``), recorded in ht.diagnostics with the exception type
    and program label, and the signature is quarantined once it has failed
    :func:`quarantine_threshold` times."""
    if isinstance(exc, (resilience.DeadlineExceeded, resilience.Shed)):
        kind = (
            "deadline_expired"
            if isinstance(exc, resilience.DeadlineExceeded) else "shed"
        )
        if not getattr(exc, "_ht_ledgered", False):
            # a rejection the scheduler already delivered (a queued staged
            # call cancelled pre-dispatch) carries the ledgered mark — it was
            # counted exactly once at the shard that pulled it; everything
            # else (the in-call _lifecycle_check raises) is counted here
            _get_scheduler().note_lifecycle(kind, _tenant_or_none())
            if forensics._enabled:
                forensics.note_event(
                    "typed-failure",
                    f"{kind}: {prog.label or _key_label(key)}",
                )
        return False
    if isinstance(exc, (resilience.PeerFailed, resilience.CollectiveTimeout)):
        # a supervision abort delivered into a queued execution: typed
        # re-raise, no eager replay (the signature is healthy, the CLUSTER
        # aborted) and no quarantine — the shed was ledgered at the shard
        return False
    for buf in donated:
        if isinstance(buf, jax.Array) and buf.is_deleted():
            diagnostics.record_resilience_event(
                "executor.execute", "data-loss",
                f"{prog.label or _key_label(key)}: donated buffer invalidated "
                f"by failed call ({type(exc).__name__}) — no eager replay possible",
            )
            return False
    label = prog.label or _key_label(key)
    phase = "execute" if prog.proven else "compile"
    with _lock:
        _stats.eager_fallbacks += 1
        prog.failures += 1
        reason = (
            f"{phase} failure {prog.failures}: {type(exc).__name__}: {exc}"
        )
        if prog.failures >= quarantine_threshold() and _programs.get(key) is prog:
            _programs[key] = UNSUPPORTED
            while len(_quarantined) >= _MAX_QUARANTINED:
                _quarantined.popitem(last=False)
            _quarantined[label] = reason
            diagnostics.record_resilience_event(
                f"executor.{phase}", "quarantine", f"{label}: {reason}"
            )
    if diagnostics._enabled:
        diagnostics.record_fallback(
            f"executor.{phase}", f"{label}: {type(exc).__name__}: {exc}"
        )
    if forensics._enabled:
        # the caller re-runs the op eagerly: the record's eager-replay leg
        forensics.note_event(
            "eager-replay", f"{label}: {type(exc).__name__}"
        )
    return True


# ------------------------------------------------------------------ padded layout
# (shared with _operations — defined here so the deferred-graph force below can
# re-mask without a circular import)


def _pad_mask(physical_shape, n: int, split: int):
    """Boolean mask, broadcast-shaped ``(1,..,m,..,1)``: True on logical slots along
    the padded split dimension."""
    shape = [1] * len(physical_shape)
    shape[split] = physical_shape[split]
    return (jnp.arange(physical_shape[split]) < n).reshape(shape)


def _zero_pads(value, gshape, split: int):
    """Restore the clean-pad invariant after computing on a padded physical value."""
    mask = _pad_mask(value.shape, gshape[split], split)
    return jnp.where(mask, value, jnp.zeros((), value.dtype))


# ------------------------------------------------------------- deferred expression graph

# Deeper graphs amortise better but compile longer and recurse at force time;
# past the cap a node's pending operands are forced first, starting a fresh graph.
_MAX_FUSED_NODES = 256

# (id(op), kwargs sig, operand aval sigs) -> (op, (shape, dtype) | UNSUPPORTED).
# eval_shape traces the op abstractly — far too slow per dispatch, so the result
# aval is resolved once per signature and replayed. Keyed on id(op) — hashing a
# jnp ufunc runs Python-level __hash__, too slow per dispatch — with the op
# itself stored in the value so the id stays pinned for the entry's lifetime.
# Guarded by its own tiny lock, NOT the executor lock: the deferral path exists
# to stay off the big lock, but the pop/re-insert recency dance and the
# evict-half loop are not GIL-atomic — two racing evictions can `del` a key the
# other already removed. The critical sections are a handful of dict ops; the
# slow eval_shape miss path runs outside the lock (a racing duplicate probe is
# benign — last writer wins with an identical value).
_aval_cache: Dict[Any, Any] = {}
_aval_lock = threading.Lock()
_MAX_AVALS = 4096


class Deferred:
    """A pending node in the executor's fused expression graph.

    ``operands`` entries are ``("d", Deferred)``, ``("a", jax.Array)`` or
    ``("s", scalar)``; all array-shaped operands are *physical* (padded layout)
    values of one aligned ``(gshape, split)`` family, so the node evaluates
    slot-wise with no in-program slicing. ``shape``/``dtype``/``ndim`` expose the
    node's physical aval (``DNDarray._is_padded`` reads them without forcing).
    ``value`` memoises the forced result — set when the node is forced as a
    root OR emitted as an interior output of another root's program — so the
    node becomes a plain array leaf in any later graph that references it.
    ``wref`` weak-references the ``DNDarray`` that wraps this node
    (:func:`note_wrapped`); ``executed`` marks that the node already ran inside
    some forced program (the re-execution canary behind
    ``executor_stats()["reexecuted"]``)."""

    __slots__ = ("operation", "fn_kwargs", "operands", "shape", "dtype",
                 "gshape", "split", "comm", "size", "value", "wref", "executed",
                 "req", "deadline")

    def __init__(self, operation, fn_kwargs, operands, shape, dtype, gshape, split, comm, size):
        self.operation = operation
        self.fn_kwargs = fn_kwargs
        self.operands = operands
        self.shape = shape
        self.dtype = dtype
        self.gshape = gshape
        self.split = split
        self.comm = comm
        self.size = size
        self.value = None
        self.wref = None
        self.executed = False
        # profiler attribution captured at defer time: a chain built inside a
        # request scope but forced later (another thread, scope closed) still
        # attributes its force to the request that built it. None when the
        # profiler is off — defer_node never pays for it idle.
        self.req = None
        # wall-clock deadline captured at defer time (same scoping as req, but
        # armed independently of the profiler switch): a chain built under
        # `request(tag, deadline_s=...)` carries its deadline to any later
        # force, from any thread. None when no deadline was ever armed in the
        # process — the deadline-off path never reads the contextvar.
        self.deadline = None

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def force(self):
        """Materialise this node (and everything it transitively needs) as one
        signature-cached program execution. A value already memoised — by an
        earlier force that emitted this node as an interior output — is
        returned as-is: the whole subchain's re-execution was avoided. A
        :class:`~._scheduler.PendingValue` — an async force of this node is
        already in flight — is resolved: the wait covers program *dispatch*
        only (the resolved jax.Array is itself asynchronous on device).

        Check-then-force is atomic under the executor lock (the force paths
        re-check every root after acquiring it): two threads racing the same
        node's first force used to merely duplicate work, but leaf donation
        would let the winner invalidate buffers the loser's already-linearised
        plan still references. Pending-value resolution always happens OUTSIDE
        the lock — the executing side may need the lock to finish."""
        v = self.value
        if v is None or (isinstance(v, PendingValue) and v.failed()):
            if v is not None:
                self.value = None  # failed dispatch: this force is the retry
            _force_graph((self,))
            v = self.value
            if v is None:
                # the dispatch failed terminally between our force and this
                # read (fail() delivered the error to its own waiters): retry
                # once more from a clean slate rather than returning nothing
                _force_graph((self,))
                v = self.value
        else:
            _stats.reexec_avoided += 1
        if isinstance(v, PendingValue):
            try:
                if profiler._active and not v.done():
                    # make the queueing + dispatch wait visible on the
                    # request's trace track — this is exactly the latency the
                    # async queue adds under load
                    with profiler.scope("wait", "force:queue_wait", req=self.req):
                        v = v.resolve()
                else:
                    v = v.resolve()
            except BaseException:
                # surface the dispatch failure to THIS reader, but clear the
                # failed future first so the next force retries — the
                # serialized path raises afresh on every read too
                if self.value is v:
                    self.value = None
                raise
            self.value = v
        return v


def note_wrapped(node: Deferred, holder) -> None:
    """Register ``holder`` (a DNDarray) as the live wrapper of ``node``.

    The dispatch layer calls this the moment it wraps a fresh ``Deferred`` into
    a DNDarray, so the force path can tell which interior nodes are still
    *reachable* by user code: such a node's value must be emitted from any
    program that executes it (the user can read it later). The reference is
    weak — when the wrapping DNDarray is garbage-collected (or rebinds its
    payload), the node silently stops counting as live; no ``__del__`` hook or
    explicit deregistration is needed."""
    node.wref = weakref.ref(holder)


def defer_node(operation, fn_kwargs, operands, gshape, split, comm):
    """Build a :class:`Deferred` for ``operation(*operands, **fn_kwargs)``, or
    :data:`UNSUPPORTED` when the op cannot join a fused graph (unhashable
    kwargs, non-slot-wise result shape, complex result — the eager paths
    check those against what the device can hold).

    The result aval comes from a cached ``eval_shape`` and must equal the
    physical operand shape: deferral is strictly elementwise over one aligned
    layout family, everything else takes the immediate one-op staged paths.

    Operation identity note: the whole deferred path keys on ``id(operation)``
    rather than hashing the operation — ``jax.numpy`` ufuncs carry a
    Python-level ``__hash__`` costing microseconds, and the dispatch hot path
    would pay it several times per op. The id is safe as a key exactly because
    every cache that stores such a key also holds a STRONG reference to the
    operation (the aval-cache value below, a cached program's plan closure),
    so the id cannot be recycled while the key is live."""
    kwsig = kwargs_sig(fn_kwargs)
    if kwsig is UNSUPPORTED:
        return UNSUPPORTED
    phys_shape = None
    sigs = []
    for kind, v in operands:
        if kind == "s":
            sigs.append(operand_sig(v))
        else:
            shape, dtype = (tuple(v.shape), v.dtype)
            if phys_shape is None:
                phys_shape = shape
            elif shape != phys_shape:
                return UNSUPPORTED  # mixed physical extents: not slot-aligned
            sigs.append(("t", shape, np.dtype(dtype).str))
    if phys_shape is None:
        return UNSUPPORTED
    akey = (id(operation), kwsig, tuple(sigs))
    with _aval_lock:
        entry = _aval_cache.pop(akey, None)
        if entry is not None:
            _aval_cache[akey] = entry  # re-insert: recency order for eviction below
    if entry is not None:
        aval = entry[1]
    else:
        specs = [jax.ShapeDtypeStruct(v.shape, v.dtype) for kind, v in operands if kind != "s"]

        def abstract(*xs):
            it = iter(xs)
            args = [v if kind == "s" else next(it) for kind, v in operands]
            return operation(*args, **fn_kwargs)

        try:
            out = jax.eval_shape(abstract, *specs)
            aval = (tuple(out.shape), np.dtype(out.dtype))
        except Exception as exc:
            # this signature cannot join a fused graph — the caller takes the
            # staged/eager path, which raises the user-visible error if the op
            # is genuinely broken. Visible, not silent: per-site counter +
            # reason (exception type + op label) in ht.diagnostics.
            if diagnostics._enabled:
                diagnostics.record_fallback(
                    "dispatch.defer",
                    f"{_op_label(operation)}: {type(exc).__name__}: {exc}",
                )
            aval = UNSUPPORTED
        with _aval_lock:
            if len(_aval_cache) >= _MAX_AVALS:
                # evict the least-recently-USED half, not everything: a
                # steady-state workload sitting near the limit must not
                # periodically lose every cached aval (same policy as the
                # _seen warm-up table; the pop/re-insert above keeps hit keys
                # at the recent end)
                for stale in list(_aval_cache)[: _MAX_AVALS // 2]:
                    del _aval_cache[stale]
            # the stored operation pins its id: an id-keyed entry can never be
            # aliased by a different (later-allocated) operation while it lives
            _aval_cache[akey] = (operation, aval)
    if aval is UNSUPPORTED:
        return UNSUPPORTED
    shape, dtype = aval
    if shape != phys_shape or jnp.issubdtype(dtype, jnp.complexfloating):
        return UNSUPPORTED
    size = 1
    for kind, v in operands:
        if kind == "d" and v.value is None:
            size += v.size
    if size > _MAX_FUSED_NODES:
        # per-edge size sums count a shared node once per path, so a
        # diamond-heavy DAG overcounts exponentially — recount the UNIQUE
        # pending nodes (bounded walk, early exit past the window) before
        # deciding to spill. Amortised: the exact count becomes this node's
        # size, deflating its consumers' sums back to reality.
        size = _pending_count(operands, _MAX_FUSED_NODES)
    if size > _MAX_FUSED_NODES:
        # graph genuinely grew past the fusion window: materialise ALL pending
        # operands through ONE multi-output program and start a fresh graph
        pending, seen = [], set()
        for kind, v in operands:
            if kind == "d" and v.value is None and id(v) not in seen:
                seen.add(id(v))
                pending.append(v)
        _force_graph(tuple(pending))
        operands = tuple(
            ("a", v.value)
            if kind == "d" and v.value is not None
            and not isinstance(v.value, PendingValue)
            else (kind, v)
            for kind, v in operands
        )
        size = 1
    node = Deferred(
        operation, fn_kwargs, tuple(operands), shape, dtype,
        tuple(gshape), split, comm, size,
    )
    if profiler._active:
        node.req = profiler.current_request()
    if profiler._deadline_seen:
        # one attribute read when no deadline was ever armed; the contextvar
        # lookup only happens in processes that actually use deadlines
        dl = profiler.current_deadline()
        if dl is not None:
            if time.monotonic() >= dl:
                # defer-time admission: a request that is ALREADY over
                # deadline dies at its first op in microseconds instead of
                # building a graph it will never be allowed to force — under
                # overload this is what lets workers churn through the
                # expired backlog fast enough to keep serving feasible work
                _get_scheduler().note_lifecycle(
                    "deadline_expired", _tenant_or_none()
                )
                if forensics._enabled:
                    forensics.note_admission(
                        "defer", "deadline-expired", dl - time.monotonic()
                    )
                raise resilience.DeadlineExceeded(
                    f"deadline passed before defer of "
                    f"{_op_label(operation)}"
                )
            node.deadline = dl
    return node


def _pending_count(operands, cap: int) -> int:
    """Exact count of unique unforced nodes under ``operands`` (+1 for the node
    being built), walking at most ``cap`` nodes — past the cap the caller
    spills, so precision beyond it is wasted work."""
    seen = set()
    stack = [v for kind, v in operands if kind == "d" and v.value is None]
    count = 1
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        count += 1
        if count > cap:
            return count
        for kind, v in n.operands:
            if kind == "d" and v.value is None:
                stack.append(v)
    return count


def _force_graph(roots: Tuple[Deferred, ...]) -> None:
    """Force the graph under ``roots``: linearise it, look up / compile ONE
    (possibly multi-output) program, execute it, and memoise every emitted
    value into its node's ``Deferred.value``.

    Two execution shapes share one planner (:func:`_linearise`):

    - **serialized** (``HEAT_TPU_ASYNC_DISPATCH=0``): plan AND program call
      run under the executor lock and values are memoised before the lock
      drops — the pre-scheduler executor, preserved bit for bit;
    - **async** (the default): only the *plan* holds the lock — linearisation,
      donation/emission decisions, per-buffer ownership claims, and
      :class:`~._scheduler.PendingValue` futures installed into every emitted
      node. The program call runs outside the lock: inline on this thread when
      nobody else is dispatching, otherwise through the fair bounded dispatch
      queue, where concurrent same-signature forces batch into one
      ``jax.vmap``-derived program variant.
    """
    if profiler._active:
        # attribute the force to the ambient request, falling back to the id a
        # root captured at defer time (the chain may be forced from another
        # thread, after the request scope that built it closed). The scope
        # spans planning + submission (and the whole execution when it runs
        # inline); a QUEUED dispatch's wait surfaces as its own
        # "force:queue_wait" slice where the reader resolves the future.
        req = next((r.req for r in roots if r.req is not None), None)
        with profiler.scope(
            "force", f"force:{_op_label(roots[0].operation)}", req=req
        ) as ctl:
            if not _force_graph_inner(roots):
                # lost the plan race to a concurrent force of the same roots:
                # nothing planned or executed here, so drop the slice — the
                # winner's force scope is the one covering the work
                ctl["keep"] = False
        return
    _force_graph_inner(roots)


def _roots_deadline(roots) -> Optional[float]:
    """The earliest wall-clock deadline governing this force: the minimum over
    the roots' defer-time captures and the ambient request deadline. None —
    after ONE module-attribute read — in any process that never armed a
    deadline (the deadline-off parity contract)."""
    if not profiler._deadline_seen:
        return None
    dl = profiler.current_deadline()
    for r in roots:
        d = r.deadline
        if d is not None and (dl is None or d < dl):
            dl = d
    return dl


def _tenant_or_none() -> Optional[str]:
    """The ambient request tag for lifecycle accounting, or None outside a
    request scope (per-tenant attribution is best-effort telemetry). Flows
    while either the profiler or the forensics plane is on — forensic
    records thread the same request contextvar."""
    return (profiler.current_request_tag()
            if profiler.attribution_active() else None)


def _force_graph_inner(roots: Tuple[Deferred, ...]) -> bool:
    """Returns True when this call planned work (executed, or submitted a
    dispatch); False when every root was already forced/in flight."""
    if supervision._aborted:
        # the executor's supervision checkpoint (the inline-dispatch
        # counterpart of the scheduler loop's): once the abort sentinel is
        # up, a force is refused TYPED at admission — nothing planned yet,
        # so the nodes stay unforced and a post-recovery force computes
        # them normally. Idle cost: one module-attribute read.
        abort = supervision.abort_error("executor.force")
        if abort is not None:
            _get_scheduler().note_lifecycle("shed", _tenant_or_none())
            raise abort
    deadline = _roots_deadline(roots)
    if deadline is not None:
        now = time.monotonic()
        if now >= deadline:
            # admission checkpoint: the deadline has already passed, so
            # planning, compiling, or dispatching would be pure waste — the
            # reader gets the typed error NOW and the nodes stay unforced.
            # The rejection CONSUMES the roots' captured deadlines (the
            # request that owned them has been told): the data itself is not
            # poisoned, so a later force outside the expired scope computes
            # these same nodes normally.
            for r in roots:
                r.deadline = None
            _get_scheduler().note_lifecycle("deadline_expired", _tenant_or_none())
            if forensics._enabled:
                forensics.note_admission(
                    "force", "deadline-expired", deadline - now
                )
            raise resilience.DeadlineExceeded(
                f"deadline passed before force admission "
                f"({_op_label(roots[0].operation)})"
            )
        if forensics._enabled:
            forensics.note_admission("force", "admitted", deadline - now)
    if async_dispatch_enabled():
        return _force_async(roots, deadline)
    # serialized legacy path: settle any dispatch-done futures an earlier
    # async force left behind BEFORE taking the lock (the in-flight executor
    # may need the lock to finish — waiting under it would deadlock), then
    # run the whole force under the lock exactly as the pre-scheduler
    # executor did.
    _settle_pending_nodes(roots)
    with _tlock:
        return _force_sync_locked(roots, deadline)


def _settle_pending_nodes(roots) -> None:
    """Resolve every in-flight :class:`PendingValue` reachable under ``roots``
    into its concrete value (used when switching async -> serialized with
    forces still in flight). Never called while holding the executor lock."""
    stack = list(roots)
    seen = set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        v = node.value
        if isinstance(v, PendingValue):
            try:
                node.value = v.resolve()
            except BaseException:
                node.value = None  # failed dispatch: the next force retries
                raise
        elif v is None:
            stack.extend(v2 for kind, v2 in node.operands if kind == "d")


class _ForcePlan:
    """Everything :func:`_linearise` decided about one force — shared by the
    serialized and async executors, and carried (via closures) by queued
    :class:`~._scheduler.WorkItem`\\ s until their dispatch completes."""

    __slots__ = (
        "root", "leaves", "leaf_donatable", "plan", "entry_sig",
        "entry_nodes", "arefs", "out_idxs", "root_idxs", "single", "key",
        "label", "gshape", "split", "padded", "out_shardings", "deadline",
    )


def _linearise(roots: Tuple[Deferred, ...]) -> Optional[_ForcePlan]:
    """Linearise the graph under ``roots`` into a :class:`_ForcePlan`:
    evaluation-ordered plan entries, deduplicated leaves, the program
    signature key, and the emission/donation bookkeeping. Runs under the
    executor lock. Roots already forced (or with a dispatch in flight) are
    dropped — ``None`` means there is nothing left to execute.

    The structural signature keys on per-node operation identity + kwargs, the
    leaf avals, the exact sharing pattern (a leaf or node referenced twice maps
    to one slot — structural CSE collapses separately-built identical
    subexpressions too), and the set of emitted outputs, so two
    identically-built graphs replay one program.

    Besides the roots, an interior entry is emitted as an extra program output
    (and memoised) when its value has a future outside this execution:

    - it is referenced by more than one entry of the plan,
    - a live ``DNDarray`` still wraps one of its nodes (:func:`note_wrapped`),
    - or a deferred graph OUTSIDE this plan holds one of its nodes — detected
      by comparing the node's refcount against the plan's own references.

    That last rule is also the leaf-donation safety net: once every
    externally-reachable entry is memoised, no future force can re-read this
    program's leaves, so a leaf whose refcount proves the plan is its only
    reader (``sanitation.sanitize_leaf_donation``) can be donated."""
    live = tuple(r for r in roots if r.value is None)
    if len(live) != len(roots):
        _stats.reexec_avoided += len(roots) - len(live)
    if not live:
        return None
    roots = live
    leaves: list = []
    leaf_index: Dict[Any, int] = {}
    leaf_donatable: List[bool] = []
    entries: list = []       # (operation, fn_kwargs, operand refs) in eval order
    entry_sig: list = []     # (op identity, kwargs sig, refs) — CSE + program key
    entry_nodes: List[List[Deferred]] = []  # CSE can map several nodes to one entry
    node_index: Dict[int, int] = {}  # id(node) -> entry idx
    sig_index: Dict[Any, int] = {}   # structural CSE: entry sig -> entry idx
    in_refs: Dict[int, int] = {}     # entry idx -> number of DISTINCT consumer entries
    drefs: Dict[int, int] = {}       # id(node) -> ("d", node) operand refs inside the plan
    arefs: Dict[int, int] = {}       # id(leaf) -> ("a", leaf) operand refs inside the plan
    memo_hits = 0
    cse_hits = 0

    def leaf_ref(value, donatable: bool):
        if isinstance(value, jax.Array) or isinstance(value, PendingValue):
            # a PendingValue is the unique stand-in for a buffer an in-flight
            # force will deliver: identity-keyed like the array it becomes,
            # never donatable (its memo must survive this program)
            k = ("a", id(value))
        else:
            try:
                # repr, not the value: equality would collapse numerically
                # distinct scalars (-0.0 == 0.0, 1 == True) into one leaf slot
                k = ("s", type(value), repr(value))
            except Exception as exc:
                # a scalar whose repr raises (exotic user subclass): fall back
                # to identity keying — correct, just no cross-call leaf
                # sharing — and leave a counted trace of the oddity
                if diagnostics._enabled:
                    diagnostics.record_fallback(
                        "executor.leaf_sig",
                        f"{type(value).__name__} repr failed: "
                        f"{type(exc).__name__}: {exc}",
                    )
                k = ("s", id(value))
        idx = leaf_index.get(k)
        if idx is None:
            idx = len(leaves)
            leaf_index[k] = idx
            leaves.append(value)
            leaf_donatable.append(donatable)
        elif not donatable:
            # the same buffer also arrived as a memoised Deferred value: that
            # memo must survive this program, so the leaf is never donatable
            leaf_donatable[idx] = False
        return ("L", idx, operand_sig(value))

    def visit(node: Deferred):
        nonlocal memo_hits, cse_hits
        idx = node_index.get(id(node))
        if idx is not None:
            return ("N", idx)
        refs = []
        for kind, v in node.operands:
            if kind == "d":
                drefs[id(v)] = drefs.get(id(v), 0) + 1
                vv = v.value
                if vv is not None and isinstance(vv, PendingValue) and vv.failed():
                    # a dispatch that failed terminally: re-plan the subchain
                    # (this force is the retry the serialized path would run)
                    v.value = vv = None
                if vv is None:
                    refs.append(visit(v))
                else:
                    # a memoised interior value from an earlier force (or its
                    # in-flight PendingValue): consume it as a plain leaf —
                    # its whole subchain is NOT replayed
                    memo_hits += 1
                    refs.append(leaf_ref(vv, False))
            elif kind == "a":
                arefs[id(v)] = arefs.get(id(v), 0) + 1
                refs.append(leaf_ref(v, True))
            else:
                refs.append(leaf_ref(v, False))
        # id(op), not the op: ufunc __hash__ is Python-level and per-node hot.
        # Safe: the node (and later the cached program's plan closure) holds
        # the operation strongly, so the id cannot alias while the sig lives.
        sig = (id(node.operation), kwargs_sig(node.fn_kwargs), tuple(refs))
        idx = sig_index.get(sig)
        if idx is not None:
            # structural CSE: a separately-built node identical to an existing
            # plan entry takes its slot (and shares its output if memoised);
            # its consumers fold into the existing entry's, so no in_refs here
            cse_hits += 1
            entry_nodes[idx].append(node)
            node_index[id(node)] = idx
            return ("N", idx)
        if node.executed:
            # this node already ran inside an earlier program but was not
            # memoised — its subchain is being re-executed (should not happen
            # structurally; the fanout benchmark gates on this staying 0)
            _stats.reexecuted += 1
        # count DISTINCT consumer entries per child; deferred ops have at most
        # two operands, so adjacent-duplicate elision is exact (and cheaper
        # than a set on this per-node hot path)
        last_ci = None
        for r in refs:
            if r[0] == "N":
                ci = r[1]
                if ci != last_ci:
                    in_refs[ci] += 1
                    last_ci = ci
        idx = len(entries)
        entries.append((node.operation, node.fn_kwargs, tuple(refs)))
        entry_sig.append(sig)
        entry_nodes.append([node])
        sig_index[sig] = idx
        node_index[id(node)] = idx
        in_refs[idx] = 0
        return ("N", idx)

    root_idxs = [visit(r)[1] for r in roots]
    root = roots[0]
    gshape, split = root.gshape, root.split
    padded = tuple(root.shape) != gshape
    if padded and diagnostics._enabled:
        diagnostics.record_pad_waste(gshape, split, root.shape[split])
    if padded and profiler._active:
        # counter track: pad fraction of the forced family (timeline view of
        # the aggregate diagnostics pad_waste gauge)
        profiler.record_counter(
            "pad_waste_fraction",
            (root.shape[split] - gshape[split]) / root.shape[split],
        )

    # ---- which entries leave the program as outputs (and get memoised)
    emit = set(root_idxs)
    for idx in range(len(entries)):
        if idx in emit:
            continue
        if in_refs[idx] > 1:
            emit.add(idx)
            continue
        for node in entry_nodes[idx]:
            w = node.wref
            if w is not None:
                holder = w()
                if holder is not None and holder._payload is node:
                    emit.add(idx)  # a live DNDarray still wraps this node
                    break
            # expected refcount when the plan is the node's only holder: its
            # ("d", node) operand tuples inside the plan + the entry_nodes
            # list + the loop variable + getrefcount's own argument. Anything
            # beyond that is a deferred graph outside this plan.
            if sys.getrefcount(node) > drefs.get(id(node), 0) + 3:
                emit.add(idx)
                break
    out_idxs = tuple(sorted(emit))
    single = len(out_idxs) == 1

    pl = _ForcePlan()
    pl.root = root
    pl.leaves = leaves
    pl.leaf_donatable = leaf_donatable
    pl.plan = tuple(entries)
    pl.entry_sig = tuple(entry_sig)
    pl.entry_nodes = entry_nodes
    pl.arefs = arefs
    pl.out_idxs = out_idxs
    pl.root_idxs = root_idxs
    pl.single = single
    pl.gshape = gshape
    pl.split = split
    pl.padded = padded
    pl.key = ("defer", root.comm.mesh, gshape, split, pl.entry_sig, out_idxs)
    pl.label = (
        f"defer:{_op_label(pl.plan[0][0])}..{_op_label(pl.plan[-1][0])}[{len(pl.plan)}]"
    )
    sharding = root.comm.sharding(root.ndim, split)
    pl.out_shardings = sharding if single else (sharding,) * len(out_idxs)

    # force-shape telemetry is a property of the PLAN, tallied here so both
    # executors (and a queued dispatch that later falls back) count it once
    n_interior = len(out_idxs) - len(set(root_idxs))
    _stats.interior_outputs += n_interior
    _stats.reexec_avoided += memo_hits
    _stats.cse_hits += cse_hits
    if diagnostics._enabled:
        if n_interior:
            diagnostics.counter("executor.interior_outputs", n_interior)
        if memo_hits:
            diagnostics.counter("executor.reexec_avoided", memo_hits)
        if cse_hits:
            diagnostics.counter("executor.cse_collapses", cse_hits)
    return pl


def _plan_builder(pl: _ForcePlan):
    """The ``build`` callback :func:`lookup` compiles a plan's program from.
    Closes over the plan TUPLE (not the _ForcePlan): the cached program must
    pin the operations (id-key safety) but not the nodes."""
    plan = pl.plan
    out_idxs = pl.out_idxs
    padded = pl.padded
    gshape, split = pl.gshape, pl.split
    single = pl.single
    out_shardings = pl.out_shardings

    def build():
        def body(*leaf_vals):
            vals = []
            for operation, fn_kwargs, refs in plan:
                args = [leaf_vals[r[1]] if r[0] == "L" else vals[r[1]] for r in refs]
                vals.append(operation(*args, **fn_kwargs))
            outs = []
            for i in out_idxs:
                result = vals[i]
                if padded:
                    # every MATERIALISED value is re-masked (interior pad
                    # garbage never escapes); non-emitted entries stay unmasked
                    result = _zero_pads(result, gshape, split)
                outs.append(result)
            return outs[0] if single else tuple(outs)

        return body, out_shardings, None, None

    return build


def _plan_spec(pl: _ForcePlan) -> Optional[dict]:
    """The JSON-able replay description of a fused-graph plan — the portable
    half of the persistent compile cache (``_compile_cache``): enough to
    rebuild an identically-shaped deferred graph in a FRESH process so AOT
    warmup recompiles (or artifact-loads) the exact same signature before the
    first request arrives.

    Portability rule: every plan operation must be a ``jax.numpy`` function
    resolvable by name to the SAME object (``getattr(jnp, name) is op`` —
    what guarantees the warm process's rebuilt graph keys identically to real
    traffic), kwargs must round-trip through JSON, and every leaf must be a
    concrete array aval or a plain/np scalar.  Anything else returns None:
    the signature simply is not warmup-coverable (counted as an
    ``executor.warmup_spec`` fallback by the lookup)."""
    import json

    entries = []
    for operation, fn_kwargs, refs in pl.plan:
        name = getattr(operation, "__name__", None)
        if not name or getattr(jnp, name, None) is not operation:
            return None
        if fn_kwargs and json.loads(json.dumps(fn_kwargs)) != fn_kwargs:
            # must round-trip VALUE-identically (a tuple kwarg would replay
            # as a list and key a different signature): not warmup-coverable
            return None
        entries.append({
            "op": name,
            "kwargs": dict(fn_kwargs) if fn_kwargs else {},
            "refs": [[r[0], r[1]] for r in refs],
        })
    leaves = []
    for leaf in pl.leaves:
        if isinstance(leaf, jax.Array):
            leaves.append({
                "shape": list(leaf.shape), "dtype": np.dtype(leaf.dtype).str,
            })
        elif isinstance(leaf, PendingValue):
            return None  # an in-flight buffer has no portable description
        elif isinstance(leaf, (bool, int, float)):
            leaves.append({"scalar": leaf, "py": type(leaf).__name__})
        elif isinstance(leaf, (np.number, np.bool_)):
            leaves.append({"scalar": leaf.item(), "np": np.dtype(leaf.dtype).str})
        else:
            return None
    mesh = pl.root.comm.mesh
    return {
        "family": "defer",
        "label": pl.label,
        "entries": entries,
        "leaves": leaves,
        "gshape": list(pl.gshape),
        "split": pl.split,
        "out_idxs": list(pl.out_idxs),
        "root_idxs": sorted(set(pl.root_idxs)),
        "mesh": {"shape": list(mesh.devices.shape),
                 "axes": list(mesh.axis_names)},
    }


def _plan_replay_eager(pl: _ForcePlan) -> list:
    """Op-by-op replay of the plan: same per-node op order, one re-mask per
    emitted value (interior pad garbage never touches logical slots), layout
    pinned by comm.shard exactly like the eager dispatch path. Used below the
    warm-up jit threshold AND as the no-data-loss fallback when a compiled
    program's compile/execute fails — the plan's ``leaves`` list holds every
    input reference until the program call succeeds, so the replay always has
    live buffers to read. Interior values are memoised identically to the
    compiled path.

    The op boundary is the one safe interruption point an eager replay has,
    so a deadline-bearing plan checks its budget between ops and raises a
    typed ``DeadlineExceeded`` rather than finishing late — nothing has been
    memoised at that point, so a later (deadline-free) force can still
    compute the same nodes. Deadline-off replays pay one ``is not None``."""
    leaves = pl.leaves
    deadline = pl.deadline
    vals = []
    for operation, fn_kwargs, refs in pl.plan:
        if deadline is not None and time.monotonic() >= deadline:
            raise resilience.DeadlineExceeded(
                f"deadline passed between ops of the eager replay "
                f"({pl.label}, {len(vals)}/{len(pl.plan)} ops done)"
            )
        args = [leaves[r[1]] if r[0] == "L" else vals[r[1]] for r in refs]
        vals.append(operation(*args, **fn_kwargs))
    results = []
    for i in pl.out_idxs:
        result = vals[i]
        if pl.padded:
            result = _zero_pads(result, pl.gshape, pl.split)
        results.append(pl.root.comm.shard(result, pl.split))
    return results


def _pick_donations(pl: _ForcePlan, prog: _Program) -> Tuple[int, ...]:
    """Leaf positions safe (and useful) to donate: donatable per the plan,
    aliasable onto an output slot of the same aval, refcount-proven sole-read
    (``sanitation.sanitize_leaf_donation``), and not wasted on a full
    donate-variant table."""
    if not any(pl.leaf_donatable):
        return ()
    from . import sanitation

    leaves = pl.leaves
    arefs = pl.arefs
    entry_nodes = pl.entry_nodes
    # a donated buffer is only usable when XLA can alias it onto an output of
    # the same aval, one donation per output slot — donating more just burns a
    # jit variant and warns "donated buffers were not usable"
    out_avals: Dict[Any, int] = {}
    for i in pl.out_idxs:
        aval = (tuple(entry_nodes[i][0].shape), np.dtype(entry_nodes[i][0].dtype))
        out_avals[aval] = out_avals.get(aval, 0) + 1
    picked = []
    for i in range(len(leaves)):
        # persistent refs when the plan is this leaf's last reader: its
        # ("a", leaf) operand tuples + the leaves list. The call shape passes
        # the subscript temp directly — no loop variable or enumerate tuple
        # may hold an extra reference here.
        if not pl.leaf_donatable[i]:
            continue
        aval = (tuple(leaves[i].shape), np.dtype(leaves[i].dtype))
        if out_avals.get(aval, 0) > 0 and sanitation.sanitize_leaf_donation(
            leaves[i], arefs.get(id(leaves[i]), 0) + 1
        ):
            out_avals[aval] -= 1
            picked.append(i)
    donate_idx = tuple(picked)
    variants = prog._variants
    if (
        donate_idx
        and variants is not None
        and donate_idx not in variants
        and len(variants) >= _MAX_DONATE_VARIANTS
    ):
        # the program's donate-variant table is full and this mask has no
        # compiled variant: the call would run undonated, so decide that here
        # — the donated_bytes tally must reflect reality
        donate_idx = ()
    return donate_idx


def _memoise(pl: _ForcePlan, outs) -> None:
    for value, i in zip(outs, pl.out_idxs):
        for node in pl.entry_nodes[i]:
            node.value = value
    for nodes in pl.entry_nodes:
        for node in nodes:
            node.executed = True


def _tally_donated(pl: _ForcePlan, donate_idx: Tuple[int, ...]) -> None:
    """Account a SUCCESSFUL donating call's aliased bytes (stats + diagnostics
    counter + profiler counter track) — one definition for both executors, so
    async-vs-serialized telemetry can never skew."""
    donated = sum(pl.leaves[i].nbytes for i in donate_idx)
    _stats.donated_bytes += donated
    if diagnostics._enabled:
        diagnostics.counter("executor.donated_leaf_bytes", donated)
    if profiler._active:
        # counter track: cumulative donated bytes over the run
        profiler.record_counter("donated_bytes", _stats.total("donated_bytes"))


def _record_force_memory(pl: _ForcePlan, outs) -> None:
    # force-boundary memory gauge: logical bytes this force touched (leaf
    # inputs + emitted outputs) — the framework's live working set at the
    # boundary, not an XLA allocator readout
    live = sum(v.nbytes for v in pl.leaves if isinstance(v, jax.Array))
    live += sum(getattr(o, "nbytes", 0) for o in outs)
    profiler.record_force_memory(live)


def _force_sync_locked(roots: Tuple[Deferred, ...],
                       deadline: Optional[float] = None) -> bool:
    """The serialized executor: plan, call, and memoise under the lock —
    today's ``HEAT_TPU_ASYNC_DISPATCH=0`` contract, bit for bit (the deadline
    is carried only for the replay's between-ops checkpoint and the typed
    re-raise below; with no deadline armed nothing here changes). Returns
    False when there was nothing left to force."""
    pl = _linearise(roots)
    if pl is None:
        return False
    pl.deadline = deadline
    prog = lookup(pl.key, _plan_builder(pl), label=pl.label,
                  spec=lambda: _plan_spec(pl))
    if prog is None:
        try:
            outs = _plan_replay_eager(pl)
        except resilience.DeadlineExceeded:
            # between-ops expiry in serialized mode: counted like every other
            # lifecycle rejection (nothing is silently dropped), typed to the
            # reader
            _get_scheduler().note_lifecycle("deadline_expired", _tenant_or_none())
            raise
    else:
        donate_idx = _pick_donations(pl, prog)
        if donate_idx and _result_cache._enabled:
            # serialized path has no _acquire_buffers claim: invalidate the
            # result-cache entries aliasing the donated leaves before the call
            _result_cache.note_donation([id(pl.leaves[i]) for i in donate_idx])
        try:
            if donate_idx:
                # donation-bearing calls never ride a retry policy: a retry
                # after a post-dispatch failure would re-read buffers XLA may
                # already have invalidated — the fallback below decides instead
                outs = prog(*pl.leaves, donate_leaves=donate_idx)
            elif resilience._active:
                outs = resilience.guard(
                    "executor.execute", prog, *pl.leaves, inject=False
                )
            else:
                outs = prog(*pl.leaves)
            if pl.single:
                outs = (outs,)
            if donate_idx:
                # tallied only after the call succeeded: a failed (or injected)
                # donated dispatch never actually aliased the buffers
                _tally_donated(pl, donate_idx)
        except Exception as exc:
            # lifecycle rejections (DeadlineExceeded/Shed) come back False —
            # typed re-raise, no eager replay, no quarantine
            if not fallback_after_failure(
                pl.key, prog, exc, donated=[pl.leaves[i] for i in donate_idx]
            ):
                raise
            try:
                outs = _plan_replay_eager(pl)  # ht: ignore[spmd-collective-in-except] -- deliberate recovery path: compile/execute failures are deterministic functions of (program, operand avals), identical on every SPMD controller, so peers fail and replay the same eager collective sequence in step; a genuinely rank-local fault is surfaced by the resilience plan/flight recorder instead of riding this path
            except resilience.DeadlineExceeded:
                _get_scheduler().note_lifecycle(
                    "deadline_expired", _tenant_or_none()
                )
                raise
    if profiler._active:
        _record_force_memory(pl, outs)
    _memoise(pl, outs)
    return True


def _force_async(roots: Tuple[Deferred, ...],
                 deadline: Optional[float] = None) -> bool:
    """The async executor: plan under the lock, dispatch outside it.

    Under the lock: linearise, look up the program, pick donations, claim the
    per-buffer ownership (:func:`_acquire_buffers` — the invariant the global
    lock used to carry), and install a dispatch-done future into every node
    the program will emit. Outside the lock: resolve leaves still pending
    from earlier in-flight forces, then execute — inline when the dispatch
    path is idle, else queued to the fair scheduler (where same-signature
    items batch). Warm-up / unsupported signatures replay op-by-op under the
    lock exactly like the serialized path: below-threshold forces never
    queue. Returns False when every root was already forced or in flight
    (a lost plan race — nothing planned here), True otherwise.

    ``deadline`` (already admission-checked by the caller) rides the plan and
    the queued :class:`~._scheduler.WorkItem`: the pre-dispatch checkpoint in
    :func:`execute` / the scheduler loop cancels expired work with a typed
    error, and with ``HEAT_TPU_SHED=1`` infeasible (service-time EWMA past
    the remaining budget) or queue-full deadline-bearing requests are SHED —
    their futures fail with ``ht.resilience.Shed`` without executing."""
    sched = _get_scheduler()
    with _tlock:
        pl = _linearise(roots)
        if pl is None:
            return False
        pl.deadline = deadline
        prog = lookup(pl.key, _plan_builder(pl), label=pl.label,
                      spec=lambda: _plan_spec(pl))
        if prog is None:
            # warm-up / unsupported / quarantined: the op-by-op replay is the
            # execution. With all-concrete leaves run it here, still under the
            # lock — identical to the serialized path. A leaf still pending
            # from an earlier in-flight force must be resolved OUTSIDE the
            # lock first (its executor may need the lock to finish), so that
            # shape falls through to the unlocked replay below.
            if not any(isinstance(v, PendingValue) for v in pl.leaves):
                try:
                    outs = _plan_replay_eager(pl)
                except resilience.DeadlineExceeded:
                    # the replay's between-ops checkpoint fired: count it and
                    # deliver the typed error to the reader — nothing was
                    # memoised, so a later deadline-free force still works
                    sched.note_lifecycle("deadline_expired", _tenant_or_none())
                    raise
                if profiler._active:
                    _record_force_memory(pl, outs)
                _memoise(pl, outs)
                return True
            donate_idx = ()
        else:
            if _result_cache._enabled and (
                deadline is None or time.monotonic() < deadline
            ):
                # result-cache consult BEFORE donation picking and queueing
                # (HEAT_TPU_RESULT_CACHE=1): a validated hit memoises straight
                # into the plan's nodes — no ownership claims, no scheduler
                # round-trip, no execution.  A leaf still pending from an
                # earlier in-flight force digests as uncacheable, and expired
                # deadlines fall through to the typed lifecycle path below.
                rkey = _result_key(prog, pl.leaves)
                if rkey is not None:
                    cached = _result_cache.lookup(
                        rkey, _tenant_or_none(), count_miss=False
                    )
                    if cached is not _result_cache.MISS:
                        outs = (cached,) if pl.single else cached
                        if profiler._active:
                            _record_force_memory(pl, outs)
                        _memoise(pl, outs)
                        return True
            donate_idx = _pick_donations(pl, prog)
        donate_set = set(donate_idx)
        read_leaves = [
            v for i, v in enumerate(pl.leaves)
            if isinstance(v, jax.Array) and i not in donate_set
        ]
        granted_leaves = _acquire_buffers(
            read_leaves, [pl.leaves[i] for i in donate_idx]
        )
        granted_ids = {id(v) for v in granted_leaves}
        granted_idx = tuple(i for i in donate_idx if id(pl.leaves[i]) in granted_ids)
        pendings = []
        for i in pl.out_idxs:
            node0 = pl.entry_nodes[i][0]
            p = PendingValue(node0.shape, node0.dtype)
            pendings.append(p)
            for node in pl.entry_nodes[i]:
                node.value = p
        req = (profiler.current_request()
               if profiler.attribution_active() else None)

    # ---- lock released: everything below runs concurrently with other plans
    # tenant for lifecycle-ledger attribution, resolved eagerly only when a
    # deadline is in play (the only case the ledger's events can fire) so the
    # per-tenant breakdown matches the totals even for expiries that race
    # past the scheduler's pop-time check into execute()
    tenant = _tenant_or_none() if pl.deadline is not None else None
    released = []

    def release_once():
        if not released:
            released.append(True)
            _release_buffers(read_leaves, granted_leaves)

    def fail(exc: BaseException) -> None:
        release_once()
        # nothing memoises: the futures stay installed but FAILED, so every
        # current waiter (including the submitting thread's force) re-raises
        # the error, and readers/planners then clear or re-plan them — the
        # serialized path's raise-on-read, retry-on-next-force semantics.
        # (Un-installing here instead would let the submitter re-read None
        # and silently return nothing.)
        for p in pendings:
            p.fail(exc)

    def complete(outs, donation_happened: bool = True) -> None:
        release_once()
        if granted_idx and donation_happened:
            # tallied only when the DONATING call succeeded: a failed (or
            # injected) dispatch that fell back to the eager replay never
            # actually aliased the buffers
            _tally_donated(pl, granted_idx)
        _memoise(pl, outs)
        for p, value in zip(pendings, outs):
            p.fulfill(value)
        if profiler._active:
            _record_force_memory(pl, outs)

    def execute() -> None:
        # the whole single-item execution, fallback included; never raises —
        # it runs on scheduler threads that must not die to user errors
        donation_happened = True
        try:
            if pl.deadline is not None and time.monotonic() >= pl.deadline:
                # pre-dispatch checkpoint (covers the inline path and the
                # pop-to-execute race the scheduler's own check can miss):
                # expired work is cancelled, its futures fail typed, and the
                # buffers release through the fail closure
                sched.note_lifecycle("deadline_expired", tenant)
                fail(resilience.DeadlineExceeded(
                    f"deadline passed before dispatch ({pl.label})"
                ))
                return
            if prog is None:
                # warm-up plan whose leaves were pending at lock time: the
                # (now-resolved) op-by-op replay is the whole execution
                try:
                    outs = tuple(_plan_replay_eager(pl))
                except resilience.DeadlineExceeded as dexc:
                    sched.note_lifecycle("deadline_expired", tenant)
                    fail(dexc)
                    return
                complete(outs, False)
                return
            try:
                with profiler.attributed(req):
                    if granted_idx:
                        # donation-bearing calls never ride a retry policy: a
                        # retry after a post-dispatch failure would re-read
                        # buffers XLA may already have invalidated
                        outs = prog(*pl.leaves, donate_leaves=granted_idx)
                    elif resilience._active:
                        outs = resilience.guard(
                            "executor.execute", prog, *pl.leaves, inject=False
                        )
                    else:
                        outs = prog(*pl.leaves)
                if pl.single:
                    outs = (outs,)
            except Exception as exc:
                # a fault (injected or real) inside a queued execution falls
                # back to the op-by-op replay with no data loss: the plan's
                # leaves list held every input buffer across the failed call.
                # Lifecycle rejections (a real or injected DeadlineExceeded,
                # a Shed) come back False — typed delivery through the
                # futures, no replay, no quarantine; the next force of these
                # nodes retries from a clean slate.
                if not fallback_after_failure(
                    pl.key, prog, exc,
                    donated=[pl.leaves[i] for i in granted_idx],
                ):
                    fail(exc)
                    return
                try:
                    outs = _plan_replay_eager(pl)  # ht: ignore[spmd-collective-in-except] -- deliberate recovery path (see _force_sync_locked): dispatch failures are deterministic across SPMD controllers, so every rank's queued execution fails and replays the same sequence; the async queue is per-process host-side state and adds no cross-rank ordering
                except resilience.DeadlineExceeded as dexc:
                    sched.note_lifecycle("deadline_expired", tenant)
                    fail(dexc)
                    return
                donation_happened = False
            complete(tuple(outs), donation_happened)
        except BaseException as exc:  # pragma: no cover - belt: waiters must
            fail(exc)                 # never strand on a bookkeeping bug

    if (
        pl.deadline is not None
        and _knobs.shed
        and prog is not None
        and prog.ewma_s > 0.0
        and time.monotonic() + prog.ewma_s >= pl.deadline
    ):
        # SLO-aware admission control (HEAT_TPU_SHED=1): the per-signature
        # service-time EWMA says this dispatch cannot finish inside the
        # remaining budget, so executing it would only steal capacity from
        # feasible requests — shed it NOW with a typed error (the work was
        # never attempted; retrying without the deadline is safe)
        sched.note_lifecycle("shed", tenant)
        fail(resilience.Shed(
            f"admission control: estimated service time "
            f"{prog.ewma_s * 1e3:.2f} ms exceeds the remaining deadline "
            f"budget ({pl.label})"
        ))
        return True

    try:
        for i, v in enumerate(pl.leaves):
            if isinstance(v, PendingValue):
                # a leaf an earlier in-flight force will deliver: wait for its
                # dispatch here, never under the lock (its executor may need
                # the lock to finish)
                pl.leaves[i] = v.resolve()
    except BaseException as exc:
        fail(exc)
        raise

    batch_key = None
    if prog is not None and not granted_idx and batch_max() > 1:
        scalar_fp: list = []
        eligible = True
        for j, v in enumerate(pl.leaves):
            if isinstance(v, jax.Array):
                continue
            if isinstance(v, (int, float, bool, np.number, np.bool_)):
                # scalar identity (type + repr) is part of the batch key: two
                # forces only share a batched program when every non-array
                # operand is literally the same value
                scalar_fp.append((j, type(v).__name__, repr(v)))
            else:
                eligible = False
                break
        if eligible:
            batch_key = (id(prog), tuple(scalar_fp))

    token = sched.try_inline(tenant if tenant is not None else _tenant_or_none())
    if token is not None:
        # nobody else is dispatching on this tenant's shard: no handoff, no
        # wake-up latency — the single-threaded cost of the async executor is
        # this one try-acquire
        try:
            execute()
        finally:
            sched.end_inline(token)
        return True
    if tenant is None:
        tenant = _tenant_or_none()
    if tenant is None:
        tenant = f"t{threading.get_ident()}"
    item = _scheduler.WorkItem(
        tenant, execute, req=req, batch_key=batch_key, prog=prog,
        leaves=pl.leaves, complete=complete, fail=fail, deadline=pl.deadline,
    )
    if not _submit_with_backpressure(sched, item):
        if _knobs.shed and pl.deadline is not None:
            # load-shedding backpressure: a queue that stayed full through
            # the whole retry ladder means the system is past capacity — a
            # deadline-bearing request is shed with a typed error instead of
            # executing inline (inline execution under overload is exactly
            # the everyone-serialises collapse shedding exists to prevent).
            # Deadline-free work still runs inline: never silently dropped.
            fail(_shed_backpressure(sched, tenant, pl.label))
            return True
        # the queue stayed full through the backpressure policy: run inline —
        # slower than queued+batched, but work is never dropped
        execute()
    return True


def _execute_batch(items) -> None:
    """Run 2+ same-signature queued forces as ONE batched program call
    (:meth:`_Program.call_batched`). Installed as the scheduler's
    ``batch_runner``; must never raise. On failure every item re-runs through
    its own single path, which carries the replay_eager fallback — a broken
    batch variant degrades to N singles, never to lost requests."""
    width = len(items)
    prog = items[0].prog
    base = items[0].leaves
    array_pos = tuple(j for j, v in enumerate(base) if isinstance(v, jax.Array))
    scalar_pos = tuple(j for j in range(len(base)) if j not in array_pos)
    try:
        flat = [it.leaves[j] for it in items for j in array_pos]
        scalars = [base[j] for j in scalar_pos]
        t0 = time.perf_counter() if forensics._enabled else 0.0
        with profiler.attributed(items[0].req):
            out_flat = prog.call_batched(width, array_pos, scalar_pos, flat, scalars)
        if forensics._enabled:
            # width-share cost fold: each of the width requests is billed
            # dt/width device seconds plus its own single program's FLOPs
            forensics.note_batch_execute(
                [it.req for it in items], prog.label or "program",
                time.perf_counter() - t0, flops_each=_program_flops(prog),
            )
        n_outs = len(out_flat) // width
        if diagnostics._enabled:
            diagnostics.counter("executor.batched_requests", width)
        for i, it in enumerate(items):
            it.complete(tuple(out_flat[i * n_outs: (i + 1) * n_outs]))
    except BaseException as exc:
        if diagnostics._enabled:
            diagnostics.record_fallback(
                "executor.batch",
                f"{prog.label or 'program'}[x{width}]: {type(exc).__name__}: "
                f"{exc} — re-running {width} forces singly",
            )
        for it in items:
            it.execute()


def _shed_backpressure(sched, tenant, label) -> "resilience.Shed":
    """Ledger + build the typed ``Shed`` for a queue that stayed full through
    the whole backpressure ladder (``HEAT_TPU_SHED=1`` + a deadline-bearing
    request): ONE definition for the fused-force and staged paths so the
    shed condition, message, and the ledgered mark (which stops
    :func:`fallback_after_failure` from counting the rejection twice) can
    never diverge between them."""
    sched.note_lifecycle("shed", tenant)
    exc = resilience.Shed(
        f"dispatch queue full through backpressure; shedding "
        f"deadline-bearing request ({label})"
    )
    exc._ht_ledgered = True
    return exc


def call_staged(key, prog: _Program, x):
    """Run a staged one-op program call (the ``l``/``r``/``c`` dispatch
    families) through the dispatch scheduler when other work is in flight, so
    concurrent same-signature staged dispatches batch into ONE
    ``jax.vmap``-derived call exactly like fused forces do (ISSUE 15).

    The caller's thread still observes the synchronous contract — this
    function returns the program's result or raises exactly what a direct
    ``prog(x)`` would — but under contention the call parks as a
    :class:`~._scheduler.WorkItem` keyed on the program's identity, where the
    shard drain loop (plus cross-shard work-stealing and the adaptive batch
    window) folds it into a batch.  With async dispatch off, batching
    disabled, or the affined shard idle (the inline fast path — one
    try-acquire, so single-threaded staged ops/s is untouched, the dispatch
    baseline gate's contract) this is a plain direct call.

    Admission runs on the CALLER's thread before queueing — the deadline
    contextvar lives here, not on the shard thread — via the same
    ``_lifecycle_check`` a direct call would hit; the captured deadline rides
    the item so the scheduler's pre-dispatch checkpoint covers the queued
    window.  Typed lifecycle rejections delivered by the scheduler carry the
    ledgered mark, so the wrapper's ``fallback_after_failure`` re-raises them
    without double-counting."""
    if not _knobs.async_dispatch or _knobs.batch_max <= 1:
        return prog(x)
    sched = _get_scheduler()
    tenant = _tenant_or_none()
    token = sched.try_inline(tenant)
    if token is not None:
        try:
            return prog(x)
        finally:
            sched.end_inline(token)
    deadline = None
    if profiler._deadline_seen:
        # one module-attribute read in deadline-free processes; raises the
        # typed DeadlineExceeded/Shed before any queueing
        prog._lifecycle_check()
        deadline = profiler.current_deadline()
    if _result_cache._enabled and prog.donate_index is None:
        # result-cache consult before queueing (HEAT_TPU_RESULT_CACHE=1): a
        # validated hit skips the scheduler round-trip entirely — the inline
        # and direct paths above consult inside prog() itself.  Admission ran
        # above, so an expired deadline is a typed rejection, never a serve.
        rkey = _result_key(prog, (x,))
        if rkey is not None:
            cached = _result_cache.lookup(rkey, tenant, count_miss=False)
            if cached is not _result_cache.MISS:
                if forensics._enabled:
                    forensics.note_result_cache(
                        "hit", nbytes=_result_cache.result_nbytes(cached)
                    )
                return cached
            # no miss note here: this pre-queue consult is an optimisation
            # (count_miss=False) — the real consult inside prog() records it
    req = (profiler.current_request()
           if profiler.attribution_active() else None)
    pending = PendingValue(x.shape, x.dtype)

    def fail(exc: BaseException) -> None:
        pending.fail(exc)

    def complete(outs, donation_happened: bool = True) -> None:
        pending.fulfill(outs[0])

    def execute() -> None:
        # single-item path on a shard thread (or inline backpressure): must
        # never raise — errors travel to the waiting wrapper via the future
        try:
            if deadline is not None and time.monotonic() >= deadline:
                # pop-to-execute race the scheduler's own checkpoint can miss
                sched.note_lifecycle("deadline_expired", tenant)
                exc = resilience.DeadlineExceeded(
                    f"deadline passed before dispatch "
                    f"({prog.label or 'program'})"
                )
                exc._ht_ledgered = True
                pending.fail(exc)
                return
            with profiler.attributed(req):
                pending.fulfill(prog(x))
        except BaseException as exc:
            pending.fail(exc)

    item = _scheduler.WorkItem(
        tenant if tenant is not None else f"t{threading.get_ident()}",
        execute, req=req, batch_key=(id(prog), ()), prog=prog, leaves=[x],
        complete=complete, fail=fail, deadline=deadline,
    )
    if not _submit_with_backpressure(sched, item):
        if _knobs.shed and deadline is not None:
            # queue full through the whole backpressure ladder: shed the
            # deadline-bearing staged request typed instead of serialising
            # everyone behind it
            raise _shed_backpressure(sched, item.tenant,
                                     prog.label or "program")
        return prog(x)  # inline: slower than batched, never dropped
    return pending.resolve()


class _QueueFull(Exception):
    pass


# Backpressure for a full dispatch queue: retried under this policy (override
# per deployment with resilience.set_policy("executor.queue", ...)), and on
# exhaustion the submitter executes inline — bounded queue, unbounded work.
_QUEUE_POLICY = resilience.Policy(
    max_attempts=4, backoff_base=0.002, jitter=0.0, max_delay_s=0.05
)


def _submit_with_backpressure(sched, item) -> bool:
    """Submit ``item``; a full queue retries under the ``executor.queue``
    resilience policy. False means the caller should execute inline (or, in
    shed mode with a deadline, shed). A draining scheduler refuses admission
    immediately — no point burning the backoff ladder on a queue that will
    not re-open."""
    bound = queue_bound()
    if sched.submit(item, bound):
        return True
    if sched.draining():
        if diagnostics._enabled:
            diagnostics.record_fallback(
                "executor.queue", "scheduler draining; admission closed"
            )
        return False

    def attempt():
        if not sched.submit(item, bound):
            raise _QueueFull(f"dispatch queue at bound {bound}")

    policy = resilience.site_policy("executor.queue") or _QUEUE_POLICY
    try:
        policy.run("executor.queue", attempt)
        return True
    except _QueueFull:
        if diagnostics._enabled:
            diagnostics.record_fallback(
                "executor.queue",
                f"queue full (bound {bound}) after backpressure; executing inline",
            )
        return False




# The executor's section of ht.diagnostics.report(): global counters plus the
# ten hottest signatures (registered as a provider so diagnostics stays
# standalone-loadable — no import cycle).
diagnostics.register_provider("executor", lambda: executor_stats(top=10))


# Interpreter-shutdown drain: a force blocked on a PendingValue whose queued
# item never executes (scheduler daemon thread killed mid-queue, a test that
# left the scheduler paused, an atexit hook reading a deferred value) would
# otherwise hang forever. The drain flushes what it can within its timeout
# and sheds the rest with typed errors — every outstanding future is settled
# either way. Registered only by the package instance (the standalone
# file-path loads never build a scheduler), and registered AT IMPORT so user
# atexit hooks (registered later, run earlier under LIFO) still see a live
# scheduler while the drain runs after them.
if __package__:

    @atexit.register
    def _drain_scheduler_at_exit() -> None:  # pragma: no cover - exit hook
        sched = _dispatch_scheduler
        if sched is None:
            return
        try:
            sched.drain(timeout=5.0)
        except Exception:  # ht: ignore[silent-except] -- atexit hook: the drain already delivered typed errors to every leftover future; raising here would mask the process's real exit status
            pass
