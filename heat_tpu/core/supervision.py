"""``ht.supervision`` — the distributed supervision plane: heartbeats, a
collective watchdog, coordinated typed abort, and elastic restart.

The runtime counterpart to the static SPMD checker (``ht.analysis``'s
collective-ordering rules, PR 12): static analysis prevents *divergent*
collective sequences, but a peer that dies or wedges mid-step still strands
every other rank inside a collective (or a coordination-channel wait)
forever — the multi-controller failure mode PR 12's commit message named as
the worst one: a hang, not a crash. This module turns ANY single-process
failure into a typed error on every survivor within a bounded budget, and —
together with checkpoint v2's reshard-on-restore — into automatic recovery:

- **Heartbeats + abort sentinel.** Each process publishes a monotonic
  heartbeat over the ``jax.distributed`` coordination KV channel (the same
  no-XLA transport as the telemetry clock handshake and the checkpoint
  agreement — works on every backend, CPU meshes included). A daemon monitor
  detects a peer whose beat has not advanced for ``HEAT_TPU_PEER_TIMEOUT_S``
  and posts a cluster-wide *abort sentinel*; every rank polls the sentinel at
  the ``MeshCommunication._guarded`` chokepoint, at the scheduler's
  pre-dispatch checkpoint, and inside every supervised coordination wait —
  raising typed :class:`~.resilience.PeerFailed` on ALL survivors, never a
  silent hang. A rank that exits cleanly publishes a departure marker first,
  so normal shutdown is not a failure.

- **Collective watchdog.** :func:`watch` arms a per-collective deadline
  around every ``_guarded`` invocation window when
  ``HEAT_TPU_COLLECTIVE_TIMEOUT_S`` is set (off by default — single-process
  meshes cannot hang in a collective). A window that overruns triggers a
  flight-recorder auto-dump (trigger kind ``supervision.watchdog``), posts
  the abort sentinel, and delivers typed
  :class:`~.resilience.CollectiveTimeout` — on the survivors at their next
  sentinel poll, and on the stuck rank itself the moment its call unblocks.
  What the watchdog can catch depends on the backend: on CPU meshes every
  cross-process wait rides the coordination channel (multiprocess XLA
  computations do not exist there), so every stuck window is also an
  abortable wait; on TPU meshes a rank stuck inside an XLA collective cannot
  be interrupted — the watchdog documents the hang (post-mortem + sentinel
  for the survivors) rather than pretending to cancel it
  (``doc/source/resilience.rst`` has the matrix).

- **Supervised coordination waits.** :func:`kv_wait` / :func:`kv_barrier`
  replace every raw ``blocking_key_value_get`` / ``wait_at_barrier`` in the
  framework (the ``coord-unbounded-wait`` analysis rule bans new ones): the
  wait is chunked so the sentinel is polled while blocked, bounded by the
  unified ``HEAT_TPU_COORD_TIMEOUT_MS`` budget, and exhaustion raises typed
  :class:`~.resilience.CoordinationTimeout` naming the key and the ranks
  still missing — instead of the raw backend error the two hardcoded
  timeouts used to surface.

- **Elastic restart.** :func:`run_supervised` (also exported as
  ``ht.resilience.run_supervised``) wraps a training loop: on
  ``PeerFailed`` / ``CollectiveTimeout`` / ``CoordinationTimeout`` it drains
  the dispatch scheduler (typed), tears down the distributed runtime,
  re-initializes at the surviving world size (the caller's ``reinit`` policy
  names the new coordinator), restores the latest ``CheckpointManager`` step
  through the reshard-on-restore path (a P=8 checkpoint restores onto P=7),
  and resumes — under a bounded restart budget (an ``ht.resilience.Policy``
  plus the ``supervision.restart`` circuit breaker).

Supervised runtime bootstrap
----------------------------
XLA's own coordination service is fail-*stop*: when a task dies, the service
propagates a fatal error and the distributed client TERMINATES the surviving
processes (``client.h:80``) — exactly the opaque behaviour this module
replaces with typed delivery. :func:`bootstrap_distributed` therefore builds
the service/client pair itself (installed into
``jax._src.distributed.global_state``, so everything else in jax sees a
normally-initialized runtime) with native failure detection effectively
disabled and ``shutdown_on_destruction`` off; supervision owns failure
detection at the KV layer. On a clean exit an atexit hook performs the
ordinary shutdown barrier, preserving the default synchronized-exit
semantics; after an abort the old runtime is *abandoned* instead
(:func:`teardown_distributed`): the dead generation's service object is kept
referenced forever (destroying it would cancel surviving clients' RPCs and
kill them), the client is destroyed (safe — it owns its own threads), and the
next generation boots on a fresh coordinator address.

Zero-cost contract (the diagnostics/profiler/resilience/telemetry
discipline): idle, the one hook on a hot path — the chokepoint check in
``MeshCommunication._guarded`` — is a single module-attribute read
(``supervision._armed``) and a branch not taken. Armed, the per-collective
cost is a relaxed bool read (:func:`poll`) plus, with the watchdog on, one
dict insert/remove. Nothing is ever injected into traced program bodies, so
compiled HLO is byte-identical armed or idle
(``tests/test_supervision.py::TestHLOByteParity``).

Thread-safety: registries — the watchdog window table, the monitor's
per-peer bookkeeping, the abort payload, the graveyard — mutate under the
one module ``_lock`` (a leaf; nothing holding it calls into another locking
module). ``_armed`` and ``_aborted`` are the relaxed hot-path switches, read
bare like ``diagnostics._enabled``; the abort payload they point at is
installed before the flag flips and never mutated after.

Env knobs (memoised; re-read by :func:`reload_env_knobs`, which
``_executor.reload_env_knobs()`` calls too):

- ``HEAT_TPU_SUPERVISION=0``          — disable the plane entirely (the
  supervised bootstrap, heartbeats, and chokepoint polls).
- ``HEAT_TPU_PEER_TIMEOUT_S``         — missed-beat budget before a peer is
  declared failed (default 60).
- ``HEAT_TPU_COLLECTIVE_TIMEOUT_S``   — per-collective watchdog deadline
  (default 0 = watchdog off).
- ``HEAT_TPU_COORD_TIMEOUT_MS``       — the unified coordination-channel
  wait budget (default 600000), replacing the hardcoded
  ``communication._HANDSHAKE_TIMEOUT_MS`` / ``checkpoint._COORD_TIMEOUT_MS``.

Stdlib-only at module load (like diagnostics/profiler/resilience/_scheduler/
telemetry): jax is imported lazily inside the functions that talk to the
coordination service, so the scheduler can import this module in its
standalone file-path mode and the analysis tooling stays jax-free.
"""

from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

try:  # standalone file-path load (driver entry points): degrade like siblings
    from . import diagnostics, resilience, telemetry
except ImportError:  # pragma: no cover - exercised via tests/test_analysis.py
    diagnostics = resilience = telemetry = None

__all__ = [
    "LocalCoordinator",
    "ClientCoordinator",
    "Monitor",
    "arm",
    "disarm",
    "armed",
    "auto_arm",
    "poll",
    "abort_error",
    "aborted",
    "post_abort",
    "current_monitor",
    "forget_peer",
    "watch",
    "kv_wait",
    "kv_barrier",
    "coord_timeout_ms",
    "peer_timeout_s",
    "collective_timeout_s",
    "enabled",
    "reload_env_knobs",
    "bootstrap_distributed",
    "teardown_distributed",
    "run_supervised",
    "supervision_stats",
]

# Hot-path gates, read bare by the MeshCommunication chokepoint and the
# scheduler loop: one attribute load + branch when idle — the zero-cost
# contract. ``_armed``: a monitor is running (or a test armed the plane).
# ``_aborted``: an abort sentinel was observed; the payload in ``_abort`` is
# installed BEFORE this flips and never mutated after, so relaxed readers can
# hand it to abort_error() without the lock.
_armed: bool = False
_aborted: bool = False

_lock = threading.RLock()

_abort: Optional[dict] = None
_monitor: Optional["Monitor"] = None
_thread: Optional[threading.Thread] = None
_thread_stop: Optional[threading.Event] = None
_generation: int = 0

# Late-bound collaborator hook (the diagnostics tee pattern): ``ht.ops``
# installs its beat publisher here at ITS import so every monitor tick also
# carries the rank's compact ops beat on the same KV channel — this module
# cannot import ops (that would be a cycle). Written once, read bare; the
# tee itself gates on ``ops._armed``, so the idle cost per tick is one
# foreign attribute load + branch.
_ops_tee: Optional[Callable[["Monitor"], None]] = None

# watchdog: token -> (site, start_monotonic, deadline_monotonic); tokens the
# scan flagged overdue move to _watch_fired so the stuck rank raises typed
# the moment its call unblocks
_watch_seq = itertools.count(1)
_watch_windows: Dict[int, Tuple[str, float, float]] = {}
_watch_fired: Dict[int, float] = {}

# the dead-generation graveyard (see the module header): service objects (and
# clients we could not safely destroy) from abandoned runtimes. Entries are
# IMMORTALIZED (an extra C-level reference via Py_IncRef) so their C++
# destructors never run — not in-flight NOR at interpreter shutdown: a
# service destructor cancels every connected client's outstanding
# coordination RPC, and a cancelled error-poll trips XLA's fail-stop
# termination (client.h:80) in whatever process still holds such a client
# (pre-failure arrays keep the old backend, and with it the old client,
# reachable — their lifetime cannot be bounded here). The OS reclaims the
# leak at process exit; one service + port per elastic restart is the
# documented cost of surviving a peer death.
_graveyard: List[Any] = []


def _immortalize(obj: Any) -> None:
    import ctypes

    ctypes.pythonapi.Py_IncRef(ctypes.py_object(obj))
    with _lock:
        _graveyard.append(obj)

# process identity as armed (mirrors telemetry's, but supervision must work
# when telemetry degraded): set by arm()
_rank: int = 0
_nprocs: int = 1

_restarts: int = 0  # elastic restarts performed by this process

# the supervised bootstrap remembers whether IT built the client (then an
# abandon-teardown may destroy it; a foreign client is only graveyarded)
_owns_client: bool = False
_atexit_registered: bool = False

_CHUNK_MS = 2000  # sentinel-poll cadence inside a supervised wait


# ----------------------------------------------------------------- env knobs
class _Knobs:
    __slots__ = ("enabled", "peer_timeout_s", "collective_timeout_s",
                 "coord_timeout_ms")

    def reload(self) -> None:
        def _num(name: str, default: float, lo: float) -> float:
            try:
                return max(lo, float(os.environ.get(name, "") or default))
            except ValueError:
                return default

        self.enabled = os.environ.get("HEAT_TPU_SUPERVISION", "1") != "0"
        self.peer_timeout_s = _num("HEAT_TPU_PEER_TIMEOUT_S", 60.0, 0.1)
        self.collective_timeout_s = _num("HEAT_TPU_COLLECTIVE_TIMEOUT_S", 0.0, 0.0)
        self.coord_timeout_ms = int(_num("HEAT_TPU_COORD_TIMEOUT_MS", 600_000, 1))


_knobs = _Knobs()
_knobs.reload()


def reload_env_knobs() -> None:
    """Re-read the memoised ``HEAT_TPU_SUPERVISION`` / ``PEER_TIMEOUT_S`` /
    ``COLLECTIVE_TIMEOUT_S`` / ``COORD_TIMEOUT_MS`` knobs from ``os.environ``
    (``_executor.reload_env_knobs()`` calls this too, so one re-read point
    covers the whole framework)."""
    _knobs.reload()


def enabled() -> bool:
    """Whether the supervision plane is enabled (``HEAT_TPU_SUPERVISION``,
    default on; memoised)."""
    return _knobs.enabled


def peer_timeout_s() -> float:
    """Missed-beat budget before a peer is declared failed
    (``HEAT_TPU_PEER_TIMEOUT_S``, default 60; memoised)."""
    return _knobs.peer_timeout_s


def collective_timeout_s() -> float:
    """Per-collective watchdog deadline (``HEAT_TPU_COLLECTIVE_TIMEOUT_S``,
    default 0 = watchdog off; memoised)."""
    return _knobs.collective_timeout_s


def coord_timeout_ms() -> int:
    """The unified coordination-channel wait budget
    (``HEAT_TPU_COORD_TIMEOUT_MS``, default 600000; memoised). Replaces the
    old hardcoded handshake/checkpoint timeouts."""
    return _knobs.coord_timeout_ms


def record_resilience_event(site: str, kind: str, detail: str = "") -> None:
    """Forward one supervision event into the always-on resilience stream
    (``supervision.*`` sites; the flight-recorder tee sees every one)."""
    if diagnostics is not None:
        diagnostics.record_resilience_event(site, kind, detail)


def _count(name: str) -> None:
    if diagnostics is not None:
        diagnostics.counter(name)


# -------------------------------------------------------------- coordinators
class LocalCoordinator:
    """An in-memory KV coordinator: the single-process stand-in for the
    ``jax.distributed`` coordination service, so the heartbeat state machine,
    the watchdog, and the supervised waits are testable (and chaos-drivable)
    without real process murder. Same surface as :class:`ClientCoordinator`.

    Thread-safe: one condition variable guards the store; :meth:`wait` blocks
    on it, so a publisher wakes waiters promptly like the real service.

    The semantics deliberately MATCH the real coordination service (verified
    against jaxlib's ``DistributedRuntimeService``), so tests exercise what
    production does: :meth:`get_dir` has DIRECTORY semantics — it returns
    keys strictly *under* the prefix, never a key exactly equal to it — and
    :meth:`delete` removes the key AND its whole subtree."""

    def __init__(self):
        self._cv = threading.Condition()
        self._kv: Dict[str, str] = {}

    @staticmethod
    def _as_dir(prefix: str) -> str:
        return prefix if prefix.endswith("/") else prefix + "/"

    def set(self, key: str, value: str, overwrite: bool = True) -> None:
        with self._cv:
            if not overwrite and key in self._kv:
                raise ValueError(f"key {key!r} already set")
            self._kv[key] = value
            self._cv.notify_all()

    def get_dir(self, prefix: str) -> List[Tuple[str, str]]:
        p = self._as_dir(prefix)
        with self._cv:
            return [(k, v) for k, v in sorted(self._kv.items())
                    if k.startswith(p)]

    def wait(self, key: str, timeout_ms: int) -> str:
        with self._cv:
            ok = self._cv.wait_for(lambda: key in self._kv,
                                   timeout=max(0.0, timeout_ms / 1e3))
            if not ok:
                raise TimeoutError(f"key {key!r} not set within {timeout_ms}ms")
            return self._kv[key]

    def delete(self, key: str) -> None:
        p = self._as_dir(key)
        with self._cv:
            self._kv.pop(key, None)
            for k in [k for k in self._kv if k.startswith(p)]:
                del self._kv[k]


class ClientCoordinator:
    """The ``jax.distributed`` coordination client behind the coordinator
    surface. Built lazily (:func:`default_coordinator`) so this module stays
    stdlib-only at load."""

    def __init__(self, client):
        self._client = client

    def set(self, key: str, value: str, overwrite: bool = True) -> None:
        self._client.key_value_set(key, value, overwrite)

    def get_dir(self, prefix: str) -> List[Tuple[str, str]]:
        return list(self._client.key_value_dir_get(prefix))

    def wait(self, key: str, timeout_ms: int) -> str:
        return self._client.blocking_key_value_get(key, timeout_ms)

    def delete(self, key: str) -> None:
        self._client.key_value_delete(key)


def _distributed_client():
    """The live jax.distributed coordination client, or None (lazy jax
    import — never at module load)."""
    try:
        import jax  # noqa: F401  (ensures _src is populated)
        from jax._src import distributed as _dist
    except ImportError:
        return None
    return _dist.global_state.client


def default_coordinator() -> Optional[ClientCoordinator]:
    """A coordinator over the live jax.distributed client, or None when the
    coordination service is not initialized (single-process runs)."""
    client = _distributed_client()
    return ClientCoordinator(client) if client is not None else None


def _require_coordinator(coordinator=None):
    if coordinator is not None:
        return coordinator
    with _lock:
        if _monitor is not None:
            return _monitor.coordinator
    co = default_coordinator()
    if co is None:
        raise RuntimeError(
            "supervised coordination wait needs the jax.distributed "
            "coordination service (or an explicit coordinator)"
        )
    return co


# ------------------------------------------------------------- typed errors
def _errors():
    """The typed supervision error classes (from ht.resilience — the error
    vocabulary module). Standalone loads degrade to RuntimeError lookups."""
    if resilience is not None:
        return (resilience.PeerFailed, resilience.CollectiveTimeout,
                resilience.CoordinationTimeout)
    raise RuntimeError("supervision typed errors need ht.resilience")


def abort_error(site: str = "") -> Optional[BaseException]:
    """The typed exception for the installed abort sentinel, or None. Each
    call constructs a FRESH exception (tracebacks must not be shared across
    raising threads)."""
    if not _aborted:
        return None
    with _lock:
        payload = dict(_abort) if _abort is not None else None
    if payload is None:  # pragma: no cover - _aborted implies _abort installed
        return None
    PeerFailed, CollectiveTimeout, CoordinationTimeout = _errors()
    kind = payload.get("kind", "peer-failed")
    if kind == "collective-timeout":
        return CollectiveTimeout(
            payload.get("site", site or "<unknown>"),
            float(payload.get("elapsed_s", 0.0)),
            detected_by=int(payload.get("by", -1)),
        )
    if kind == "coordination-timeout":
        return CoordinationTimeout(
            payload.get("site", site or "<unknown>"),
            key=payload.get("key", ""),
            timeout_ms=int(payload.get("timeout_ms", 0)),
            waiting_on=payload.get("waiting_on", ()),
        )
    return PeerFailed(
        int(payload.get("rank", -1)),
        float(payload.get("last_seen_s", 0.0)),
        detected_by=int(payload.get("by", -1)),
    )


def poll(site: str = "") -> None:
    """The sentinel chokepoint: raise the typed abort error if one is
    installed, else return immediately (one relaxed bool read). Called by
    ``MeshCommunication._guarded``, the scheduler's pre-dispatch checkpoint,
    and every supervised coordination wait."""
    if _aborted:
        exc = abort_error(site)
        if exc is not None:
            raise exc


def aborted() -> Optional[dict]:
    """The installed abort-sentinel payload, or None."""
    with _lock:
        return dict(_abort) if _abort is not None else None


def _install_abort_locked(payload: dict) -> None:
    # called with _lock held (the _locked-suffix convention)
    global _abort, _aborted
    if _abort is None:
        _abort = dict(payload)
        _aborted = True


def _replace_abort_locked(payload: dict) -> None:
    # adopt a racing peer's earlier sentinel payload; with _lock held
    global _abort, _aborted
    _abort = dict(payload)
    _aborted = True


def post_abort(kind: str, *, site: str = "", coordinator=None, **fields) -> dict:
    """Post the cluster-wide abort sentinel (first poster wins — a racing
    second abort keeps the original payload) and install it locally. Returns
    the effective payload. Records a ``supervision.abort`` resilience event
    of ``kind`` — the kinds (``peer-failed`` / ``collective-timeout`` /
    ``coordination-timeout``) are flight-recorder auto-dump triggers, so
    every abort ships a post-mortem."""
    payload = {"kind": kind, "by": _rank, "site": site, **fields}
    with _lock:
        mon = _monitor
        _install_abort_locked(payload)
        effective = dict(_abort)
    co = coordinator or (mon.coordinator if mon is not None else None)
    if co is not None and mon is not None:
        try:
            co.set(mon.sentinel_key, json.dumps(effective), False)
        except Exception as exc:
            # a racing rank posted first, or the channel is already gone:
            # adopt the original payload when readable; either way the LOCAL
            # abort above already guarantees typed delivery on this rank
            record_resilience_event("supervision.abort", "post-raced",
                    f"{type(exc).__name__}: {exc}")
            try:
                found = co.get_dir(mon.abort_key)
                if found:
                    prior = json.loads(found[0][1])
                    with _lock:
                        _replace_abort_locked(prior)
                    effective = prior
            except Exception as exc2:
                record_resilience_event("supervision.abort", "sentinel-unreadable",
                        f"{type(exc2).__name__}: {exc2}")
    record_resilience_event("supervision.abort", kind, json.dumps(effective))
    _count(f"supervision.abort.{kind}")
    return effective


# ----------------------------------------------------------------- monitor
class Monitor:
    """The heartbeat + watchdog state machine, one :meth:`step` per tick.

    Deliberately thread-free: the daemon thread :func:`arm` starts just calls
    ``step(clock())`` in a loop, and tests drive the same machine with an
    injected clock and a :class:`LocalCoordinator` — the
    heartbeat/departure/detection logic is exercised without wall time or
    real processes.

    Peer liveness is judged on the OBSERVER's clock: a peer's beat value is
    tracked with the local time it last *changed*; a beat that has not
    advanced for ``peer_timeout_s`` marks the peer failed. Cross-process
    clock skew therefore never enters the decision, and a peer that died
    before its first beat is aged from this monitor's start."""

    def __init__(self, coordinator, rank: int, nprocs: int, *,
                 generation: int, peer_timeout_s: float,
                 clock: Callable[[], float] = time.monotonic):
        self.coordinator = coordinator
        self.rank = int(rank)
        self.nprocs = int(nprocs)
        self.generation = int(generation)
        self.peer_timeout_s = float(peer_timeout_s)
        self.clock = clock
        self.ns = f"heat_tpu/sup/{generation}"
        # the sentinel is STORED under the prefix (abort_key is the
        # directory, sentinel_key the one entry in it): the real service's
        # key_value_dir_get has directory semantics — a key exactly equal to
        # the prefix is never returned — so readers get_dir(abort_key) and
        # the payload must live strictly below it
        self.abort_key = f"{self.ns}/abort"
        self.sentinel_key = f"{self.ns}/abort/0"
        self._seq = 0
        started = clock()
        # rank -> (last beat value seen, local time it last changed)
        self._seen: Dict[int, Tuple[Optional[str], float]] = {
            r: (None, started) for r in range(self.nprocs) if r != self.rank
        }
        self._departed: set = set()

    # ------------------------------------------------------------ publishing
    def beat(self) -> None:
        """Publish this rank's next heartbeat (monotonic counter)."""
        self._seq += 1
        self.coordinator.set(f"{self.ns}/hb/{self.rank}", str(self._seq), True)

    def depart(self) -> None:
        """Publish the clean-departure marker: peers stop expecting beats."""
        try:
            self.coordinator.set(f"{self.ns}/bye/{self.rank}", "1", True)
        except Exception as exc:
            record_resilience_event("supervision.heartbeat", "depart-unpublished",
                    f"{type(exc).__name__}: {exc}")

    def forget(self, rank: int) -> None:
        """Stop expecting beats from ``rank``: its failure has been HANDLED
        (e.g. the serving failover shed its work typed and the pool serves
        on) — without this the next scan would re-detect the same silent
        peer and re-post the abort the handler just cleared."""
        self._departed.add(int(rank))

    # ------------------------------------------------------------- detection
    def scan(self, now: float) -> Optional[dict]:
        """One detection pass: read peers' beats and departures, age the
        silent ones, and post the abort sentinel for the first peer past the
        budget. Returns the posted payload, or None."""
        beats: Dict[int, str] = {}
        for key, value in self.coordinator.get_dir(f"{self.ns}/hb/"):
            try:
                beats[int(key.rsplit("/", 1)[-1])] = value
            except ValueError:
                continue  # foreign key under the prefix: not a beat
        for key, _ in self.coordinator.get_dir(f"{self.ns}/bye/"):
            try:
                self._departed.add(int(key.rsplit("/", 1)[-1]))
            except ValueError:
                continue
        for r, (last, changed) in list(self._seen.items()):
            if r in self._departed:
                continue
            cur = beats.get(r)
            if cur is not None and cur != last:
                self._seen[r] = (cur, now)
                continue
            age = now - changed
            if age > self.peer_timeout_s:
                record_resilience_event(
                    "supervision.heartbeat", "peer-missed",
                    f"rank {r} silent for {age:.3f}s "
                    f"(budget {self.peer_timeout_s:.3f}s)",
                )
                return post_abort(
                    "peer-failed", site="supervision.heartbeat",
                    rank=r, last_seen_s=round(age, 3),
                )
        return None

    def check_sentinel(self) -> Optional[dict]:
        """Adopt a peer-posted abort sentinel into the local abort state."""
        if _aborted:
            return aborted()
        found = self.coordinator.get_dir(self.abort_key)
        if not found:
            return None
        try:
            payload = json.loads(found[0][1])
        except ValueError:
            payload = {"kind": "peer-failed", "rank": -1, "last_seen_s": 0.0}
        with _lock:
            _install_abort_locked(payload)
        record_resilience_event("supervision.abort", "adopted", json.dumps(payload))
        return payload

    # -------------------------------------------------------------- watchdog
    def watchdog_scan(self, now: float) -> Optional[dict]:
        """Flag in-flight collective windows past their deadline: mark the
        window fired (the stuck rank raises when it unblocks), dump a
        ``supervision.watchdog`` post-mortem, and post the sentinel so every
        survivor aborts typed."""
        overdue: Optional[Tuple[int, str, float]] = None
        with _lock:
            for token, (site, start, deadline) in _watch_windows.items():
                if now >= deadline and token not in _watch_fired:
                    _watch_fired[token] = now - start
                    overdue = (token, site, now - start)
                    break
        if overdue is None:
            return None
        _token, site, elapsed = overdue
        record_resilience_event(
            "supervision.watchdog", "watchdog-fired",
            f"collective window at {site!r} stuck for {elapsed:.3f}s "
            f"(budget {collective_timeout_s():.3f}s)",
        )
        _count("supervision.watchdog.fired")
        if telemetry is not None:
            telemetry.flight_record(
                "supervision", site,
                f"stuck collective window: {elapsed:.3f}s", kind="watchdog",
            )
            telemetry.flight_dump("supervision.watchdog")
        return post_abort(
            "collective-timeout", site=site, elapsed_s=round(elapsed, 3),
        )

    def step(self, now: Optional[float] = None) -> None:
        """One monitor tick: beat, adopt/post sentinels, age peers, scan the
        watchdog. Each leg is independent; a channel error in one must not
        starve the others (it is recorded and retried next tick)."""
        now = self.clock() if now is None else now
        try:
            self.beat()
        except Exception as exc:
            record_resilience_event("supervision.heartbeat", "beat-unpublished",
                    f"{type(exc).__name__}: {exc}")
        tee = _ops_tee
        if tee is not None:
            try:
                tee(self)
            except Exception as exc:
                record_resilience_event(
                    "supervision.heartbeat", "ops-beat-unpublished",
                    f"{type(exc).__name__}: {exc}")
        try:
            self.check_sentinel()
            if not _aborted:
                self.scan(now)
        except Exception as exc:
            record_resilience_event("supervision.heartbeat", "scan-failed",
                    f"{type(exc).__name__}: {exc}")
        self.watchdog_scan(now)


# ------------------------------------------------------------ arm / disarm
def _tick_interval(timeout_s: float) -> float:
    """Monitor cadence: a few beats per peer-timeout window, bounded to stay
    responsive for test-scale budgets and cheap for production ones."""
    return min(1.0, max(0.05, timeout_s / 5.0))


def arm(coordinator=None, *, rank: Optional[int] = None,
        nprocs: Optional[int] = None, peer_timeout_s: Optional[float] = None,
        clock: Callable[[], float] = time.monotonic,
        start_thread: bool = True) -> Monitor:
    """Arm the supervision plane: start heartbeats + the monitor daemon on
    ``coordinator`` (default: the live jax.distributed client) for this
    ``rank`` of ``nprocs``. Re-arming replaces the previous monitor (a new
    generation namespace). ``start_thread=False`` leaves stepping to the
    caller — the injected-clock tests."""
    global _armed, _monitor, _thread, _thread_stop, _generation, _rank, _nprocs
    if rank is None or nprocs is None:
        if telemetry is not None:
            t_rank, t_count = telemetry.process_info()
        else:  # pragma: no cover - standalone load
            t_rank, t_count = 0, 1
        rank = t_rank if rank is None else rank
        nprocs = t_count if nprocs is None else nprocs
    co = coordinator if coordinator is not None else default_coordinator()
    if co is None:
        co = LocalCoordinator()
    disarm()
    with _lock:
        _generation += 1
        _rank, _nprocs = int(rank), int(nprocs)
        monitor = Monitor(
            co, rank, nprocs, generation=_generation,
            peer_timeout_s=(peer_timeout_s if peer_timeout_s is not None
                            else _knobs.peer_timeout_s),
            clock=clock,
        )
        _monitor = monitor
        stop = _thread_stop = threading.Event()
    _armed = True
    _register_atexit()
    if start_thread and nprocs > 1:
        interval = _tick_interval(monitor.peer_timeout_s)

        def loop() -> None:
            try:
                # first beat before any sleep: peers start aging us now; a
                # transient channel error here must not kill the daemon (the
                # next step()'s beat retries) — an armed-looking plane whose
                # thread died at birth would get this healthy rank declared
                # dead by every peer
                monitor.beat()
            except Exception as exc:
                record_resilience_event(
                    "supervision.heartbeat", "beat-unpublished",
                    f"{type(exc).__name__}: {exc}")
            while not stop.wait(interval):
                monitor.step()

        t = threading.Thread(target=loop, name="heat-tpu-supervision",
                             daemon=True)
        with _lock:
            _thread = t
        t.start()
    record_resilience_event("supervision.plane", "armed",
            f"rank {rank}/{nprocs}, peer_timeout {monitor.peer_timeout_s:.3f}s,"
            f" generation {_generation}")
    return monitor


def current_monitor() -> Optional["Monitor"]:
    """The armed :class:`Monitor`, or None — the handle ``ht.ops`` folds
    cluster beats through (``cluster_snapshot`` sweeps ``<ns>/ops/`` on its
    coordinator)."""
    with _lock:
        return _monitor


def disarm() -> None:
    """Stop the monitor daemon and return the plane to zero-cost idle. The
    abort state is kept (a typed failure must stay deliverable until
    :func:`reset_abort`); watchdog windows are cleared."""
    global _armed, _monitor, _thread, _thread_stop
    with _lock:
        thread, stop = _thread, _thread_stop
        _thread = _thread_stop = None
        _monitor = None
    _armed = False
    if stop is not None:
        stop.set()
    if thread is not None and thread.is_alive():
        thread.join(timeout=5.0)
    with _lock:
        _watch_windows.clear()
        _watch_fired.clear()


def armed() -> bool:
    """Whether the supervision plane is armed."""
    return _armed


def reset_abort() -> None:
    """Clear the installed abort sentinel (failover handled / elastic
    restart / test isolation). While a monitor is still armed — the
    single-host serving failover, where the SAME generation keeps running —
    the store copy is deleted FIRST (its ``check_sentinel`` would re-adopt a
    lingering key every tick); after a disarm the store copy belongs to the
    dead generation's namespace and is simply left behind."""
    global _abort, _aborted
    with _lock:
        mon = _monitor
    if mon is not None:
        try:
            mon.coordinator.delete(mon.abort_key)
        except Exception as exc:
            record_resilience_event("supervision.abort", "sentinel-clear-failed",
                    f"{type(exc).__name__}: {exc}")
    with _lock:
        _abort = None
        _aborted = False


def forget_peer(rank: int) -> None:
    """Tell the armed monitor that ``rank``'s failure has been handled: it
    stops expecting the dead peer's beats, so clearing the abort sentinel
    (``reset_abort``) does not just get it re-posted at the next scan. The
    single-host failover verb — ``ModelPool.on_peer_failure`` uses it; the
    multi-host elastic restart re-arms a fresh monitor at the surviving
    world size instead."""
    with _lock:
        mon = _monitor
    if mon is not None:
        mon.forget(rank)


def auto_arm() -> None:
    """Arm the plane for a multi-process job when enabled — called by the
    communication bootstrap after the runtime is up. Single-process runs (or
    ``HEAT_TPU_SUPERVISION=0``) stay zero-cost idle."""
    if not _knobs.enabled:
        return
    client = _distributed_client()
    if client is None:
        return
    try:
        import jax
        nprocs = jax.process_count()
        rank = jax.process_index()
    except Exception as exc:  # backend not initialized yet: stay idle
        record_resilience_event("supervision.plane", "arm-deferred",
                f"{type(exc).__name__}: {exc}")
        return
    if nprocs <= 1:
        return
    arm(ClientCoordinator(client), rank=rank, nprocs=nprocs)


# ------------------------------------------------------------ the watchdog
@contextlib.contextmanager
def watch(site: str):
    """Supervise one collective invocation window: poll the sentinel on
    entry and exit, and — when ``HEAT_TPU_COLLECTIVE_TIMEOUT_S`` is set —
    arm a watchdog deadline for the window. A window the watchdog flagged
    raises typed :class:`~.resilience.CollectiveTimeout` on this rank as soon
    as the call unblocks (survivors raise at their own sentinel polls)."""
    poll(site)
    budget = collective_timeout_s()
    mon = _monitor  # snapshot: a concurrent disarm() may null the global
    if budget <= 0.0 or mon is None:
        yield
        poll(site)
        return
    token = next(_watch_seq)
    start = mon.clock()
    with _lock:
        _watch_windows[token] = (site, start, start + budget)
    fired: Optional[float] = None
    try:
        yield
    finally:
        with _lock:
            _watch_windows.pop(token, None)
            fired = _watch_fired.pop(token, None)
    if fired is not None:
        PeerFailed, CollectiveTimeout, CoordinationTimeout = _errors()
        raise CollectiveTimeout(site, fired, detected_by=_rank)
    poll(site)


# ------------------------------------------------- supervised coordination
def _looks_like_timeout(exc: BaseException) -> bool:
    if isinstance(exc, TimeoutError):
        return True
    text = f"{type(exc).__name__}: {exc}".lower()
    return "deadline" in text or "timeout" in text or "timed out" in text


def kv_wait(key: str, timeout_ms: Optional[int] = None, *,
            site: str = "supervision.kv", coordinator=None) -> str:
    """A supervised ``blocking_key_value_get``: the wait is chunked so the
    abort sentinel is polled while blocked (a detected peer failure raises
    typed :class:`~.resilience.PeerFailed` MID-WAIT, not after the full
    budget), bounded by ``timeout_ms`` (default: the unified
    ``HEAT_TPU_COORD_TIMEOUT_MS``), and exhaustion raises typed
    :class:`~.resilience.CoordinationTimeout` naming the key — never the raw
    backend error. This wrapper (and :func:`kv_barrier`) is the only
    sanctioned coordination-wait form: the ``coord-unbounded-wait`` analysis
    rule flags raw waits anywhere else."""
    co = _require_coordinator(coordinator)
    budget = coord_timeout_ms() if timeout_ms is None else int(timeout_ms)
    mon = _monitor  # snapshot: a concurrent disarm() may null the global
    clock = mon.clock if mon is not None else time.monotonic
    deadline = clock() + budget / 1e3
    last: Optional[BaseException] = None
    while True:
        poll(site)
        remaining_ms = (deadline - clock()) * 1e3
        if remaining_ms <= 0.0:
            PeerFailed, CollectiveTimeout, CoordinationTimeout = _errors()
            detail = f"{type(last).__name__}: {last}" if last else ""
            raise CoordinationTimeout(
                site, key=key, timeout_ms=budget, detail=detail
            ) from last
        try:
            return co.wait(key, int(max(1.0, min(_CHUNK_MS, remaining_ms))))
        except Exception as exc:
            last = exc
            if not _looks_like_timeout(exc):
                # a genuine channel failure (service gone, connection reset):
                # typed immediately — waiting out the budget cannot fix it
                PeerFailed, CollectiveTimeout, CoordinationTimeout = _errors()
                raise CoordinationTimeout(
                    site, key=key, timeout_ms=budget,
                    detail=f"{type(exc).__name__}: {exc}",
                ) from exc
            # chunk expired: loop to poll the sentinel, then keep waiting


def kv_barrier(ns: str, *, nprocs: Optional[int] = None,
               rank: Optional[int] = None, timeout_ms: Optional[int] = None,
               site: str = "supervision.barrier", coordinator=None) -> None:
    """A supervised barrier over the KV store: every rank publishes
    ``<ns>/<rank>`` and waits for all ``nprocs`` keys. Unlike the native
    ``wait_at_barrier`` this is sentinel-abortable mid-wait, and a timeout
    raises typed :class:`~.resilience.CoordinationTimeout` NAMING the ranks
    that never arrived. The namespace must be fresh per use (callers thread
    their own sequence numbers, e.g. ``checkpoint._coord_ns``)."""
    co = _require_coordinator(coordinator)
    if nprocs is None or rank is None:
        with _lock:
            mon = _monitor
        if mon is None:
            raise ValueError("kv_barrier needs nprocs/rank when disarmed")
        nprocs = mon.nprocs if nprocs is None else nprocs
        rank = mon.rank if rank is None else rank
    budget = coord_timeout_ms() if timeout_ms is None else int(timeout_ms)
    mon = _monitor  # snapshot: a concurrent disarm() may null the global
    clock = mon.clock if mon is not None else time.monotonic
    deadline = clock() + budget / 1e3
    co.set(f"{ns}/{rank}", "1", True)
    PeerFailed, CollectiveTimeout, CoordinationTimeout = _errors()
    for r in range(int(nprocs)):
        remaining = max(1, int((deadline - clock()) * 1e3))
        try:
            kv_wait(f"{ns}/{r}", remaining, site=site, coordinator=co)
        except CoordinationTimeout as exc:
            # one directory listing of the arrived ranks (keys {ns}/{rank}
            # sit strictly under the namespace, so directory semantics
            # return them; an exact-key probe per rank would not — the real
            # service never returns a key equal to the prefix)
            arrived = set()
            try:
                for k, _v in co.get_dir(ns):
                    try:
                        arrived.add(int(k.rsplit("/", 1)[-1]))
                    except ValueError:
                        continue
            except Exception as exc2:
                # channel gone: report the timeout unadorned
                record_resilience_event(
                    "supervision.barrier", "arrived-unreadable",
                    f"{type(exc2).__name__}: {exc2}")
                arrived = None
            waiting = ([w for w in range(int(nprocs)) if w not in arrived]
                       if arrived is not None else [])
            raise CoordinationTimeout(
                site, key=f"{ns}/{r}", timeout_ms=budget, waiting_on=waiting,
                detail=exc.detail,
            ) from exc


# ------------------------------------------------- supervised jax runtime
#: seconds; effectively never (the old 10 s beat x 1e6 missed-beat budget)
_NATIVE_HEARTBEAT_TIMEOUT_S = 10_000_000


def _service_bind_address(coordinator_address: str) -> str:
    return "[::]:" + coordinator_address.rsplit(":", 1)[1]


def bootstrap_distributed(coordinator_address: str, num_processes: int,
                          process_id: int, *,
                          init_timeout_s: Optional[int] = None) -> None:
    """Initialize the jax distributed runtime in SUPERVISED mode: same
    observable result as ``jax.distributed.initialize`` (the service/client
    pair lands in ``jax._src.distributed.global_state``), but XLA's native
    fail-stop is disabled — peer failure detection, typed delivery, and
    recovery belong to this module (see the module header). Survivors of a
    peer failure can therefore abandon this runtime and re-initialize at the
    surviving world size, which the default runtime's process-terminating
    error propagation makes impossible."""
    import jax  # noqa: F401
    from jax._src import distributed as _dist
    from jax._src.lib import _jax as xe

    global _owns_client
    state = _dist.global_state
    if state.client is not None:
        return  # already initialized (explicit user bootstrap): respect it
    timeout = (int(init_timeout_s) if init_timeout_s is not None
               else max(1, coord_timeout_ms() // 1000))
    if process_id == 0 and state.service is None:
        # native failure detection OFF (a practically infinite heartbeat
        # timeout): supervision's KV heartbeats own detection, and the
        # service must never fail-stop the survivors
        state.service = xe.get_distributed_runtime_service(
            _service_bind_address(coordinator_address), num_processes,
            heartbeat_timeout=_NATIVE_HEARTBEAT_TIMEOUT_S,
        )
    client = xe.get_distributed_runtime_client(
        coordinator_address, process_id, init_timeout=timeout,
        heartbeat_timeout=_NATIVE_HEARTBEAT_TIMEOUT_S,
        shutdown_on_destruction=False, use_compression=True,
    )
    client.connect()
    state.client = client
    state.process_id = process_id
    state.num_processes = num_processes
    state.coordinator_address = coordinator_address
    with _lock:
        _owns_client = True
    _register_atexit()
    record_resilience_event("supervision.runtime", "bootstrapped",
            f"rank {process_id}/{num_processes} at {coordinator_address}")


def teardown_distributed(*, clean: Optional[bool] = None) -> None:
    """Tear the distributed runtime down. ``clean`` (default: no abort
    installed) performs the ordinary synchronized shutdown (barrier across
    all tasks — only safe when every peer is alive). Dirty teardown ABANDONS
    the runtime instead: the service object joins the graveyard (destroying
    it would cancel surviving peers' coordination RPCs and terminate them),
    the supervised client is destroyed (it owns only its own threads), a
    foreign client is graveyarded too (its destructor may run a shutdown
    barrier that can never complete), and every jax backend/topology cache is
    cleared so the next :func:`bootstrap_distributed` rebuilds the world at
    its new size."""
    import gc

    import jax
    from jax._src import distributed as _dist
    from jax._src import xla_bridge as xb

    global _owns_client
    state = _dist.global_state
    client, service = state.client, state.service
    if clean is None:
        clean = not _aborted
    state.client = None
    state.service = None
    state.preemption_sync_manager = None
    with _lock:
        owns = _owns_client
        _owns_client = False
    if clean and client is not None:
        try:
            client.shutdown()
            if service is not None:
                service.shutdown()
            client = service = None
        except Exception as exc:
            # a peer vanished between the abort check and the barrier:
            # fall through to the abandon path below
            record_resilience_event("supervision.runtime", "shutdown-degraded",
                    f"{type(exc).__name__}: {exc}")
    if service is not None:
        _immortalize(service)
    if client is not None and not owns:
        _immortalize(client)
    client = None  # a supervised client: destroying it stops its own threads
    gc.collect()
    jax.clear_caches()
    with xb._backend_lock:
        xb._backends.clear()
        xb._backend_errors.clear()
        xb._default_backend = None
    for attr in dir(xb):
        fn = getattr(xb, attr, None)
        if callable(fn) and hasattr(fn, "cache_clear"):
            fn.cache_clear()
    record_resilience_event("supervision.runtime", "teardown",
            "clean" if clean else "abandoned (graveyarded)")


def _register_atexit() -> None:
    global _atexit_registered
    with _lock:
        if _atexit_registered:
            return
        _atexit_registered = True
    atexit.register(_atexit_shutdown)


def _atexit_shutdown() -> None:
    """Process-exit hook for supervised runs: publish the clean-departure
    marker (peers must not read a normal exit as a failure), then — when this
    module built the runtime and no abort happened — perform the ordinary
    synchronized shutdown the default client would have done from its
    destructor. After an abort the runtime is abandoned instead: the
    destructors must not run (see :func:`teardown_distributed`)."""
    with _lock:
        mon = _monitor
        owns = _owns_client
    if mon is not None:
        mon.depart()
    disarm()
    if not owns:
        return
    try:
        teardown_distributed()
    except Exception as exc:
        # the process is exiting: a failed courtesy shutdown must not turn
        # a clean exit into a crash
        record_resilience_event("supervision.runtime", "atexit-degraded",
                f"{type(exc).__name__}: {exc}")


# --------------------------------------------------------- elastic restart
def _drain_scheduler(timeout_s: float) -> None:
    """Flush the dispatch scheduler before teardown: queued work is delivered
    or shed TYPED (DrainTimeout's contract), so no request future can survive
    into the new generation blocked."""
    from . import _executor

    try:
        _executor._get_scheduler().drain(timeout_s)
    except Exception as exc:
        if resilience is not None and isinstance(exc, resilience.DrainTimeout):
            return  # typed + delivered to every waiter: exactly the contract
        raise


def _reanchor_framework(spec: Optional[dict]) -> None:
    """After the teardown: join the new generation's job (``spec``; None stays
    single-process), which rebuilds communication's world and ends in
    :func:`auto_arm`; then drop what else the old world left: the executor's
    caches and memoised process-count, the checkpoint coordination counters."""
    from . import _executor, checkpoint, communication

    if spec is not None:
        communication.initialize(**spec)
    else:
        communication.build_world()
    _executor.clear_executor_cache()
    _executor._single_controller = None
    with checkpoint._state_lock:
        checkpoint._coord_seq = 0
        checkpoint._coord_my_keys.clear()
    _executor._get_scheduler().reopen()


def elastic_restart(exc: BaseException, *, reinit=None,
                    drain_timeout_s: float = 10.0) -> dict:
    """One supervised restart: drain → disarm → teardown → (re)initialize →
    re-anchor → re-arm. ``reinit(exc)`` is the caller's elasticity policy: it
    returns ``{"coordinator_address", "num_processes", "process_id"}`` for
    the surviving world (a fresh coordinator address — the dead generation's
    port is abandoned, not reused), or None to continue single-process.
    Returns a summary dict. Used by :func:`run_supervised`; callable directly
    by serving-side failover logic."""
    global _restarts
    record_resilience_event("supervision.restart", "elastic-restart",
            f"{type(exc).__name__}: {exc}")
    _count("supervision.restart")
    _drain_scheduler(drain_timeout_s)
    disarm()
    # the abort is being HANDLED from here on: clear it before the reinit
    # policy runs, whose own supervised waits (negotiating the new
    # coordinator over the old KV store) must not re-raise it
    reset_abort()
    had_client = _distributed_client() is not None
    spec = reinit(exc) if reinit is not None else None
    if had_client:
        teardown_distributed(clean=False)
    if had_client or spec is not None:
        _reanchor_framework(spec)
    else:
        from . import _executor

        _executor._get_scheduler().reopen()
        auto_arm()
    with _lock:
        _restarts += 1
        restarts = _restarts
    summary = {
        "cause": f"{type(exc).__name__}: {exc}",
        "world": (spec or {}).get("num_processes", 1),
        "rank": (spec or {}).get("process_id", 0),
        "restarts": restarts,
    }
    record_resilience_event("supervision.restart", "restarted", json.dumps(summary))
    return summary


def run_supervised(step_fn, manager, policy=None, *, template=None,
                   state=None, start_step: int = 0,
                   max_steps: Optional[int] = None, save_every: int = 1,
                   reinit=None, drain_timeout_s: float = 10.0,
                   restore_kwargs: Optional[dict] = None) -> dict:
    """Run a training loop under the supervision plane with elastic restart.

    ``step_fn(step, state) -> state`` is one training step;
    ``manager`` is a :class:`~.checkpoint.CheckpointManager`; ``template``
    the restore template pytree, or a CALLABLE returning one — pass a
    callable for elastic multi-process jobs, because a template's DNDarray
    leaves pin the communicator and the restore after a world-size change
    must build against the surviving world's mesh (defaults to ``state``).
    Steps where
    ``step % save_every == 0`` are checkpointed. On a typed supervision
    failure (:class:`~.resilience.PeerFailed` /
    :class:`~.resilience.CollectiveTimeout` /
    :class:`~.resilience.CoordinationTimeout`) the harness performs
    :func:`elastic_restart` — drain, teardown, re-init at the surviving world
    size per the ``reinit`` policy, restore the latest step through the
    reshard-on-restore path — and resumes, under a bounded restart budget:
    ``policy.max_attempts`` restarts (default 3) gated by the
    ``supervision.restart`` circuit breaker. An exhausted budget (or an open
    breaker) re-raises the typed failure unchanged.

    Returns ``{"state", "steps", "restarts"}``."""
    if resilience is None:  # pragma: no cover - standalone load
        raise RuntimeError("run_supervised needs the heat_tpu package")
    PeerFailed, CollectiveTimeout, CoordinationTimeout = _errors()
    pol = policy or resilience.Policy(max_attempts=3, backoff_base=0.5)
    br = resilience.breaker("supervision.restart")
    template = template if template is not None else state

    def _template():
        return template() if callable(template) else template

    restore_kwargs = dict(restore_kwargs or {})
    if state is None:
        latest = manager.latest_step
        if latest is None:
            raise ValueError("run_supervised needs an initial state or a "
                             "restorable checkpoint step")
        state = manager.restore(_template(), **restore_kwargs)
        start_step = latest + 1
    step = int(start_step)
    restarts = 0
    while max_steps is None or step < max_steps:
        try:
            poll("supervision.step")
            state = step_fn(step, state)
            if save_every and step % save_every == 0:
                manager.save(step, state)
            br.record_success()
            step += 1
        except (PeerFailed, CollectiveTimeout, CoordinationTimeout) as exc:
            restarts += 1
            br.record_failure(f"{type(exc).__name__}: {exc}")
            budget_left = (pol.max_attempts is None
                           or restarts < pol.max_attempts)
            if not budget_left or not br.allows():
                record_resilience_event(
                    "supervision.restart", "exhausted",
                    f"restart {restarts} refused "
                    f"(budget_left={budget_left}, breaker={br.state}): "
                    f"{type(exc).__name__}: {exc}",
                )
                raise
            time.sleep(pol.delay_s(restarts))
            elastic_restart(exc, reinit=reinit,
                            drain_timeout_s=drain_timeout_s)
            latest = manager.latest_step
            if latest is None:
                raise
            state = manager.restore(_template(), **restore_kwargs)
            step = latest + 1
    return {"state": state, "steps": step, "restarts": restarts}


# ------------------------------------------------------------------ stats
def supervision_stats() -> dict:
    """The supervision section of ``ht.diagnostics.report()``: armed state,
    identity, abort payload, watchdog windows, restart count."""
    with _lock:
        mon = _monitor
        return {
            "armed": _armed,
            "enabled": _knobs.enabled,
            "rank": _rank,
            "nprocs": _nprocs,
            "generation": _generation,
            "peer_timeout_s": (mon.peer_timeout_s if mon is not None
                               else _knobs.peer_timeout_s),
            "collective_timeout_s": _knobs.collective_timeout_s,
            "coord_timeout_ms": _knobs.coord_timeout_ms,
            "aborted": dict(_abort) if _abort is not None else None,
            "watch_windows": len(_watch_windows),
            "restarts": _restarts,
            "graveyard": len(_graveyard),
        }


if diagnostics is not None:
    diagnostics.register_provider("supervision", supervision_stats)

if resilience is not None:
    def _go_silent_for_peer_death() -> None:
        """The ``peer-dead`` fault hook: stop heartbeating WITHOUT the
        clean-departure marker — peers must observe a crash (silence, then
        absence), not a shutdown. The exit that follows skips atexit, so the
        marker can never leak out after this."""
        disarm()

    resilience._peer_dead_hook = _go_silent_for_peer_death
