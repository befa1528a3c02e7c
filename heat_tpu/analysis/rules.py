"""Rule registry: ids, one-paragraph explanations (``--explain``), runners."""

from __future__ import annotations

from . import (
    rules_coord,
    rules_donation,
    rules_fallbacks,
    rules_imports,
    rules_layout,
    rules_locks,
    rules_purity,
    rules_spmd,
)

RULES = {
    "spmd-divergent-collective": (
        "A conditional, loop bound, or early return/raise controlled by a "
        "rank-tainted value (jax.process_index() / comm.rank / _is_writer() "
        "and everything assigned from them) makes the emitted collective "
        "sequence differ across ranks — one rank enters a collective its "
        "peers never reach and every process blocks inside XLA forever. "
        "Classic MPI deadlock detection adapted to the mesh-collective "
        "world; the runtime twin is `telemetry merge --check`'s cross-rank "
        "sequence gate. Restructure rank-symmetrically: guard only the "
        "host-local work and let every rank reach the collective (the "
        "io._serialized_shard_write shape)."
    ),
    "spmd-collective-in-except": (
        "A collective (or a call that transitively emits one) inside an "
        "except handler: exceptions are per-process, so ranks whose peers "
        "did not raise never enter the handler's collective and the job "
        "hangs. Move the collective out of the handler, or make the "
        "failure rank-symmetric first (e.g. allgather the error state)."
    ),
    "layout-shard-claim-mismatch": (
        "A value laid out via comm.shard(v, S1) is wrapped in a DNDarray "
        "claiming split=S2 (both statically known, different): the metadata "
        "lies about the physical layout, so every downstream chunk/lshape/"
        "collective decision keyed off split is wrong. Make the claimed "
        "split the one the value was actually laid out with."
    ),
    "layout-resplit-roundtrip": (
        "The same value resharded to two different splits inside one "
        "function: each hop is a full cross-device reshard and the "
        "intermediate layout pads/trims the wrong axis for padded physical "
        "values. The padded-physical contract routes layout changes through "
        "ONE comm.shard to the final split."
    ),
    "layout-pad-mask-dropped": (
        "A value computed from a padded physical operand (.parray through "
        "an op the checker cannot prove pad-preserving) is wrapped or laid "
        "out without a sanctioned re-mask (_zero_pads / _padded_reduce_"
        "value): pad slots may hold garbage, breaking the 'pads always "
        "hold zero' invariant that guards like jnp.isnan(x.parray).any() "
        "rely on. Re-mask, or declare the padded-physical hand-off in "
        "analysis/layout_contracts.py."
    ),
    "layout-contract": (
        "A returned DNDarray/wrap_result construction claims a split that "
        "is not among the allowed forms declared for the function in "
        "analysis/layout_contracts.py (the machine-readable registry "
        "transcribed from the dispatch docstrings). Change the code's "
        "contract and the registry together, or the checker blocks — that "
        "is the point."
    ),
    "layout-contract-stale": (
        "A layout_contracts.py entry names a function that no longer "
        "exists: the contract outlived the code. Move the entry with the "
        "refactor or delete it — a dangling contract checks nothing and "
        "gives false confidence."
    ),
    "trace-env-read": (
        "No os.environ/os.getenv reads inside traced bodies. A traced body "
        "runs once per compile; an env value read there is frozen into the "
        "executable and silently ignored on every replay — the HLO-byte-"
        "parity contract (doc/source/observability.rst) and the env-knob "
        "semantics both break. Hoist the read to the host-side dispatch "
        "path (see _executor's memoised knob accessors)."
    ),
    "trace-time-call": (
        "No time.* / random.* / np.random.* calls inside traced bodies: "
        "trace-time wall-clock or host randomness bakes one value into the "
        "cached program. Use jax.random with explicit keys for traced "
        "randomness; host timing belongs around the dispatch, not in it."
    ),
    "trace-telemetry-unguarded": (
        "diagnostics/profiler record calls inside traced bodies must be "
        "gated on the subsystem switch (if diagnostics._enabled: ...). "
        "Ungated, they run per TRACE (surprising counts) and break the "
        "zero-cost-when-disabled contract every telemetry module documents."
    ),
    "trace-global-write": (
        "No mutable-global writes inside traced bodies: the write happens at "
        "trace time only, so replays never repeat it — state silently "
        "diverges between the first call and every later one."
    ),
    "trace-lazy-import": (
        "No import statements inside traced bodies: lazy package imports at "
        "trace time run module init under jit and make the first trace "
        "behave differently from a warm process."
    ),
    "lock-unlocked-write": (
        "State classified locked-exact by its module's thread-safety policy "
        "(the diagnostics.py docstring pattern, transcribed into "
        "rules_locks.LOCK_POLICY) must only be written under `with <lock>`. "
        "Functions named *_locked are called with the lock held (documented "
        "convention); __init__ construction is exempt. Relaxed state is "
        "listed per module and exempt by name."
    ),
    "lock-racing-increment": (
        "`+=` on shared module-level state outside any lock is a racing "
        "read-modify-write — the exact undercount bug the executor's _stats "
        "per-thread cells (the sanctioned exemption) were built to kill. "
        "Route increments through a per-thread cell or take the owning lock."
    ),
    "lock-order-cycle": (
        "The cross-module lock-acquisition graph (edge A->B when code "
        "holding A acquires B) must stay acyclic, or two threads can "
        "deadlock. The committed graph lives at "
        "doc/source/_static/lock_graph.json (regenerate with "
        "--dump-lockgraph); scheduler-sharding work must keep it a DAG."
    ),
    "import-nonstdlib": (
        "diagnostics/profiler/resilience/_scheduler/telemetry (and "
        "heat_tpu.analysis itself) import only the stdlib at module level, "
        "so jax-free tooling can load them by file path. Heavy imports "
        "belong inside functions. "
        "tests/test_analysis.py proves the same contract dynamically."
    ),
    "import-backend-touch": (
        "A process can join a jax.distributed job only while it has no XLA "
        "backend. heat_tpu.core therefore calls _bootstrap.run() before it "
        "imports anything else, and the modules that load before that call "
        "(_bootstrap and what it imports at module level) make no "
        "module-level call of jax.devices / default_backend / local_devices "
        "/ device_count / process_count / process_index / "
        "distributed.initialize. One early import broke every multi-process "
        "launch from PR 21 to PR 28. tests/test_analysis.py imports the "
        "package with the backend factory patched to prove the same order."
    ),
    "silent-except": (
        "except Exception without re-raise or a diagnostics.record_fallback/"
        "record_resilience_event/fallback_after_failure call swallows "
        "failures invisibly — the pre-PR-5 bug class. Narrow the handler to "
        "the expected types, account the fallback, or pragma with a reason."
    ),
    "donation-uncontracted": (
        "donate_argnums outside _executor.py bypasses the sanitation "
        "refcount contracts (sanitize_donation / sanitize_leaf_donation) "
        "that prove no live reader holds the buffer being invalidated."
    ),
    "collective-uncontracted": (
        "Direct jax.lax collectives outside communication.py are invisible "
        "to ht.diagnostics (the per-collective telemetry contract) and "
        "ht.resilience/_guarded. Call the MeshCommunication method instead."
    ),
    "coord-unbounded-wait": (
        "A raw jax.distributed coordination wait (blocking_key_value_get / "
        "wait_at_barrier) outside the supervision wrapper, or one without a "
        "bounded timeout inside it: an unbounded coordination block is "
        "exactly the hang the supervision plane (ISSUE 14) eliminates. "
        "Route the wait through supervision.kv_wait/kv_barrier — bounded by "
        "HEAT_TPU_COORD_TIMEOUT_MS, sentinel-abortable mid-wait, and typed "
        "(resilience.CoordinationTimeout names the key and the ranks that "
        "never arrived; a detected peer death raises PeerFailed instead of "
        "waiting out the budget)."
    ),
    "pragma-no-reason": (
        "Every suppression pragma must carry `-- reason`: suppressions "
        "without recorded justification are how grandfathered bugs hide."
    ),
    "pragma-unknown-rule": (
        "The pragma names a rule id the checker does not know — it would "
        "never match anything and gives false confidence."
    ),
    "pragma-unused": (
        "The pragma suppresses nothing on its line. Dead pragmas silently "
        "grandfather FUTURE violations; remove them as soon as the finding "
        "they covered is fixed."
    ),
    "baseline-stale": (
        "A baseline entry matched no current finding: the offending code was "
        "fixed. Delete the entry (python -m heat_tpu.analysis "
        "--write-baseline) so the grandfathered set only ever shrinks."
    ),
}

RULE_RUNNERS = [
    rules_purity.run,
    rules_locks.run_discipline,
    rules_locks.run_lock_order,
    rules_imports.run,
    rules_fallbacks.run,
    rules_donation.run,
    rules_spmd.run,
    rules_layout.run,
    rules_coord.run,
]


def explain(rule: str) -> str:
    doc = RULES.get(rule)
    if doc is None:
        known = ", ".join(sorted(RULES))
        return f"unknown rule {rule!r}; known rules: {known}"
    return f"{rule}\n{'=' * len(rule)}\n{doc}"
