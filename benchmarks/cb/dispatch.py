"""Dispatch-layer microbenchmark: ops/s for a 64-op elementwise chain and a
shared-subchain fan-out graph.

Measures the framework-level dispatch throughput of the signature-cached jit
executor (``heat_tpu/core/_executor.py``) against the fully eager path
(``HEAT_TPU_EAGER_DISPATCH=1``), on the layouts that exercise every epilogue:

- ``split0_even``   — split array, extent divisible by P (shard-constraint epilogue)
- ``split0_ragged`` — split array, ragged extent (pad re-mask + physical pad fuse)
- ``unsplit_even`` / ``unsplit_odd`` — replicated operands (no layout epilogue)
- ``fanout``        — diamond/fan-out graph: a 64-op transcendental shared
  subchain feeding 8 consumers plus a direct read (ISSUE 5). Exercises the
  multi-output force: the shared nodes must compile AND execute exactly once
  (``reexecuted_steady`` — gated at 0 under ``--check``), with every consumer
  riding one cached one-op program after warm-up. The recorded baseline locks
  the >=2x ops/s win over the pre-multi-output executor, which re-ran the
  shared subchain inside every consumer's program.

The chain is 16 cycles of ``x = x + y; x = x * 0.5; x = x - y; x = x + 1.0`` —
64 framework-level binary ops, 4 distinct cached programs, so the steady state is
pure signature-cache replay. Ops/s is the per-case framework-op count over
wall-clock around a ``block_until_ready`` sync; best of 5 (host-scheduler noise
on shared CPU boxes is one-sided, so more repeats converge on the true dispatch
ceiling — the baseline gate depends on that stability).

Standalone (bootstraps a virtual CPU mesh, the conftest pattern):

    python benchmarks/cb/dispatch.py --devices 8 [--check]

``--check`` exits non-zero when the executor path regresses to less than half the
eager path's ops/s on any case — the CI gate: the cache must never make dispatch
slower. ``--baseline benchmarks/cb/dispatch_baseline.json`` adds the
observability gate (ISSUE 4): with diagnostics disabled (the default here), each
case must stay within ``--baseline-tol`` (default 10%) of the recorded
pre-instrumentation ops/s — the zero-cost-when-off contract, enforced. Also
registered with the cb monitor for ``benchmarks/cb/main.py`` runs.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

CHAIN_CYCLES = 16  # 4 ops per cycle → 64-op chain
N_EVEN = 4096
N_RAGGED = 4093
# fanout: the shared subchain is transcendental-heavy (8 exp/tanh per cycle
# set) and the array big enough that re-executing the subchain per consumer
# (the pre-ISSUE-5 executor's behaviour) dominates the per-execution floor —
# the case measures redundant XLA *work*, not just execution counts. Cheap
# elementwise chains would NOT show the win: fused into a consumer kernel
# their re-execution hides inside the same memory pass.
N_FANOUT = 1 << 19  # 512k floats
FANOUT_CONSUMERS = 8
FANOUT_SHARED_CYCLES = 16  # 4 ops per cycle → 64 shared ops, half transcendental


def _bootstrap(devices: int) -> None:
    """Re-exec into a hermetic virtual CPU mesh of ``devices`` devices (the flag
    must be set before the backend initialises)."""
    if os.environ.get("_HEAT_TPU_DISPATCH_BENCH_REEXEC") == "1":
        return
    env = dict(os.environ)
    env["_HEAT_TPU_DISPATCH_BENCH_REEXEC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    # measure the diagnostics-OFF executor path (the gates' contract) even when
    # the ambient environment enables metrics/tracing for the driver run or has
    # the eager escape hatch exported from a debugging session
    for knob in (
        "HEAT_TPU_METRICS",
        "HEAT_TPU_TRACE",
        "HEAT_TPU_DIAG_DUMP",
        "HEAT_TPU_EAGER_DISPATCH",
        "HEAT_TPU_JIT_THRESHOLD",  # an ambient warm-up threshold would time
        # the eager fallback while labelling it "executor"
        "HEAT_TPU_SCHED_SHARDS",   # the bench measures the production
        "HEAT_TPU_BATCH_WINDOW_US",  # default scheduler shape
        "HEAT_TPU_EXEC_CACHE",     # artifact loads would mislabel compile_s
        "HEAT_TPU_FORENSICS",      # per-request lifecycle records would tax
        "HEAT_TPU_FORENSICS_RING",   # the measured dispatch path
        "HEAT_TPU_FORENSICS_EXEMPLARS",
    ):
        env.pop(knob, None)
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def _chain(ht, x, y):
    for _ in range(CHAIN_CYCLES):
        x = x + y
        x = x * 0.5
        x = x - y
        x = x + 1.0
    return x


def _fanout(ht, x, y):
    """Diamond/fan-out graph: a 64-op transcendental shared subchain, 8
    consumers forced one by one, and a direct read of the shared value. The
    multi-output executor materialises the shared chain exactly once (forcing
    the first consumer emits ``t`` as an extra output); every later consumer
    replays a cached one-op program over the memoised leaf. The pre-ISSUE-5
    executor re-executed all 64 shared ops inside every consumer's program."""
    t = x
    for _ in range(FANOUT_SHARED_CYCLES):
        t = ht.exp(t)        # first cycle: x ~ N(0,1) → (0, ~20)
        t = t + y
        t = ht.tanh(t)       # bounded (-1, 1) keeps every later cycle tame
        t = t * 0.5
    outs = [t * (1.0 + i) for i in range(FANOUT_CONSUMERS)]
    for o in outs:
        o.parray
    t.parray
    return outs[-1]


def _time_case(ht, jax, fn, x, y, repeats: int = 5) -> float:
    """Best-of-``repeats`` seconds for one case run (after a compile warmup)."""
    jax.block_until_ready(fn(ht, x, y).parray)  # compile + warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(ht, x, y)
        jax.block_until_ready(out.parray)
        best = min(best, time.perf_counter() - t0)
    return best


def _cases(ht, jax, jnp):
    chain_ops = 4 * CHAIN_CYCLES
    for name, fn, n_ops, n, split in (
        ("split0_even", _chain, chain_ops, N_EVEN, 0),
        ("split0_ragged", _chain, chain_ops, N_RAGGED, 0),
        ("unsplit_even", _chain, chain_ops, N_EVEN, None),
        ("unsplit_odd", _chain, chain_ops, N_RAGGED, None),
        ("fanout", _fanout, 4 * FANOUT_SHARED_CYCLES + FANOUT_CONSUMERS, N_FANOUT, 0),
    ):
        x = ht.array(
            jax.random.normal(jax.random.key(0), (n,), jnp.float32), split=split
        )
        y = ht.array(
            jax.random.normal(jax.random.key(1), (n,), jnp.float32) * 0.1, split=split
        )
        yield name, fn, n_ops, x, y


def run(
    check: bool = False,
    emit=print,
    baseline: dict = None,
    baseline_tol: float = 0.10,
) -> list:
    """Run all four layouts, executor vs eager; one JSON-able record per case.

    ``baseline`` maps ``str(devices) -> {case_name: ops_s}`` (the committed
    ``dispatch_baseline.json``): any case below ``(1 - baseline_tol) ×`` its
    recorded pre-diagnostics ops/s fails the run — instrumentation that is
    supposed to be free when disabled must prove it here."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core import _executor, diagnostics

    # the microbenchmark measures (and the baseline gate enforces) the
    # diagnostics-OFF dispatch path, whatever the ambient env says; restored on
    # exit so an in-process caller (the cb monitor) keeps its metrics
    was_enabled, was_tracing = diagnostics.enabled(), diagnostics.tracing()
    diagnostics.disable()
    ndev = len(jax.devices())
    base_cases = (baseline or {}).get(str(ndev), {})
    if baseline is not None and not base_cases:
        # a baseline that silently matches nothing is a gate that silently
        # checks nothing — make the coverage gap visible in the output
        emit(json.dumps({
            "warning": f"baseline has no entry for {ndev} devices; "
            "the zero-overhead gate is not being enforced on this run"
        }))
    records = []
    failed = False
    try:
        records, failed = _run_cases(
            ht, jax, jnp, _executor, ndev, base_cases,
            check, baseline_tol, emit,
        )
    finally:
        if was_enabled:
            diagnostics.enable(trace=was_tracing)
        else:
            diagnostics.disable(trace=was_tracing)  # tracing-only callers too
    if (check or baseline) and failed:
        sys.exit(1)
    return records


def _run_cases(ht, jax, jnp, _executor, ndev, base_cases, check, baseline_tol, emit):
    records = []
    failed = False
    for name, fn, n_ops, x, y in _cases(ht, jax, jnp):
        assert os.environ.get("HEAT_TPU_EAGER_DISPATCH") != "1"
        jax.block_until_ready(fn(ht, x, y).parray)  # compile, uncounted
        _executor.reset_executor_stats()  # so retraces_steady really is steady-state
        t_exec = _time_case(ht, jax, fn, x, y)
        stats = _executor.executor_stats()
        os.environ["HEAT_TPU_EAGER_DISPATCH"] = "1"
        _executor.reload_env_knobs()  # the knob is memoised: re-read for the eager arm
        try:
            t_eager = _time_case(ht, jax, fn, x, y)
        finally:
            del os.environ["HEAT_TPU_EAGER_DISPATCH"]
            _executor.reload_env_knobs()
        rec = {
            "metric": f"dispatch_chain{n_ops}_{name}_ops_s",
            "value": round(n_ops / t_exec, 1),
            "unit": "ops/s",
            "eager_ops_s": round(n_ops / t_eager, 1),
            "speedup": round(t_eager / t_exec, 2),
            "retraces_steady": stats["retraces"],
            # multi-output force contract: a shared subchain executes once —
            # steady-state re-executions must be zero on every case
            "reexecuted_steady": stats["reexecuted"],
            "devices": ndev,
        }
        records.append(rec)
        emit(json.dumps(rec))
        if check and rec["value"] < 0.5 * rec["eager_ops_s"]:
            failed = True
            emit(
                json.dumps(
                    {
                        "error": f"{name}: executor {rec['value']} ops/s is below "
                        f"half the eager path's {rec['eager_ops_s']} ops/s"
                    }
                )
            )
        if check and rec["reexecuted_steady"] != 0:
            failed = True
            emit(
                json.dumps(
                    {
                        "error": f"{name}: {rec['reexecuted_steady']} steady-state "
                        "re-executions of already-executed deferred nodes — the "
                        "multi-output force must memoise shared subchains"
                    }
                )
            )
        base = base_cases.get(name)
        if base is None and base_cases:
            emit(json.dumps({
                "warning": f"baseline has no '{name}' entry at {ndev} devices; "
                "case not gated"
            }))
        if base is not None and rec["value"] < (1.0 - baseline_tol) * base:
            failed = True
            emit(
                json.dumps(
                    {
                        "error": f"{name}: {rec['value']} ops/s with diagnostics "
                        f"disabled regressed more than {baseline_tol:.0%} below "
                        f"the recorded baseline {base} ops/s"
                    }
                )
            )
    return records, failed


try:  # registered for benchmarks/cb/main.py runs; standalone mode needs no monitor
    from benchmarks.cb.monitor import monitor

    @monitor("dispatch_chain64")
    def dispatch_chain64():
        import jax
        import jax.numpy as jnp

        import heat_tpu as ht

        name, x, y = next(iter(_cases(ht, jax, jnp)))
        return _chain(ht, x, y).parray
except ImportError:  # pragma: no cover - standalone invocation without package path
    pass


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument(
        "--check",
        action="store_true",
        help="exit non-zero if the executor is slower than half the eager path",
    )
    parser.add_argument(
        "--baseline",
        help="JSON file of recorded ops/s ({devices: {case: ops_s}}); exit "
        "non-zero if any case falls more than --baseline-tol below it "
        "(the diagnostics-disabled zero-overhead gate)",
    )
    parser.add_argument(
        "--baseline-tol",
        type=float,
        default=float(os.environ.get("HEAT_TPU_DISPATCH_BASELINE_TOL", "0.10")),
        help="allowed fractional regression vs --baseline (default 0.10)",
    )
    args = parser.parse_args()
    _bootstrap(args.devices)
    baseline = None
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
    run(check=args.check, baseline=baseline, baseline_tol=args.baseline_tol)
