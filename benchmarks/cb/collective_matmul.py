"""Communication-optimal linalg gate: ring collective matmul vs the gathered
baseline, reduce-scatter contractions, and the all_to_all resplit (ISSUE 20).

Measures, on a hermetic virtual CPU mesh (3 and 8 devices in CI — run once per
count), the comm planner in ``heat_tpu/core/linalg/comm_plan.py``:

- **bytes** — the planner's modeled wire-byte counters
  (``linalg.bytes.ring`` / ``linalg.bytes.gather_baseline`` /
  ``linalg.bytes.resplit*``; see doc/source/performance.rst for the bytes
  math). ``--check`` enforces the acceptance bounds: ring ≤ 0.6× the
  gather-both baseline for both-operands-split square matmuls, all_to_all
  resplit ≤ (2/P)× the gather path.
- **memory** — ``compiled.memory_analysis()`` of the ring program: per-device
  arguments are true 1/P shards and temps stay ≤ output-shard + ~2 panels —
  the gathered operand is never materialised (the XLA-default program on the
  same operands is measured for contrast: its temp holds the full gathered
  operand).
- **parity** — the ring plan must match the XLA-default plan bit-for-bit on
  integer-valued float data (exactly representable partial products).
- **wall time** — steady-state GFLOP/s of the ring and XLA plans and resplit
  GB/s, gated against the committed lower-envelope baseline
  (``collective_matmul_baseline.json``) under ``--baseline``.

Standalone (bootstraps a virtual CPU mesh, the conftest pattern):

    python benchmarks/cb/collective_matmul.py --devices 8 --check \
        [--baseline benchmarks/cb/collective_matmul_baseline.json]

Also registered with the cb monitor for ``benchmarks/cb/main.py`` runs.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

N = 384  # divisible by 3 and 8: even shards keep the memory assertions exact
RESPLIT_N = 1536


def _bootstrap(devices: int) -> None:
    """Re-exec into a hermetic virtual CPU mesh of ``devices`` devices (the
    dispatch.py pattern: the flag must be set before the backend initialises)."""
    if os.environ.get("_HEAT_TPU_CMM_BENCH_REEXEC") == "1":
        return
    env = dict(os.environ)
    env["_HEAT_TPU_CMM_BENCH_REEXEC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    for knob in (
        "HEAT_TPU_METRICS",
        "HEAT_TPU_TRACE",
        "HEAT_TPU_DIAG_DUMP",
        "HEAT_TPU_EAGER_DISPATCH",
        "HEAT_TPU_JIT_THRESHOLD",   # warm-up thresholds would time the eager
        "HEAT_TPU_LINALG_PLAN",     # fallback while labelling it by plan
        "HEAT_TPU_SCHED_SHARDS",
        "HEAT_TPU_BATCH_WINDOW_US",
        "HEAT_TPU_EXEC_CACHE",
        "HEAT_TPU_FORENSICS",
        "HEAT_TPU_FORENSICS_RING",
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


def _set_plan(ht, value) -> None:
    if value is None:
        os.environ.pop("HEAT_TPU_LINALG_PLAN", None)
    else:
        os.environ["HEAT_TPU_LINALG_PLAN"] = value
    ht.reload_env_knobs()


def _counters(diagnostics) -> dict:
    return diagnostics.report().get("counters", {})


def _time_best(fn, sync, repeats: int = 5) -> float:
    sync(fn())  # compile + warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        sync(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def run(
    check: bool = False,
    emit=print,
    baseline: dict = None,
    baseline_tol: float = 0.25,
) -> list:
    """One record per metric; under ``--check`` the byte/memory/parity bounds
    are hard gates and ``--baseline`` adds the wall-time lower-envelope gate
    (``str(devices) -> {case: value}``, fail below ``(1 - tol) ×`` base)."""
    import numpy as np
    import jax

    import heat_tpu as ht
    from heat_tpu.core import diagnostics
    from heat_tpu.core.communication import get_comm
    from heat_tpu.core.linalg import comm_plan

    comm = get_comm()
    P = comm.size
    ndev = len(jax.devices())
    base_cases = (baseline or {}).get(str(ndev), {})
    if baseline is not None and not base_cases:
        emit(json.dumps({
            "warning": f"baseline has no entry for {ndev} devices; "
            "the wall-time gate is not being enforced on this run"
        }))
    records = []
    failed = []

    def gate(ok: bool, message: str) -> None:
        if not ok:
            failed.append(message)
            emit(json.dumps({"error": message}))

    rng = np.random.default_rng(20)
    A = rng.integers(-8, 9, size=(N, N)).astype(np.float32)
    B = rng.integers(-8, 9, size=(N, N)).astype(np.float32)

    def rec(metric, value, unit, **extra):
        r = {"metric": f"collective_matmul_{metric}", "value": value,
             "unit": unit, "devices": ndev}
        r.update(extra)
        records.append(r)
        emit(json.dumps(r))
        return r

    # ---- bit parity: ring vs the XLA-default plan, integer-valued data ----
    _set_plan(ht, "ring")
    ring_out = np.asarray(ht.matmul(ht.array(A, split=0), ht.array(B, split=0)).larray)
    _set_plan(ht, "xla")
    xla_out = np.asarray(ht.matmul(ht.array(A, split=0), ht.array(B, split=0)).larray)
    parity = bool(np.array_equal(ring_out, xla_out))
    rec("ring_bit_parity", int(parity), "bool")
    gate(parity, "ring plan diverged bitwise from the XLA-default plan")

    # ---- modeled wire bytes: ring vs the gather-both baseline ----
    _set_plan(ht, None)  # auto picks ring for both-operands-split
    ht.clear_executor_cache()
    diagnostics.reset()
    diagnostics.enable()
    try:
        ht.matmul(ht.array(A, split=0), ht.array(B, split=0)).parray
        counters = _counters(diagnostics)
    finally:
        diagnostics.disable()
    ring_bytes = counters.get("linalg.bytes.ring", 0)
    base_bytes = counters.get("linalg.bytes.gather_baseline", 0)
    ratio = ring_bytes / base_bytes if base_bytes else float("inf")
    rec("ring_bytes_ratio", round(ratio, 4), "ratio",
        ring_bytes=ring_bytes, gather_baseline_bytes=base_bytes)
    gate(counters.get("linalg.plan.ring", 0) >= 1,
         "auto did not pick the ring plan for a both-operands-split matmul")
    gate(ratio <= 0.6,
         f"ring moved {ratio:.3f}x the gathered baseline's bytes (bound: 0.6x)")

    # ---- modeled wire bytes: all_to_all resplit vs the gather path ----
    X = rng.standard_normal((RESPLIT_N, RESPLIT_N)).astype(np.float32)
    ht.clear_executor_cache()
    diagnostics.reset()
    diagnostics.enable()
    try:
        ht.array(X, split=0).resplit(1).parray
        counters = _counters(diagnostics)
    finally:
        diagnostics.disable()
    a2a = counters.get("linalg.bytes.resplit", 0)
    gather = counters.get("linalg.bytes.resplit_gather_baseline", 0)
    ratio = a2a / gather if gather else float("inf")
    rec("resplit_bytes_ratio", round(ratio, 4), "ratio",
        all_to_all_bytes=a2a, gather_bytes=gather, bound=round(2.0 / P, 4))
    gate(counters.get("linalg.plan.resplit", 0) >= 1,
         "split->split resplit did not take the all_to_all program")
    gate(ratio <= 2.0 / P,
         f"resplit moved {ratio:.3f}x the gather path's bytes (bound: {2.0 / P:.3f}x)")

    # ---- compiled per-device memory: ring peak <= shard + ~2 panels ----
    a = ht.array(A, split=0)
    b = ht.array(B, split=0)
    body, out_split = comm_plan._ring_body("rA", comm, a.gshape, b.gshape, None)
    mem = (
        jax.jit(body, out_shardings=comm.sharding(2, out_split))
        .lower(a.parray, b.parray)
        .compile()
        .memory_analysis()
    )
    operand_bytes = N * N * 4
    shard_bytes = operand_bytes // P
    envelope = 3 * shard_bytes + 65536  # output shard + ~2 in-flight panels
    rec("ring_temp_bytes", int(mem.temp_size_in_bytes), "bytes",
        envelope=envelope, gathered_operand=operand_bytes)
    gate(mem.argument_size_in_bytes == 2 * shard_bytes,
         "ring program arguments are not true 1/P shards")
    gate(mem.temp_size_in_bytes <= envelope,
         f"ring temp {mem.temp_size_in_bytes} exceeds the shard+2-panel "
         f"envelope {envelope}")
    gate(mem.temp_size_in_bytes < operand_bytes,
         "ring temp reaches a full gathered operand")
    # contrast: the XLA-default program on the same operands gathers
    import jax.numpy as jnp

    sharding = comm.sharding(2, 0)
    xmem = (
        jax.jit(lambda x, y: jnp.matmul(x, y), out_shardings=sharding)
        .lower(a.parray, b.parray)
        .compile()
        .memory_analysis()
    )
    rec("xla_temp_bytes", int(xmem.temp_size_in_bytes), "bytes")

    # ---- wall time: steady-state plan throughput vs the lower envelope ----
    gflop = 2.0 * N * N * N / 1e9

    def mm():
        return ht.matmul(a, b).parray

    _set_plan(ht, "ring")
    t_ring = _time_best(mm, jax.block_until_ready)
    _set_plan(ht, "xla")
    t_xla = _time_best(mm, jax.block_until_ready)
    _set_plan(ht, None)
    x_src = ht.array(X, split=0)
    t_resplit = _time_best(lambda: x_src.resplit(1).parray, jax.block_until_ready)
    wall = {
        "ring_mm_gflops": round(gflop / t_ring, 2),
        "xla_mm_gflops": round(gflop / t_xla, 2),
        "resplit_gbps": round(RESPLIT_N * RESPLIT_N * 4 / t_resplit / 1e9, 3),
    }
    for case, value in wall.items():
        rec(case, value, case.rsplit("_", 1)[-1])
        base = base_cases.get(case)
        if base is None and base_cases:
            emit(json.dumps({
                "warning": f"baseline has no '{case}' entry at {ndev} devices; "
                "case not gated"
            }))
        elif base is not None:
            gate(value >= (1.0 - baseline_tol) * base,
                 f"{case}: {value} fell more than {baseline_tol:.0%} below "
                 f"the recorded lower-envelope baseline {base}")

    if (check or baseline) and failed:
        sys.exit(1)
    return records


try:  # registered for benchmarks/cb/main.py runs; standalone mode needs no monitor
    from benchmarks.cb.monitor import monitor

    @monitor("collective_matmul_ring")
    def collective_matmul_ring():
        import numpy as np

        import heat_tpu as ht

        os.environ["HEAT_TPU_LINALG_PLAN"] = "ring"
        ht.reload_env_knobs()
        try:
            A = np.ones((N, N), np.float32)
            return ht.matmul(ht.array(A, split=0), ht.array(A, split=0)).parray
        finally:
            os.environ.pop("HEAT_TPU_LINALG_PLAN", None)
            ht.reload_env_knobs()
except ImportError:  # pragma: no cover - standalone invocation without package path
    pass


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument(
        "--check",
        action="store_true",
        help="exit non-zero when a byte/memory/parity bound fails",
    )
    parser.add_argument(
        "--baseline",
        help="JSON file of recorded lower-envelope values "
        "({devices: {case: value}}); exit non-zero if a wall-time case falls "
        "more than --baseline-tol below it",
    )
    parser.add_argument(
        "--baseline-tol",
        type=float,
        default=float(os.environ.get("HEAT_TPU_CMM_BASELINE_TOL", "0.25")),
        help="allowed fractional regression vs --baseline (default 0.25)",
    )
    args = parser.parse_args()
    _bootstrap(args.devices)
    baseline = None
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
    run(check=args.check, baseline=baseline, baseline_tol=args.baseline_tol)
