"""End-to-end serving load harness: throughput and latency percentiles under
concurrency, gated against a committed lower envelope.

Drives the four workloads in ``workloads.py`` — plus the **mixed** scenario,
which interleaves all four request types through ONE shared worker pool
(deterministic rotation), surfacing cross-signature executor-cache and
dispatch-queue contention that the per-workload cases cannot; its records
carry a ``per_workload`` p50/p99 breakdown next to the aggregate — through
two load shapes:

- **closed loop** — ``--concurrency`` worker threads issue requests
  back-to-back; measures the system's sustainable throughput and the service
  latency at full utilisation. This is the gated mode.
- **open loop** — requests arrive on a Poisson schedule at an offered rate of
  ``--open-fraction`` × the measured closed-loop throughput, served by the
  same worker pool; latency is measured from the *scheduled arrival*, so
  queueing delay counts — the number a user behind a load balancer would see.

Every request runs inside ``ht.profiler.request(tag)``, so the emitted records
carry the profiler's log-bucketed latency-histogram snapshots (mergeable
offline across rounds/shards) next to the exact percentiles, and
``--trace-out`` dumps the whole run as a Chrome/Perfetto trace with one track
per request. Each record also attaches a ``scheduler`` block — the dispatch
queue's pressure over that load loop (``queue_full_events``,
``queue_depth_peak``, queued dispatches, and the lifecycle ledger's
shed/expired/cancelled deltas; the mixed scenario breaks the ledger down
``per_workload``) — so overload behaviour is visible in the bench trajectory.

Output is one BENCH-style JSON line per (workload, mode)::

    {"metric": "serving_kmeans_assign_closed_rps", "value": 41.2,
     "unit": "req/s", "p50_ms": ..., "p99_ms": ..., "latency_hist": {...},
     "profiler_schema": "heat-tpu-profiler/1", "devices": 8, ...}

``--check --baseline benchmarks/serving/serving_baseline.json`` gates the
closed-loop records: throughput must stay above ``min_rps`` and p50/p99 below
``max_p50_ms``/``max_p99_ms`` for the device count — a lower envelope recorded
well below the observed numbers (CI boxes are noisy; the gate catches
collapses, not jitter), the ``dispatch_baseline.json`` pattern one level up
the stack. A device count or workload with no baseline entry emits a VISIBLE
warning instead of silently not gating.

Standalone (always bootstraps a virtual CPU mesh; call :func:`run` in-process to
serve on the ambient backend, as ``chip_smoke.py`` does)::

    python benchmarks/serving/harness.py --devices 8 --smoke --check \\
        --baseline benchmarks/serving/serving_baseline.json \\
        --trace-out serving-trace.json --diag-out serving-diag.json
"""

import itertools
import json
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

WARMUP_REQUESTS = 3


def _bootstrap(devices: int) -> None:
    """Re-exec into a hermetic virtual CPU mesh of ``devices`` devices (see
    benchmarks/cb/dispatch.py)."""
    if os.environ.get("_HEAT_TPU_SERVING_BENCH_REEXEC") == "1":
        return
    env = dict(os.environ)
    env["_HEAT_TPU_SERVING_BENCH_REEXEC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    # the harness measures the metrics-off framework with only the profiler on;
    # scrub ambient knobs a debugging session may have exported
    for knob in (
        "HEAT_TPU_METRICS",
        "HEAT_TPU_TRACE",
        "HEAT_TPU_DIAG_DUMP",
        "HEAT_TPU_EAGER_DISPATCH",
        "HEAT_TPU_JIT_THRESHOLD",
        "HEAT_TPU_PROFILE",
        "HEAT_TPU_PROFILE_TRACE",
        "HEAT_TPU_ASYNC_DISPATCH",
        "HEAT_TPU_DISPATCH_QUEUE",
        "HEAT_TPU_BATCH_MAX",
        "HEAT_TPU_SHED",
        "HEAT_TPU_SCHED_SHARDS",
        "HEAT_TPU_BATCH_WINDOW_US",
        "HEAT_TPU_EXEC_CACHE",
        "HEAT_TPU_RESULT_CACHE",
        "HEAT_TPU_RESULT_CACHE_BYTES",
        "HEAT_TPU_FORENSICS",  # the baseline measures the forensics-OFF path
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


def _percentile_ms(latencies, q: float) -> float:
    """Exact nearest-rank percentile of a latency list, in milliseconds."""
    ordered = sorted(latencies)
    idx = min(len(ordered) - 1, max(0, round(q * (len(ordered) - 1))))
    return ordered[idx] * 1e3


def _load_loop(profiler, pick, n_requests: int, concurrency: int,
               arrivals=None):
    """``concurrency`` worker threads drain ``n_requests``. ``pick(i)`` names
    request ``i``'s work as ``(fn, tag)`` — a single workload for the
    per-workload cases, a deterministic rotation over all four for the mixed
    scenario (ONE shared pool, interleaved request types). With ``arrivals``
    None this is the closed loop: requests issue back-to-back and latency is
    bare service time. With ``arrivals`` (a list of start offsets in seconds)
    it is the open loop: each request waits for its scheduled arrival and
    latency counts FROM that arrival, so queueing delay when all workers are
    busy is part of the number (an M/?/c queue's response time, not its bare
    service time). Returns (per-request ``(tag, latency_s)`` pairs, wall
    seconds)."""
    counter = itertools.count()
    lat_lists = [[] for _ in range(concurrency)]
    errors = []
    start = time.perf_counter()

    def worker(slot: int) -> None:
        while True:
            i = next(counter)
            if i >= n_requests:
                return
            fn, tag = pick(i)
            if arrivals is None:
                t0 = time.perf_counter()
            else:
                t0 = start + arrivals[i]
                now = time.perf_counter()
                if now < t0:
                    time.sleep(t0 - now)
            try:
                with profiler.request(tag):
                    fn(i)
            except Exception as exc:  # a failed request fails the whole case
                errors.append(exc)
                return
            lat_lists[slot].append((tag, time.perf_counter() - t0))

    threads = [
        threading.Thread(target=worker, args=(s,), daemon=True)
        for s in range(concurrency)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - start
    if errors:
        raise errors[0]
    return [pair for lats in lat_lists for pair in lats], wall


def _poisson_arrivals(n_requests: int, rate_rps: float, seed: int = 0):
    rng = random.Random(seed)
    arrivals, t = [], 0.0
    for _ in range(n_requests):
        t += rng.expovariate(rate_rps)
        arrivals.append(t)
    return arrivals


def _zipf_identities(n_requests: int, n_identities: int, alpha: float = 1.1,
                     seed: int = 0):
    """Zipf-distributed request identities: request ``i`` re-issues staged
    input slot ``out[i]`` (0..n_identities-1), with rank ``r`` weighted
    ``1/r**alpha`` — the production traffic shape where a few hot inputs
    dominate (exactly what a cross-request result cache exploits) while the
    tail keeps forcing real recomputes.  Deterministic per seed so the cache
    arm and the recompute arm of a gate replay the IDENTICAL identity
    sequence."""
    weights = [1.0 / (r ** alpha) for r in range(1, n_identities + 1)]
    rng = random.Random(seed)
    # shuffle rank->slot so the hot slot isn't always slot 0 across seeds
    slots = list(range(n_identities))
    rng.shuffle(slots)
    return [slots[rng.choices(range(n_identities), weights)[0]]
            for _ in range(n_requests)]


def _zipf_replay(n_requests: int, rate_rps: float, seed: int = 0,
                 burst_every: int = 16, burst_len: int = 4):
    """Arrival schedule for the Zipf traffic-replay gate: a Poisson base
    process at ``rate_rps`` with a short near-simultaneous burst injected
    every ``burst_every`` requests (``burst_len`` arrivals squeezed into the
    same instant) — the replayed-traffic shape where cached hot entries pay
    off hardest and queueing under miss storms is visible.  Monotonic
    non-decreasing offsets, deterministic per seed; mean offered rate stays
    ``rate_rps`` because burst arrivals borrow their gaps from the base
    process rather than adding requests."""
    rng = random.Random(seed)
    arrivals, t = [], 0.0
    i = 0
    while i < n_requests:
        if burst_every and i and i % burst_every == 0:
            # the burst's arrivals land together at the END of the window the
            # base process would have spread them over, keeping the mean rate
            burst = min(burst_len, n_requests - i)
            t += sum(rng.expovariate(rate_rps) for _ in range(burst))
            arrivals.extend([t] * burst)
            i += burst
        else:
            t += rng.expovariate(rate_rps)
            arrivals.append(t)
            i += 1
    return arrivals[:n_requests]


def _record(name: str, mode: str, latencies, wall: float, ndev: int,
            concurrency: int, hist_snapshot, offered_rps=None) -> dict:
    from heat_tpu.core import profiler

    rec = {
        "metric": f"serving_{name}_{mode}_rps",
        "value": round(len(latencies) / wall, 2),
        "unit": "req/s",
        "workload": name,
        "mode": mode,
        "devices": ndev,
        "concurrency": concurrency,
        "requests": len(latencies),
        "p50_ms": round(_percentile_ms(latencies, 0.50), 3),
        "p95_ms": round(_percentile_ms(latencies, 0.95), 3),
        "p99_ms": round(_percentile_ms(latencies, 0.99), 3),
        "max_ms": round(max(latencies) * 1e3, 3),
        "latency_hist": hist_snapshot,
        "profiler_schema": profiler.SCHEMA,
    }
    if offered_rps is not None:
        rec["offered_rps"] = round(offered_rps, 2)
    return rec


def _gate_closed(rec: dict, envelope, emit) -> bool:
    """Apply the lower-envelope gate to one closed-loop record. Returns True
    on failure. ``envelope`` None → visible warning, not a silent pass."""
    name = rec["workload"]
    if envelope is None:
        emit(json.dumps({
            "warning": f"baseline has no '{name}' entry at {rec['devices']} "
            "devices; serving SLO not gated for this case"
        }))
        return False
    failed = False
    min_rps = envelope.get("min_rps")
    if min_rps is not None and rec["value"] < min_rps:
        failed = True
        emit(json.dumps({
            "error": f"{name}: {rec['value']} req/s below the baseline "
            f"lower envelope {min_rps} req/s"
        }))
    for pkey, ekey in (("p50_ms", "max_p50_ms"), ("p99_ms", "max_p99_ms")):
        bound = envelope.get(ekey)
        if bound is not None and rec[pkey] > bound:
            failed = True
            emit(json.dumps({
                "error": f"{name}: {pkey} {rec[pkey]} ms above the baseline "
                f"envelope {bound} ms"
            }))
    return failed


def _sched_snapshot() -> dict:
    """The executor-stats fields that describe scheduler pressure (cumulative
    since process start; records attach per-case deltas)."""
    import heat_tpu as ht

    s = ht.executor_stats()
    return {
        "queue_full_events": s["queue_full_events"],
        "queue_depth_peak": s["queue_depth_peak"],
        "queued_dispatches": s["queued_dispatches"],
        "drain_rejects": s["drain_rejects"],
        "shed": s["shed_requests"],
        "expired": s["expired_requests"],
        "cancelled": s["cancelled_requests"],
        "by_tenant": s["lifecycle_by_tenant"],
    }


def _sched_pressure(before: dict, after: dict, tags=None) -> dict:
    """Scheduler-pressure delta for one load loop, attached to its record so
    overload behaviour (queue-full backpressure, shed/cancel/expiry) is
    visible in the bench trajectory. ``queue_depth_peak`` is
    a process-lifetime high-water mark, not a delta. ``tags`` (the mixed
    scenario's request tags) adds a per-workload breakdown keyed by the
    middle tag component."""
    out = {
        k: after[k] - before[k]
        for k in ("queue_full_events", "queued_dispatches", "drain_rejects",
                  "shed", "expired", "cancelled")
    }
    out["queue_depth_peak"] = after["queue_depth_peak"]
    if tags:
        per = {}
        for tag in tags:
            b = before["by_tenant"].get(tag, {})
            a = after["by_tenant"].get(tag, {})
            delta = {
                "shed": a.get("shed", 0) - b.get("shed", 0),
                "expired": (a.get("deadline_expired", 0)
                            - b.get("deadline_expired", 0)),
                "cancelled": a.get("cancelled", 0) - b.get("cancelled", 0),
            }
            parts = tag.split(".")
            name = parts[1] if len(parts) == 3 else parts[0]
            agg = per.setdefault(name, {"shed": 0, "expired": 0, "cancelled": 0})
            for k, v in delta.items():
                agg[k] += v
        out["per_workload"] = per
    return out


def _merged_hist(profiler, tags):
    """Fold the per-tag request histograms into one snapshot (the mixed
    scenario's aggregate) using the histogram's exact bucket-count merge."""
    snaps = profiler.histogram_snapshots()
    merged = None
    for tag in tags:
        snap = snaps.get(f"request.{tag}")
        if snap is None:
            continue
        h = profiler.Histogram.from_snapshot(snap)
        merged = h if merged is None else merged.merge(h)
    return merged.snapshot() if merged is not None else None


def _per_workload_ms(pairs) -> dict:
    """Per-request-type latency breakdown of a mixed run: ``{workload:
    {requests, p50_ms, p99_ms}}``. Mixed tags are ``mixed.<workload>.<mode>``;
    the middle component names the request type."""
    by_type = {}
    for tag, lat in pairs:
        parts = tag.split(".")
        name = parts[1] if len(parts) == 3 else parts[0]
        by_type.setdefault(name, []).append(lat)
    return {
        name: {
            "requests": len(lats),
            "p50_ms": round(_percentile_ms(lats, 0.50), 3),
            "p99_ms": round(_percentile_ms(lats, 0.99), 3),
        }
        for name, lats in sorted(by_type.items())
    }


MIXED = "mixed"


def run(
    smoke: bool = True,
    requests: int = 32,
    concurrency: int = 4,
    open_fraction: float = 0.6,
    which=None,
    check: bool = False,
    baseline: dict = None,
    trace_out: str = None,
    diag_out: str = None,
    telemetry_out: str = None,
    open_rps: dict = None,
    forensics: bool = False,
    emit=print,
):
    """Run the suite; returns ``(records, failed)`` — one record per
    (workload, mode) plus the ``mixed`` interleaved scenario, and whether any
    closed-loop record broke its envelope under ``check``/``baseline``
    (``{str(devices): {workload: envelope}}``). ``open_rps`` pins a
    workload's open-loop offered rate (``{workload: rps}``) instead of
    deriving it from this run's closed-loop throughput — the async-executor
    gate uses this to drive both executor modes at the SAME offered rate.
    The CLI turns ``failed`` into a non-zero exit; in-process callers get the
    gate verdict as a value instead of a ``SystemExit``."""
    import jax

    from heat_tpu.core import diagnostics, profiler, telemetry
    from heat_tpu.core import forensics as _forensics
    from benchmarks.serving.workloads import build_workloads

    ndev = len(jax.devices())
    base_cases = (baseline or {}).get(str(ndev), {})
    open_rps = open_rps or {}
    if baseline is not None and not base_cases:
        emit(json.dumps({
            "warning": f"baseline has no entry for {ndev} devices; "
            "the serving SLO gate is not being enforced on this run"
        }))

    was_active = profiler.active()
    profiler.enable()
    was_collecting = telemetry.collecting()
    if telemetry_out:
        telemetry.enable()  # the shard should carry collective windows too
    # the bootstrap scrubs HEAT_TPU_FORENSICS from the re-exec env (baselines
    # measure the forensics-OFF path), so arming the request-forensics plane
    # for a run is an explicit flag, never ambient
    was_armed = _forensics.armed()
    if forensics:
        _forensics.arm()
    records, failed = [], False

    def suffixed(pick, mode):
        def p(i):
            fn, tag = pick(i)
            return fn, f"{tag}.{mode}"

        return p

    def one_case(name, pick, tags):
        nonlocal failed
        tag_closed = [f"{t}.closed" for t in tags]
        sched_before = _sched_snapshot()
        pairs, wall = _load_loop(
            profiler, suffixed(pick, "closed"), requests, concurrency,
        )
        lats = [lat for _, lat in pairs]
        hist = _merged_hist(profiler, tag_closed)
        rec = _record(name, "closed", lats, wall, ndev, concurrency, hist)
        rec["scheduler"] = _sched_pressure(
            sched_before, _sched_snapshot(),
            tags=tag_closed if len(tags) > 1 else None,
        )
        if len(tags) > 1:
            rec["per_workload"] = _per_workload_ms(pairs)
        records.append(rec)
        emit(json.dumps(rec))
        if check or baseline:
            failed |= _gate_closed(rec, base_cases.get(name), emit)

        closed_rps = rec["value"]
        offered = open_rps.get(name) or max(0.5, open_fraction * closed_rps)
        n_open = max(8, (2 * requests) // 3)
        tag_open = [f"{t}.open" for t in tags]
        sched_before = _sched_snapshot()
        pairs, wall = _load_loop(
            profiler, suffixed(pick, "open"), n_open, concurrency,
            arrivals=_poisson_arrivals(n_open, offered),
        )
        lats = [lat for _, lat in pairs]
        hist = _merged_hist(profiler, tag_open)
        rec = _record(name, "open", lats, wall, ndev, concurrency, hist,
                      offered_rps=offered)
        rec["scheduler"] = _sched_pressure(
            sched_before, _sched_snapshot(),
            tags=tag_open if len(tags) > 1 else None,
        )
        if len(tags) > 1:
            rec["per_workload"] = _per_workload_ms(pairs)
        records.append(rec)
        emit(json.dumps(rec))

    try:
        names = list(which) if which else None
        run_mixed = names is None or MIXED in names
        explicit = [n for n in (names or []) if n != MIXED]
        # the mixed scenario interleaves ALL request types, so asking for it
        # builds the full zoo even when only a subset runs standalone cases
        build_names = None if (names is None or run_mixed) else explicit
        wls = build_workloads(smoke=smoke, which=build_names)
        for wl in wls:
            for i in range(WARMUP_REQUESTS):  # compile paths, uncounted
                wl.fn(i)
        for wl in wls:
            if names is not None and wl.name not in explicit:
                continue
            one_case(wl.name, lambda i, wl=wl: (wl.fn, wl.name), [wl.name])
        if run_mixed and len(wls) > 1:
            # the ROADMAP's interleaved scenario: all request types through
            # ONE shared worker pool, rotating deterministically so every
            # type's signatures contend in the same executor cache and queue
            def pick(i, wls=wls):
                wl = wls[i % len(wls)]
                return wl.fn, f"{MIXED}.{wl.name}"

            one_case(MIXED, pick, [f"{MIXED}.{wl.name}" for wl in wls])
        if trace_out:
            profiler.dump_trace(trace_out)
            emit(json.dumps({"artifact": "perfetto_trace", "path": trace_out}))
        if diag_out:
            diagnostics.dump(diag_out)
            emit(json.dumps({"artifact": "diagnostics_json", "path": diag_out}))
        if telemetry_out:
            # one self-describing telemetry shard for this (single-process)
            # run — the same artifact a multi-host deployment merges with
            # `python -m heat_tpu.telemetry merge`
            path = telemetry.dump_shard(telemetry_out)
            emit(json.dumps({"artifact": "telemetry_shard", "path": path}))
    finally:
        if not was_active:
            profiler.disable()
        if telemetry_out and not was_collecting:
            telemetry.disable()
        if forensics and not was_armed:
            _forensics.disarm()
    return records, failed


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--smoke", action="store_true",
                        help="CI shapes: tiny corpora, sub-minute suite")
    parser.add_argument("--requests", type=int, default=None,
                        help="closed-loop requests per workload "
                        "(default 32 smoke, 128 full)")
    parser.add_argument("--concurrency", type=int, default=4)
    parser.add_argument("--open-fraction", type=float, default=0.6,
                        help="open-loop offered rate as a fraction of the "
                        "measured closed-loop throughput")
    parser.add_argument("--workloads", nargs="*", default=None,
                        help="subset of workload names (default: all four)")
    parser.add_argument("--check", action="store_true",
                        help="exit non-zero when a closed-loop record breaks "
                        "its baseline envelope")
    parser.add_argument("--baseline",
                        help="JSON lower-envelope file "
                        "({devices: {workload: {min_rps, max_p50_ms, max_p99_ms}}})")
    parser.add_argument("--trace-out", help="dump the run's Perfetto trace here")
    parser.add_argument("--diag-out", help="dump the ht.diagnostics report here")
    parser.add_argument("--telemetry-out",
                        help="directory for this run's ht.telemetry shard "
                        "(mergeable via `python -m heat_tpu.telemetry merge`)")
    parser.add_argument("--forensics", action="store_true",
                        help="arm the request-forensics plane for this run "
                        "(the bootstrap scrubs HEAT_TPU_FORENSICS from the "
                        "re-exec env, so the opt-in is this flag); exemplars "
                        "ride the --telemetry-out shard and `python -m "
                        "heat_tpu.telemetry slow` renders them")
    args = parser.parse_args()
    _bootstrap(args.devices)
    baseline = None
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
    _, failed = run(
        smoke=args.smoke,
        requests=args.requests or (32 if args.smoke else 128),
        concurrency=args.concurrency,
        open_fraction=args.open_fraction,
        which=args.workloads,
        check=args.check,
        baseline=baseline,
        trace_out=args.trace_out,
        diag_out=args.diag_out,
        telemetry_out=args.telemetry_out,
        forensics=args.forensics,
    )
    if args.check and failed:
        sys.exit(1)
