"""Cold-start gate: a warm persistent compile cache + AOT warmup must make a
FRESH process's first request p99-clean (ISSUE 15 tentpole (2)).

Every restarted or newly added serving host used to pay full trace + XLA
compile for every signature on its first request — multi-second first-request
latency against millisecond steady-state, exactly the elastic-restart gap
PR 14 made routine.  This gate proves the persistent compile cache
(``HEAT_TPU_EXEC_CACHE``: signature fingerprints + serialized executables)
plus AOT warmup (``ht.executor_warmup``) close it, by booting REAL fresh
processes:

1. **record** — a throwaway process drives the executor-path workloads (the
   overload gate's ``chain_fused`` / ``staged_reduce`` request shapes: fused
   deferred chains + staged one-op programs — the signatures a serving host
   actually compiles), then ``executor_save_warmup`` records the manifest +
   artifacts into the cache dir (and ``JAX_COMPILATION_CACHE_DIR`` places
   JAX's own persistent cache there too).
2. **cold boot** — a fresh process with NO cache measures, per workload, its
   FIRST request's latency and then the steady-state p99 over the remaining
   requests.
3. **warm boot** — an identical fresh process with the cache armed runs
   ``ht.executor_warmup`` at boot (counted separately as ``warmup_s`` — it
   happens BEFORE the host would ``reopen()``), then measures the same.

Gate (``--check``): for EVERY workload the warm boot's first-request latency
must be ≤ ``FIRST_REQUEST_MULTIPLE`` (2x) its own steady-state p99 (with a
``FLOOR_MS`` absolute floor so millisecond workloads are not gated on timer
noise), AND the cold boot must demonstrably VIOLATE the same bound on at
least one workload in the same run — proving the bound measures cold-start
elimination, not a generously slow workload.  Results are recorded in
``serving_baseline.json``'s ``_coldstart_gate`` section for the trail.

CI also runs the cache-poisoning step: ``--poison`` truncates one cached
artifact mid-file before the warm boot — the boot must log a typed
``cache-corrupt`` rejection, recompile that signature, and STILL pass the
gate (corruption can slow a boot, never break one).

Standalone::

    python benchmarks/serving/coldstart_gate.py --devices 8 --smoke --check
"""

import json
import os
import shutil
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

from benchmarks.serving.harness import _bootstrap, _percentile_ms  # noqa: E402

#: warm first-request latency must be within this multiple of steady p99
FIRST_REQUEST_MULTIPLE = 2.0
#: absolute floor (ms): sub-millisecond steady states are not gated on noise
FLOOR_MS = 50.0
#: the gate's cache dir: one fixed path (JAX's cache keys include the
#: directory), wiped at the start of every run so "cold" stays cold
DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "out", "coldstart_cache"
)
#: steady-state sample count per workload (p99 over these)
STEADY_REQUESTS_SMOKE = 24
STEADY_REQUESTS_FULL = 64


def _workloads(smoke: bool):
    from benchmarks.serving.overload_gate import build_overload_workloads

    return build_overload_workloads(smoke=smoke)


def child_main(args) -> int:
    """One boot measurement (run in a FRESH subprocess): optionally warm up
    from the cache, then per workload measure the first request's latency
    and the steady-state p99. Emits one JSON line on stdout."""
    import heat_tpu as ht  # noqa: F401  (boot cost is part of what cold means)

    out = {"mode": args.mode, "warmup_s": None, "workloads": {}}
    if args.mode in ("record", "warm") and args.cache:
        os.environ.setdefault("HEAT_TPU_EXEC_CACHE", args.cache)
        ht.reload_env_knobs()
    if args.mode == "warm":
        t0 = time.perf_counter()
        stats = ht.executor_warmup(args.cache)
        out["warmup_s"] = round(time.perf_counter() - t0, 4)
        out["warmup"] = stats
        from heat_tpu.core import diagnostics

        with diagnostics._lock:
            out["cache_corrupt_events"] = sum(
                1 for e in diagnostics._resilience_events
                if e["kind"] == "cache-corrupt"
            )
    steady_n = STEADY_REQUESTS_SMOKE if args.smoke else STEADY_REQUESTS_FULL
    for name, fn in _workloads(args.smoke):
        t0 = time.perf_counter()
        fn(0)
        first_ms = (time.perf_counter() - t0) * 1e3
        lats = []
        for i in range(1, steady_n + 1):
            t0 = time.perf_counter()
            fn(i)
            lats.append(time.perf_counter() - t0)
        out["workloads"][name] = {
            "first_request_ms": round(first_ms, 3),
            "steady_p50_ms": round(_percentile_ms(lats, 0.50), 3),
            "steady_p99_ms": round(_percentile_ms(lats, 0.99), 3),
            "requests": steady_n + 1,
        }
    if args.mode == "record" and args.cache:
        out["saved"] = ht.executor_save_warmup(args.cache, top=32)
    print(json.dumps(out))
    return 0


def _spawn_child(mode, cache, smoke, devices, extra_env=None):
    """A FRESH interpreter (new XLA client, empty executor table): the only
    honest way to measure a boot."""
    env = dict(os.environ)
    env.pop("HEAT_TPU_EXEC_CACHE", None)
    # JAX's persistent cache is placed from outside: record/warm share one
    # dir (persisting every program — the CPU programs here compile in
    # milliseconds), the cold boot gets its own empty one
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "xla-cold")
    if mode in ("record", "warm"):
        env["HEAT_TPU_EXEC_CACHE"] = cache
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(cache, "xla")
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
        env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env.update(extra_env or {})
    cmd = [
        sys.executable, os.path.abspath(__file__), "--child", "--mode", mode,
        "--cache", cache, "--devices", str(devices),
    ]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=1200
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"coldstart {mode} child failed rc={proc.returncode}:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    line = proc.stdout.strip().splitlines()[-1]
    return json.loads(line), proc.stderr


def _poison_one_blob(cache) -> str:
    blob_dir = os.path.join(cache, "blobs")
    blobs = sorted(os.listdir(blob_dir)) if os.path.isdir(blob_dir) else []
    if not blobs:
        raise RuntimeError("cache-poisoning step: no artifacts to poison")
    path = os.path.join(blob_dir, blobs[0])
    data = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(data[: max(1, len(data) // 2)])  # truncate mid-file
    return path


def evaluate(cold, warm, emit=print):
    """Score one cold/warm boot pair; returns ``(records, failed)``.  Pure
    record math so tests can drive it with canned boots."""
    records, failed = [], False
    warm_ok_all = True
    cold_violates_any = False
    for name in sorted(warm["workloads"]):
        w = warm["workloads"][name]
        c = cold["workloads"].get(name)
        bound_ms = max(
            FIRST_REQUEST_MULTIPLE * w["steady_p99_ms"], FLOOR_MS
        )
        warm_ok = w["first_request_ms"] <= bound_ms
        rec = {
            "metric": f"serving_coldstart_{name}",
            "workload": name,
            "warm_first_request_ms": w["first_request_ms"],
            "warm_steady_p99_ms": w["steady_p99_ms"],
            "warm_bound_ms": round(bound_ms, 3),
            "warm_ok": warm_ok,
        }
        if c is not None:
            cold_bound_ms = max(
                FIRST_REQUEST_MULTIPLE * c["steady_p99_ms"], FLOOR_MS
            )
            rec["cold_first_request_ms"] = c["first_request_ms"]
            rec["cold_steady_p99_ms"] = c["steady_p99_ms"]
            rec["cold_violates"] = c["first_request_ms"] > cold_bound_ms
            cold_violates_any = cold_violates_any or rec["cold_violates"]
        records.append(rec)
        emit(json.dumps(rec))
        if not warm_ok:
            warm_ok_all = False
            emit(json.dumps({
                "error": f"{name}: warm-boot first request "
                f"{w['first_request_ms']:.1f} ms exceeds "
                f"{FIRST_REQUEST_MULTIPLE}x steady p99 "
                f"({bound_ms:.1f} ms): cold start NOT eliminated"
            }))
    if not cold_violates_any:
        failed = True
        emit(json.dumps({
            "error": "cold boot never violated the first-request bound: the "
            "gate is not measuring cold-start elimination on this "
            "workload/host combination"
        }))
    if not warm_ok_all:
        failed = True
    summary = {
        "metric": "serving_coldstart_summary",
        "warmup_s": warm.get("warmup_s"),
        "warmup": warm.get("warmup"),
        "warm_ok_all": warm_ok_all,
        "cold_violates_any": cold_violates_any,
        "first_request_multiple": FIRST_REQUEST_MULTIPLE,
    }
    records.append(summary)
    emit(json.dumps(summary))
    return records, failed


def run_gate(devices, smoke=True, poison=False, cache=None, emit=print):
    if cache is None:
        cache = os.path.abspath(DEFAULT_CACHE)
        shutil.rmtree(cache, ignore_errors=True)
    os.makedirs(cache, exist_ok=True)
    emit(json.dumps({"info": "coldstart gate: recording warm signatures",
                     "cache": cache}))
    recorded, _ = _spawn_child("record", cache, smoke, devices)
    emit(json.dumps({"info": "recorded", "saved": recorded.get("saved")}))
    cold, _ = _spawn_child("cold", cache, smoke, devices)
    if poison:
        path = _poison_one_blob(cache)
        emit(json.dumps({"info": "cache-poisoning step: truncated artifact",
                         "blob": os.path.basename(path)}))
    warm, warm_err = _spawn_child("warm", cache, smoke, devices)
    records, failed = evaluate(cold, warm, emit=emit)
    if poison:
        # the poisoned boot must have REJECTED the artifact typed (a
        # cache-corrupt event on the always-on resilience stream, a
        # recompile covering the signature) and still passed the gate above
        corrupt_events = warm.get("cache_corrupt_events", 0)
        saved_arts = (recorded.get("saved") or {}).get("artifacts", 0)
        poison_rec = {
            "metric": "serving_coldstart_poison",
            "artifacts_recorded": saved_arts,
            "aot_loaded_after_poison": (warm.get("warmup") or {}).get(
                "aot_loaded", 0),
            "cache_corrupt_events": corrupt_events,
            "warmup_failed": (warm.get("warmup") or {}).get("failed", 0),
        }
        records.append(poison_rec)
        emit(json.dumps(poison_rec))
        if saved_arts > 0 and corrupt_events < 1:
            failed = True
            emit(json.dumps({
                "error": "poisoned artifact produced no typed cache-corrupt "
                "rejection: the content-address verification is not "
                "catching corruption"
            }))
        if (warm.get("warmup") or {}).get("failed", 0):
            failed = True
            emit(json.dumps({
                "error": "warmup FAILED on a poisoned artifact instead of "
                "recompiling: corruption must never break a boot"
            }))
    return records, failed


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--check", action="store_true")
    parser.add_argument("--poison", action="store_true",
                        help="truncate one cached artifact before the warm "
                        "boot (the CI cache-poisoning step)")
    parser.add_argument("--cache", default=None,
                        help="cache dir (default: benchmarks/out/"
                        "coldstart_cache, wiped first)")
    parser.add_argument("--child", action="store_true")
    parser.add_argument("--mode", choices=("record", "cold", "warm"),
                        default="cold")
    args = parser.parse_args(argv)
    if args.child:
        return child_main(args)
    _bootstrap(args.devices)
    _, failed = run_gate(args.devices, smoke=args.smoke, poison=args.poison,
                         cache=args.cache)
    if failed and args.check:
        # one retry with a fresh cache: first-boot latencies on a shared CI
        # box can hiccup; only failing BOTH fresh runs is a red gate
        print(json.dumps({"info": "coldstart gate failed once; retrying"}))
        _, failed = run_gate(args.devices, smoke=args.smoke,
                             poison=args.poison)
    return 1 if (failed and args.check) else 0


if __name__ == "__main__":
    sys.exit(main())
