"""Checkpoint bandwidth microbenchmark: save/restore GB/s, v1 single-writer vs
v2 parallel chunked, plus the resharding-restore arm (ISSUE 13).

Measures the state-management subsystem the way the dispatch microbenchmark
measures the executor — hermetic virtual CPU mesh, host-side only:

- ``checkpoint_v1_save_gbps``    — the serialized single-writer path
  (``save_checkpoint(..., parallel=False)``): full host gather, one thread
  writing + hashing every leaf. The degradation target.
- ``checkpoint_v2_save_gbps``    — the parallel chunked path: per-shard chunk
  payloads overlapped on the bounded writer pool. The ``v2_over_v1`` ratio is
  the headline: ``--check`` fails when it drops below ``--ratio-min``
  (default 2.0) at 8+ devices — parallel chunking must actually buy the
  bandwidth it was built for.
- ``checkpoint_v2_restore_gbps`` — verified streaming restore onto the
  writer's layout.
- ``checkpoint_v2_reshard_gbps`` — restore onto a DIFFERENT shard count;
  the record carries ``host_peak_bytes`` from
  ``checkpoint.last_restore_stats()`` and ``--check`` fails when the peak
  exceeds one target shard of the widest leaf (times a small slack) — the
  restore must stream shard-by-shard, never materialise a leaf.

``--baseline benchmarks/cb/checkpoint_bw_baseline.json`` gates every GB/s
metric against a committed lower envelope (recorded far below observed —
CI boxes are noisy; the gate catches collapses, not jitter).

Standalone::

    python benchmarks/cb/checkpoint_bw.py --devices 8 --check \\
        --baseline benchmarks/cb/checkpoint_bw_baseline.json
"""

import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

#: leaf geometry: (leaves, rows, cols) — row-split float32 leaves. Sized so
#: per-chunk bytes amortise the per-file durability RPC (on network
#: filesystems fsync is latency-bound: tiny chunks would measure fsync
#: round-trips, not checkpoint bandwidth)
SMOKE_SHAPE = (3, 524288, 16)   # 3 x 32 MiB = 96 MiB tree
FULL_SHAPE = (8, 524288, 16)    # 8 x 32 MiB = 256 MiB tree
REPEATS = 3
#: the v2-over-v1 save gate (acceptance: >=2x at 8 virtual devices)
RATIO_MIN_DEFAULT = 2.0
#: reshard-restore host peak must stay within one target shard (small slack
#: for the dtype/rounding edges of the canonical grid)
PEAK_SLACK = 1.25


def _bootstrap(devices: int) -> None:
    """Re-exec into a hermetic virtual CPU mesh (the dispatch.py pattern)."""
    if os.environ.get("_HEAT_TPU_CKPT_BENCH_REEXEC") == "1":
        return
    env = dict(os.environ)
    env["_HEAT_TPU_CKPT_BENCH_REEXEC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    for knob in ("HEAT_TPU_METRICS", "HEAT_TPU_TRACE", "HEAT_TPU_DIAG_DUMP",
                 "HEAT_TPU_FAULT_PLAN"):
        env.pop(knob, None)
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def _build_tree(ht, leaves: int, rows: int, cols: int, comm=None):
    import numpy as np

    tree = {}
    for i in range(leaves):
        arr = np.arange(i, i + rows * cols, dtype=np.float32).reshape(rows, cols)
        tree[f"w{i}"] = ht.array(arr, split=0, comm=comm)
    nbytes = leaves * rows * cols * 4
    return tree, nbytes


def _best_of(fn, repeats: int = REPEATS) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(check=False, baseline=None, baseline_tol=0.5, ratio_min=None,
        smoke=True, emit=print):
    import jax

    import heat_tpu as ht
    from heat_tpu.core import checkpoint as ck
    from heat_tpu.core.communication import MeshCommunication

    ndev = len(jax.devices())
    leaves, rows, cols = SMOKE_SHAPE if smoke else FULL_SHAPE
    ratio_min = ratio_min if ratio_min is not None else float(
        os.environ.get("HEAT_TPU_CKPT_BW_RATIO_MIN", RATIO_MIN_DEFAULT)
    )
    base_cases = (baseline or {}).get(str(ndev), {})
    if baseline is not None and not base_cases:
        emit(json.dumps({
            "warning": f"baseline has no entry for {ndev} devices; the "
            "checkpoint bandwidth gate is not being enforced on this run"
        }))
    tmp = tempfile.mkdtemp(prefix="heat-tpu-ckpt-bw-")
    records, failed = [], False
    try:
        tree, nbytes = _build_tree(ht, leaves, rows, cols)
        tmpl, _ = _build_tree(ht, leaves, rows, cols)
        gib = nbytes / (1 << 30)
        common = {
            "unit": "GB/s", "devices": ndev, "tree_mib": nbytes >> 20,
            "leaves": leaves, "leaf_shape": [rows, cols],
        }

        def rec_case(name, seconds, **extra):
            nonlocal failed
            r = {
                "metric": f"checkpoint_{name}_gbps",
                "value": round(gib / seconds, 3), "seconds": round(seconds, 4),
                **common, **extra,
            }
            records.append(r)
            emit(json.dumps(r))
            base = base_cases.get(name)
            if base is None and base_cases:
                emit(json.dumps({"warning": f"baseline has no '{name}' entry "
                                 f"at {ndev} devices; case not gated"}))
            elif base is not None and r["value"] < (1.0 - baseline_tol) * base:
                failed = True
                emit(json.dumps({
                    "error": f"{name}: {r['value']} GB/s fell more than "
                    f"{baseline_tol:.0%} below the recorded envelope "
                    f"{base} GB/s"
                }))
            return r

        d_v1 = os.path.join(tmp, "v1")
        t_v1 = _best_of(lambda: ht.save_checkpoint(tree, d_v1, parallel=False))
        v1 = rec_case("v1_save", t_v1, schema=ck.read_manifest(d_v1)["schema"])

        d_v2 = os.path.join(tmp, "v2")
        t_v2 = _best_of(lambda: ht.save_checkpoint(tree, d_v2))
        v2 = rec_case("v2_save", t_v2, schema=ck.read_manifest(d_v2)["schema"])

        ratio = round(v2["value"] / max(v1["value"], 1e-9), 2)
        ratio_rec = {
            "metric": "checkpoint_v2_over_v1_save", "value": ratio,
            "unit": "x", "devices": ndev,
        }
        records.append(ratio_rec)
        emit(json.dumps(ratio_rec))
        if check and ndev >= 8 and ratio < ratio_min:
            failed = True
            emit(json.dumps({
                "error": f"parallel v2 save is only {ratio}x the v1 "
                f"single-writer throughput (gate: >= {ratio_min}x at "
                f"{ndev} devices)"
            }))

        t_rs = _best_of(lambda: ht.load_checkpoint(tmpl, d_v2))
        rec_case("v2_restore", t_rs)

        # reshard arm: restore onto a different shard count; the target shard
        # of the widest leaf bounds the streaming path's host peak
        target = max(2, ndev // 2) if ndev >= 2 else 1
        comm_t = MeshCommunication(devices=jax.devices()[:target])
        tmpl_rs, _ = _build_tree(ht, leaves, rows, cols, comm=comm_t)
        t_re = _best_of(lambda: ht.load_checkpoint(tmpl_rs, d_v2))
        stats = ck.last_restore_stats()
        shard_bytes = (-(-rows // target)) * cols * 4
        r = rec_case(
            "v2_reshard", t_re, target_shards=target,
            host_peak_bytes=stats["host_bytes_peak"],
            one_shard_bytes=shard_bytes,
            read_bytes=stats["read_bytes"],
        )
        if check and stats["host_bytes_peak"] > PEAK_SLACK * shard_bytes:
            failed = True
            emit(json.dumps({
                "error": f"resharded restore materialised "
                f"{stats['host_bytes_peak']} host bytes — above one target "
                f"shard ({shard_bytes} B x {PEAK_SLACK} slack); the "
                "streaming path must stay shard-bounded"
            }))
        del r
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if (check or baseline) and failed:
        sys.exit(1)
    return records


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--devices", type=int, default=8)
    parser.add_argument("--full", action="store_true",
                        help="256 MiB tree (8 leaves) instead of the 96 MiB smoke shape")
    parser.add_argument("--check", action="store_true",
                        help="exit non-zero when v2 save is below --ratio-min "
                        "x the v1 throughput (8+ devices) or the reshard "
                        "restore is not shard-bounded")
    parser.add_argument("--ratio-min", type=float, default=None)
    parser.add_argument("--baseline",
                        help="JSON lower envelopes ({devices: {case: gbps}})")
    parser.add_argument("--baseline-tol", type=float, default=0.5,
                        help="allowed fractional regression vs --baseline "
                        "(default 0.5 — IO on shared CI boxes is noisy)")
    args = parser.parse_args()
    _bootstrap(args.devices)
    baseline = None
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
    run(check=args.check, baseline=baseline, baseline_tol=args.baseline_tol,
        ratio_min=args.ratio_min, smoke=not args.full)
