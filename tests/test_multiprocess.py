"""Multi-controller execution tests: launch REAL separate processes with
``jax.distributed.initialize`` on localhost, the TPU-native analogue of the
reference's ``mpirun -n 3/4 pytest heat/`` CI mode (reference
.github/workflows/ci.yaml:65-66).

Every other test in this suite is single-process (one controller, 8 virtual
devices); these are the only runs where ``jax.process_count() > 1`` branches —
``is_split`` assembly, cross-host ``numpy()``, the single-writer io contract —
actually execute. See tests/_mp_worker.py for the per-process assertions.

ISSUE 11 adds the distributed-telemetry job (tests/_mp_telemetry_worker.py):
every process dumps a telemetry shard, the parent merges them and asserts the
global report — exact counter sums, associativity-independent histogram
quantiles, aligned monotone trace timestamps, and a deterministically injected
straggler named by the skew scoreboard. Set ``HEAT_TPU_TELEMETRY_TEST_OUT`` to
a directory to keep the shards + merged artifacts (the CI job uploads them and
re-runs the ``python -m heat_tpu.telemetry merge --check`` CLI over them).
"""

import contextlib
import glob
import io
import json
import os
import shutil
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_mp_worker.py")
_TELEMETRY_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_mp_telemetry_worker.py"
)
_DIVERGENCE_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_mp_divergence_worker.py"
)
_CKPT_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_mp_ckpt_worker.py"
)
_SUPERVISION_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_mp_supervision_worker.py"
)
_OPS_WORKER = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "_mp_ops_worker.py"
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _launch(nprocs: int, devices_per_proc: int, tmpdir: str, worker: str = _WORKER):
    coordinator = f"localhost:{_free_port()}"
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices_per_proc}",
    )
    env.pop("HEAT_TPU_TEST_DEVICES", None)
    # stdout goes to files, not pipes: a failing worker with a long traceback
    # must never block on a full pipe while its peers wait in a collective
    logs = [os.path.join(tmpdir, f"worker{i}.log") for i in range(nprocs)]
    handles = [open(log, "w") for log in logs]
    procs = [
        subprocess.Popen(
            [sys.executable, worker, coordinator, str(nprocs), str(i), tmpdir],
            env=env,
            stdout=handles[i],
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(nprocs)
    ]
    try:
        for p in procs:
            p.wait(timeout=420)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for h in handles:
            h.close()
    return [(p.returncode, open(log).read()) for p, log in zip(procs, logs)]


@pytest.mark.parametrize("nprocs,devices_per_proc", [(2, 2), (4, 1)])
def test_multiprocess_spmd(nprocs, devices_per_proc, tmp_path):
    outs = _launch(nprocs, devices_per_proc, str(tmp_path))
    for i, (rc, out) in enumerate(outs):
        assert rc == 0, f"worker {i} failed (rc={rc}):\n{out[-4000:]}"
        assert f"WORKER_OK {i}" in out, f"worker {i} incomplete:\n{out[-4000:]}"


@pytest.mark.parametrize("nprocs,devices_per_proc", [(2, 2), (4, 1)])
def test_multiprocess_checkpoint_v2(nprocs, devices_per_proc, tmp_path):
    """ISSUE 13: parallel per-process chunk writes commit one manifest; a
    writer crash surfaces as an exception on EVERY rank (never a hang); a
    non-writer chunk-write failure degrades every rank to v1 together."""
    outs = _launch(nprocs, devices_per_proc, str(tmp_path), worker=_CKPT_WORKER)
    for i, (rc, out) in enumerate(outs):
        assert rc == 0, f"worker {i} failed (rc={rc}):\n{out[-4000:]}"
        assert f"CKPT_OK {i}" in out, f"worker {i} incomplete:\n{out[-4000:]}"


@pytest.mark.parametrize("nprocs,devices_per_proc", [(2, 1), (4, 1)])
def test_multiprocess_supervision(nprocs, devices_per_proc, tmp_path):
    """ISSUE 14, the kill-a-rank proof: the last rank of an N-process
    supervised training job dies abruptly (deterministic ``peer-dead`` fault
    — os._exit, no departure marker) mid-run. Every survivor must raise
    typed ``PeerFailed`` naming the dead rank within the supervision budget
    (never a hang — this test is bounded by the launcher timeout), dump a
    flight-recorder post-mortem, and ``run_supervised`` must resume from the
    last committed checkpoint at the surviving world size with restored
    state bit-identical to the pre-kill save."""
    from heat_tpu.core import resilience

    outs = _launch(nprocs, devices_per_proc, str(tmp_path),
                   worker=_SUPERVISION_WORKER)
    for i, (rc, out) in enumerate(outs):
        if i == nprocs - 1:
            assert rc == resilience.PEER_DEAD_EXIT_STATUS, (
                f"rank {i} should have died peer-dead (rc={rc}):\n{out[-4000:]}"
            )
            assert "SUPERVISION_OK" not in out
        else:
            assert rc == 0, f"survivor {i} failed (rc={rc}):\n{out[-4000:]}"
            assert f"SUPERVISION_OK {i}" in out, (
                f"survivor {i} incomplete:\n{out[-4000:]}"
            )
            assert "TYPED PeerFailed rank=" + str(nprocs - 1) in out


@pytest.mark.parametrize("nprocs,devices_per_proc", [(2, 2), (4, 1)])
def test_multiprocess_ops_cluster_beats(nprocs, devices_per_proc, tmp_path):
    """ISSUE 18, the cluster-beat proof: every rank of an N-process job
    publishes its ops beat on the real coordination KV channel,
    ``cluster_snapshot`` folds all N with one non-blocking sweep (the last
    rank publishes late — the mid-drain stand-in — and nobody waits on it),
    and the beat FILES render one table row per rank through the public
    ``telemetry top --dir`` CLI (asserted in-worker by rank 0 and re-checked
    here in the parent)."""
    outs = _launch(nprocs, devices_per_proc, str(tmp_path), worker=_OPS_WORKER)
    for i, (rc, out) in enumerate(outs):
        assert rc == 0, f"worker {i} failed (rc={rc}):\n{out[-4000:]}"
        assert f"OPS_OK {i}" in out, f"worker {i} incomplete:\n{out[-4000:]}"

    from heat_tpu.core import telemetry

    beats_dir = os.path.join(str(tmp_path), "beats")
    beats = telemetry.load_ops_beats(beats_dir)
    assert sorted(beats) == [str(r) for r in range(nprocs)]
    for rank, beat in beats.items():
        assert beat["schema"] == "heat-tpu-ops-beat/1"
        assert str(beat["rank"]) == rank
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = telemetry.main(["top", "--dir", beats_dir])
    out = buf.getvalue()
    assert rc == 0, out
    rows = [ln for ln in out.splitlines()
            if ln.strip() and ln.strip().split()[0].isdigit()]
    assert len(rows) == nprocs, out


@pytest.mark.parametrize("nprocs,devices_per_proc", [(2, 2), (4, 1)])
def test_multiprocess_telemetry(nprocs, devices_per_proc, tmp_path):
    """The ISSUE-11 acceptance shape: an N-process job yields ONE merged
    report and ONE aligned merged trace, with the injected straggler named."""
    outs = _launch(nprocs, devices_per_proc, str(tmp_path),
                   worker=_TELEMETRY_WORKER)
    for i, (rc, out) in enumerate(outs):
        assert rc == 0, f"worker {i} failed (rc={rc}):\n{out[-4000:]}"
        assert f"TELEMETRY_OK {i}" in out, f"worker {i} incomplete:\n{out[-4000:]}"

    from heat_tpu.core import profiler, telemetry

    shard_dir = os.path.join(str(tmp_path), "shards")
    shards = telemetry.load_shards(shard_dir)
    assert len(shards) == nprocs, os.listdir(shard_dir)
    merged = telemetry.merge(shards)

    # --- exact counter sums across processes ------------------------------
    assert merged["processes"] == nprocs
    assert merged["counters"]["mp.marker"] == sum(range(1, nprocs + 1))
    assert merged["clock"]["aligned"] is True
    assert len(merged["clock"]["anchors_monotonic_ns"]) == nprocs

    # --- histogram quantiles independent of merge associativity ----------
    hist = merged["histograms"]["mp.lat"]
    assert hist["count"] == 4 * nprocs
    reversed_hist = telemetry.merge(list(reversed(shards)))["histograms"]["mp.lat"]
    assert hist["buckets"] == reversed_hist["buckets"]
    for q in ("p50_s", "p95_s", "p99_s"):
        assert hist[q] == reversed_hist[q]
    # and equal to folding the per-process snapshots by hand, pairwise
    folded = None
    for shard in shards:
        h = profiler.Histogram.from_snapshot(
            shard["diagnostics"]["profiler"]["histograms"]["mp.lat"]
        )
        folded = h if folded is None else folded.merge(h)
    assert folded.snapshot()["buckets"] == hist["buckets"]

    # --- clean run: the cross-rank collective sequences are consistent ----
    seq = merged["sequence"]
    assert seq["valid"] is True, seq
    assert seq["consistent"] is True, seq["divergences"]
    assert seq["windows_checked"] > 0

    # --- the injected straggler is named by the scoreboard ----------------
    straggler = nprocs - 1
    skew = merged["skew"]
    assert skew["collectives_measured"] > 0
    assert skew["slowest_rank"] == straggler, skew["scoreboard"]
    site = skew["sites"]["comm.shard"]
    assert site["slowest_rank"] == straggler, site
    # the retried injected timeout stretches the enter skew to ~0.6 s
    assert site["max_skew_us"] >= 200_000, site
    assert f"skew.{'shard'}" in merged["histograms"]
    board = skew["scoreboard"][str(straggler)]
    assert board["worst_site"] == "comm.shard"

    # --- merged trace: per-process pid ranges, aligned monotone ts --------
    trace = telemetry.merged_trace(shards)
    events = trace["traceEvents"]
    stride = telemetry.PID_STRIDE
    pids_seen = set()
    last = {}
    for ev in events:
        proc_slot = ev["pid"] // stride
        assert 1 <= proc_slot <= nprocs, ev
        pids_seen.add(proc_slot)
        if "ts" in ev:
            assert ev["ts"] >= 0.0, ev
        if ev.get("ph") in ("B", "E"):
            key = (ev["pid"], ev["tid"])
            assert ev["ts"] >= last.get(key, -1.0), ev
            last[key] = ev["ts"]
    assert pids_seen == set(range(1, nprocs + 1))
    # flow arrows exist linking collectives across the process tracks
    flows = [ev for ev in events if ev.get("cat") == "collective-skew"]
    assert flows and {ev["ph"] for ev in flows} >= {"s", "f"}

    # --- flight recorder: the straggler's fault firings left a post-mortem -
    dumps = glob.glob(os.path.join(str(tmp_path), "flight", "*.json"))
    assert dumps, "no flight-recorder dump from the injected faults"
    with open(dumps[0]) as f:
        assert json.load(f)["schema"] == telemetry.FLIGHT_SCHEMA

    # --- keep the artifacts for CI upload + the CLI merge gate ------------
    keep = os.environ.get("HEAT_TPU_TELEMETRY_TEST_OUT")
    if keep:
        dest = os.path.join(keep, f"n{nprocs}")
        os.makedirs(os.path.join(dest, "shards"), exist_ok=True)
        for path in glob.glob(os.path.join(shard_dir, "telemetry-shard-*.json")):
            shutil.copy(path, os.path.join(dest, "shards"))
        telemetry.write_report(merged, os.path.join(dest, "merged-report.json"))
        telemetry.write_trace(trace, os.path.join(dest, "merged-trace.json"))


def test_multiprocess_sequence_divergence(tmp_path):
    """The ISSUE-12 acceptance shape: a rank-dependent branch issues one
    extra guarded collective on the last rank of a 2-process job; the
    telemetry merge sequence gate must FAIL, naming the rank and the site —
    the runtime twin of the static ``spmd-divergent-collective`` rule."""
    nprocs = 2
    outs = _launch(nprocs, 2, str(tmp_path), worker=_DIVERGENCE_WORKER)
    for i, (rc, out) in enumerate(outs):
        assert rc == 0, f"worker {i} failed (rc={rc}):\n{out[-4000:]}"
        assert f"DIVERGENCE_OK {i}" in out, f"worker {i} incomplete:\n{out[-4000:]}"

    from heat_tpu.core import telemetry

    shard_dir = os.path.join(str(tmp_path), "shards")
    shards = telemetry.load_shards(shard_dir)
    assert len(shards) == nprocs

    # merge() reports the divergence precisely…
    merged = telemetry.merge(shards)
    seq = merged["sequence"]
    assert seq["valid"] is True
    assert seq["consistent"] is False, seq
    d = seq["divergences"][0]
    assert d["rank"] == nprocs - 1
    assert d["reference_rank"] == 0
    assert d["actual"] == "comm.shard"
    assert d["index"] == 3  # three symmetric rounds, the 4th call is extra
    assert (d["expected_len"], d["actual_len"]) == (3, 4)

    # …and the CI gate (the public CLI surface) fails, naming rank and site
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = telemetry.main(["merge", "--dir", shard_dir,
                             "--expect", str(nprocs), "--check"])
    out = buf.getvalue()
    assert rc == 1, out
    assert f"rank {nprocs - 1}" in out
    assert "comm.shard" in out
    assert "divergence" in out

    # without --check the merge still succeeds (report-only mode)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = telemetry.main(["merge", "--dir", shard_dir,
                             "--expect", str(nprocs)])
    assert rc == 0, buf.getvalue()
    assert '"sequence_consistent": false' in buf.getvalue()
