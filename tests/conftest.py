"""Test bootstrap.

The reference runs its suite under ``mpirun -n 3/4 pytest heat/`` (ci.yaml:65-66) so the
same assertions are exercised at several world sizes. The TPU equivalent is a virtual
multi-device CPU mesh via ``--xla_force_host_platform_device_count``. Platform and
device count must be in the environment **before** the JAX backend initialises;
nothing initialises it before this file is imported, so setting them here is enough.

- default: 8 virtual CPU devices (override with HEAT_TPU_TEST_DEVICES=N)
- HEAT_TPU_TEST_NATIVE=1: leave the environment alone and run on the ambient platform
  (the real TPU; one process per chip, so run one pytest at a time)
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
# the plain references of the model cells (``reference_<model>.py``) are the benchmark's own;
# appended, so that nothing in tests/ is shadowed
sys.path.append(os.path.join(_REPO, "benchmarks", "chip"))

# The suite's call profile is the dispatch executor's worst case: thousands of
# distinct op signatures, most exercised once or twice, so compile-on-first-miss
# (the production default, HEAT_TPU_JIT_THRESHOLD=1) would pay a fresh XLA
# compile per assertion for programs that never replay. Threshold 2 keeps
# one-shot signatures on the eager path and still compiles + replays every
# repeated one, so the staged programs stay exercised suite-wide.
# test_executor.py pins the threshold back to 1 to test the production default.
os.environ.setdefault("HEAT_TPU_JIT_THRESHOLD", "2")

# One scheduler shard for the suite: the deterministic queue/batch/lifecycle
# tests assert the committed single-queue contract (pause -> N submits -> one
# width-N batch), which HEAT_TPU_SCHED_SHARDS=1 reproduces bit-for-bit. The
# sharded scheduler (the ISSUE 15 default, min(4, cores)) is covered
# explicitly by TestShardedScheduler, which rebuilds the scheduler at the
# shard counts it asserts about.
os.environ.setdefault("HEAT_TPU_SCHED_SHARDS", "1")


if os.environ.get("HEAT_TPU_TEST_NATIVE") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    if "host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
        _ndev = os.environ.get("HEAT_TPU_TEST_DEVICES", "8")
        os.environ["XLA_FLAGS"] = (
            f"{os.environ.get('XLA_FLAGS', '')} "
            f"--xla_force_host_platform_device_count={_ndev}"
        ).strip()
