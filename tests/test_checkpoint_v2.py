"""Checkpoint v2 (ISSUE 13): the crash matrix, resharding-on-restore
round-trips, the degradation ladder, and the hardened manager pruning.

The crash matrix parametrizes a deterministic fault at every v2 site —
mid-chunk / between chunks (``checkpoint.chunk_write``, with the v1
degradation target also faulted so the save genuinely dies), pre-manifest
(``checkpoint.manifest``), and both commit points (``checkpoint.commit``
fires once before EACH of the two renames) — crossed with (fresh directory,
overwrite). The invariant under every point: restore yields exactly the old
or the new generation — never a torn middle, never a hang — and the next
fault-free save commits cleanly with no stale ``.tmp``/``.old`` debris.
"""

import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import heat_tpu as ht
import jax
from heat_tpu.core import checkpoint as _ckpt
from heat_tpu.core import diagnostics, resilience
from heat_tpu.core.communication import MeshCommunication
from heat_tpu.testing import TestCase


def _resilience_reset():
    resilience.disarm_fault_plan()
    resilience.reset(clear_breakers=True)


class _CkptCase(TestCase):
    def setUp(self):
        self.tmp = tempfile.mkdtemp()
        _resilience_reset()

    def tearDown(self):
        shutil.rmtree(self.tmp, ignore_errors=True)
        _resilience_reset()


def _tree(scale: float = 1.0):
    return {
        "a": ht.array(
            (np.arange(42, dtype=np.float32) * scale).reshape(7, 6), split=0
        ),
        "b": ht.array(np.full((5,), 2.0 * scale, np.float32)),
        "step": np.int64(int(scale)),
    }


def _template():
    return {
        "a": ht.zeros((7, 6), split=0),
        "b": ht.zeros((5,)),
        "step": np.int64(0),
    }


def _values(tree):
    return (
        np.asarray(tree["a"].numpy() if hasattr(tree["a"], "numpy") else tree["a"]),
        np.asarray(tree["b"].numpy() if hasattr(tree["b"], "numpy") else tree["b"]),
        int(tree["step"]),
    )


#: (name, fault-plan, save_must_fail_fresh, save_must_fail_overwrite)
#: checkpoint.commit fires once before EACH rename: on a fresh directory there
#: is no backup rename, so on_call=2 never fires and the save commits.
CRASH_POINTS = [
    ("mid-chunk-write",
     [{"site": "checkpoint.chunk_write", "on_call": 1, "count": 9999,
       "kind": "raise"},
      {"site": "checkpoint.write", "on_call": 1, "count": 9999,
       "kind": "raise"}],
     True, True),
    ("between-chunks",
     [{"site": "checkpoint.chunk_write", "on_call": 3, "count": 9999,
       "kind": "raise"},
      {"site": "checkpoint.write", "on_call": 2, "count": 9999,
       "kind": "raise"}],
     True, True),
    ("pre-manifest",
     [{"site": "checkpoint.manifest", "on_call": 1, "count": 9999,
       "kind": "raise"}],
     True, True),
    ("commit-first-rename",
     [{"site": "checkpoint.commit", "on_call": 1, "count": 1,
       "kind": "raise"}],
     True, True),
    ("commit-between-renames",
     [{"site": "checkpoint.commit", "on_call": 2, "count": 1,
       "kind": "raise"}],
     False, True),
]


class TestCrashMatrix(_CkptCase):
    def _no_debris(self, path):
        parent = os.path.dirname(path)
        base = os.path.basename(path)
        stale = [
            n for n in os.listdir(parent)
            if n.startswith(f"{base}.tmp.") or n.startswith(f"{base}.old.")
        ]
        self.assertEqual(stale, [])

    def _run_point(self, plan, overwrite, must_fail):
        path = os.path.join(self.tmp, "ckpt")
        shutil.rmtree(path, ignore_errors=True)
        for n in glob.glob(path + ".*"):
            shutil.rmtree(n, ignore_errors=True)
        old = _tree(1.0)
        if overwrite:
            ht.save_checkpoint(old, path)
        resilience.reset(clear_breakers=True)
        resilience.arm_fault_plan(plan)
        new = _tree(5.0)
        failed = False
        try:
            ht.save_checkpoint(new, path)
        except Exception:
            failed = True
        resilience.disarm_fault_plan()
        self.assertEqual(failed, must_fail)
        if failed and not overwrite:
            # fresh dir + failed save: nothing restorable, loudly
            with self.assertRaises(ht.CheckpointCorrupt):
                ht.load_checkpoint(_template(), path)
        else:
            # exactly the old or the new generation, bit-identical and clean
            expect = _values(old) if failed else _values(new)
            self.assertEqual(_ckpt.verify_checkpoint(path), [])
            back = ht.load_checkpoint(_template(), path)
            a, b, step = _values(back)
            np.testing.assert_array_equal(a, expect[0])
            np.testing.assert_array_equal(b, expect[1])
            self.assertEqual(step, expect[2])
        # recovery: the next fault-free save commits cleanly, no debris
        resilience.reset(clear_breakers=True)
        final = _tree(9.0)
        ht.save_checkpoint(final, path)
        self.assertEqual(_ckpt.verify_checkpoint(path), [])
        back = ht.load_checkpoint(_template(), path)
        np.testing.assert_array_equal(_values(back)[0], _values(final)[0])
        self._no_debris(path)

    def test_crash_matrix(self):
        for name, plan, fail_fresh, fail_over in CRASH_POINTS:
            with self.subTest(point=name, dir="fresh"):
                self._run_point(plan, overwrite=False, must_fail=fail_fresh)
            with self.subTest(point=name, dir="overwrite"):
                self._run_point(plan, overwrite=True, must_fail=fail_over)

    def test_torn_chunk_is_detected_not_restored(self):
        """A torn-write fault commits a silently-short chunk; the manifest's
        per-chunk digest refuses the restore with the chunk named."""
        path = os.path.join(self.tmp, "torn")
        resilience.arm_fault_plan(
            [{"site": "checkpoint.chunk_write", "on_call": 1,
              "kind": "torn-write", "fraction": 0.25}]
        )
        ht.save_checkpoint(_tree(3.0), path)
        resilience.disarm_fault_plan()
        problems = _ckpt.verify_checkpoint(path)
        self.assertEqual(len(problems), 1)
        self.assertIn("torn write", problems[0])
        with self.assertRaises(ht.CheckpointCorrupt):
            ht.load_checkpoint(_template(), path)

    def test_chunk_read_fault_is_typed_not_hang(self):
        path = os.path.join(self.tmp, "rd")
        ht.save_checkpoint(_tree(2.0), path)
        resilience.arm_fault_plan(
            [{"site": "checkpoint.chunk_read", "on_call": 1, "count": 9999,
              "kind": "raise"}]
        )
        with self.assertRaises(resilience.FaultInjected):
            ht.load_checkpoint(_template(), path)

    def test_degrades_to_v1_with_recorded_fallback(self):
        path = os.path.join(self.tmp, "deg")
        resilience.arm_fault_plan(
            [{"site": "checkpoint.chunk_write", "on_call": 1, "count": 9999,
              "kind": "raise"}]
        )
        ht.save_checkpoint(_tree(4.0), path)
        resilience.disarm_fault_plan()
        # degraded but committed — as schema 1, still restorable
        self.assertEqual(_ckpt.read_manifest(path)["schema"], _ckpt.SCHEMA_V1)
        back = ht.load_checkpoint(_template(), path)
        np.testing.assert_array_equal(_values(back)[0], _values(_tree(4.0))[0])
        events = [
            e for e in diagnostics.report()["resilience_events"]
            if e["site"] == "checkpoint.save" and e["kind"] == "fallback"
        ]
        self.assertTrue(events, "degradation must be recorded, never silent")
        self.assertIn("serialized v1", events[-1]["detail"])

    def test_open_breaker_short_circuits_to_v1_until_cooldown(self):
        clock = [0.0]
        br = resilience.breaker(
            "checkpoint.chunk_write", failure_threshold=3, cooldown_s=60.0,
            clock=lambda: clock[0],
        )
        for _ in range(3):
            br.record_failure("disk went away")
        self.assertEqual(br.state, resilience.OPEN)
        path = os.path.join(self.tmp, "bro")
        ht.save_checkpoint(_tree(6.0), path)  # no plan armed: v2 would work
        self.assertEqual(_ckpt.read_manifest(path)["schema"], _ckpt.SCHEMA_V1)
        # cooldown elapses: the half-open trial runs the parallel path again
        clock[0] = 61.0
        ht.save_checkpoint(_tree(6.0), path)
        self.assertEqual(_ckpt.read_manifest(path)["schema"], _ckpt.SCHEMA)
        self.assertEqual(br.state, resilience.CLOSED)


class TestResharding(_CkptCase):
    """Save at (P, split) → restore at (P', split') is bit-identical,
    pads re-masked, for every shard-count/split combination the mesh offers."""

    def _comms(self):
        ndev = len(jax.devices())
        sizes = sorted({1, min(3, ndev), ndev})
        return {s: MeshCommunication(devices=jax.devices()[:s]) for s in sizes}

    def test_reshard_roundtrip_matrix(self):
        rng = np.random.default_rng(7)
        base = rng.standard_normal((7, 6)).astype(np.float32)
        comms = self._comms()
        splits = (None, 0, 1)
        for ps, sa in ((p, s) for p in comms for s in splits):
            src = ht.array(base, split=sa, comm=comms[ps])
            path = os.path.join(self.tmp, f"rs_{ps}_{sa}")
            ht.save_checkpoint({"x": src}, path)
            for pt, sb in ((p, s) for p in comms for s in splits):
                with self.subTest(src=(ps, sa), dst=(pt, sb)):
                    tmpl = {"x": ht.zeros((7, 6), split=sb, comm=comms[pt])}
                    back = ht.load_checkpoint(tmpl, path)
                    self.assertEqual(back["x"].split, sb)
                    self.assertEqual(back["x"].comm.size, pt)
                    self.assert_array_equal(back["x"], base)
                    # pads re-masked: the physical value beyond the logical
                    # extent must be exactly zero
                    phys = np.asarray(back["x"].parray)
                    if phys.shape != base.shape:
                        pad = phys.copy()
                        pad[tuple(slice(0, s) for s in base.shape)] = 0.0
                        self.assertEqual(float(np.abs(pad).sum()), 0.0)

    def test_reshard_bfloat16_and_plain_leaves(self):
        import ml_dtypes

        comms = self._comms()
        big = max(comms)
        small = min(comms)
        val = np.arange(24, dtype=ml_dtypes.bfloat16).reshape(8, 3)
        tree = {
            "w": ht.array(val, split=0, comm=comms[big]),
            "meta": np.arange(4, dtype=np.int64),
        }
        path = os.path.join(self.tmp, "bf16")

        def _save_fallbacks():
            return len([
                e for e in diagnostics.report()["resilience_events"]
                if e["site"] == "checkpoint.save" and e["kind"] == "fallback"
            ])

        before = _save_fallbacks()
        ht.save_checkpoint(tree, path)
        # bf16 must ride the PARALLEL chunked path (extension dtypes lack the
        # buffer protocol — a regression here silently degrades every bf16
        # save to v1 and trips the chunk-write breaker); the event stream is
        # cumulative across tests, so compare against the pre-save count
        self.assertEqual(_ckpt.read_manifest(path)["schema"], _ckpt.SCHEMA)
        self.assertEqual(_save_fallbacks(), before)
        tmpl = {
            "w": ht.zeros((8, 3), dtype=ht.bfloat16, split=1, comm=comms[small]),
            "meta": np.zeros(4, np.int64),
        }
        back = ht.load_checkpoint(tmpl, path)
        np.testing.assert_array_equal(
            np.asarray(back["w"].numpy(), np.float32), np.asarray(val, np.float32)
        )
        np.testing.assert_array_equal(back["meta"], np.arange(4, dtype=np.int64))

    def test_strict_layout_rejects_reshard(self):
        comms = self._comms()
        big = max(comms)
        src = ht.array(np.arange(12, dtype=np.float32), split=0, comm=comms[big])
        path = os.path.join(self.tmp, "strict")
        ht.save_checkpoint({"x": src}, path)
        # same layout passes
        same = ht.load_checkpoint(
            {"x": ht.zeros((12,), split=0, comm=comms[big])}, path, strict="layout"
        )
        self.assert_array_equal(same["x"], np.arange(12, dtype=np.float32))
        # different split or shard count is refused
        with self.assertRaises(ht.CheckpointLayoutMismatch):
            ht.load_checkpoint(
                {"x": ht.zeros((12,), split=None, comm=comms[big])},
                path, strict="layout",
            )
        if len(comms) > 1:
            small = min(comms)
            with self.assertRaises(ht.CheckpointLayoutMismatch):
                ht.load_checkpoint(
                    {"x": ht.zeros((12,), split=0, comm=comms[small])},
                    path, strict="layout",
                )

    def test_strict_layout_applies_to_v1_checkpoints(self):
        """``strict="layout"`` must bind on schema-1 checkpoints too: a v1
        save stores the split, so a mismatched template is a refusable layout
        change, not a silent reshard."""
        src = ht.array(np.arange(12, dtype=np.float32).reshape(4, 3), split=0)
        path = os.path.join(self.tmp, "v1strict")
        ht.save_checkpoint({"x": src}, path, parallel=False)
        self.assertEqual(_ckpt.read_manifest(path)["schema"], _ckpt.SCHEMA_V1)
        same = ht.load_checkpoint(
            {"x": ht.zeros((4, 3), split=0)}, path, strict="layout"
        )
        self.assert_array_equal(same["x"], np.arange(12, dtype=np.float32).reshape(4, 3))
        with self.assertRaises(ht.CheckpointLayoutMismatch):
            ht.load_checkpoint(
                {"x": ht.zeros((4, 3), split=1)}, path, strict="layout"
            )
        # the default still reshards v1 onto the new layout
        moved = ht.load_checkpoint({"x": ht.zeros((4, 3), split=1)}, path)
        self.assert_array_equal(moved["x"], np.arange(12, dtype=np.float32).reshape(4, 3))
        self.assertEqual(moved["x"].split, 1)

    def test_strict_layout_accepts_replicated_leaves(self):
        """A replicated (split=None) leaf is ONE whole-value chunk — it
        matches any comm size, so strict="layout" must not reject the
        identical layout just because the comm has more than one device."""
        src = {"b": ht.array(np.arange(5, dtype=np.float32), split=None)}
        path = os.path.join(self.tmp, "strict_repl")
        ht.save_checkpoint(src, path)
        back = ht.load_checkpoint(
            {"b": ht.zeros((5,), split=None)}, path, strict="layout"
        )
        self.assert_array_equal(back["b"], np.arange(5, dtype=np.float32))

    def test_streaming_restore_host_peak_bounded_by_one_shard(self):
        """The resharded restore's largest host buffer is one target shard
        of one leaf — never a full leaf, never the tree."""
        comms = self._comms()
        big = max(comms)
        n = 64 * big
        tree = {
            "a": ht.array(
                np.arange(n * 8, dtype=np.float32).reshape(n, 8), split=0,
                comm=comms[big],
            ),
            "b": ht.array(
                np.arange(n * 4, dtype=np.float32).reshape(n, 4), split=0,
                comm=comms[big],
            ),
        }
        path = os.path.join(self.tmp, "peak")
        ht.save_checkpoint(tree, path)
        small = min(c for c in comms if c > 1) if len(comms) > 1 else big
        tmpl = {
            "a": ht.zeros((n, 8), split=0, comm=comms[small]),
            "b": ht.zeros((n, 4), split=0, comm=comms[small]),
        }
        back = ht.load_checkpoint(tmpl, path)
        self.assert_array_equal(back["a"], np.asarray(tree["a"].numpy()))
        stats = _ckpt.last_restore_stats()
        shard_rows = -(-n // small)
        one_shard = shard_rows * 8 * 4  # widest leaf's target shard bytes
        self.assertGreater(stats["read_bytes"], 0)
        self.assertLessEqual(stats["host_bytes_peak"], one_shard)

    def test_verify_false_skips_digests_but_checks_lengths(self):
        path = os.path.join(self.tmp, "nv")
        src = ht.array(np.arange(32, dtype=np.float32), split=0)
        ht.save_checkpoint({"x": src}, path)
        manifest = _ckpt.read_manifest(path)
        chunk = os.path.join(path, manifest["leaves"][0]["chunks"][0]["file"])
        # a bit flip passes verify=False (documented tradeoff)…
        with open(chunk, "r+b") as fh:
            fh.seek(1)
            fh.write(b"\xff")
        ht.load_checkpoint({"x": ht.zeros((32,), split=0)}, path, verify=False)
        # …but a torn chunk still fails the per-read byte-length check
        with open(chunk, "r+b") as fh:
            fh.truncate(4)
        with self.assertRaises(ht.CheckpointCorrupt):
            ht.load_checkpoint(
                {"x": ht.zeros((32,), split=0)}, path, verify=False
            )


class TestTrainingStateRoundtrip(_CkptCase):
    def test_optimizer_and_rng_resume_bit_identical(self):
        """Params + optimizer state + RNG counters checkpoint as ONE tree and
        resume a training run bit-identically — including the next random
        draws."""
        model = ht.nn.Sequential(ht.nn.Linear(4, 8), ht.nn.ReLU(), ht.nn.Linear(8, 2))
        opt = ht.optim.DataParallelOptimizer("sgd", lr=0.05)
        ht.nn.DataParallel(model, optimizer=opt)
        crit = ht.nn.CrossEntropyLoss()
        rng = np.random.default_rng(0)
        x = ht.array(rng.standard_normal((64, 4)).astype(np.float32), split=0)
        y = ht.array(rng.integers(0, 2, 64), split=0)

        def loss_fn(params, xb, yb):
            return crit(model.apply(params, xb), yb)

        ht.random.seed(1234)
        for _ in range(3):
            opt.step(loss_fn, x, y)
        _ = ht.random.rand(10, split=0)  # advance the counter mid-run
        kind, seed, counter, _i, _f = ht.random.get_state()
        state = {
            "params": model.params,
            "opt": opt._opt_state,
            "rng": np.asarray([seed, counter], np.int64),
        }
        path = os.path.join(self.tmp, "resume")
        ht.save_checkpoint(state, path)
        continued = [float(opt.step(loss_fn, x, y)) for _ in range(2)]
        draw = ht.random.rand(6, split=0).numpy()

        # fresh pipeline, resumed from the checkpoint
        model2 = ht.nn.Sequential(ht.nn.Linear(4, 8), ht.nn.ReLU(), ht.nn.Linear(8, 2))
        opt2 = ht.optim.DataParallelOptimizer("sgd", lr=0.05)
        ht.nn.DataParallel(model2, optimizer=opt2)
        opt2.step(lambda p, xb, yb: crit(model2.apply(p, xb), yb), x, y)
        back = ht.load_checkpoint(
            {
                "params": model2.params,
                "opt": opt2._opt_state,
                "rng": np.zeros(2, np.int64),
            },
            path,
        )
        model2.params = back["params"]
        opt2._opt_state = back["opt"]
        ht.random.set_state(("Threefry", int(back["rng"][0]), int(back["rng"][1]), 0, 0.0))

        def loss_fn2(params, xb, yb):
            return crit(model2.apply(params, xb), yb)

        resumed = [float(opt2.step(loss_fn2, x, y)) for _ in range(2)]
        np.testing.assert_allclose(resumed, continued, rtol=1e-6)
        draw2 = ht.random.rand(6, split=0).numpy()
        np.testing.assert_array_equal(draw, draw2)

    def test_split_opt_state_reshards(self):
        """A (synthetic) optimizer-moment tree of split leaves round-trips
        through a different shard count bit-identically."""
        ndev = len(jax.devices())
        comms = {
            s: MeshCommunication(devices=jax.devices()[:s])
            for s in sorted({1, ndev})
        }
        big = max(comms)
        m = np.linspace(-1, 1, 40, dtype=np.float32).reshape(10, 4)
        v = (m * m).astype(np.float32)
        tree = {
            "mu": ht.array(m, split=0, comm=comms[big]),
            "nu": ht.array(v, split=1, comm=comms[big]),
            "count": np.int64(17),
        }
        path = os.path.join(self.tmp, "opt")
        ht.save_checkpoint(tree, path)
        small = min(comms)
        tmpl = {
            "mu": ht.zeros((10, 4), split=1, comm=comms[small]),
            "nu": ht.zeros((10, 4), split=0, comm=comms[small]),
            "count": np.int64(0),
        }
        back = ht.load_checkpoint(tmpl, path)
        self.assert_array_equal(back["mu"], m)
        self.assert_array_equal(back["nu"], v)
        self.assertEqual(int(back["count"]), 17)


class TestManagerPruning(_CkptCase):
    def test_prune_records_events(self):
        mgr = ht.CheckpointManager(os.path.join(self.tmp, "run"), max_to_keep=1)
        x = ht.arange(12, dtype=ht.float32, split=0)
        mgr.save(1, {"x": x})
        mgr.save(2, {"x": x * 2.0})
        self.assertEqual(mgr.all_steps(), [2])
        events = [
            e for e in diagnostics.report()["resilience_events"]
            if e["site"] == "checkpoint.prune" and e["kind"] == "pruned"
        ]
        self.assertTrue(events)
        self.assertIn("step_1", events[-1]["detail"])
        mgr.close()

    def test_prune_deferred_while_restore_holds_then_retried(self):
        mgr = ht.CheckpointManager(os.path.join(self.tmp, "hold"), max_to_keep=1)
        x = ht.arange(8, dtype=ht.float32, split=0)
        mgr.save(1, {"x": x})
        step1 = os.path.join(self.tmp, "hold", "step_1")
        with _ckpt._hold_restore(step1):
            mgr.save(2, {"x": x * 2.0})
            # held open: rotation must skip it, loudly
            self.assertTrue(os.path.exists(step1))
            events = [
                e for e in diagnostics.report()["resilience_events"]
                if e["kind"] == "prune-deferred"
            ]
            self.assertTrue(events)
        # released: the next save's rotation collects it
        mgr.save(3, {"x": x * 3.0})
        self.assertFalse(os.path.exists(step1))
        self.assertEqual(mgr.all_steps(), [3])
        mgr.close()

    def test_prune_deferred_on_cross_process_hold_sentinel(self):
        """A ``<dir>.hold.*`` sentinel left by another process's in-flight
        restore (shared filesystem) defers pruning exactly like a local hold."""
        mgr = ht.CheckpointManager(os.path.join(self.tmp, "xhold"), max_to_keep=1)
        x = ht.arange(8, dtype=ht.float32, split=0)
        mgr.save(1, {"x": x})
        step1 = os.path.join(self.tmp, "xhold", "step_1")
        sentinel = f"{step1}.hold.p1.99999.1"
        with open(sentinel, "w") as fh:
            fh.write("in-flight restore hold\n")
        mgr.save(2, {"x": x * 2.0})
        self.assertTrue(os.path.exists(step1))
        self.assertTrue([
            e for e in diagnostics.report()["resilience_events"]
            if e["kind"] == "prune-deferred" and "step_1" in e["detail"]
        ])
        os.unlink(sentinel)
        mgr.save(3, {"x": x * 3.0})
        self.assertFalse(os.path.exists(step1))
        mgr.close()

    def test_prune_failure_is_loud(self):
        mgr = ht.CheckpointManager(os.path.join(self.tmp, "loud"), max_to_keep=1)
        x = ht.arange(8, dtype=ht.float32, split=0)
        mgr.save(1, {"x": x})
        resilience.arm_fault_plan(
            [{"site": "checkpoint.prune", "on_call": 1, "count": 9999,
              "kind": "raise"}]
        )
        with self.assertRaises(resilience.FaultInjected):
            mgr.save(2, {"x": x * 2.0})
        resilience.disarm_fault_plan()
        events = [
            e for e in diagnostics.report()["resilience_events"]
            if e["kind"] == "prune-failed"
        ]
        self.assertTrue(events)
        mgr.close()


class TestDiagnosticsGauges(_CkptCase):
    def test_gathered_and_written_bytes_recorded(self):
        was = diagnostics.enabled()
        diagnostics.enable()
        try:
            diagnostics.reset()
            path = os.path.join(self.tmp, "gauge")
            tree = {"x": ht.array(np.ones((16, 4), np.float32), split=0)}
            ht.save_checkpoint(tree, path)
            counters = diagnostics.report()["counters"]
            self.assertEqual(counters.get("checkpoint.gathered_bytes"), 16 * 4 * 4)
            self.assertEqual(counters.get("checkpoint.written_bytes"), 16 * 4 * 4)
        finally:
            if not was:
                diagnostics.disable()


class TestSidecarMerge(_CkptCase):
    def test_writer_merges_peer_sidecars_into_manifest(self):
        """The multi-controller manifest assembly: rank 0 folds the other
        processes' sidecar chunk metadata in, verifies grid completeness, and
        commits — unit-tested here because single-process suites can never
        run two controllers."""
        import hashlib

        tmpdir = os.path.join(self.tmp, "asm.tmp.v2")
        target = os.path.join(self.tmp, "asm")
        os.makedirs(tmpdir)
        n, shards = 8, 2
        entry = {"shape": [n], "dtype": "float32", "split": 0, "shards": shards}
        payloads = {
            0: np.arange(4, dtype=np.float32).tobytes(),
            4: np.arange(4, 8, dtype=np.float32).tobytes(),
        }
        metas = {}
        for off, payload in payloads.items():
            fname = _ckpt._chunk_file(0, off // 4)
            with open(os.path.join(tmpdir, fname), "wb") as fh:
                fh.write(payload)
            metas[off] = {
                "file": fname, "offset": off, "rows": 4,
                "nbytes": len(payload),
                "sha256": hashlib.sha256(payload).hexdigest(),
            }
        # rank 1's metadata arrives via its sidecar, rank 0's in memory
        with open(os.path.join(tmpdir, "chunkmeta.p1.json"), "w") as fh:
            json.dump({"0": [metas[4]]}, fh)
        _ckpt._assemble_and_commit_v2(target, tmpdir, [entry], {0: [metas[0]]})
        manifest = _ckpt.read_manifest(target)
        self.assertEqual(
            [c["offset"] for c in manifest["leaves"][0]["chunks"]], [0, 4]
        )
        self.assertEqual(_ckpt.verify_checkpoint(target), [])
        back = ht.load_checkpoint({"x": ht.zeros((n,), split=0)}, target)
        self.assert_array_equal(back["x"], np.arange(n, dtype=np.float32))

    def test_incomplete_chunk_grid_refuses_commit(self):
        tmpdir = os.path.join(self.tmp, "inc.tmp.v2")
        target = os.path.join(self.tmp, "inc")
        os.makedirs(tmpdir)
        entry = {"shape": [8], "dtype": "float32", "split": 0, "shards": 2}
        with self.assertRaises(_ckpt.CheckpointWriteFailed):
            _ckpt._assemble_and_commit_v2(target, tmpdir, [entry], {})
        self.assertFalse(os.path.exists(target))


class TestEnvCannedPlan(_CkptCase):
    def test_env_canned_plan_fires_at_v2_sites(self):
        """The chaos-CI shape: a HEAT_TPU_FAULT_PLAN armed from the
        environment fires at the new checkpoint sites in a hermetic child."""
        plan = json.dumps([
            {"site": "checkpoint.chunk_write", "on_call": 2, "count": 1,
             "kind": "raise"},
            {"site": "checkpoint.commit", "on_call": 1, "count": 1,
             "kind": "raise"},
        ])
        code = (
            "import json, numpy as np\n"
            "import heat_tpu as ht\n"
            "from heat_tpu.core import checkpoint as ck, resilience\n"
            "import sys\n"
            "out = sys.argv[1]\n"
            "assert resilience._armed, 'env plan must arm at import'\n"
            "x = ht.array(np.arange(24, dtype=np.float32), split=0)\n"
            "failed = 0\n"
            "try:\n"
            "    ht.save_checkpoint({'x': x}, out + '/c')\n"
            "except Exception:\n"
            "    failed = 1\n"
            "stats = resilience.resilience_stats()\n"
            "print(json.dumps({'failed': failed,\n"
            "                  'fired': stats['faults_fired'],\n"
            "                  'calls': stats['site_calls']}))\n"
        )
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=3",
            HEAT_TPU_FAULT_PLAN=plan,
        )
        proc = subprocess.run(
            [sys.executable, "-c", code, self.tmp],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        rec = json.loads(proc.stdout.strip().splitlines()[-1])
        self.assertGreaterEqual(rec["fired"], 1, rec)
        self.assertIn("checkpoint.chunk_write", rec["calls"], rec)


if __name__ == "__main__":
    import unittest

    unittest.main()
