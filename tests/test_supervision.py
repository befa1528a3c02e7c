"""Tests for ``ht.supervision`` — the distributed supervision plane (ISSUE 14).

Single-process coverage of the machinery the real kill-a-rank proof
(tests/test_multiprocess.py::test_multiprocess_supervision +
tests/_mp_supervision_worker.py) exercises across processes: the heartbeat
state machine driven by an injected clock over a :class:`LocalCoordinator`,
watchdog fire/disarm, sentinel poll ordering at every chokepoint, the
supervised coordination waits' typed timeouts, the deterministic ``peer-dead``
fault kind, ``run_supervised``'s restart budget, the serving pool's failover
accounting, and the HLO byte-parity proof that an armed-but-idle plane never
touches a compiled program.
"""

import glob
import json
import os
import tempfile
import threading
import time
import unittest

import numpy as np

import heat_tpu as ht
import jax
from heat_tpu.core import _executor, checkpoint, diagnostics, resilience, supervision
from heat_tpu.testing import program_text


class _SupervisionCase(unittest.TestCase):
    """Every test leaves the plane disarmed, abort-free, and knob-default."""

    def setUp(self):
        self._env = dict(os.environ)
        supervision.disarm()
        supervision.reset_abort()
        resilience.disarm_fault_plan()
        resilience.reset(clear_breakers=True)

    def tearDown(self):
        supervision.disarm()
        supervision.reset_abort()
        resilience.disarm_fault_plan()
        resilience.reset(clear_breakers=True)
        for key in set(os.environ) - set(self._env):
            del os.environ[key]
        os.environ.update(self._env)
        supervision.reload_env_knobs()
        _executor.reload_env_knobs()


class TestHeartbeatStateMachine(_SupervisionCase):
    """The monitor with an injected clock: detection is a pure function of
    observed beat changes on the observer's clock."""

    def _armed_pair(self, timeout=5.0):
        co = supervision.LocalCoordinator()
        clock = [0.0]
        mon = supervision.arm(co, rank=0, nprocs=2, peer_timeout_s=timeout,
                              clock=lambda: clock[0], start_thread=False)
        return co, clock, mon

    def test_silent_peer_past_budget_posts_typed_abort(self):
        co, clock, mon = self._armed_pair()
        mon.step(0.0)
        self.assertIsNone(supervision.aborted())
        clock[0] = 4.9
        mon.step(4.9)  # inside budget: no abort
        self.assertIsNone(supervision.aborted())
        clock[0] = 5.1
        mon.step(5.1)
        payload = supervision.aborted()
        self.assertIsNotNone(payload)
        self.assertEqual(payload["kind"], "peer-failed")
        self.assertEqual(payload["rank"], 1)
        self.assertGreater(payload["last_seen_s"], 5.0)
        with self.assertRaises(resilience.PeerFailed) as ctx:
            supervision.poll("test.site")
        self.assertEqual(ctx.exception.rank, 1)
        self.assertEqual(ctx.exception.detected_by, 0)

    def test_beating_peer_never_aborts(self):
        co, clock, mon = self._armed_pair()
        for t in (0.0, 4.0, 8.0, 12.0):
            co.set("heat_tpu/sup/%d/hb/1" % mon.generation, f"beat-{t}", True)
            clock[0] = t
            mon.step(t)
        self.assertIsNone(supervision.aborted())

    def test_stalled_beat_value_is_silence(self):
        # a peer whose beat value stops ADVANCING is as dead as one whose key
        # vanishes — liveness is change, not presence
        co, clock, mon = self._armed_pair()
        co.set(f"heat_tpu/sup/{mon.generation}/hb/1", "42", True)
        mon.step(0.0)
        clock[0] = 5.5
        mon.step(5.5)  # same value 42 for 5.5s > budget
        payload = supervision.aborted()
        self.assertIsNotNone(payload)
        self.assertEqual(payload["rank"], 1)

    def test_departed_peer_is_not_a_failure(self):
        co, clock, mon = self._armed_pair()
        co.set(f"heat_tpu/sup/{mon.generation}/bye/1", "1", True)
        clock[0] = 100.0
        mon.step(100.0)
        self.assertIsNone(supervision.aborted())

    def test_second_monitor_adopts_peer_posted_sentinel(self):
        co, clock, mon = self._armed_pair()
        # a "remote" rank posted the sentinel directly on the shared channel
        # — at the production key, which sits strictly UNDER the abort
        # prefix (directory semantics: get_dir never returns a key equal to
        # the prefix itself, on the real service or this double)
        co.set(mon.sentinel_key, json.dumps(
            {"kind": "peer-failed", "rank": 1, "last_seen_s": 9.9, "by": 1}
        ), False)
        mon.step(0.1)
        payload = supervision.aborted()
        self.assertEqual(payload["by"], 1)
        self.assertEqual(payload["last_seen_s"], 9.9)

    def test_local_coordinator_matches_real_directory_semantics(self):
        # the contract the real DistributedRuntimeService exhibits (verified
        # against jaxlib 0.4.37): dir-get returns keys strictly under the
        # prefix — NEVER one exactly equal to it — and delete removes the
        # key and its whole subtree. The double must match, or tests pass
        # on paths (sentinel adoption, barrier rank listing) that are dead
        # code in production.
        co = supervision.LocalCoordinator()
        co.set("ns/abort", "exact")
        co.set("ns/abort/0", "child")
        co.set("ns/hb/1", "7")
        self.assertEqual(co.get_dir("ns/abort"), [("ns/abort/0", "child")])
        self.assertEqual(co.get_dir("ns/abort/"), [("ns/abort/0", "child")])
        self.assertEqual(co.get_dir("ns/hb"), [("ns/hb/1", "7")])
        co.delete("ns/abort")  # directory delete: exact key + subtree
        self.assertEqual(co.get_dir("ns/abort"), [])
        self.assertEqual(co.wait("ns/hb/1", 100), "7")  # exact get still works

    def test_sentinel_roundtrip_posts_under_abort_prefix(self):
        # post_abort -> check_sentinel -> reset_abort must work through
        # directory semantics end to end: the sentinel lives below the
        # prefix and reset deletes it from the store (an armed monitor
        # would otherwise re-adopt it every tick)
        co = supervision.LocalCoordinator()
        mon = supervision.arm(co, rank=0, nprocs=2, peer_timeout_s=50.0,
                              start_thread=False)
        supervision.post_abort("peer-failed", rank=1, last_seen_s=1.0)
        self.assertEqual(len(co.get_dir(mon.abort_key)), 1)
        supervision.reset_abort()
        self.assertIsNone(supervision.aborted())
        self.assertEqual(co.get_dir(mon.abort_key), [])
        mon.check_sentinel()  # nothing left to re-adopt
        self.assertIsNone(supervision.aborted())


class TestSentinelPollOrdering(_SupervisionCase):
    def test_idle_poll_is_a_noop(self):
        supervision.poll("anything")  # disarmed AND armed-idle
        co = supervision.LocalCoordinator()
        supervision.arm(co, rank=0, nprocs=1, start_thread=False)
        supervision.poll("anything")

    def test_post_abort_then_poll_raises_each_time(self):
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=2,
                        start_thread=False)
        supervision.post_abort("peer-failed", rank=1, last_seen_s=3.0)
        for _ in range(3):  # fresh exception per poll, payload stable
            with self.assertRaises(resilience.PeerFailed) as ctx:
                supervision.poll("site.x")
            self.assertEqual(ctx.exception.rank, 1)

    def test_collective_timeout_payload_maps_to_typed(self):
        supervision.arm(supervision.LocalCoordinator(), rank=2, nprocs=4,
                        start_thread=False)
        supervision.post_abort("collective-timeout", site="comm.psum",
                               elapsed_s=12.5)
        with self.assertRaises(resilience.CollectiveTimeout) as ctx:
            supervision.poll()
        self.assertEqual(ctx.exception.site, "comm.psum")
        self.assertEqual(ctx.exception.elapsed_s, 12.5)
        self.assertEqual(ctx.exception.detected_by, 2)

    def test_first_sentinel_wins(self):
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=3,
                        start_thread=False)
        supervision.post_abort("peer-failed", rank=2, last_seen_s=1.0)
        supervision.post_abort("peer-failed", rank=1, last_seen_s=9.0)
        self.assertEqual(supervision.aborted()["rank"], 2)

    def test_communication_chokepoint_delivers_typed(self):
        # the _guarded chokepoint: a layout op must raise PeerFailed, and
        # recover after the abort clears
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=2,
                        start_thread=False)
        supervision.post_abort("peer-failed", rank=1, last_seen_s=2.0)
        with self.assertRaises(resilience.PeerFailed):
            ht.arange(16, split=0).parray  # noqa: B018 - forces comm.shard
        supervision.reset_abort()
        self.assertEqual(float(ht.arange(16, split=0).sum().item()), 120.0)

    def test_scheduler_predispatch_sheds_typed(self):
        # queued work behind a paused scheduler is shed with the typed abort
        # at the pre-dispatch checkpoint, and lands in the lifecycle ledger
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=2,
                        start_thread=False)
        sched = _executor._get_scheduler()
        self.assertTrue(sched.wait_idle(10.0))
        base = sched.stats()["lifecycle"]["shed"]
        for _ in range(2):  # past the warm-up threshold: the next force queues
            ((ht.arange(32, split=0) + 1.0) * 2.0).numpy()
        sched.pause()
        outcome = {}

        def force():
            try:
                x = ht.arange(32, split=0)
                y = (x + 1.0) * 2.0
                y.parray  # noqa: B018 - the force parks in the paused queue
                outcome["error"] = None
            except BaseException as exc:
                outcome["error"] = exc

        t = threading.Thread(target=force, daemon=True)
        try:
            t.start()
            deadline = time.monotonic() + 10.0
            while sched.depth() == 0 and time.monotonic() < deadline:
                time.sleep(0.005)
            self.assertGreater(sched.depth(), 0)
            supervision.post_abort("peer-failed", rank=1, last_seen_s=2.0)
        finally:
            sched.resume()
        t.join(timeout=30.0)
        self.assertFalse(t.is_alive(), "forced read stayed blocked")
        self.assertIsInstance(outcome["error"], resilience.PeerFailed)
        self.assertTrue(sched.wait_idle(10.0))
        self.assertGreater(sched.stats()["lifecycle"]["shed"], base)
        supervision.reset_abort()
        np.testing.assert_allclose(
            ((ht.arange(32, split=0) + 1.0) * 2.0).numpy(),
            (np.arange(32, dtype=np.float32) + 1.0) * 2.0,
        )


class TestWatchdog(_SupervisionCase):
    def _arm_watchdog(self, budget="0.25"):
        os.environ["HEAT_TPU_COLLECTIVE_TIMEOUT_S"] = budget
        supervision.reload_env_knobs()
        clock = [0.0]
        mon = supervision.arm(supervision.LocalCoordinator(), rank=0,
                              nprocs=1, peer_timeout_s=100.0,
                              clock=lambda: clock[0], start_thread=False)
        return clock, mon

    def test_overdue_window_fires_typed_with_postmortem(self):
        flight_dir = tempfile.mkdtemp(prefix="ht-sup-flight-")
        os.environ["HEAT_TPU_FLIGHT_DIR"] = flight_dir
        clock, mon = self._arm_watchdog()
        with self.assertRaises(resilience.CollectiveTimeout) as ctx:
            with supervision.watch("comm.stuck"):
                clock[0] = 1.0
                mon.watchdog_scan(1.0)  # the monitor tick during the hang
        self.assertEqual(ctx.exception.site, "comm.stuck")
        self.assertGreaterEqual(ctx.exception.elapsed_s, 1.0)
        # survivors see the sentinel as the same typed class
        payload = supervision.aborted()
        self.assertEqual(payload["kind"], "collective-timeout")
        self.assertEqual(payload["site"], "comm.stuck")
        # and the watchdog shipped its own post-mortem trigger kind
        dumps = glob.glob(os.path.join(flight_dir, "*.json"))
        self.assertTrue(any("supervision-watchdog" in d for d in dumps), dumps)
        with open(sorted(dumps)[0]) as f:
            dump = json.load(f)
        self.assertTrue(
            any(e["kind"] == "watchdog" for e in dump["events"]), dump["events"]
        )

    def test_window_disarms_on_exit(self):
        clock, mon = self._arm_watchdog()
        with supervision.watch("comm.fine"):
            clock[0] = 0.1
        clock[0] = 10.0
        mon.watchdog_scan(10.0)  # window already gone: nothing to flag
        self.assertIsNone(supervision.aborted())

    def test_watchdog_off_by_default(self):
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=1,
                        start_thread=False)
        self.assertEqual(supervision.collective_timeout_s(), 0.0)
        with supervision.watch("comm.cheap"):
            pass
        self.assertEqual(supervision.supervision_stats()["watch_windows"], 0)


class TestSupervisedCoordWaits(_SupervisionCase):
    def test_kv_wait_returns_value(self):
        co = supervision.LocalCoordinator()
        threading.Timer(0.1, lambda: co.set("k", "v42")).start()
        self.assertEqual(
            supervision.kv_wait("k", 5_000, site="t.kv", coordinator=co), "v42"
        )

    def test_kv_wait_exhaustion_is_typed_and_names_the_key(self):
        co = supervision.LocalCoordinator()
        t0 = time.monotonic()
        with self.assertRaises(resilience.CoordinationTimeout) as ctx:
            supervision.kv_wait("missing/key", 200, site="t.kv",
                                coordinator=co)
        self.assertLess(time.monotonic() - t0, 5.0)
        self.assertEqual(ctx.exception.key, "missing/key")
        self.assertEqual(ctx.exception.timeout_ms, 200)
        self.assertEqual(ctx.exception.site, "t.kv")

    def test_kv_wait_aborts_typed_mid_wait(self):
        # the wait must deliver PeerFailed from the sentinel well before its
        # own (long) budget — the no-hang contract
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=2,
                        start_thread=False)
        co = supervision.LocalCoordinator()
        threading.Timer(
            0.15, lambda: supervision.post_abort("peer-failed", rank=1,
                                                 last_seen_s=2.0)
        ).start()
        t0 = time.monotonic()
        with self.assertRaises(resilience.PeerFailed):
            supervision.kv_wait("never", 60_000, site="t.kv", coordinator=co)
        self.assertLess(time.monotonic() - t0, 30.0)

    def test_kv_barrier_names_missing_ranks(self):
        co = supervision.LocalCoordinator()
        co.set("bar/x/2", "1")  # rank 2 arrived, 1 and 3 never do
        with self.assertRaises(resilience.CoordinationTimeout) as ctx:
            supervision.kv_barrier("bar/x", nprocs=4, rank=0, timeout_ms=250,
                                   site="t.bar", coordinator=co)
        self.assertEqual(ctx.exception.waiting_on, [1, 3])

    def test_kv_barrier_missing_ranks_with_double_digit_world(self):
        # the arrived set comes from ONE directory listing of the namespace,
        # so rank 1 arriving must not read as rank 10/11 arrived (a per-rank
        # startswith probe would alias them)
        co = supervision.LocalCoordinator()
        for r in (1, 11):
            co.set(f"bar/w/{r}", "1")
        with self.assertRaises(resilience.CoordinationTimeout) as ctx:
            supervision.kv_barrier("bar/w", nprocs=12, rank=0, timeout_ms=250,
                                   site="t.bar", coordinator=co)
        self.assertEqual(ctx.exception.waiting_on,
                         [2, 3, 4, 5, 6, 7, 8, 9, 10])

    def test_kv_barrier_completes(self):
        co = supervision.LocalCoordinator()
        for r in (1, 2):
            co.set(f"bar/y/{r}", "1")
        supervision.kv_barrier("bar/y", nprocs=3, rank=0, timeout_ms=5_000,
                               site="t.bar", coordinator=co)

    def test_unified_knob_reload(self):
        os.environ["HEAT_TPU_COORD_TIMEOUT_MS"] = "12345"
        self.assertNotEqual(supervision.coord_timeout_ms(), 12345)  # memoised
        _executor.reload_env_knobs()  # the one re-read point covers supervision
        self.assertEqual(supervision.coord_timeout_ms(), 12345)


class TestPeerDeadFault(_SupervisionCase):
    def test_peer_dead_fires_hook_then_exits(self):
        calls = []
        orig_exit = resilience._peer_dead_exit
        resilience._peer_dead_exit = lambda status: calls.append(status)
        try:
            resilience.arm_fault_plan(
                [{"site": "train.step", "kind": "peer-dead", "on_call": 2}]
            )
            resilience.maybe_fault("train.step")  # call 1: nothing
            self.assertEqual(calls, [])
            with self.assertRaises(resilience.FaultInjected):
                resilience.maybe_fault("train.step")  # call 2: dies
            self.assertEqual(calls, [resilience.PEER_DEAD_EXIT_STATUS])
        finally:
            resilience._peer_dead_exit = orig_exit

    def test_rank_targeting(self):
        calls = []
        orig_exit = resilience._peer_dead_exit
        resilience._peer_dead_exit = lambda status: calls.append(status)
        try:
            resilience.set_fault_rank(0)
            resilience.arm_fault_plan([
                {"site": "s", "kind": "peer-dead", "on_call": 1, "rank": 3},
            ])
            resilience.maybe_fault("s")  # targeted at rank 3; we are rank 0
            self.assertEqual(calls, [])
            resilience.set_fault_rank(3)
            resilience.reset()
            with self.assertRaises(resilience.FaultInjected):
                resilience.maybe_fault("s")
            self.assertEqual(calls, [resilience.PEER_DEAD_EXIT_STATUS])
        finally:
            resilience._peer_dead_exit = orig_exit
            resilience.set_fault_rank(jax.process_index())

    def test_plan_validation(self):
        with self.assertRaises(ValueError):
            resilience.arm_fault_plan(
                [{"site": "s", "kind": "peer-dead", "rank": -2}]
            )
        with self.assertRaises(ValueError):
            resilience.arm_fault_plan([{"site": "s", "kind": "no-such-kind"}])


class TestRunSupervised(_SupervisionCase):
    def _manager(self):
        return checkpoint.CheckpointManager(
            tempfile.mkdtemp(prefix="ht-sup-ckpt-"), max_to_keep=8
        )

    def test_restart_restores_and_resumes(self):
        mgr = self._manager()
        tpl = {"w": ht.zeros((12,), split=0)}
        fail_once = [True]

        def step_fn(step, state):
            if step == 3 and fail_once[0]:
                fail_once[0] = False
                raise resilience.PeerFailed(1, 2.0)
            return {"w": state["w"] + 1.0}

        out = resilience.run_supervised(
            step_fn, mgr, template=tpl,
            state={"w": ht.zeros((12,), split=0)}, max_steps=6,
        )
        self.assertEqual(out["steps"], 6)
        self.assertEqual(out["restarts"], 1)
        # no step double-applied, none skipped: 6 increments exactly
        self.assertEqual(float(out["state"]["w"].sum().item()), 72.0)

    def test_budget_exhaustion_reraises_typed(self):
        mgr = self._manager()
        tpl = {"w": ht.zeros((4,), split=0)}

        def always_fails(step, state):
            raise resilience.CollectiveTimeout("comm.x", 9.0)

        t0 = time.monotonic()
        with self.assertRaises(resilience.CollectiveTimeout):
            resilience.run_supervised(
                always_fails, mgr, template=tpl,
                state={"w": ht.zeros((4,), split=0)}, max_steps=4,
                policy=resilience.Policy(max_attempts=2, backoff_base=0.01),
            )
        self.assertLess(time.monotonic() - t0, 30.0)

    def test_unrelated_errors_propagate_untouched(self):
        mgr = self._manager()

        def boom(step, state):
            raise ValueError("not a supervision failure")

        with self.assertRaises(ValueError):
            resilience.run_supervised(
                boom, mgr, template={"w": ht.zeros((4,), split=0)},
                state={"w": ht.zeros((4,), split=0)}, max_steps=2,
            )


class TestModelPoolFailover(_SupervisionCase):
    def test_on_peer_failure_sheds_typed_and_reopens(self):
        pool = ht.serving.ModelPool({"w": ht.zeros((8,), split=0)},
                                    name="failover-unit")
        pool._rebind({"w": ht.ones((8,), split=0)}, None)
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=2,
                        start_thread=False)
        supervision.post_abort("peer-failed", rank=1, last_seen_s=2.0)
        entry = pool.on_peer_failure(
            resilience.PeerFailed(1, 2.0), drain_timeout_s=5.0
        )
        self.assertEqual(entry["kind"], "peer-failover")
        self.assertIsNone(supervision.aborted())  # sentinel cleared
        sched = _executor._get_scheduler()
        self.assertFalse(sched.draining())  # admission reopened
        # the pool still serves its generation
        self.assertEqual(float(pool.state["w"].sum().item()), 8.0)
        ledger = pool.swap_ledger()
        self.assertEqual(ledger[-1]["kind"], "peer-failover")


class TestHLOByteParity(_SupervisionCase):
    """Armed-but-idle supervision must compile byte-identical HLO: the plane
    exists strictly OUTSIDE traced program bodies (same contract as
    resilience/profiler/telemetry)."""

    @staticmethod
    def _chain_hlos():
        _executor.clear_executor_cache()
        np_x = np.arange(8, dtype=np.float32)
        np_y = np.full(8, 0.5, dtype=np.float32)
        for _ in range(2):  # conftest's HEAT_TPU_JIT_THRESHOLD=2 warm-up
            x = ht.array(np_x, split=0)
            y = ht.array(np_y, split=0)
            (x + y).sum().parray  # noqa: B018 - forces the chain
        with _executor._lock:
            entries = [
                e for e in _executor._programs.values()
                if e is not _executor.UNSUPPORTED and e.arg_specs is not None
            ]
        texts = {}
        for entry in entries:
            fn = jax.jit(
                entry._traced(),
                out_shardings=entry.out_shardings,
                keep_unused=entry.donate_index is not None,
            )
            texts[entry.label] = program_text(fn.lower(*entry.arg_specs).compile())
        return texts

    def test_hlo_byte_parity_armed_idle(self):
        diagnostics.disable()
        baseline = self._chain_hlos()
        self.assertGreaterEqual(len(baseline), 2, list(baseline))
        os.environ["HEAT_TPU_COLLECTIVE_TIMEOUT_S"] = "30"
        supervision.reload_env_knobs()
        supervision.arm(supervision.LocalCoordinator(), rank=0, nprocs=1,
                        start_thread=False)
        try:
            armed = self._chain_hlos()
        finally:
            supervision.disarm()
            del os.environ["HEAT_TPU_COLLECTIVE_TIMEOUT_S"]
            supervision.reload_env_knobs()
        self.assertEqual(armed, baseline,
                         "arming supervision changed compiled HLO")
        again = self._chain_hlos()
        self.assertEqual(again, baseline,
                         "disarming did not restore byte-identical HLO")


if __name__ == "__main__":
    unittest.main()
