"""Chaos / resilience suite (ISSUE 6 tentpole).

Injects deterministic faults (``HEAT_TPU_FAULT_PLAN`` semantics via
``resilience.arm_fault_plan``) at the four instrumented site families —
collective invocation, executor compile, executor execute (including the
donation-armed case), and checkpoint writes — and asserts:

- recovery is **bit-identical** to the fault-free run (retry or eager fallback,
  never silently different numerics);
- the diagnostics counters/events explain what happened (retries, fallbacks,
  breaker transitions, quarantines);
- compiled HLO is **byte-identical** whether or not a fault plan is armed
  (the resilience layer lives strictly outside traced program bodies);
- the policy engine and circuit breaker follow their documented state machines
  under injectable clocks (zero wall-time in tests).
"""

import json
import os
import subprocess
import sys
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import _executor, diagnostics, resilience
from heat_tpu.testing import TestCase, program_text

_OLD_THRESHOLD = None


def setUpModule():
    # chaos tests assert the production compile-on-first-miss behaviour (the
    # suite conftest raises the warm-up threshold for signature-diverse tests)
    global _OLD_THRESHOLD
    _OLD_THRESHOLD = os.environ.get("HEAT_TPU_JIT_THRESHOLD")
    os.environ["HEAT_TPU_JIT_THRESHOLD"] = "1"
    _executor.reload_env_knobs()


def tearDownModule():
    if _OLD_THRESHOLD is None:
        os.environ.pop("HEAT_TPU_JIT_THRESHOLD", None)
    else:
        os.environ["HEAT_TPU_JIT_THRESHOLD"] = _OLD_THRESHOLD
    _executor.reload_env_knobs()


class _ResilienceCase(TestCase):
    """Isolation: every test starts disarmed with fresh counters/breakers and
    restores the diagnostics switches it flips."""

    def setUp(self):
        resilience.disarm_fault_plan()
        resilience.reset(clear_breakers=True)
        self._was_enabled = diagnostics._enabled
        self._was_tracing = diagnostics._tracing
        diagnostics.reset()

    def tearDown(self):
        resilience.disarm_fault_plan()
        resilience.reset(clear_breakers=True)
        diagnostics._enabled = self._was_enabled
        diagnostics._tracing = self._was_tracing

    @staticmethod
    def _counters():
        with diagnostics._lock:
            return dict(diagnostics._counters)

    @staticmethod
    def _resilience_events():
        with diagnostics._lock:
            return list(diagnostics._resilience_events)


class _FakeClock:
    def __init__(self, t0=0.0):
        self.t = t0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


# ------------------------------------------------------------------ policy engine
class TestPolicy(_ResilienceCase):
    def test_backoff_sequence_is_deterministic(self):
        pol = resilience.Policy(max_attempts=5, backoff_base=0.5, jitter=0.0)
        sleeps = []
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 4:
                raise OSError("transient")
            return "ok"

        out = pol.run("t.backoff", flaky, sleep=sleeps.append)
        self.assertEqual(out, "ok")
        self.assertEqual(calls["n"], 4)
        self.assertEqual(sleeps, [0.5, 1.0, 2.0])

    def test_exhaustion_reraises_the_original_exception(self):
        pol = resilience.Policy(max_attempts=3, backoff_base=0.1, jitter=0.0)
        sleeps = []
        with self.assertRaisesRegex(ValueError, "boom"):
            pol.run(
                "t.exhaust",
                lambda: (_ for _ in ()).throw(ValueError("boom")),
                sleep=sleeps.append,
            )
        self.assertEqual(sleeps, [0.1, 0.2])  # no sleep after the final attempt
        kinds = [e["kind"] for e in self._resilience_events() if e["site"] == "t.exhaust"]
        self.assertEqual(kinds, ["retry", "retry", "exhausted"])

    def test_deadline_bounds_unlimited_attempts(self):
        pol = resilience.Policy(
            max_attempts=None, backoff_base=10.0, jitter=0.0,
            deadline_s=35.0, max_delay_s=10.0,
        )
        clock = _FakeClock()
        calls = {"n": 0}

        def always_down():
            calls["n"] += 1
            raise TimeoutError("down")

        with self.assertRaises(TimeoutError):
            pol.run("t.deadline", always_down, sleep=clock.sleep, clock=clock)
        # attempts at t=0, 10, 20, 30; the next backoff would cross 35 s
        self.assertEqual(calls["n"], 4)

    def test_non_retryable_exception_propagates_immediately(self):
        pol = resilience.Policy(max_attempts=5, backoff_base=0.1,
                                retry_on=(OSError,))
        calls = {"n": 0}

        def typed():
            calls["n"] += 1
            raise KeyError("not retryable")

        with self.assertRaises(KeyError):
            pol.run("t.typed", typed, sleep=lambda _s: None)
        self.assertEqual(calls["n"], 1)

    def test_unbounded_without_deadline_is_rejected(self):
        with self.assertRaises(ValueError):
            resilience.Policy(max_attempts=None)


# ------------------------------------------------------------------ circuit breaker
class TestCircuitBreaker(_ResilienceCase):
    def test_state_machine(self):
        clock = _FakeClock()
        br = resilience.CircuitBreaker(
            "t.breaker", failure_threshold=2, cooldown_s=60.0, clock=clock
        )
        self.assertEqual(br.state, resilience.CLOSED)
        br.record_failure("one")
        self.assertEqual(br.state, resilience.CLOSED)
        br.record_failure("two")
        self.assertEqual(br.state, resilience.OPEN)
        self.assertFalse(br.allows())  # short-circuit while open
        clock.t += 61.0
        self.assertEqual(br.state, resilience.HALF_OPEN)
        self.assertTrue(br.allows())  # the half-open trial
        br.record_failure("trial failed")
        self.assertEqual(br.state, resilience.OPEN)  # re-open restarts cooldown
        clock.t += 61.0
        self.assertTrue(br.allows())
        br.record_success()
        self.assertEqual(br.state, resilience.CLOSED)
        self.assertEqual(br.snapshot()["opens"], 2)

    def test_transitions_recorded_via_diagnostics(self):
        clock = _FakeClock()
        br = resilience.CircuitBreaker("t.events", failure_threshold=1,
                                       cooldown_s=5.0, clock=clock)
        br.record_failure("down")
        clock.t += 6.0
        br.allows()
        br.record_success()
        details = [
            e["detail"] for e in self._resilience_events()
            if e["site"] == "t.events" and e["kind"] == "breaker"
        ]
        self.assertTrue(any(d.startswith("closed->open") for d in details), details)
        self.assertTrue(any(d.startswith("open->half-open") for d in details), details)
        self.assertTrue(any(d.startswith("half-open->closed") for d in details), details)

    def test_success_resets_consecutive_failures(self):
        br = resilience.CircuitBreaker("t.reset", failure_threshold=2)
        br.record_failure()
        br.record_success()
        br.record_failure()
        self.assertEqual(br.state, resilience.CLOSED)

    def test_half_open_admits_exactly_one_probe_per_window(self):
        clock = _FakeClock()
        br = resilience.CircuitBreaker("t.probe", failure_threshold=1,
                                       cooldown_s=60.0, clock=clock)
        br.record_failure("down")
        clock.t += 61.0
        self.assertEqual(br.state, resilience.HALF_OPEN)
        self.assertTrue(br.allows())      # the ONE trial probe of this window
        self.assertFalse(br.allows())     # everyone else sees it as open
        self.assertFalse(br.allows())
        self.assertTrue(br.snapshot()["half_open_probe_out"])
        br.record_failure("trial failed")  # probe reports: re-open
        self.assertEqual(br.state, resilience.OPEN)
        clock.t += 61.0
        self.assertTrue(br.allows())      # fresh window, fresh single token
        self.assertFalse(br.allows())
        br.record_success()
        self.assertEqual(br.state, resilience.CLOSED)
        self.assertTrue(br.allows())      # closed: everyone passes again
        self.assertTrue(br.allows())

    def test_half_open_vanished_probe_forfeits_after_another_cooldown(self):
        clock = _FakeClock()
        br = resilience.CircuitBreaker("t.vanish", failure_threshold=1,
                                       cooldown_s=30.0, clock=clock)
        br.record_failure("down")
        clock.t += 31.0
        self.assertTrue(br.allows())   # probe holder... who never reports back
        self.assertFalse(br.allows())
        clock.t += 31.0                # a whole cooldown with no verdict
        self.assertTrue(br.allows())   # new window: the token re-grants
        self.assertFalse(br.allows())

    def test_half_open_deadline_failed_trial_releases_the_probe_token(self):
        clock = _FakeClock()
        br = resilience.CircuitBreaker("t.dlprobe", failure_threshold=1,
                                       cooldown_s=60.0, clock=clock)
        br.record_failure("down")
        clock.t += 61.0
        pol = resilience.Policy(max_attempts=3, backoff_base=0.0)

        def trial_whose_request_expired():
            raise resilience.DeadlineExceeded("budget gone mid-trial")

        with pytest.raises(resilience.DeadlineExceeded):
            pol.run("t.dlprobe", trial_whose_request_expired,
                    breaker=br, sleep=lambda s: None, clock=clock)
        # the trial said nothing about the backend: the token is released so
        # the NEXT caller probes now instead of waiting out another cooldown
        self.assertEqual(br.state, resilience.HALF_OPEN)
        self.assertTrue(br.allows())

    def test_half_open_concurrent_threads_get_one_probe(self):
        import threading

        clock = _FakeClock()
        br = resilience.CircuitBreaker("t.herd", failure_threshold=1,
                                       cooldown_s=60.0, clock=clock)
        br.record_failure("down")
        clock.t += 61.0
        barrier = threading.Barrier(16)
        grants = []

        def caller():
            barrier.wait()
            if br.allows():
                grants.append(threading.get_ident())

        threads = [threading.Thread(target=caller) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        self.assertEqual(
            len(grants), 1,
            f"{len(grants)} threads got the half-open probe (thundering herd)",
        )
        # the breaker re-probed a down backend ONCE, not 16 times
        self.assertGreaterEqual(br.snapshot()["short_circuits"], 15)


# ------------------------------------------------------------------ fault plans
class TestFaultPlan(_ResilienceCase):
    def test_fires_on_exact_nth_call_window(self):
        resilience.arm_fault_plan(
            [{"site": "t.site", "on_call": 3, "count": 2, "kind": "raise"}]
        )
        fired = []
        for _ in range(6):
            fired.append(resilience.fault_signal("t.site") is not None)
        self.assertEqual(fired, [False, False, True, True, False, False])

    def test_kinds_raise_their_exception_types(self):
        resilience.arm_fault_plan(
            [
                {"site": "t.raise", "kind": "raise"},
                {"site": "t.timeout", "kind": "timeout"},
                {"site": "t.down", "kind": "backend-down"},
            ]
        )
        with self.assertRaises(resilience.FaultInjected):
            resilience.maybe_fault("t.raise")
        with self.assertRaises(TimeoutError):  # InjectedTimeout is a TimeoutError
            resilience.maybe_fault("t.timeout")
        with self.assertRaises(resilience.InjectedBackendDown):
            resilience.maybe_fault("t.down")

    def test_disarm_restores_zero_cost_gate(self):
        resilience.arm_fault_plan([{"site": "t.site", "kind": "raise"}])
        self.assertTrue(resilience._armed)
        resilience.disarm_fault_plan()
        self.assertFalse(resilience._armed)
        self.assertIsNone(resilience.fault_signal("t.site"))
        self.assertEqual(resilience.fault_plan(), [])

    def test_json_string_and_validation(self):
        resilience.arm_fault_plan(
            '[{"site": "t.json", "on_call": 2, "kind": "torn-write", "fraction": 0.25}]'
        )
        plan = resilience.fault_plan()
        self.assertEqual(plan[0]["site"], "t.json")
        self.assertEqual(plan[0]["fraction"], 0.25)
        for bad in (
            "not json",
            '{"site": "x"}',  # not a list
            '[{"kind": "raise"}]',  # no site
            '[{"site": "x", "kind": "nope"}]',  # unknown kind
            '[{"site": "x", "on_call": 0}]',  # on_call < 1
            '[{"site": "x", "typo": 1}]',  # unknown key
        ):
            with self.assertRaises(ValueError):
                resilience.arm_fault_plan(bad)

    def test_env_plan_arms_at_import(self):
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            HEAT_TPU_FAULT_PLAN='[{"site": "e.site", "on_call": 5, "kind": "timeout"}]',
        )
        code = (
            "import importlib.util, os\n"
            "p = os.path.join(%r, 'heat_tpu', 'core', 'resilience.py')\n"
            "spec = importlib.util.spec_from_file_location('_r', p)\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "assert m._armed and m.fault_plan()[0]['site'] == 'e.site'\n"
            "print('ENV_PLAN_OK')\n"
        ) % (os.path.dirname(os.path.dirname(os.path.abspath(__file__))),)
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True, text=True,
            timeout=120,
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-500:])
        self.assertIn("ENV_PLAN_OK", proc.stdout)


# ------------------------------------------------------------------ chaos: collectives
class TestChaosCollective(_ResilienceCase):
    def test_shard_fault_retried_bit_identically(self):
        np_a = np.arange(10, dtype=np.float32)  # ragged at 3 and 8 devices
        baseline = ht.array(np_a, split=0)
        diagnostics.enable()
        resilience.arm_fault_plan(
            [{"site": "comm.shard", "on_call": 1, "kind": "raise"}]
        )
        x = ht.array(np_a, split=0)  # the layout call absorbs the injected fault
        np.testing.assert_array_equal(x.numpy(), baseline.numpy())
        self.assertGreaterEqual(self._counters().get("resilience.retry.comm.shard", 0), 1)

    def test_psum_fault_retried_inside_shard_map(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        comm = ht.get_comm()
        x = jnp.arange(comm.size, dtype=jnp.float32) + 1.0

        def total():
            # a fresh callable per run so shard_map re-traces (the collective
            # hook — and therefore the fault site — runs at trace time)
            fn = shard_map(
                lambda v: comm.psum(v, comm.axis_name),
                mesh=comm.mesh,
                in_specs=P(comm.axis_name),
                out_specs=P(),
            )
            return np.asarray(fn(x))

        expected = total()
        diagnostics.enable()
        resilience.arm_fault_plan(
            [{"site": "comm.psum", "on_call": 1, "kind": "timeout"}]
        )
        np.testing.assert_array_equal(total(), expected)
        self.assertGreaterEqual(self._counters().get("resilience.retry.comm.psum", 0), 1)


# ------------------------------------------------------------------ chaos: executor
class TestChaosExecutor(_ResilienceCase):
    def _chain(self, np_a):
        x = ht.array(np_a, split=0)
        return ((x + 1.0) * 2.0 - 0.5).numpy()

    def test_compile_fault_falls_back_to_eager_bit_identically(self):
        np_a = np.linspace(0.0, 1.0, 11, dtype=np.float32)
        expected = (np_a + 1.0) * 2.0 - 0.5
        _executor.clear_executor_cache()
        diagnostics.enable()
        resilience.arm_fault_plan(
            [{"site": "executor.compile", "on_call": 1, "count": 99, "kind": "raise"}]
        )
        got = self._chain(np_a)
        np.testing.assert_array_equal(got, expected)
        stats = ht.executor_stats()
        self.assertGreaterEqual(stats["eager_fallbacks"], 1)
        self.assertTrue(
            any(c.startswith("fallback.executor.") for c in self._counters()),
            self._counters(),
        )

    def test_transient_execute_fault_recovers_via_retry(self):
        np_a = np.linspace(-1.0, 1.0, 9, dtype=np.float32)
        expected = (np_a + 1.0) * 2.0 - 0.5
        _executor.clear_executor_cache()
        diagnostics.enable()
        resilience.arm_fault_plan(
            [{"site": "executor.execute", "on_call": 1, "count": 1, "kind": "raise"}]
        )
        got = self._chain(np_a)
        np.testing.assert_array_equal(got, expected)
        stats = ht.executor_stats()
        # one retry absorbed the fault: the compiled program ran, no fallback
        self.assertEqual(stats["eager_fallbacks"], 0)
        self.assertGreaterEqual(
            self._counters().get("resilience.retry.executor.execute", 0), 1
        )

    def test_execute_fault_with_pending_donation_no_data_loss(self):
        np_a = np.arange(16, dtype=np.float32)
        _executor.clear_executor_cache()
        resilience.arm_fault_plan(
            [{"site": "executor.execute", "on_call": 1, "count": 99, "kind": "raise"}]
        )
        x = ht.array(np_a, split=0)
        y = x * 2.0
        del x  # the plan becomes the leaf's sole reader: donation is armed
        np.testing.assert_array_equal(y.numpy(), np_a * 2.0)
        stats = ht.executor_stats()
        self.assertGreaterEqual(stats["eager_fallbacks"], 1)
        # the injected failure struck before dispatch: nothing was donated, the
        # eager replay read live buffers — zero bytes counted as donated
        self.assertEqual(stats["donated_bytes"], 0)

    def test_repeated_failures_quarantine_with_explained_reason(self):
        np_a = np.arange(12, dtype=np.float32)
        _executor.clear_executor_cache()
        os.environ["HEAT_TPU_QUARANTINE_AFTER"] = "3"
        _executor.reload_env_knobs()
        try:
            resilience.arm_fault_plan(
                [{"site": "executor.execute", "on_call": 1, "count": 9999, "kind": "raise"}]
            )
            for i in range(4):
                x = ht.array(np_a + i, split=0)
                y = (x + 1.0) * 3.0
                np.testing.assert_array_equal(y.numpy(), (np_a + i + 1.0) * 3.0)
            stats = ht.executor_stats()
            self.assertGreaterEqual(stats["eager_fallbacks"], 3)
            self.assertTrue(stats["quarantined"], stats)
            label, reason = next(iter(stats["quarantined"].items()))
            self.assertIn("FaultInjected", reason)
            self.assertIn("failure 3", reason)
        finally:
            os.environ.pop("HEAT_TPU_QUARANTINE_AFTER", None)
            _executor.reload_env_knobs()
        # quarantined: later identical dispatches take the eager path and stay correct
        x = ht.array(np_a, split=0)
        np.testing.assert_array_equal(((x + 1.0) * 3.0).numpy(), (np_a + 1.0) * 3.0)

# ----------------------------------------------------- chaos: async executor
class TestChaosAsyncExecutor(_ResilienceCase):
    """ISSUE 8: faults firing inside QUEUED executions (single and batched)
    must fall back via the op-by-op replay with no data loss — the scheduler
    thread is not the caller, so the failure contract has to travel through
    the dispatch-done future and the plan's held leaf references."""

    def _sched(self):
        import threading
        import time

        sched = _executor._get_scheduler()
        sched.resume()
        self.assertTrue(sched.wait_idle(30.0))
        return sched, threading, time

    def tearDown(self):
        sched = _executor._dispatch_scheduler
        if sched is not None:
            sched.resume()
            # wait_idle's bool must be checked: a timed-out wait here means a
            # stuck scheduler leaking into every later test
            self.assertTrue(sched.wait_idle(30.0), "scheduler stuck busy")
        super().tearDown()

    def test_fault_inside_queued_execution_replays_eager_no_data_loss(self):
        sched, threading, time = self._sched()
        _executor.clear_executor_cache()
        np_a = np.linspace(-2.0, 2.0, 16, dtype=np.float32)
        x = ht.array(np_a, split=0)
        expected = ((x + 1.0) * 2.0 - 0.5).numpy()  # warm + reference bits
        diagnostics.enable()
        resilience.arm_fault_plan(
            [{"site": "executor.execute", "on_call": 1, "count": 99,
              "kind": "raise"}]
        )
        got = {}
        errors = []

        def force():
            try:
                got["v"] = ((x + 1.0) * 2.0 - 0.5).numpy()
            except Exception as exc:
                errors.append(exc)

        sched.pause()  # the force must park in the queue, not run inline
        try:
            th = threading.Thread(target=force, daemon=True)
            th.start()
            deadline = time.monotonic() + 30.0
            while sched.depth() < 1 and time.monotonic() < deadline:
                time.sleep(0.005)
            self.assertGreaterEqual(sched.depth(), 1, "force never queued")
        finally:
            sched.resume()
        th.join(60.0)
        self.assertFalse(errors, errors)
        np.testing.assert_array_equal(got["v"], expected)
        stats = ht.executor_stats()
        self.assertGreaterEqual(stats["eager_fallbacks"], 1)
        self.assertEqual(stats.get("quarantined", {}), {})

    def test_fault_inside_batched_execution_no_data_loss(self):
        sched, threading, time = self._sched()
        _executor.clear_executor_cache()
        datas = [
            np.linspace(-1.0, 1.0, 16, dtype=np.float32) * (i + 1)
            for i in range(2)
        ]
        arrs = [ht.array(d, split=0) for d in datas]
        expected = [((a * 2.0) + 1.0).numpy() for a in arrs]  # warm, unbatched
        diagnostics.enable()
        got = [None, None]
        errors = []

        def force(i):
            try:
                got[i] = ((arrs[i] * 2.0) + 1.0).numpy()
            except Exception as exc:
                errors.append(exc)

        sched.pause()
        try:
            threads = [
                threading.Thread(target=force, args=(i,), daemon=True)
                for i in range(2)
            ]
            for th in threads:
                th.start()
            deadline = time.monotonic() + 30.0
            while sched.depth() < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            self.assertGreaterEqual(sched.depth(), 2, "forces never queued")
            # armed only now: the faults fire inside the BATCHED execution
            resilience.arm_fault_plan(
                [{"site": "executor.execute", "on_call": 1, "count": 99,
                  "kind": "raise"}]
            )
        finally:
            sched.resume()
        for th in threads:
            th.join(60.0)
        self.assertFalse(errors, errors)
        for i in range(2):
            np.testing.assert_array_equal(got[i], expected[i])
        stats = ht.executor_stats()
        # the batch degraded to singles, each single to the eager replay
        self.assertGreaterEqual(stats["eager_fallbacks"], 2)
        self.assertTrue(
            any(c.startswith("fallback.executor.") for c in self._counters()),
            self._counters(),
        )


# --------------------------------------------------- chaos: request lifecycle
class TestChaosLifecycle(_ResilienceCase):
    """ISSUE 10: the `deadline-exceeded` fault kind fired inside queued and
    batched executions, plus drain-under-load — in every case each
    outstanding ``PendingValue`` is fulfilled with a value or a TYPED error,
    never stranded, and over-deadline work is never salvaged by the eager
    replay (no quarantine: the signature stays healthy)."""

    def _sched(self):
        import threading
        import time

        sched = _executor._get_scheduler()
        sched.reopen()
        sched.resume()
        self.assertTrue(sched.wait_idle(30.0))
        return sched, threading, time

    def tearDown(self):
        sched = _executor._dispatch_scheduler
        if sched is not None:
            sched.reopen()
            sched.resume()
            self.assertTrue(sched.wait_idle(30.0), "scheduler stuck busy")
        super().tearDown()

    def test_deadline_fault_inside_queued_execution_is_typed_then_retries(self):
        sched, threading, time = self._sched()
        _executor.clear_executor_cache()
        np_a = np.linspace(-2.0, 2.0, 16, dtype=np.float32)
        x = ht.array(np_a, split=0)
        expected = ((x + 1.0) * 2.0 - 0.5).numpy()  # warm + reference bits
        diagnostics.enable()
        outcome = {}

        def force():
            try:
                outcome["v"] = ((x + 1.0) * 2.0 - 0.5).numpy()
            except Exception as exc:
                outcome["err"] = exc

        sched.pause()
        try:
            th = threading.Thread(target=force, daemon=True)
            th.start()
            deadline = time.monotonic() + 30.0
            while sched.depth() < 1 and time.monotonic() < deadline:
                time.sleep(0.005)
            self.assertGreaterEqual(sched.depth(), 1, "force never queued")
            # fires inside the QUEUED execution, exactly once
            resilience.arm_fault_plan(
                [{"site": "executor.execute", "on_call": 1, "count": 1,
                  "kind": "deadline-exceeded"}]
            )
        finally:
            sched.resume()
        th.join(60.0)
        # the reader got the TYPED error — not a hang, not a silent eager
        # replay of over-deadline work
        self.assertIn("err", outcome, outcome)
        self.assertIsInstance(outcome["err"], resilience.DeadlineExceeded)
        stats = ht.executor_stats()
        self.assertGreaterEqual(stats["expired_requests"], 1)
        self.assertEqual(stats["eager_fallbacks"], 0,
                         "over-deadline work must not replay eagerly")
        self.assertEqual(stats.get("quarantined", {}), {},
                         "a deadline expiry is not a signature failure")
        # the fault window has passed: the next force retries cleanly
        np.testing.assert_array_equal(((x + 1.0) * 2.0 - 0.5).numpy(), expected)

    def test_deadline_fault_inside_batched_execution_strands_nothing(self):
        sched, threading, time = self._sched()
        _executor.clear_executor_cache()
        datas = [
            np.linspace(-1.0, 1.0, 16, dtype=np.float32) * (i + 1)
            for i in range(2)
        ]
        arrs = [ht.array(d, split=0) for d in datas]
        expected = [((a * 2.0) + 1.0).numpy() for a in arrs]  # warm, unbatched
        diagnostics.enable()
        got = [None, None]
        errors = []

        def force(i):
            try:
                got[i] = ((arrs[i] * 2.0) + 1.0).numpy()
            except Exception as exc:
                errors.append(exc)

        sched.pause()
        try:
            threads = [
                threading.Thread(target=force, args=(i,), daemon=True)
                for i in range(2)
            ]
            for th in threads:
                th.start()
            deadline = time.monotonic() + 30.0
            while sched.depth() < 2 and time.monotonic() < deadline:
                time.sleep(0.005)
            self.assertGreaterEqual(sched.depth(), 2, "forces never queued")
            # fires once, inside the BATCHED call. The batch degrades to
            # singles; each single re-checks ITS OWN deadline (none armed
            # here), so both requests complete — per-item deadlines are why
            # one item's expiry must never fail a whole batch
            resilience.arm_fault_plan(
                [{"site": "executor.execute", "on_call": 1, "count": 1,
                  "kind": "deadline-exceeded"}]
            )
        finally:
            sched.resume()
        for th in threads:
            th.join(60.0)
        self.assertFalse(errors, errors)
        for i in range(2):
            np.testing.assert_array_equal(got[i], expected[i])
        self.assertEqual(ht.executor_stats().get("quarantined", {}), {})

    def test_drain_under_load_strands_no_future(self):
        sched, threading, time = self._sched()
        _executor.clear_executor_cache()
        datas = [
            np.linspace(-1.0, 1.0, 32, dtype=np.float32) * (i + 1)
            for i in range(6)
        ]
        arrs = [ht.array(d, split=0) for d in datas]
        for a in arrs:
            ((a * 1.5) + 0.5).parray  # warm
        outcomes = [None] * 6

        def force(i):
            try:
                outcomes[i] = ("ok", ((arrs[i] * 1.5) + 0.5).numpy())
            except BaseException as exc:
                outcomes[i] = ("err", exc)

        sched.pause()  # build a queue mid-"load"
        threads = [
            threading.Thread(target=force, args=(i,), daemon=True)
            for i in range(6)
        ]
        for th in threads:
            th.start()
        deadline = time.monotonic() + 30.0
        while sched.depth() < 6 and time.monotonic() < deadline:
            time.sleep(0.005)
        self.assertGreaterEqual(sched.depth(), 6, "forces never queued")
        # drain with a real timeout: lifts the pause, flushes everything
        result = sched.drain(timeout=60.0)
        self.assertTrue(result["flushed"])
        for th in threads:
            th.join(60.0)
        for i, out in enumerate(outcomes):
            self.assertIsNotNone(out, f"reader {i} stranded")
            status, payload = out
            if status == "ok":
                np.testing.assert_allclose(
                    payload, datas[i] * 1.5 + 0.5, rtol=1e-6, atol=1e-6
                )
            else:  # a typed lifecycle error is acceptable; a hang was not
                self.assertIsInstance(
                    payload,
                    (resilience.DrainTimeout, resilience.Shed,
                     resilience.RequestCancelled),
                )
        sched.reopen()

    def test_atexit_drain_settles_queued_futures_in_subprocess(self):
        """Interpreter shutdown with a PAUSED scheduler and a queued force:
        the executor's atexit drain must settle the dispatch-done future
        (value or typed error) and the process must exit cleanly — no hang."""
        script = r"""
import atexit, threading, time
import numpy as np

state = {}

def check():  # registered BEFORE heat_tpu: runs AFTER the executor's drain
    pv = state.get("pending")
    if pv is None:
        print("VERDICT: no-pending")
    elif pv.done():
        print("VERDICT: settled failed=%s" % pv.failed())
    else:
        print("VERDICT: STRANDED")

atexit.register(check)

import heat_tpu as ht
from heat_tpu.core import _executor, _scheduler

sched = _executor._get_scheduler()
sched.pause()
np_a = np.arange(16, dtype=np.float32)
x = ht.array(np_a, split=0)
v = (x + 7.0) * 2.0

def read():
    v.parray  # blocks on the paused queue

t = threading.Thread(target=read, daemon=True)
t.start()
deadline = time.monotonic() + 30.0
while sched.depth() < 1 and time.monotonic() < deadline:
    time.sleep(0.005)
assert sched.depth() >= 1, "force never queued"
pv = v._payload.value
assert isinstance(pv, _scheduler.PendingValue), type(pv)
state["pending"] = pv
print("QUEUED ok")
# main exits here with the scheduler paused: only the atexit drain can
# settle the future
"""
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120, env=env,
        )
        self.assertEqual(proc.returncode, 0, proc.stderr)
        self.assertIn("QUEUED ok", proc.stdout, proc.stdout)
        self.assertIn("VERDICT: settled", proc.stdout,
                      f"stdout={proc.stdout!r} stderr={proc.stderr[-500:]!r}")


# ------------------------------------------------------------------ chaos: checkpoint
class TestChaosCheckpoint(_ResilienceCase):
    def setUp(self):
        super().setUp()
        import tempfile

        self.tmp = tempfile.mkdtemp()

    def tearDown(self):
        import shutil

        shutil.rmtree(self.tmp, ignore_errors=True)
        super().tearDown()

    def test_transient_write_fault_retried_roundtrip_identical(self):
        diagnostics.enable()
        x = ht.array(np.arange(20, dtype=np.float32).reshape(4, 5), split=0)
        # ISSUE 13: the default save is the parallel chunked v2 path — its
        # writes run under the checkpoint.chunk_write site
        resilience.arm_fault_plan(
            [{"site": "checkpoint.chunk_write", "on_call": 1, "count": 1,
              "kind": "raise"}]
        )
        path = os.path.join(self.tmp, "ckpt")
        ht.save_checkpoint({"x": x}, path)  # attempt 1 injected, attempt 2 lands
        back = ht.load_checkpoint({"x": ht.zeros((4, 5), split=0)}, path)
        self.assert_array_equal(back["x"], x.numpy())
        self.assertGreaterEqual(
            self._counters().get("resilience.retry.checkpoint.chunk_write", 0), 1
        )

    def test_torn_write_rejected_on_restore(self):
        x = ht.array(np.arange(24, dtype=np.float32), split=0)
        resilience.arm_fault_plan(
            [{"site": "checkpoint.chunk_write", "on_call": 1,
              "kind": "torn-write", "fraction": 0.25}]
        )
        path = os.path.join(self.tmp, "torn")
        ht.save_checkpoint({"x": x}, path)  # commits a silently truncated chunk
        with self.assertRaises(ht.CheckpointCorrupt) as ctx:
            ht.load_checkpoint({"x": ht.zeros((24,), split=0)}, path)
        self.assertIn("torn write", str(ctx.exception))
        events = [
            e for e in self._resilience_events()
            if e["site"] == "checkpoint.restore" and e["kind"] == "corrupt"
        ]
        self.assertTrue(events, self._resilience_events())


# ------------------------------------------------------------------ HLO byte-parity
class TestHLOByteParity(_ResilienceCase):
    """Armed-but-idle (plan at sites that never fire) and disarmed builds must
    compile byte-identical HLO: the resilience layer exists strictly OUTSIDE
    traced program bodies."""

    @staticmethod
    def _chain_hlos():
        _executor.clear_executor_cache()
        np_x = np.arange(8, dtype=np.float32)
        np_y = np.full(8, 0.5, dtype=np.float32)
        x = ht.array(np_x, split=0)
        y = ht.array(np_y, split=0)
        (x + y).sum().parray
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

    def test_hlo_byte_parity_armed_vs_disarmed(self):
        diagnostics.disable()
        baseline = self._chain_hlos()
        self.assertGreaterEqual(len(baseline), 2, list(baseline))
        resilience.arm_fault_plan(
            [{"site": "never.fires", "on_call": 10**9, "kind": "raise"}]
        )
        armed = self._chain_hlos()
        self.assertEqual(armed, baseline, "arming a fault plan changed compiled HLO")
        resilience.disarm_fault_plan()
        again = self._chain_hlos()
        self.assertEqual(again, baseline, "disarming did not restore byte-identical HLO")


# ------------------------------------------------------------------ canned env plan (CI)
class TestEnvCannedPlan(_ResilienceCase):
    def test_env_canned_plan_end_to_end(self):
        """The CI chaos job's shape: a hermetic child arms a canned
        HEAT_TPU_FAULT_PLAN from the environment, computes through the faulted
        sites, and must match numpy bit-for-bit."""
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        plan = [
            {"site": "comm.shard", "on_call": 1, "kind": "raise"},
            {"site": "executor.execute", "on_call": 1, "count": 99, "kind": "raise"},
        ]
        ndev = os.environ.get("HEAT_TPU_TEST_DEVICES", "8")
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
            HEAT_TPU_FAULT_PLAN=json.dumps(plan),
            HEAT_TPU_JIT_THRESHOLD="1",
        )
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "import numpy as np\n"
            "import heat_tpu as ht\n"
            "from heat_tpu.core import resilience\n"
            "assert resilience._armed, 'env plan must arm at import'\n"
            "np_a = np.arange(10, dtype=np.float32)\n"
            "x = ht.array(np_a, split=0)\n"
            "y = (x + 1.0) * 2.0\n"
            "np.testing.assert_array_equal(y.numpy(), (np_a + 1.0) * 2.0)\n"
            "stats = ht.executor_stats()\n"
            "assert stats['eager_fallbacks'] >= 1, stats\n"
            "print('CANNED_PLAN_OK')\n"
        ) % (here,)
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True, text=True,
            timeout=300,
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-1000:])
        self.assertIn("CANNED_PLAN_OK", proc.stdout)

    def test_env_canned_plan_deadline_exceeded_kind(self):
        """ISSUE 10 chaos shape: an env-armed plan fires `deadline-exceeded`
        inside a dispatch — the reader gets the TYPED error (no eager replay,
        no quarantine) and the very next force retries clean."""
        here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        plan = [
            {"site": "executor.execute", "on_call": 2, "count": 1,
             "kind": "deadline-exceeded"},
        ]
        ndev = os.environ.get("HEAT_TPU_TEST_DEVICES", "8")
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
            HEAT_TPU_FAULT_PLAN=json.dumps(plan),
            HEAT_TPU_JIT_THRESHOLD="1",
        )
        code = (
            "import sys; sys.path.insert(0, %r)\n"
            "import numpy as np\n"
            "import heat_tpu as ht\n"
            "from heat_tpu.core import resilience\n"
            "assert resilience._armed, 'env plan must arm at import'\n"
            "np_a = np.arange(10, dtype=np.float32)\n"
            "y = (ht.array(np_a, split=0) + 1.0) * 2.0\n"
            "np.testing.assert_array_equal(y.numpy(), (np_a + 1.0) * 2.0)\n"
            "z = (ht.array(np_a * 2, split=0) + 1.0) * 2.0\n"
            "try:\n"
            "    z.numpy()\n"
            "    raise SystemExit('fault did not surface')\n"
            "except resilience.DeadlineExceeded:\n"
            "    pass\n"
            "np.testing.assert_array_equal(z.numpy(), (np_a * 2 + 1.0) * 2.0)\n"
            "stats = ht.executor_stats()\n"
            "assert stats['expired_requests'] >= 1, stats\n"
            "assert stats['eager_fallbacks'] == 0, stats\n"
            "assert not stats['quarantined'], stats\n"
            "print('DEADLINE_PLAN_OK')\n"
        ) % (here,)
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True, text=True,
            timeout=300,
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-1000:])
        self.assertIn("DEADLINE_PLAN_OK", proc.stdout)


if __name__ == "__main__":
    import unittest

    unittest.main()
