"""``ht.profiler`` tests (ISSUE 7 tentpole).

Five contracts, mirroring ``heat_tpu/core/profiler.py``:

- **Histogram math** against exact ground truth: log-bucketed percentile
  estimates stay within the bucket-resolution error bound of ``np.quantile``
  on known distributions, and ``merge`` is associative and equivalent to
  having observed the union stream.
- **Trace export** is valid Chrome trace-event JSON: parses, every ``B`` has
  its matching ``E`` per (pid, tid) in properly nested order, timestamps are
  monotone in emitted order, one metadata-named track per request, counter
  events are numeric.
- **Request-id propagation**: dispatch slices attribute to the ambient
  request scope even when requests interleave across threads, and a deferred
  chain built inside a request attributes its force to that request when
  forced later from OTHER threads (the captured-at-defer-time id).
- **Memory gauges**: force boundaries sample live logical bytes; peak ≥ last.
- **Zero-overhead**: compiled HLO is byte-identical with the profiler
  enabled, disabled, and toggled back (nothing ever enters a traced body),
  and a disabled profiler records nothing at all.
"""

import json
import os
import threading

import numpy as np

import jax

import heat_tpu as ht
from heat_tpu.core import _executor, profiler
from heat_tpu.testing import TestCase, program_text

_OLD_THRESHOLD = None


def setUpModule():
    # compile-on-first-miss so compile/execute slice expectations are
    # deterministic (the suite conftest raises the warm-up threshold)
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


class _ProfTestCase(TestCase):
    """Reset + disable the profiler around every test."""

    def setUp(self):
        super().setUp()
        profiler.disable()
        profiler.reset()

    def tearDown(self):
        profiler.disable()
        profiler.reset()
        super().tearDown()


def _chain64(x, y):
    for _ in range(16):
        x = x + y
        x = x * 0.5
        x = x - y
        x = x + 1.0
    return x


def _validate_trace(testcase, obj):
    """Schema-check one dump_trace object; returns the non-metadata events."""
    testcase.assertEqual(obj["schema"], profiler.TRACE_SCHEMA)
    events = obj["traceEvents"]
    testcase.assertIsInstance(events, list)
    stacks = {}
    last_ts = None
    for ev in events:
        testcase.assertIn(ev["ph"], ("B", "E", "M", "C"))
        if ev["ph"] == "M":
            continue
        for key in ("name", "pid", "tid", "ts"):
            testcase.assertIn(key, ev)
        if ev["ph"] in ("B", "E"):
            # monotone in emitted order (Perfetto requires sorted-by-ts input)
            if last_ts is not None:
                testcase.assertGreaterEqual(ev["ts"], last_ts)
            last_ts = ev["ts"]
        if ev["ph"] == "B":
            stacks.setdefault((ev["pid"], ev["tid"]), []).append(ev)
        elif ev["ph"] == "E":
            stack = stacks.get((ev["pid"], ev["tid"]))
            testcase.assertTrue(stack, f"E without open B: {ev}")
            top = stack.pop()
            # properly nested: the E closes the innermost open B
            testcase.assertEqual(top["name"], ev["name"])
            testcase.assertEqual(top.get("cat"), ev.get("cat"))
        elif ev["ph"] == "C":
            for v in ev["args"].values():
                testcase.assertIsInstance(v, (int, float))
    leftovers = {k: v for k, v in stacks.items() if v}
    testcase.assertEqual(leftovers, {}, "unmatched B events")
    return events


class TestHistogram(_ProfTestCase):
    def _check_quantiles(self, samples, places_rel=0.08):
        h = profiler.Histogram()
        for s in samples:
            h.observe(float(s))
        for q in (0.50, 0.95, 0.99):
            exact = float(np.quantile(samples, q))
            est = h.percentile(q)
            self.assertLessEqual(
                abs(est - exact) / exact, places_rel,
                f"p{int(q * 100)}: estimate {est} vs exact {exact}",
            )
        self.assertAlmostEqual(h.max_s, float(np.max(samples)), places=9)
        self.assertEqual(h.count, len(samples))

    def test_percentile_accuracy_lognormal(self):
        rng = np.random.default_rng(0)
        self._check_quantiles(np.exp(rng.normal(-5.0, 1.0, size=20_000)))

    def test_percentile_accuracy_uniform(self):
        rng = np.random.default_rng(1)
        self._check_quantiles(rng.uniform(1e-3, 2e-1, size=20_000))

    def test_merge_associative_and_equivalent_to_union(self):
        rng = np.random.default_rng(2)
        parts = [np.exp(rng.normal(-6.0, 0.7, size=3_000)) for _ in range(3)]

        def hist(samples):
            h = profiler.Histogram()
            for s in samples:
                h.observe(float(s))
            return h

        left = hist(parts[0]).merge(hist(parts[1])).merge(hist(parts[2]))
        right = hist(parts[0]).merge(hist(parts[1]).merge(hist(parts[2])))
        union = hist(np.concatenate(parts))
        for a, b in ((left, right), (left, union)):
            self.assertEqual(a.buckets, b.buckets)
            self.assertEqual(a.count, b.count)
            self.assertEqual(a.max_s, b.max_s)
            self.assertEqual(a.min_s, b.min_s)
            self.assertAlmostEqual(a.sum_s, b.sum_s, places=9)
            for q in (0.5, 0.99):
                self.assertEqual(a.percentile(q), b.percentile(q))

    def test_merge_rejects_mismatched_configs(self):
        with self.assertRaises(ValueError):
            profiler.Histogram().merge(profiler.Histogram(growth=1.5))

    def test_snapshot_roundtrip(self):
        h = profiler.Histogram()
        for v in (1e-4, 2e-3, 5e-2, 5e-2, 1.0):
            h.observe(v)
        back = profiler.Histogram.from_snapshot(
            json.loads(json.dumps(h.snapshot()))
        )
        self.assertEqual(back.buckets, h.buckets)
        self.assertEqual(back.count, h.count)
        self.assertEqual(back.percentile(0.5), h.percentile(0.5))

    def test_bounded_memory(self):
        h = profiler.Histogram()
        h.observe(1e-9)   # below base: bucket 0
        h.observe(1e9)    # absurd: clamps to MAX_INDEX, not an unbounded index
        self.assertEqual(sorted(h.buckets), [0, profiler.Histogram.MAX_INDEX])


class TestHistogramDelta(_ProfTestCase):
    """Windowed snapshots (ISSUE 11): ``delta(prev_snapshot)`` yields the
    interval histogram between two cumulative dumps, and merge/delta
    round-trip exactly."""

    def test_delta_counts_only_the_window(self):
        rng = np.random.default_rng(3)
        first = np.exp(rng.normal(-6.0, 0.8, size=2_000))
        second = np.exp(rng.normal(-4.0, 0.5, size=1_500))
        h = profiler.Histogram()
        for v in first:
            h.observe(float(v))
        snap = json.loads(json.dumps(h.snapshot()))  # a dump's JSON round-trip
        for v in second:
            h.observe(float(v))
        window = h.delta(snap)
        self.assertEqual(window.count, len(second))
        # interval quantiles reflect ONLY the window's distribution
        ref = profiler.Histogram()
        for v in second:
            ref.observe(float(v))
        self.assertEqual(window.buckets, ref.buckets)
        for q in (0.5, 0.99):
            exact = float(np.quantile(second, q))
            self.assertLessEqual(abs(window.percentile(q) - exact) / exact, 0.08)

    def test_merge_delta_roundtrip_associativity(self):
        rng = np.random.default_rng(4)
        h = profiler.Histogram()
        for v in np.exp(rng.normal(-5.0, 1.0, size=1_000)):
            h.observe(float(v))
        snap = h.snapshot()
        for v in np.exp(rng.normal(-5.0, 1.0, size=700)):
            h.observe(float(v))
        window = h.delta(snap)
        rebuilt = profiler.Histogram.from_snapshot(snap).merge(window)
        self.assertEqual(rebuilt.buckets, h.buckets)
        self.assertEqual(rebuilt.count, h.count)
        self.assertAlmostEqual(rebuilt.sum_s, h.sum_s, places=6)
        for q in (0.5, 0.95, 0.99):
            self.assertEqual(rebuilt.percentile(q), h.percentile(q))

    def test_delta_accepts_histogram_and_empty_window(self):
        h = profiler.Histogram()
        h.observe(0.01)
        prev = profiler.Histogram.from_snapshot(h.snapshot())
        window = h.delta(prev)  # nothing happened between the dumps
        self.assertEqual(window.count, 0)
        self.assertIsNone(window.percentile(0.5))

    def test_delta_rejects_non_prefix_and_mismatched_config(self):
        a = profiler.Histogram()
        a.observe(0.01)
        b = profiler.Histogram()
        b.observe(10.0)
        b.observe(20.0)
        with self.assertRaises(ValueError):
            a.delta(b.snapshot())  # different stream: buckets go negative
        with self.assertRaises(ValueError):
            a.delta(profiler.Histogram(growth=1.5))


class TestTraceExport(_ProfTestCase):
    def test_trace_schema_and_tracks(self):
        _executor.clear_executor_cache()
        profiler.enable()
        with profiler.request("alpha") as rid_a:
            x = ht.array(np.arange(29, dtype=np.float32), split=0)
            y = ht.array(np.full(29, 0.5, dtype=np.float32), split=0)
            _chain64(x, y).parray
        with profiler.request("beta") as rid_b:
            (x * 2.0).sum().parray
        path = os.path.join(self._tmp(), "trace.json")
        obj = profiler.dump_trace(path)
        with open(path) as f:
            self.assertEqual(json.load(f), obj)
        events = _validate_trace(self, obj)
        names = {
            ev["pid"]: ev["args"]["name"]
            for ev in events
            if ev["ph"] == "M" and ev["name"] == "process_name"
        }
        self.assertIn("alpha", names[rid_a])
        self.assertIn("beta", names[rid_b])
        cats = {ev.get("cat") for ev in events}
        for expected in ("request", "dispatch", "force", "compile", "collective"):
            self.assertIn(expected, cats, f"no {expected!r} slice in the trace")
        # the two requests' slices live on their own tracks
        for rid in (rid_a, rid_b):
            self.assertTrue(
                any(ev["ph"] == "B" and ev["pid"] == rid for ev in events)
            )

    def test_disable_enable_keeps_one_time_origin(self):
        # a disable/enable cycle with data collected must NOT rebase the
        # timestamp origin — mixed origins would interleave two sessions'
        # B/E events on one track and break the pairing below
        profiler.enable()
        with profiler.request("first"):
            pass
        profiler.disable()
        profiler.enable()
        with profiler.request("second"):
            pass
        obj = {"schema": profiler.TRACE_SCHEMA,
               "traceEvents": profiler._trace_events_locked()}
        events = _validate_trace(self, obj)
        reqs = sorted(
            (ev["ts"], ev["name"]) for ev in events
            if ev.get("cat") == "request" and ev["ph"] == "B"
        )
        self.assertEqual([name for _, name in reqs], ["first", "second"])

    def test_counter_tracks(self):
        profiler.enable()
        x = ht.array(np.arange(13, dtype=np.float32), split=0)  # ragged: pad waste
        (x + 1.0).parray
        obj = profiler.dump_trace(os.path.join(self._tmp(), "trace.json"))
        counters = {ev["name"] for ev in obj["traceEvents"] if ev["ph"] == "C"}
        self.assertIn("force_live_bytes", counters)
        if self.world_size > 1:
            self.assertIn("pad_waste_fraction", counters)

    def _tmp(self):
        import tempfile

        d = tempfile.mkdtemp(prefix="ht_profiler_")
        self.addCleanup(lambda: __import__("shutil").rmtree(d, ignore_errors=True))
        return d


class TestRequestPropagation(_ProfTestCase):
    def test_deferred_chain_forced_from_two_threads(self):
        _executor.clear_executor_cache()
        profiler.enable()
        with profiler.request("deferred-chain") as rid:
            x = ht.array(np.arange(32, dtype=np.float32), split=0)
            y = ht.array(np.full(32, 0.25, dtype=np.float32), split=0)
            z = _chain64(x, y)
        # the scope is closed and the chain still pending: force it from two
        # OTHER threads (no ambient request there) — the force must attribute
        # to the request captured at defer time, exactly once
        results = []
        threads = [
            threading.Thread(target=lambda: results.append(np.asarray(z.parray)))
            for _ in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.assertEqual(len(results), 2)
        np.testing.assert_array_equal(results[0], results[1])
        obj = profiler.dump_trace(os.path.join("/tmp", f"prop-{os.getpid()}.json"))
        self.addCleanup(
            lambda: os.path.exists(f"/tmp/prop-{os.getpid()}.json")
            and os.remove(f"/tmp/prop-{os.getpid()}.json")
        )
        forces = [
            ev for ev in obj["traceEvents"]
            if ev.get("cat") == "force" and ev["ph"] == "B"
        ]
        self.assertEqual(len(forces), 1, "the chain must force exactly once")
        self.assertEqual(forces[0]["pid"], rid)
        # the program call nested under the force rides the same attribution
        execs = [
            ev for ev in obj["traceEvents"]
            if ev.get("cat") in ("compile", "execute") and ev["ph"] == "B"
            and ev["pid"] == rid
        ]
        self.assertGreaterEqual(len(execs), 1)

    def test_concurrent_requests_attribute_disjointly(self):
        profiler.enable()
        rids = {}
        barrier = threading.Barrier(2)

        def serve(tag):
            barrier.wait()
            for _ in range(3):
                with profiler.request(tag) as rid:
                    rids.setdefault(tag, set()).add(rid)
                    a = ht.array(np.arange(16, dtype=np.float32), split=0)
                    ((a + 1.0) * 2.0).sum().parray

        threads = [
            threading.Thread(target=serve, args=(tag,)) for tag in ("t1", "t2")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.assertEqual(len(rids["t1"] & rids["t2"]), 0, "request ids collided")
        hists = profiler.histogram_snapshots()
        self.assertEqual(hists["request.t1"]["count"], 3)
        self.assertEqual(hists["request.t2"]["count"], 3)
        obj = profiler.dump_trace(os.path.join("/tmp", f"conc-{os.getpid()}.json"))
        self.addCleanup(
            lambda: os.path.exists(f"/tmp/conc-{os.getpid()}.json")
            and os.remove(f"/tmp/conc-{os.getpid()}.json")
        )
        _validate_trace(self, obj)
        # every dispatch slice recorded inside a request belongs to a real one
        dispatch_pids = {
            ev["pid"] for ev in obj["traceEvents"]
            if ev.get("cat") == "dispatch" and ev["ph"] == "B" and ev["pid"] != 0
        }
        self.assertLessEqual(dispatch_pids, rids["t1"] | rids["t2"])


class TestDeadlineCapture(_ProfTestCase):
    """ISSUE 10: `request(tag, deadline_s=...)` arms a wall-clock deadline in
    the same contextvar scope as the request id; `Deferred` nodes capture it
    at defer time, so a chain forced later — from ANOTHER thread, after the
    scope closed — still carries its deadline; and an already-expired
    deadline at force time yields a typed `DeadlineExceeded`, never a hang
    and never a silent full execution."""

    def test_64_op_chain_carries_deadline_when_forced_from_another_thread(self):
        from heat_tpu.core import resilience

        _executor.clear_executor_cache()
        profiler.enable()
        with profiler.request("dl-chain", deadline_s=60.0) as rid:
            self.assertIsNotNone(profiler.current_deadline())
            x = ht.array(np.arange(32, dtype=np.float32), split=0)
            y = ht.array(np.full(32, 0.25, dtype=np.float32), split=0)
            z = _chain64(x, y)
        # the scope is closed: no ambient deadline on this thread anymore...
        self.assertIsNone(profiler.current_deadline())
        # ...but the pending nodes captured it at defer time
        node = z._payload
        self.assertIsInstance(node, _executor.Deferred)
        self.assertIsNotNone(node.deadline)
        # forced from another thread, the (far-future) deadline rides along
        # and the chain completes normally, attributed to the request
        results, errors = [], []

        def force():
            try:
                results.append(np.asarray(z.parray))
            except BaseException as exc:  # pragma: no cover - the assertion
                errors.append(exc)

        th = threading.Thread(target=force)
        th.start()
        th.join(60.0)
        self.assertFalse(errors, errors)
        self.assertEqual(len(results), 1)
        del resilience  # imported for symmetry with the expiry test below

    def test_expired_deadline_at_force_time_is_typed_not_a_hang(self):
        from heat_tpu.core import resilience

        _executor.clear_executor_cache()
        profiler.enable()
        with profiler.request("dl-exp", deadline_s=0.2):
            x = ht.array(np.arange(32, dtype=np.float32), split=0)
            y = ht.array(np.full(32, 0.25, dtype=np.float32), split=0)
            z = _chain64(x, y)
        import time as _time

        _time.sleep(0.3)  # the captured deadline expires before any force
        before = ht.executor_stats()
        outcome = {}

        def force():
            try:
                outcome["v"] = np.asarray(z.parray)
            except BaseException as exc:
                outcome["err"] = exc

        th = threading.Thread(target=force)
        th.start()
        th.join(30.0)
        self.assertFalse(th.is_alive(), "force hung on an expired deadline")
        self.assertIn("err", outcome,
                      "expired deadline silently executed the full chain")
        self.assertIsInstance(outcome["err"], resilience.DeadlineExceeded)
        after = ht.executor_stats()
        # rejected at admission: the 64-op program was never planned/compiled
        self.assertEqual(after["misses"], before["misses"])
        self.assertEqual(after["retraces"], before["retraces"])
        self.assertGreater(after["expired_requests"],
                           before["expired_requests"])
        # the rejection consumed the captured deadline: the same chain is
        # computable by a later, deadline-free read (bit-identical to a
        # fresh, never-deadlined build of the identical graph)
        x2 = ht.array(np.arange(32, dtype=np.float32), split=0)
        y2 = ht.array(np.full(32, 0.25, dtype=np.float32), split=0)
        exp = np.asarray(_chain64(x2, y2).parray)
        np.testing.assert_array_equal(np.asarray(z.parray), exp)


class TestMemoryGauges(_ProfTestCase):
    def test_force_boundary_samples(self):
        profiler.enable()
        x = ht.array(np.arange(1024, dtype=np.float32), split=0)
        y = ht.array(np.full(1024, 2.0, dtype=np.float32), split=0)
        (x + y).parray
        small = profiler.report()["memory"]
        self.assertGreaterEqual(small["forces"], 1)
        self.assertGreater(small["last_force_live_bytes"], 0)
        a = ht.array(np.zeros(1 << 16, dtype=np.float32), split=0)
        (a * 3.0).parray
        mem = profiler.report()["memory"]
        self.assertGreaterEqual(mem["peak_force_live_bytes"],
                                mem["last_force_live_bytes"])
        # the big force dominates the peak: 2 × 256 KiB (leaf in + out)
        self.assertGreaterEqual(mem["peak_force_live_bytes"], 2 * (1 << 18))


class TestHLOParity(_ProfTestCase):
    """The profiler never touches traced bodies: compiled HLO is byte-identical
    enabled / disabled / toggled back — the same proof shape as diagnostics'
    and resilience's zero-overhead contracts."""

    @staticmethod
    def _chain_hlos():
        from heat_tpu.core import diagnostics

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

    def test_hlo_byte_parity_across_toggles(self):
        profiler.disable()
        baseline = self._chain_hlos()
        self.assertGreaterEqual(len(baseline), 2, list(baseline))
        profiler.enable()
        try:
            with profiler.request("parity"):
                enabled = self._chain_hlos()
        finally:
            profiler.disable()
        self.assertEqual(enabled, baseline, "profiler-on collection changed HLO")
        again = self._chain_hlos()
        self.assertEqual(again, baseline, "disabled HLO must be byte-identical")

    def test_disabled_records_nothing(self):
        profiler.disable()
        profiler.reset()
        with profiler.request("never") as rid:
            a = ht.array(np.arange(9, dtype=np.float32), split=0)
            (a + 1.0).parray
        self.assertIsNone(rid)
        rep = profiler.report()
        self.assertEqual(rep["histograms"], {})
        self.assertEqual(rep["slices_recorded"], 0)
        self.assertEqual(rep["memory"]["forces"], 0)

    def test_enable_env_knob(self):
        import subprocess
        import sys

        env = dict(os.environ)
        env["HEAT_TPU_PROFILE"] = "1"
        env.setdefault("JAX_PLATFORMS", "cpu")
        code = (
            "from heat_tpu.core import profiler; "
            "assert profiler.active(); "
            "print('armed')"
        )
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=300,
        )
        self.assertEqual(out.returncode, 0, out.stderr[-500:])
        self.assertIn("armed", out.stdout)


class TestProfilerHammer(_ProfTestCase):
    def test_concurrent_requests_exact_histogram_counts(self):
        profiler.enable()
        n_threads, n_requests = 6, 25
        errors = []

        def serve(slot):
            try:
                for i in range(n_requests):
                    with profiler.request("hammer"):
                        profiler.observe("custom", 0.001 * (slot + 1))
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        threads = [
            threading.Thread(target=serve, args=(s,)) for s in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.assertEqual(errors, [])
        hists = profiler.histogram_snapshots()
        self.assertEqual(hists["request.hammer"]["count"], n_threads * n_requests)
        self.assertEqual(hists["custom"]["count"], n_threads * n_requests)
        _validate_trace(
            self, {"schema": profiler.TRACE_SCHEMA,
                   "traceEvents": profiler._trace_events_locked()},
        )


class TestHostSpanRouting(_ProfTestCase):
    """ISSUE 25: ``request`` and ``scope`` are also ``diagnostics.span`` host spans —
    ``request.<tag>`` and ``<cat>`` — exactly while ``diagnostics._enabled``."""

    def setUp(self):
        super().setUp()
        from heat_tpu.core import diagnostics

        self.diagnostics = diagnostics
        self._was_enabled = diagnostics.enabled()
        diagnostics.disable(trace=diagnostics.tracing())
        diagnostics.reset()

    def tearDown(self):
        self.diagnostics.reset()
        if self._was_enabled:
            self.diagnostics.enable()
        else:
            self.diagnostics.disable(trace=self.diagnostics.tracing())
        super().tearDown()

    def _annotations(self):
        """The annotations :func:`diagnostics.span` opens, as (text, keywords) pairs."""
        from unittest import mock

        self.diagnostics._bind_jax()
        opened = []

        class Annotation:
            def __init__(self, text, **kwargs):
                opened.append((text, kwargs))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        patch = mock.patch.object(self.diagnostics, "_annotation", Annotation)
        patch.start()
        self.addCleanup(patch.stop)
        return opened

    def test_request_and_scope_emit_only_when_diagnostics_is_enabled(self):
        opened = self._annotations()
        profiler.enable()
        with profiler.request("quiet"):
            with profiler.scope("dispatch", "add"):
                pass
        self.assertEqual(opened, [])
        self.assertEqual(self.diagnostics.report()["spans"], {})
        self.assertEqual(profiler.report()["slices_recorded"], 2)  # its own slices stay

        self.diagnostics.enable()
        with profiler.request("loud") as rid:
            with profiler.scope("dispatch", "add"):
                pass
            with profiler.scope("dispatch", "mul"):
                pass
        counters = self.diagnostics.report()["counters"]
        self.assertEqual(counters["span_n.request.loud"], 1)
        self.assertEqual(counters["span_n.dispatch"], 2)  # aggregated by category
        self.assertNotIn("span_n.request.quiet", counters)
        # the annotation keeps the full cat:name, and every one carries the request id
        self.assertEqual(opened, [("ht.request.loud", {"req": rid}),
                                  ("ht.dispatch:add", {"req": rid}),
                                  ("ht.dispatch:mul", {"req": rid})])
        self.assertEqual(profiler.report()["slices_recorded"], 5)

    def test_request_span_does_not_wait_for_the_profiler(self):
        opened = self._annotations()
        self.diagnostics.enable()
        self.assertFalse(profiler.active())
        with profiler.request("solo") as rid:
            pass
        self.assertIsNone(rid)
        self.assertEqual(opened, [("ht.request.solo", {})])  # no ambient id to carry
        self.assertEqual(self.diagnostics.report()["counters"]["span_n.request.solo"], 1)
        self.assertEqual(profiler.report()["slices_recorded"], 0)

    def test_executor_program_calls_are_compile_and_execute_spans(self):
        opened = self._annotations()
        _executor.clear_executor_cache()
        x = ht.array(np.arange(8, dtype=np.float32), split=0)
        profiler.enable()
        self.diagnostics.enable()
        with profiler.request("chain"):
            (x + 1.0).sum().parray
            (x + 1.0).sum().parray
        counters = self.diagnostics.report()["counters"]
        self.assertGreaterEqual(counters["span_n.compile"], 1)
        self.assertGreaterEqual(counters["span_n.execute"], 1)
        texts = [text for text, _ in opened]
        self.assertTrue(any(t.startswith("ht.compile:") for t in texts), texts)
        self.assertTrue(any(t.startswith("ht.execute:") for t in texts), texts)
        # what the executor's own HEAT_TPU_TRACE branches used to write is gone
        self.assertFalse(any(t.startswith("ht.dispatch:program") for t in texts), texts)
