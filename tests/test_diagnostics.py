"""``ht.diagnostics`` tests (ISSUE 4 tentpole).

Four groups, mirroring the subsystem's contract
(``heat_tpu/core/diagnostics.py``):

- report plumbing: enable/disable/reset/report/dump, span aggregation, the
  ``HEAT_TPU_METRICS=1`` env knob honored at import (subprocess);
- enabled-mode accounting against HAND-COUNTED ground truth: a 64-op deferred
  chain is exactly ONE compile event, a split=0 matmul is exactly one ``shard``
  record with its logical byte count, a ragged-extent mean leaves a pad-waste
  gauge, and a ``shard_map`` ``psum`` records payload × participants bytes;
- backend-health stream: transitions-only recording, JSONL persistence via
  ``HEAT_TPU_DIAG_LOG``, outage-window folding;
- the zero-overhead-when-off contract: the compiled HLO of an
  instrumented-but-disabled ``(x + y).sum()`` chain is byte-identical across
  disable → enable(trace) → disable round trips — the disabled executable
  contains nothing the pre-diagnostics one did not.
"""

import contextlib
import json
import os
import subprocess
import sys
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import _executor, diagnostics
from heat_tpu.testing import TestCase, program_text

_OLD_THRESHOLD = None


def setUpModule():
    # compile-on-first-miss (the production default) so compile-event counts
    # are deterministic; the suite conftest raises the warm-up threshold
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


@contextlib.contextmanager
def metrics(trace=None):
    """Enable diagnostics for a block, restoring the prior switch state."""
    was_enabled, was_tracing = diagnostics.enabled(), diagnostics.tracing()
    diagnostics.enable(trace=trace)
    try:
        yield
    finally:
        diagnostics.reset()
        if was_enabled:
            diagnostics.enable(trace=was_tracing)
        else:
            diagnostics.disable(trace=was_tracing)


@contextlib.contextmanager
def eager_dispatch():
    old = os.environ.get("HEAT_TPU_EAGER_DISPATCH")
    os.environ["HEAT_TPU_EAGER_DISPATCH"] = "1"
    _executor.reload_env_knobs()  # knobs are memoised: re-read after the flip
    try:
        yield
    finally:
        if old is None:
            del os.environ["HEAT_TPU_EAGER_DISPATCH"]
        else:
            os.environ["HEAT_TPU_EAGER_DISPATCH"] = old
        _executor.reload_env_knobs()


def _chain64(x, y):
    for _ in range(16):
        x = x + y
        x = x * 0.5
        x = x - y
        x = x + 1.0
    return x


class _DiagTestCase(TestCase):
    """Save/restore the global diagnostics switches around every test, so a
    suite-wide HEAT_TPU_METRICS=1 run (the CI artifact) keeps COLLECTING after
    this module. (The hand-count tests still reset() the shared registry, so
    the artifact holds the post-test_diagnostics tail of the run plus the
    executor's lifetime per-signature tallies — documented in ci.yaml.)"""

    def setUp(self):
        super().setUp()
        self._was_enabled = diagnostics.enabled()
        self._was_tracing = diagnostics.tracing()

    def tearDown(self):
        diagnostics.reset()
        if self._was_enabled:
            diagnostics.enable(trace=self._was_tracing)
        else:
            diagnostics.disable(trace=self._was_tracing)
        super().tearDown()


class TestReportPlumbing(_DiagTestCase):
    def test_top_level_namespace(self):
        for name in ("enable", "disable", "report", "dump", "span", "reset"):
            self.assertTrue(hasattr(ht.diagnostics, name))

    def test_disabled_records_nothing(self):
        diagnostics.disable()
        diagnostics.reset()
        a = ht.array(np.arange(13, dtype=np.float32), split=0)
        (a + 1.0).parray
        ht.mean(a).parray
        rep = diagnostics.report()
        self.assertFalse(rep["enabled"])
        self.assertEqual(rep["collectives"], [])
        self.assertEqual(rep["pad_waste"], [])
        self.assertEqual(rep["compile_events"], [])
        self.assertEqual(rep["counters"], {})

    def test_span_and_counter_aggregation(self):
        with metrics():
            diagnostics.reset()
            for _ in range(3):
                with diagnostics.span("unit-test-span"):
                    pass
            diagnostics.counter("unit-test-counter", 2)
            diagnostics.counter("unit-test-counter")
            rep = diagnostics.report()
        span = rep["spans"]["unit-test-span"]
        self.assertEqual(span["count"], 3)
        self.assertGreaterEqual(span["total_s"], 0.0)
        self.assertGreaterEqual(span["max_s"], 0.0)
        self.assertEqual(rep["counters"]["unit-test-counter"], 3)

    def test_dump_writes_schema_json(self):
        with metrics():
            with tempfile.TemporaryDirectory() as d:
                path = os.path.join(d, "diag.json")
                diagnostics.dump(path)
                with open(path) as f:
                    rep = json.load(f)
        self.assertEqual(rep["schema"], diagnostics.SCHEMA)
        self.assertIn("executor", rep)
        self.assertIn("backend_events", rep)

    def test_env_knob_enables_at_import(self):
        # HEAT_TPU_METRICS=1 must take effect at import with no enable() call;
        # exercised in a subprocess because the env is read once at module load
        code = (
            "import heat_tpu as ht\n"
            "assert ht.diagnostics.enabled()\n"
            "assert not ht.diagnostics.tracing()\n"
            "import numpy as np\n"
            "x = ht.array(np.arange(13, dtype=np.float32), split=0)\n"
            "(x + 1.0).parray\n"
            "rep = ht.diagnostics.report()\n"
            "assert rep['enabled'] and rep['collectives'], rep['collectives']\n"
            "print('env-knob-ok')\n"
        )
        env = dict(os.environ)
        env["HEAT_TPU_METRICS"] = "1"
        proc = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True, text=True,
            timeout=240,
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        self.assertIn("env-knob-ok", proc.stdout)


class TestHandCountedTelemetry(_DiagTestCase):
    """Enabled-mode counters must match collectives counted by reading the
    implementation — observability that cannot be trusted is noise."""

    def test_deferred_chain_is_one_compile_event(self):
        # 64 framework-level ops forced via .parray = ONE program = ONE compile
        np_x = np.arange(13, dtype=np.float32)
        np_y = np.ones(13, dtype=np.float32)
        x = ht.array(np_x, split=0)
        y = ht.array(np_y, split=0)
        _executor.clear_executor_cache()
        with metrics():
            diagnostics.reset()
            out = _chain64(x, y)
            out.parray
            rep = diagnostics.report()
        self.assertEqual(len(rep["compile_events"]), 1, rep["compile_events"])
        label = rep["compile_events"][0]["label"]
        self.assertTrue(label.startswith("defer:"), label)
        self.assertIn("[64]", label)
        self.assertGreater(rep["compile_events"][0]["seconds"], 0.0)
        # the ragged (13,) split-0 family leaves its pad-waste gauge
        self.assertTrue(
            any(g["gshape"] == [13] and g["split"] == 0 for g in rep["pad_waste"]),
            rep["pad_waste"],
        )
        # the miss is explained
        misses = [e for e in rep["dispatch_events"] if e["kind"] == "miss"]
        self.assertEqual(len(misses), 1)
        self.assertTrue(misses[0]["reason"])

    def test_matmul_split0_shard_bytes(self):
        # split=0 matmul: exactly ONE layout collective — _wrap_like lays the
        # (8, 8) float32 product out over the mesh = 8*8*4 = 256 logical bytes
        np_a = np.random.default_rng(0).standard_normal((8, 8)).astype(np.float32)
        np_b = np.random.default_rng(1).standard_normal((8, 8)).astype(np.float32)
        a = ht.array(np_a, split=0)
        b = ht.array(np_b, split=None)
        with metrics():
            diagnostics.reset()
            ht.linalg.matmul(a, b)
            rep = diagnostics.report()
        self.assertEqual(len(rep["collectives"]), 1, rep["collectives"])
        rec = rep["collectives"][0]
        self.assertEqual(rec["op"], "shard")
        self.assertEqual(rec["count"], 1)
        self.assertEqual(rec["bytes"], 8 * 8 * 4)
        self.assertEqual(rec["participants"], self.world_size)

    def test_ragged_mean_staged_vs_eager(self):
        # staged path: the reduction runs INSIDE the cached program (zero
        # MeshCommunication calls) but the padded operand family is gauged;
        # eager path: _padded_reduce + one comm.shard of the scalar result
        np_x = np.arange(13, dtype=np.float32)
        x = ht.array(np_x, split=0)
        _executor.clear_executor_cache()
        with metrics():
            diagnostics.reset()
            ht.mean(x).parray
            rep = diagnostics.report()
        self.assertEqual(rep["collectives"], [])
        gauges = [g for g in rep["pad_waste"] if g["gshape"] == [13] and g["split"] == 0]
        self.assertEqual(len(gauges), 1, rep["pad_waste"])
        padded = x.comm.padded_dim(13)
        self.assertEqual(gauges[0]["physical_dim"], padded)
        self.assertEqual(gauges[0]["logical_dim"], 13)
        self.assertAlmostEqual(gauges[0]["pad_fraction"], (padded - 13) / padded, places=6)

        with metrics(), eager_dispatch():
            diagnostics.reset()
            ht.mean(ht.array(np_x, split=0))
            rep = diagnostics.report()
        shards = [c for c in rep["collectives"] if c["op"] == "shard"]
        # one shard for the operand layout (ht.array) + one for the scalar result
        self.assertEqual(sum(c["count"] for c in shards), 2, rep["collectives"])
        self.assertEqual(sum(c["bytes"] for c in shards), 13 * 4 + 4)
        self.assertTrue(
            any(g["gshape"] == [13] and g["split"] == 0 for g in rep["pad_waste"])
        )

    def test_shard_map_psum_payload_times_participants(self):
        from jax import shard_map
        from jax.sharding import PartitionSpec

        comm = self.comm
        p = comm.size
        xs = jnp.arange(2.0 * p, dtype=jnp.float32)
        with metrics():
            diagnostics.reset()
            fn = shard_map(
                lambda v: comm.psum(v),
                mesh=comm.mesh,
                in_specs=PartitionSpec(comm.axis_name),
                out_specs=PartitionSpec(comm.axis_name),
            )
            fn(xs)
            rep = diagnostics.report()
        psums = [c for c in rep["collectives"] if c["op"] == "psum"]
        self.assertEqual(len(psums), 1, rep["collectives"])
        self.assertEqual(psums[0]["count"], 1)
        self.assertEqual(psums[0]["participants"], p)
        # per-shard payload is (2,) float32 = 8 bytes; logical bytes = 8 * P
        self.assertEqual(psums[0]["bytes"], 8 * p)

    def test_executor_provider_in_report(self):
        a = ht.array(np.arange(8, dtype=np.float32), split=0)
        (a + 1.0).parray
        (a + 1.0).parray
        with metrics():
            rep = diagnostics.report()
        self.assertIn("executor", rep)
        for key in ("hits", "misses", "retraces", "programs", "top_signatures"):
            self.assertIn(key, rep["executor"])


class TestBackendHealth(_DiagTestCase):
    def test_transitions_only(self):
        # _backend_state survives reset() by design (it is the dedup memory) —
        # seed a known DOWN state so the assertions don't depend on what any
        # earlier test or process history left behind
        diagnostics.record_backend_event(False, "seed known state")
        diagnostics.reset()
        first = diagnostics.record_backend_event(True, "probe 1")
        self.assertTrue(first["transition"])  # up after seeded down
        self.assertFalse(diagnostics.record_backend_event(True, "probe 2")["transition"])
        self.assertTrue(diagnostics.record_backend_event(False, "probe 3")["transition"])
        self.assertFalse(diagnostics.record_backend_event(False, "probe 4")["transition"])
        self.assertTrue(diagnostics.record_backend_event(True, "probe 5")["transition"])
        events = diagnostics.report()["backend_events"]
        self.assertEqual([e["up"] for e in events], [True, False, True])
        diagnostics.reset()

    def test_diag_log_jsonl(self):
        # seed a known DOWN state BEFORE pointing the log at our file, so the
        # "log 1" up-event below is a transition regardless of sibling tests
        diagnostics.record_backend_event(False, "seed known state")
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "backend.jsonl")
            old = os.environ.get("HEAT_TPU_DIAG_LOG")
            os.environ["HEAT_TPU_DIAG_LOG"] = path
            try:
                diagnostics.reset()
                diagnostics.record_backend_event(True, "log 1")
                diagnostics.record_backend_event(True, "suppressed")
                diagnostics.record_backend_event(False, "log 2")
            finally:
                if old is None:
                    del os.environ["HEAT_TPU_DIAG_LOG"]
                else:
                    os.environ["HEAT_TPU_DIAG_LOG"] = old
            lines = [json.loads(line) for line in open(path)]
        self.assertEqual(len(lines), 2)  # transitions only
        self.assertTrue(lines[0]["backend"]["up"])
        self.assertFalse(lines[1]["backend"]["up"])
        diagnostics.reset()

    def test_standalone_file_load(self):
        # jax-free tooling loads diagnostics.py by path — the module must be
        # stdlib-only
        code = (
            "import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location('d', %r)\n"
            "mod = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(mod)\n"
            "assert 'jax' not in sys.modules, 'diagnostics.py imported jax at load'\n"
            "mod.record_backend_event(False, 'standalone')\n"
            "print(len(mod.report()['backend_events']))\n"
        ) % os.path.join(os.path.dirname(diagnostics.__file__), "diagnostics.py")
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
            env={k: v for k, v in os.environ.items() if k != "HEAT_TPU_DIAG_LOG"},
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        self.assertEqual(proc.stdout.strip(), "1")


class TestZeroOverheadContract(_DiagTestCase):
    """Instrumented-but-disabled must be byte-identical to uninstrumented: the
    disabled traced bodies contain no diagnostics constructs at all, so their
    compiled HLO equals the pre-diagnostics executable's."""

    @staticmethod
    def _chain_hlos():
        """Run ``(x + y).sum()`` through the executor and return
        ``{label: compiled HLO text}`` for every program it cached, re-lowered
        exactly as the executor jits them (same traced wrapper, same
        out_shardings / keep_unused)."""
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
        diagnostics.disable()
        baseline = self._chain_hlos()
        self.assertGreaterEqual(len(baseline), 2, list(baseline))  # defer + reduce
        for label, text in baseline.items():
            self.assertNotIn("/ht.", text, f"disabled build of {label} carries scopes")

        # metrics-only: host-side counting must not touch the executable
        with metrics():
            counted = self._chain_hlos()
        self.assertEqual(counted, baseline, "metrics-only collection changed HLO")

        # tracing: named_scope labels ARE compiled into the metadata
        with metrics(trace=True):
            traced = self._chain_hlos()
        self.assertTrue(
            any("/ht." in text for text in traced.values()),
            "HEAT_TPU_TRACE must inject framework-level scope names",
        )

        # back off: byte-identical to the first disabled build
        diagnostics.disable()
        again = self._chain_hlos()
        self.assertEqual(again, baseline, "disabled HLO must be byte-identical")

    def test_disabled_flag_checks_only(self):
        # the hot-path gate is a module attribute — flipping it must be enough
        # (explicitly disable: the ambient suite may run with HEAT_TPU_METRICS=1,
        # e.g. the CI tier-1 artifact run; _DiagTestCase.tearDown restores it)
        diagnostics.disable()
        self.assertFalse(diagnostics._enabled)
        a = ht.array(np.arange(13, dtype=np.float32), split=0)
        diagnostics.reset()
        (a * 2.0).parray
        self.assertEqual(diagnostics.report()["pad_waste"], [])


class TestThreadSafety(_DiagTestCase):
    """ISSUE 7 satellite: the serving harness hammers the registries from many
    threads at once — every lock-protected mutation site must stay EXACT
    (counters, spans, collective aggregates, bounded deques), and concurrent
    framework dispatch with metrics on must neither crash nor let an event
    stream outgrow its bound. The deliberately relaxed sites (hot-path
    executor tallies, the enable/disable switches) are documented in the
    diagnostics module docstring, not asserted exact here."""

    def test_hammer_exact_counts(self):
        import threading

        diagnostics.reset()
        n_threads, n_iters = 8, 500
        errors = []

        def hammer(slot):
            try:
                for i in range(n_iters):
                    diagnostics.counter("hammer.counter", 1)
                    with diagnostics.span("hammer.span"):
                        pass
                    diagnostics.record_collective("hammer", "d", 8, 64)
                    diagnostics.record_dispatch_event("miss", "hammer", f"{slot}:{i}")
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        with metrics():
            threads = [
                __import__("threading").Thread(target=hammer, args=(s,))
                for s in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            self.assertEqual(errors, [])
            rep = diagnostics.report()
        total = n_threads * n_iters
        self.assertEqual(rep["counters"]["hammer.counter"], total)
        self.assertEqual(rep["spans"]["hammer.span"]["count"], total)
        coll = [c for c in rep["collectives"] if c["op"] == "hammer"]
        self.assertEqual(len(coll), 1)
        self.assertEqual(coll[0]["count"], total)
        self.assertEqual(coll[0]["bytes"], total * 64)
        # the bounded deque holds the most recent tail, never more
        self.assertLessEqual(len(rep["dispatch_events"]), diagnostics._MAX_EVENTS)

    def test_concurrent_framework_dispatch(self):
        import threading

        errors = []

        def serve(seed):
            try:
                a = ht.array(np.full(32, float(seed), dtype=np.float32), split=0)
                for _ in range(5):
                    ((a + 1.0) * 0.5).sum().parray
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        with metrics():
            threads = [threading.Thread(target=serve, args=(s,)) for s in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            rep = diagnostics.report()
        self.assertEqual(errors, [])
        # shard is recorded per layout request — at least one per thread's array
        shards = [c for c in rep["collectives"] if c["op"] == "shard"]
        self.assertTrue(shards)

    def test_provider_registration_during_report(self):
        # register_provider now takes the registry lock; racing registration
        # against report() must neither drop sections nor raise
        import threading

        stop = threading.Event()

        def spin_register():
            i = 0
            while not stop.is_set():
                diagnostics.register_provider(f"_hammer_{i % 4}", lambda: {"ok": 1})
                i += 1

        t = threading.Thread(target=spin_register)
        t.start()
        try:
            for _ in range(20):
                rep = diagnostics.report()
                self.assertIn("schema", rep)
        finally:
            stop.set()
            t.join()
        for i in range(4):
            diagnostics._providers.pop(f"_hammer_{i}", None)


class _RecordedAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: what was opened inside what."""

    def __init__(self):
        self.events = []  # (name, parent name or None, keyword arguments), in entry order
        self._open = {}

    def __call__(self, name, **kwargs):
        recorder = self

        class _Annotation:
            def __enter__(self):
                import threading

                stack = recorder._open.setdefault(threading.get_ident(), [])
                recorder.events.append((name, stack[-1] if stack else None, kwargs))
                stack.append(name)

            def __exit__(self, *exc):
                import threading

                recorder._open[threading.get_ident()].pop()

        return _Annotation()

    def parents(self, name):
        return {parent for n, parent, _ in self.events if n == name}


@contextlib.contextmanager
def recorded_annotations():
    from unittest import mock

    diagnostics._bind_jax()  # the real binding first, so that the fake is not replaced
    recorder = _RecordedAnnotation()
    with mock.patch.object(diagnostics, "_annotation", recorder):
        yield recorder


class TestHostSpans(_DiagTestCase):
    """ISSUE 25: ``diagnostics.span`` is the program's one host span — an annotation in
    the profiler's own trace, a per-thread stack (parent, self time), flat per-name
    aggregates and compile attribution; free and silent while disabled."""

    def test_nesting_and_self_time(self):
        import time

        with metrics():
            diagnostics.reset()
            with diagnostics.span("outer"):
                time.sleep(0.02)
                for _ in range(2):
                    with diagnostics.span("inner"):
                        time.sleep(0.03)
            spans = diagnostics.report()["spans"]
        outer, inner = spans["outer"], spans["inner"]
        self.assertEqual((outer["count"], inner["count"]), (1, 2))
        self.assertGreaterEqual(inner["total_s"], 0.06)
        self.assertAlmostEqual(inner["self_s"], inner["total_s"])  # no child of its own
        self.assertGreaterEqual(outer["total_s"], inner["total_s"] + 0.02)
        # self time is the duration less what the child spans cover, to the clock's digit
        self.assertAlmostEqual(outer["self_s"], outer["total_s"] - inner["total_s"], places=9)
        self.assertGreaterEqual(outer["self_s"], 0.02)
        self.assertLess(outer["self_s"], 0.05)  # the children's 60 ms are not in it
        self.assertGreaterEqual(inner["max_s"], 0.03)

    def test_flat_counters_and_their_window_deltas(self):
        with metrics():
            diagnostics.reset()
            for _ in range(3):
                with diagnostics.span("win.a"):
                    pass
            before = diagnostics.report()["counters"]
            for _ in range(5):
                with diagnostics.span("win.a"):
                    with diagnostics.span("win.b"):
                        pass
            after = diagnostics.report()["counters"]
            spans = diagnostics.report()["spans"]
        self.assertEqual(before["span_n.win.a"], 3)
        self.assertNotIn("span_n.win.b", before)
        delta = {k: after[k] - before.get(k, 0) for k in after}
        self.assertEqual(delta["span_n.win.a"], 5)
        self.assertEqual(delta["span_n.win.b"], 5)
        self.assertGreater(delta["span_s.win.a"], delta["span_self_s.win.a"])
        self.assertAlmostEqual(delta["span_s.win.a"] - delta["span_self_s.win.a"],
                               delta["span_s.win.b"], places=9)
        # the field leads the key, so a prefix selects one field of every span
        self.assertEqual({k for k in after if k.startswith("span_n.")},
                         {"span_n.win.a", "span_n.win.b"})
        self.assertEqual(set(spans["win.a"]), {"count", "total_s", "self_s", "max_s"})
        self.assertEqual(after["span_s.win.a"], spans["win.a"]["total_s"])

    def test_exact_counts_and_parents_under_four_threads(self):
        import threading
        import time

        n_threads, n_iters = 4, 200
        errors = []

        def work(slot):
            try:
                for _ in range(n_iters):
                    with diagnostics.span("mt.outer"):
                        with diagnostics.span(f"mt.inner.{slot}"):
                            time.sleep(0)
            except Exception as exc:  # pragma: no cover - failure path
                errors.append(exc)

        with metrics(), recorded_annotations() as seen:
            diagnostics.reset()
            threads = [threading.Thread(target=work, args=(s,)) for s in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            self.assertFalse(any(t.is_alive() for t in threads))
            spans = diagnostics.report()["spans"]
        self.assertEqual(errors, [])
        self.assertEqual(spans["mt.outer"]["count"], n_threads * n_iters)
        inner_total = 0.0
        for slot in range(n_threads):
            self.assertEqual(spans[f"mt.inner.{slot}"]["count"], n_iters)
            # the stack is per thread: a span's parent is its own thread's outer
            self.assertEqual(seen.parents(f"ht.mt.inner.{slot}"), {"ht.mt.outer"})
            inner_total += spans[f"mt.inner.{slot}"]["total_s"]
        self.assertEqual(seen.parents("ht.mt.outer"), {None})
        self.assertAlmostEqual(spans["mt.outer"]["total_s"] - spans["mt.outer"]["self_s"],
                               inner_total, places=6)

    def test_disabled_is_one_shared_no_op(self):
        diagnostics.disable()
        diagnostics.reset()
        with recorded_annotations() as seen:
            self.assertIs(diagnostics.span("off.a"), diagnostics.NO_SPAN)
            with diagnostics.span("off.a"):
                with diagnostics.span("off.b", operand=jnp.ones(2)):
                    pass
            x = ht.array(np.arange(12, dtype=np.float32).reshape(6, 2), split=0)
            ht.argmin(ht.spatial.cdist(x, x), axis=1).parray
        rep = diagnostics.report()
        self.assertEqual(seen.events, [])
        self.assertEqual(rep["spans"], {})
        self.assertEqual(rep["counters"], {})

    def test_entry_point_under_jit_opens_no_span_and_leaves_hlo_alone(self):
        model = ht.nn.Sequential(ht.nn.Linear(4, 3), ht.nn.ReLU(), ht.nn.Linear(3, 2))
        model.reset_parameters(1)
        v = jnp.ones((5, 4), jnp.float32)

        def lowered():
            return program_text(jax.jit(lambda t: model(t)).lower(v).compile())

        diagnostics.disable()
        off = lowered()
        with metrics(), recorded_annotations() as seen:
            diagnostics.reset()
            on = lowered()
            traced = diagnostics.report()["spans"]
            model(v)  # the same call on a concrete array does open one
            eager = diagnostics.report()["spans"]
        self.assertEqual(on, off, "host spans changed compiled HLO")
        self.assertNotIn("nn.forward", traced)
        self.assertEqual(eager["nn.forward"]["count"], 1)
        self.assertEqual([n for n, _, _ in seen.events], ["ht.nn.forward"])

    def test_compile_is_attributed_to_the_innermost_span(self):
        v = jnp.arange(7, dtype=jnp.float32)
        with metrics():
            diagnostics.reset()
            with diagnostics.span("cmp.outer"):
                with diagnostics.span("cmp.inner"):
                    jax.jit(lambda t: t * 3.0 + 25.0)(v).block_until_ready()
                with diagnostics.span("cmp.quiet"):
                    pass
            jax.jit(lambda t: t * 5.0 - 25.0)(v).block_until_ready()
            counters = diagnostics.report()["counters"]
        self.assertGreaterEqual(counters["compile_n.cmp.inner"], 1)
        self.assertGreater(counters["compile_s.cmp.inner"], 0.0)
        self.assertGreaterEqual(counters["compile_n.none"], 1)
        self.assertNotIn("compile_n.cmp.outer", counters)
        self.assertNotIn("compile_n.cmp.quiet", counters)
        diagnostics.disable()
        diagnostics.reset()
        jax.jit(lambda t: t * 7.0 - 25.0)(v).block_until_ready()
        self.assertEqual(diagnostics.report()["counters"], {})  # the listener reads the switch

    def test_served_entry_points_nest_as_the_table_says(self):
        from heat_tpu.core import profiler

        rng = np.random.default_rng(0)
        x = ht.array(rng.normal(size=(48, 8)).astype(np.float32), split=0)
        q = ht.array(rng.normal(size=(6, 8)).astype(np.float32))
        km = ht.cluster.KMeans(n_clusters=3, init=ht.array(x.numpy()[:3]), max_iter=2, tol=-1.0)
        model = ht.nn.Sequential(ht.nn.Linear(8, 4), ht.nn.ReLU(), ht.nn.Linear(4, 2))
        a = ht.array(rng.normal(size=(16, 16)).astype(np.float32), split=0)
        b = ht.array(rng.normal(size=(16, 16)).astype(np.float32), split=1)
        with metrics(), recorded_annotations() as seen:
            diagnostics.reset()
            km.fit(x)
            with profiler.request("t.kmeans"):
                km.predict(x).parray
            with profiler.request("t.knn"):
                ht.argmin(ht.spatial.cdist(q, x), axis=1).parray
            with profiler.request("t.mlp"):
                model(x).parray
            ht.linalg.matmul(a, b).parray
            counters = diagnostics.report()["counters"]
        self.assertEqual(seen.parents("ht.cluster.fit"), {None})
        self.assertEqual(seen.parents("ht.cluster.fit.lloyd"), {"ht.cluster.fit"})
        self.assertEqual(seen.parents("ht.cluster.predict"), {"ht.request.t.kmeans"})
        self.assertEqual(seen.parents("ht.spatial.cdist"),
                         {"ht.cluster.predict", "ht.request.t.knn"})
        self.assertEqual(seen.parents("ht.statistics.argreduce"),
                         {"ht.cluster.predict", "ht.request.t.knn"})
        self.assertEqual(seen.parents("ht.nn.forward"), {"ht.request.t.mlp"})
        self.assertEqual(seen.parents("ht.linalg.matmul"), {None})
        self.assertEqual(seen.parents("ht.linalg.plan"), {"ht.linalg.matmul"})
        for name in ("cluster.fit", "cluster.fit.lloyd", "cluster.predict", "nn.forward",
                     "linalg.matmul", "linalg.plan", "request.t.kmeans", "request.t.knn",
                     "request.t.mlp"):
            self.assertEqual(counters[f"span_n.{name}"], 1, name)
        self.assertEqual(counters["span_n.spatial.cdist"], 2)
        self.assertEqual(counters["span_n.statistics.argreduce"], 2)
        # what a request spends inside the entry points is never more than the request
        for tag in ("t.kmeans", "t.knn", "t.mlp"):
            whole = counters[f"span_s.request.{tag}"]
            self.assertGreater(whole, whole - counters[f"span_self_s.request.{tag}"])
            self.assertGreater(whole - counters[f"span_self_s.request.{tag}"], 0.0)

    def test_spans_lie_in_the_profiler_trace_on_one_clock(self):
        """A real ``jax.profiler`` trace: ``ht.request.*`` and its children are events
        of the thread's own line, inside an annotation the caller opened itself."""
        import glob

        from jax.profiler import ProfileData

        from heat_tpu.core import profiler

        rng = np.random.default_rng(1)
        x = ht.array(rng.normal(size=(32, 4)).astype(np.float32), split=0)
        km = ht.cluster.KMeans(n_clusters=2, init=ht.array(x.numpy()[:2]), max_iter=2, tol=-1.0)
        km.fit(x)
        km.predict(x).parray  # compiled before the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        with tempfile.TemporaryDirectory() as d, metrics():
            was_active = profiler.active()
            profiler.enable()  # the request id is threaded only while it collects
            jax.profiler.start_trace(d, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("bench.request:kmeans"):
                    with profiler.request("bench.kmeans") as rid:
                        jax.block_until_ready(km.predict(x).parray)
            finally:
                jax.profiler.stop_trace()
                if not was_active:
                    profiler.disable()
                    profiler.reset()
            (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
            profile = ProfileData.from_file(path)
        found = {}
        for plane in profile.planes:
            for line in plane.lines:
                events = {}
                for ev in line.events:
                    if ev.name.startswith(("bench.", "ht.")):
                        events[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns,
                                           dict(ev.stats))
                if "ht.request.bench.kmeans" in events:
                    found = events
        self.assertTrue(found, "no ht.request.* event in the trace")
        nest = ["bench.request:kmeans", "ht.request.bench.kmeans", "ht.cluster.predict"]
        for outer, inner in zip(nest, nest[1:] + ["ht.spatial.cdist"]):
            self.assertIn(inner, found)
            self.assertLessEqual(found[outer][0], found[inner][0], (outer, inner))
            self.assertGreaterEqual(found[outer][1], found[inner][1], (outer, inner))
        predict, cdist, arg = (found[n] for n in ("ht.cluster.predict", "ht.spatial.cdist",
                                                  "ht.statistics.argreduce"))
        self.assertLessEqual(cdist[1], arg[0])  # siblings, one after the other
        self.assertGreaterEqual(predict[1], arg[1])
        self.assertIsNotNone(rid)
        for name in nest[1:] + ["ht.spatial.cdist", "ht.statistics.argreduce"]:
            self.assertEqual(found[name][2].get("req"), rid, name)  # one request, one id


def _run_python(code, **env):
    """``code`` in a fresh interpreter on the CPU, metrics as the caller's ``env`` says."""
    base = {k: v for k, v in os.environ.items()
            if k not in ("HEAT_TPU_METRICS", "HEAT_TPU_DIAG_DUMP", "JAX_COMPILATION_CACHE_DIR")}
    base.update(env)
    return subprocess.run([sys.executable, "-c", code], env=base, capture_output=True,
                          text=True, timeout=240)


TOP_PHASES = ["import.jax", "bootstrap.config", "bootstrap.join", "bootstrap.world",
              "import.core", "import.packages"]
WORLD_PHASES = ["bootstrap.world.backend", "bootstrap.world.telemetry"]


class TestStartupRecord(_DiagTestCase):
    """``report()["startup"]``: the phases of ``import heat_tpu``, always on, written once."""

    def test_the_record_is_there_with_metrics_off(self):
        diagnostics.disable()
        record = diagnostics.report()["startup"]
        for name in TOP_PHASES + WORLD_PHASES + ["before_import"]:
            self.assertGreaterEqual(record[name]["start_s"], 0.0, name)
            self.assertGreaterEqual(record[name]["seconds"], 0.0, name)
        self.assertIn("jax_preimported", record["import.jax"])
        self.assertIn("backend_created", record["bootstrap.world.backend"])
        self.assertGreater(record["import_s"], 0.0)
        self.assertRegex(record["wall_start"], r"^\d{4}-\d\d-\d\dT\d\d:\d\d:\d\dZ$")
        json.dumps(record)  # plain numbers, strings and booleans all through

    def test_phases_nest_and_sum_to_no_more_than_the_import(self):
        record = diagnostics.report()["startup"]

        def ends(name):
            return record[name]["start_s"] + record[name]["seconds"]

        for earlier, later in zip(TOP_PHASES, TOP_PHASES[1:]):  # one after the other
            self.assertLessEqual(ends(earlier), record[later]["start_s"] + 1e-9, later)
        world = record["bootstrap.world"]
        for name in WORLD_PHASES:
            self.assertGreaterEqual(record[name]["start_s"], world["start_s"], name)
            self.assertLessEqual(ends(name), ends("bootstrap.world") + 1e-9, name)
        steps = sum(record[n]["seconds"] for n in TOP_PHASES[1:4])
        self.assertLessEqual(steps, record["bootstrap_s"] + 1e-9)
        parts = (record["import.jax"]["seconds"] + record["bootstrap_s"]
                 + record["import.core"]["seconds"] + record["import.packages"]["seconds"])
        self.assertLessEqual(parts, record["import_s"] + 1e-9)
        self.assertGreater(parts, 0.5 * record["import_s"])  # the phases cover the import
        # the record's clock: process start, the import and now lie in that order
        import time
        since_start = time.perf_counter() - record["perf_counter_at_start"]
        self.assertLessEqual(record["before_import"]["seconds"] + record["import_s"], since_start)
        self.assertLessEqual(ends("import.packages"),
                             record["before_import"]["seconds"] + record["import_s"] + 1e-6)

    def test_reset_keeps_it_and_nothing_writes_it_after_the_import(self):
        before = diagnostics.report()["startup"]
        diagnostics.reset()
        self.assertIs(diagnostics.startup("late.phase", field=1), diagnostics.NO_SPAN)
        with diagnostics.startup("late.phase"):
            pass
        ht.core.communication.build_world()  # an elastic restart's tail runs the phase again
        diagnostics.startup_imported(0.0)
        self.assertEqual(diagnostics.report()["startup"], before)

    def test_a_program_that_imported_jax_and_made_the_backend_first(self):
        code = (
            "import jax, json\n"
            "jax.devices()\n"
            "import heat_tpu as ht\n"
            "print(json.dumps(ht.diagnostics.report()['startup']))\n"
        )
        proc = _run_python(code)
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        record = json.loads(proc.stdout.strip().splitlines()[-1])
        self.assertIs(record["import.jax"]["jax_preimported"], True)
        self.assertIs(record["bootstrap.world.backend"]["backend_created"], True)
        self.assertLess(record["import.jax"]["seconds"], 0.25)
        # `import jax` and the backend lie before the import, where the record says
        self.assertGreater(record["before_import"]["seconds"], record["import.jax"]["seconds"])
        own = diagnostics.report()["startup"]  # this process left both to the import
        if not own["import.jax"]["jax_preimported"]:
            self.assertGreater(own["import.jax"]["seconds"],
                               record["import.jax"]["seconds"])

    def test_metrics_unset_fills_the_record_and_registers_no_listener(self):
        code = (
            "import heat_tpu as ht, jax, jax.numpy as jnp\n"
            "from jax._src import monitoring\n"
            "d = ht.diagnostics\n"
            "def ours():\n"
            "    return [f for f in monitoring.get_event_listeners()\n"
            "            + monitoring.get_event_duration_listeners()\n"
            "            + monitoring.get_scalar_listeners()\n"
            "            if getattr(f, '__module__', '') == d.__name__]\n"
            "jax.jit(lambda t: t + 1)(jnp.ones(3)).block_until_ready()\n"
            "rep = d.report()\n"
            "assert not rep['enabled'] and ours() == [], ours()\n"
            "assert rep['programs'] == {} and rep['counters'] == {}, rep['counters']\n"
            "assert rep['startup']['import_s'] > 0 and rep['startup']['bootstrap_s'] > 0\n"
            "d.enable()\n"
            "assert sorted(f.__name__ for f in ours()) == ['_on_duration', '_on_event', '_on_stage_begins']\n"
            "d.disable(); d.enable()\n"
            "assert len(ours()) == 3  # registered once\n"
            "print('unset-ok')\n"
        )
        proc = _run_python(code)
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        self.assertIn("unset-ok", proc.stdout)


class TestCompileAccounting(_DiagTestCase):
    """What ``jax.monitoring`` tells of tracing, lowering, compiling and the persistent
    cache, kept by program (``report()["programs"]``) and flat (``jit.*``)."""

    def test_a_jitted_function_is_one_program_and_a_cached_call_adds_nothing(self):
        def accounted_program(t):
            return jnp.tanh(t) * 3.0 - 41.0

        fn = jax.jit(accounted_program)
        v = jnp.arange(11, dtype=jnp.float32)
        with metrics():
            diagnostics.reset()
            fn(v).block_until_ready()
            first = diagnostics.report()
            fn(v).block_until_ready()
            second = diagnostics.report()
        entry = first["programs"]["accounted_program"]
        self.assertEqual((entry["trace_n"], entry["backend_n"]), (1, 1))
        self.assertGreater(entry["trace_s"], 0.0)
        self.assertGreater(entry["lower_s"], 0.0)  # `jit(accounted_program)` is the same entry
        self.assertGreater(entry["backend_s"], 0.0)
        counters = first["counters"]
        for name in diagnostics._JIT_COUNTERS:
            self.assertIn(name, counters)
        self.assertGreaterEqual(counters["jit.backend_n"], 1)
        self.assertGreaterEqual(counters["jit.backend_s"], entry["backend_s"] - 1e-9)
        self.assertEqual(second["programs"], first["programs"])
        for name in diagnostics._JIT_COUNTERS:
            self.assertEqual(second["counters"][name], counters[name], name)

    def test_a_stage_inside_another_is_counted_once_in_the_flat_seconds(self):
        def inner_program(t):
            return jnp.sinh(t) + 43.0

        inner = jax.jit(inner_program)

        def outer_program(t):
            return inner(t) * 2.0

        v = jnp.arange(13, dtype=jnp.float32)
        with metrics():
            diagnostics.reset()
            jax.jit(outer_program)(v).block_until_ready()
            rep = diagnostics.report()
        programs, counters = rep["programs"], rep["counters"]
        self.assertEqual(programs["inner_program"]["trace_n"], 1)
        self.assertEqual(programs["inner_program"]["backend_n"], 0)  # no program of its own
        self.assertGreater(programs["outer_program"]["trace_s"],
                           programs["inner_program"]["trace_s"])
        by_program = sum(p["trace_s"] for p in programs.values())
        self.assertLess(counters["jit.trace_s"], by_program)
        # nothing else was traced at the top: the flat seconds are the outer trace's own
        top = sum(p["trace_s"] for name, p in programs.items() if p["backend_n"])
        self.assertAlmostEqual(counters["jit.trace_s"], top, delta=1e-3)
        self.assertEqual(getattr(diagnostics._open, "stages", []), [])  # every stage closed

    def test_the_flat_seconds_by_hand(self):
        trace, lower = list(diagnostics._STAGES)[:2]
        with metrics():
            diagnostics.reset()
            diagnostics._on_stage_begins(trace, 0.0, fun_name="outer_fn")
            for seconds in (0.25, 0.125):
                diagnostics._on_stage_begins(trace, 0.0, fun_name="inner_fn")
                diagnostics._on_duration(trace, seconds, fun_name="inner_fn")
            diagnostics._on_duration(trace, 10.0, fun_name="outer_fn")
            diagnostics._on_stage_begins(lower, 0.0, fun_name="jit(outer_fn)")
            diagnostics._on_duration(lower, 2.0, fun_name="jit(outer_fn)")
            diagnostics._on_duration(lower, 1.0, fun_name="jit(late_fn)")  # began while off
            rep = diagnostics.report()
        self.assertEqual(rep["programs"]["inner_fn"]["trace_n"], 2)
        self.assertAlmostEqual(rep["programs"]["inner_fn"]["trace_s"], 0.375)
        self.assertAlmostEqual(rep["programs"]["outer_fn"]["trace_s"], 10.0)
        self.assertAlmostEqual(rep["programs"]["outer_fn"]["lower_s"], 2.0)
        self.assertAlmostEqual(rep["counters"]["jit.trace_s"], 10.0)  # not 10.375
        self.assertAlmostEqual(rep["counters"]["jit.lower_s"], 3.0)

    def test_the_table_is_bounded(self):
        with metrics():
            diagnostics.reset()
            for i in range(diagnostics._MAX_PROGRAMS + 44):
                diagnostics._on_duration(diagnostics._COMPILE_EVENT, 1e-9, fun_name=f"jit(p{i})")
            rep = diagnostics.report()
        self.assertEqual(len(rep["programs"]), diagnostics._MAX_PROGRAMS + 1)
        self.assertEqual(rep["programs"]["other"]["backend_n"], 44)
        self.assertEqual(rep["counters"]["jit.backend_n"], diagnostics._MAX_PROGRAMS + 44)
        self.assertEqual(rep["counters"]["compile_n.none"], diagnostics._MAX_PROGRAMS + 44)

    def test_a_second_compile_is_read_from_the_persistent_cache(self):
        code = (
            "import sys, jax, jax.numpy as jnp\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)\n"
            "import heat_tpu as ht\n"
            "d = ht.diagnostics\n"
            "def cached_program(t):\n"
            "    return jnp.cos(t) * 5.0 + 17.0\n"
            "fn = jax.jit(cached_program)\n"
            "v = jnp.arange(9, dtype=jnp.float32)\n"
            "d.enable()\n"
            "fn(v).block_until_ready()\n"
            "c = d.report()['counters']\n"
            "assert c['jit.cache_hit_n'] == 0 and c['jit.cache_miss_n'] >= 1, c\n"
            "assert c['jit.cache_read_s'] == 0, c\n"
            "jax.clear_caches()\n"
            "fn(v).block_until_ready()\n"
            "rep = d.report()\n"
            "c, p = rep['counters'], rep['programs']['cached_program']\n"
            "assert c['jit.cache_hit_n'] >= 1 and c['jit.cache_read_s'] > 0, c\n"
            "assert c['jit.cache_read_s'] <= c['jit.backend_s'], c\n"
            "assert (p['trace_n'], p['backend_n']) == (2, 2), p\n"
            "assert (p['cache_miss_n'], p['cache_hit_n']) == (1, 1), p\n"
            "assert 'jit.cache_saved_s' in c, c\n"
            "print('cache-ok')\n"
        )
        with tempfile.TemporaryDirectory() as cache:
            proc = _run_python(code, JAX_COMPILATION_CACHE_DIR=cache)
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        self.assertIn("cache-ok", proc.stdout)
