"""Self-check for ``ht.analysis`` — the framework invariant checker.

Three layers, per the checker's own contract:

- **rule fixtures** — every shipped rule family has a minimal violating and a
  minimal conforming snippet, compiled through a throwaway package tree whose
  module names line up with the real policy keys (``heat_tpu.core.diagnostics``
  et al.), so the lock policy / import contract / donation-home logic is
  exercised exactly as it runs against the real tree;
- **pragma + baseline round-trips** — a reasoned pragma suppresses, a
  reasonless or unknown-rule or unused pragma is itself a finding, and a stale
  baseline entry fails the run;
- **the whole-repo gate** — the real tree must be clean against the committed
  baseline (tier-1 keeps the repo lint-clean), the committed lock graph must
  match the discovered one, and injecting the acceptance-criteria synthetic
  violations (an unlocked write to locked diagnostics state; a top-level
  ``import jax`` in ``resilience.py``) must fail with the right rule ids.

Plus the runtime twins of the import contracts: a subprocess loads every
stdlib-only module by file path under a ``sys.meta_path`` hook that raises on
any ``jax``/``numpy``/``jaxlib`` import, and another imports the package under
the multi-controller launch contract with JAX's backend factory patched to
raise until the process has joined ``jax.distributed``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap
import unittest

from heat_tpu.analysis import baseline as baseline_mod
from heat_tpu.analysis import rules
from heat_tpu.analysis.engine import Finding, run_analysis
from heat_tpu.analysis.rules_locks import lock_graph_payload

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_fixture(files):
    """Run the checker over a throwaway package tree. ``files`` maps paths
    relative to the fake ``heat_tpu`` package root to (dedented) sources."""
    with tempfile.TemporaryDirectory() as td:
        pkg = os.path.join(td, "heat_tpu")
        for rel, src in files.items():
            path = os.path.join(pkg, rel)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w", encoding="utf-8") as fh:
                fh.write(textwrap.dedent(src))
        findings, _ = run_analysis(package_root=pkg)
        return findings


def rule_ids(findings):
    return sorted({f.rule for f in findings})


class TestTracePurityRules(unittest.TestCase):
    def test_env_read_violating_and_conforming(self):
        bad = run_fixture({"core/x.py": """
            import os
            import jax

            def outer():
                def body(v):
                    if os.environ.get("KNOB"):
                        return v
                    return v
                return jax.jit(body)
        """})
        self.assertIn("trace-env-read", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            import os
            import jax

            KNOB = os.environ.get("KNOB")  # host-side, at import

            def outer():
                def body(v):
                    return v
                return jax.jit(body)
        """})
        self.assertNotIn("trace-env-read", rule_ids(good))

    def test_time_call_in_shard_map_body(self):
        bad = run_fixture({"core/x.py": """
            import time
            import jax

            def outer(mesh):
                def body(v):
                    time.perf_counter()
                    return v
                return jax.shard_map(body, mesh=mesh)
        """})
        self.assertIn("trace-time-call", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            import time
            import jax

            def outer(mesh):
                t0 = time.perf_counter()  # around the trace, not in it
                def body(v):
                    return v
                return jax.shard_map(body, mesh=mesh)
        """})
        self.assertNotIn("trace-time-call", rule_ids(good))

    def test_unguarded_telemetry_vs_gated(self):
        bad = run_fixture({"core/x.py": """
            import jax
            from . import diagnostics

            def outer():
                def body(v):
                    diagnostics.counter("ops")
                    return v
                return jax.jit(body)
        """})
        self.assertIn("trace-telemetry-unguarded", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            import jax
            from . import diagnostics

            def outer():
                def body(v):
                    if diagnostics._enabled:
                        diagnostics.counter("ops")
                    return v
                return jax.jit(body)
        """})
        self.assertNotIn("trace-telemetry-unguarded", rule_ids(good))

    def test_global_write_and_lazy_import(self):
        bad = run_fixture({"core/x.py": """
            import jax

            _memo = {}

            def outer():
                def body(v):
                    import os
                    global _state
                    _state = 1
                    _memo[1] = v
                    return v
                return jax.jit(body)
        """})
        ids = rule_ids(bad)
        self.assertIn("trace-global-write", ids)
        self.assertIn("trace-lazy-import", ids)
        good = run_fixture({"core/x.py": """
            import jax

            def outer():
                def body(v):
                    local = {}
                    local[1] = v
                    return v
                return jax.jit(body)
        """})
        ids = rule_ids(good)
        self.assertNotIn("trace-global-write", ids)
        self.assertNotIn("trace-lazy-import", ids)

    def test_build_callback_convention_seeds_traced_set(self):
        # the _executor.lookup protocol: the function RETURNED by build() is
        # the traced program body even though jax.jit never appears here
        bad = run_fixture({"core/x.py": """
            import os

            def stage():
                def build():
                    def body(v):
                        os.environ.get("KNOB")
                        return v
                    return body, None, None, None
                return build
        """})
        self.assertIn("trace-env-read", rule_ids(bad))


class TestLockRules(unittest.TestCase):
    DIAG_BAD = """
        import threading

        _lock = threading.RLock()
        _counters = {}

        def bump():
            _counters["x"] = 1
    """
    DIAG_GOOD = """
        import threading

        _lock = threading.RLock()
        _counters = {}

        def bump():
            with _lock:
                _counters["x"] = 1

        def _fold_locked():
            _counters["y"] = 2  # _locked suffix: caller holds the lock
    """

    def test_unlocked_write_to_locked_diagnostics_state(self):
        # the acceptance-criteria synthetic violation: an unlocked write to
        # locked diagnostics registry state must fail with lock-unlocked-write
        bad = run_fixture({"core/diagnostics.py": self.DIAG_BAD})
        self.assertIn("lock-unlocked-write", rule_ids(bad))
        good = run_fixture({"core/diagnostics.py": self.DIAG_GOOD})
        self.assertNotIn("lock-unlocked-write", rule_ids(good))

    def test_racing_increment(self):
        bad = run_fixture({"core/x.py": """
            _total = 0

            def bump():
                global _total
                _total += 1
        """})
        self.assertIn("lock-racing-increment", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            import threading

            _lock = threading.Lock()
            _total = 0

            def bump():
                global _total
                with _lock:
                    _total += 1
        """})
        self.assertNotIn("lock-racing-increment", rule_ids(good))

    def test_lock_order_cycle(self):
        files = {
            "core/diagnostics.py": """
                import threading
                from . import profiler

                _lock = threading.RLock()

                def a():
                    with _lock:
                        profiler.pb()

                def pa():
                    with _lock:
                        pass
            """,
            "core/profiler.py": """
                import threading
                from . import diagnostics

                _lock = threading.RLock()

                def pb():
                    with _lock:
                        pass

                def b():
                    with _lock:
                        diagnostics.pa()
            """,
        }
        bad = run_fixture(files)
        self.assertIn("lock-order-cycle", rule_ids(bad))
        # drop the reversed edge: acyclic, no finding
        files["core/profiler.py"] = """
            import threading

            _lock = threading.RLock()

            def pb():
                with _lock:
                    pass
        """
        good = run_fixture(files)
        self.assertNotIn("lock-order-cycle", rule_ids(good))


class TestImportContractRule(unittest.TestCase):
    def test_toplevel_jax_in_resilience_fails(self):
        # the acceptance-criteria synthetic violation: resilience.py is
        # stdlib-only at load, a top-level import jax must fail the run
        bad = run_fixture({"core/resilience.py": """
            import json
            import jax
        """})
        self.assertIn("import-nonstdlib", rule_ids(bad))

    def test_stdlib_and_lazy_imports_pass(self):
        good = run_fixture({"core/resilience.py": """
            import json
            import threading

            def probe():
                import numpy as np  # lazy: sanctioned
                return np
        """})
        self.assertNotIn("import-nonstdlib", rule_ids(good))

    def test_relative_import_within_contract_set_passes(self):
        good = run_fixture({"core/resilience.py": """
            import json

            try:
                from . import diagnostics
            except ImportError:
                diagnostics = None
        """})
        self.assertNotIn("import-nonstdlib", rule_ids(good))


class TestBringUpOrderRule(unittest.TestCase):
    """``import-backend-touch``: the static twin of
    ``TestRuntimeImportContract.test_no_backend_touch_before_distributed_join``."""

    DEVICES = """
        import jax

        _default_platform = jax.default_backend()
    """
    BOOTSTRAP = """
        import jax

        def run():
            jax.distributed.initialize()
            from . import devices
    """
    CORE_INIT = """
        from . import _bootstrap

        _bootstrap.run()

        from . import devices
    """

    def test_early_module_touching_backend_fails(self):
        # the PR 21 shape: a module the bring-up loads before the join reads
        # the backend at module level
        bad = run_fixture({
            "core/__init__.py": self.CORE_INIT,
            "core/_bootstrap.py": "from .devices import x\n" + textwrap.dedent(self.BOOTSTRAP),
            "core/devices.py": self.DEVICES,
        })
        hits = [f for f in bad if f.rule == "import-backend-touch"]
        self.assertEqual([(f.path, f.line) for f in hits],
                         [("heat_tpu/core/devices.py", 4)], bad)
        good = run_fixture({
            "core/__init__.py": self.CORE_INIT,
            "core/_bootstrap.py": self.BOOTSTRAP,
            "core/devices.py": self.DEVICES,  # imported after run(): may read it
        })
        self.assertNotIn("import-backend-touch", rule_ids(good))

    def test_import_before_the_bring_up_call_fails(self):
        bad = run_fixture({
            "core/__init__.py": """
                from . import devices
                from . import _bootstrap

                _bootstrap.run()
            """,
            "core/_bootstrap.py": self.BOOTSTRAP,
            "core/devices.py": self.DEVICES,
        })
        self.assertIn("import-backend-touch", rule_ids(bad))


class TestFallbackRule(unittest.TestCase):
    def test_silent_except_vs_typed_vs_accounted(self):
        bad = run_fixture({"core/x.py": """
            def f():
                try:
                    return 1
                except Exception:
                    return None
        """})
        self.assertIn("silent-except", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            from . import diagnostics

            def typed():
                try:
                    return 1
                except (OSError, ValueError):
                    return None

            def reraises():
                try:
                    return 1
                except Exception as exc:
                    raise RuntimeError("wrapped") from exc

            def accounted():
                try:
                    return 1
                except Exception as exc:
                    diagnostics.record_fallback("site", str(exc))
                    return None
        """})
        self.assertNotIn("silent-except", rule_ids(good))


class TestDonationCollectiveRules(unittest.TestCase):
    def test_donation_outside_executor(self):
        bad = run_fixture({"core/x.py": """
            import jax

            def f(body):
                return jax.jit(body, donate_argnums=(0,))
        """})
        self.assertIn("donation-uncontracted", rule_ids(bad))
        good = run_fixture({"core/_executor.py": """
            import jax

            def f(body):
                return jax.jit(body, donate_argnums=(0,))
        """})
        self.assertNotIn("donation-uncontracted", rule_ids(good))

    def test_collective_outside_communication(self):
        bad = run_fixture({"core/x.py": """
            import jax

            def f(v):
                return jax.lax.psum(v, "d")
        """})
        self.assertIn("collective-uncontracted", rule_ids(bad))
        good = run_fixture({"core/communication.py": """
            import jax

            def f(v):
                return jax.lax.psum(v, "d")
        """})
        self.assertNotIn("collective-uncontracted", rule_ids(good))


class TestPragmas(unittest.TestCase):
    BAD_BODY = """
        def f():
            try:
                return 1
            except Exception:{pragma}
                return None
    """

    def _with_pragma(self, pragma):
        return run_fixture({"core/x.py": self.BAD_BODY.format(pragma=pragma)})

    def test_reasoned_pragma_suppresses(self):
        out = self._with_pragma(
            "  # ht: ignore[silent-except] -- fixture: deliberate swallow"
        )
        self.assertEqual(rule_ids(out), [])

    def test_reasonless_pragma_is_finding_and_does_not_suppress(self):
        out = self._with_pragma("  # ht: ignore[silent-except]")
        ids = rule_ids(out)
        self.assertIn("pragma-no-reason", ids)
        self.assertIn("silent-except", ids)

    def test_unknown_rule_pragma(self):
        out = self._with_pragma("  # ht: ignore[no-such-rule] -- whatever")
        ids = rule_ids(out)
        self.assertIn("pragma-unknown-rule", ids)
        self.assertIn("silent-except", ids)

    def test_unused_pragma_is_finding(self):
        out = run_fixture({"core/x.py": """
            def f():  # ht: ignore[silent-except] -- nothing here to suppress
                return 1
        """})
        self.assertEqual(rule_ids(out), ["pragma-unused"])


class TestBaseline(unittest.TestCase):
    def _findings(self):
        return [
            Finding("silent-except", "heat_tpu/core/x.py", 4,
                    "msg", "except Exception:"),
        ]

    def test_round_trip_and_staleness(self):
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "baseline.json")
            found = self._findings()
            baseline_mod.save(path, found)
            entries = baseline_mod.load(path)
            new, old, stale = baseline_mod.apply(found, entries)
            self.assertEqual((len(new), len(old), len(stale)), (0, 1, 0))
            # the offending line was fixed: the entry goes stale and FAILS
            new, old, stale = baseline_mod.apply([], entries)
            self.assertEqual((len(new), len(old)), (0, 0))
            self.assertEqual([f.rule for f in stale], ["baseline-stale"])

    def test_line_drift_does_not_go_stale(self):
        entries = [{"rule": "silent-except", "path": "heat_tpu/core/x.py",
                    "snippet": "except Exception:"}]
        drifted = [Finding("silent-except", "heat_tpu/core/x.py", 400,
                           "msg", "except Exception:")]
        new, old, stale = baseline_mod.apply(drifted, entries)
        self.assertEqual((len(new), len(old), len(stale)), (0, 1, 0))

    def test_unknown_schema_rejected(self):
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "b.json")
            with open(path, "w") as fh:
                json.dump({"schema": "bogus/9", "findings": []}, fh)
            with self.assertRaises(ValueError):
                baseline_mod.load(path)


class TestWholeRepo(unittest.TestCase):
    """Tier-1 keeps the tree lint-clean: the real package must have zero
    non-baselined findings, and the committed lock graph must match."""

    @classmethod
    def setUpClass(cls):
        cls.findings, cls.universe = run_analysis()

    def test_repo_is_clean_against_committed_baseline(self):
        baseline_path = os.path.join(REPO_ROOT, "analysis_baseline.json")
        entries = baseline_mod.load(baseline_path) if os.path.exists(baseline_path) else []
        new, _, stale = baseline_mod.apply(self.findings, entries)
        msg = "\n".join(f.render() for f in new + stale)
        self.assertEqual(new + stale, [], f"repo not analysis-clean:\n{msg}")

    def test_rule_catalogue_has_explanations(self):
        for rule in rules.RULES:
            text = rules.explain(rule)
            self.assertNotIn("unknown rule", text)
        self.assertIn("known rules", rules.explain("definitely-not-a-rule"))

    def test_lock_graph_matches_committed_artifact_and_is_acyclic(self):
        payload = lock_graph_payload(self.universe)
        self.assertEqual(payload["cycles"], [],
                         f"lock-order cycle introduced: {payload['cycles']}")
        committed_path = os.path.join(
            REPO_ROOT, "doc", "source", "_static", "lock_graph.json"
        )
        with open(committed_path) as fh:
            committed = json.load(fh)
        discovered = {(e["from"], e["to"]) for e in payload["edges"]}
        recorded = {(e["from"], e["to"]) for e in committed["edges"]}
        self.assertEqual(
            discovered, recorded,
            "lock-acquisition graph changed; review the new ordering edges "
            "and regenerate with `python -m heat_tpu.analysis "
            "--dump-lockgraph doc/source/_static/lock_graph.json` (and .dot)",
        )

    def test_executor_lock_edges_present(self):
        # the edges ISSUE-8 follow-ups (multi-queue scheduler sharding) must
        # respect: the executor lock is always the OUTER lock
        payload = lock_graph_payload(self.universe)
        edges = {(e["from"], e["to"]) for e in payload["edges"]}
        self.assertIn(
            ("heat_tpu.core._executor:_lock", "heat_tpu.core._executor:_own_lock"),
            edges,
        )
        self.assertIn(
            ("heat_tpu.core._executor:_lock", "heat_tpu.core.diagnostics:_lock"),
            edges,
        )



class TestFixUnusedPragmas(unittest.TestCase):
    """The mechanical remover: dry-run by default, --write applies, and the
    result round-trips to a clean checker run."""

    BODY = textwrap.dedent("""
        def f():  # ht: ignore[silent-except] -- covered nothing, remove me
            return 1


        def g():
            try:
                return 1
            except Exception:  # ht: ignore[silent-except, trace-env-read] -- the swallow is deliberate
                return None
    """)

    def _fixture(self):
        td = tempfile.TemporaryDirectory()
        pkg = os.path.join(td.name, "heat_tpu")
        os.makedirs(os.path.join(pkg, "core"))
        target = os.path.join(pkg, "core", "x.py")
        with open(target, "w", encoding="utf-8") as fh:
            fh.write(self.BODY)
        return td, pkg, target

    def test_dry_run_changes_nothing(self):
        from heat_tpu.analysis.__main__ import main

        td, pkg, target = self._fixture()
        with td:
            before = open(target).read()
            rc = main(["--root", pkg, "--no-cache", "--fix-unused-pragmas"])
            self.assertEqual(rc, 0)
            self.assertEqual(open(target).read(), before)

    def test_write_round_trip(self):
        from heat_tpu.analysis.__main__ import main

        td, pkg, target = self._fixture()
        with td:
            rc = main(["--root", pkg, "--no-cache",
                       "--fix-unused-pragmas", "--write"])
            self.assertEqual(rc, 0)
            after = open(target).read()
            # the fully-unused pragma is gone; the used one lost only the
            # dead rule id and kept its reason
            self.assertNotIn("covered nothing", after)
            self.assertNotIn("trace-env-read", after)
            self.assertIn("ht: ignore[silent-except] -- the swallow is deliberate", after)
            # round trip: the fixed tree is pragma-clean
            findings, _ = run_analysis(package_root=pkg)
            self.assertEqual([f for f in findings if f.rule.startswith("pragma")], [])


class TestIncrementalCache(unittest.TestCase):
    """Content-hash keyed findings reuse with an all-or-nothing validity
    rule: a byte-identical tree is served from the cache, ANY edit re-runs
    everything — a stale cache must never mask a new violation."""

    CLEAN = """
        def f():
            return 1
    """
    VIOLATING = """
        def f():
            try:
                return 1
            except Exception:
                return None
    """

    def _fixture(self, body):
        td = tempfile.TemporaryDirectory()
        pkg = os.path.join(td.name, "heat_tpu")
        os.makedirs(os.path.join(pkg, "core"))
        target = os.path.join(pkg, "core", "x.py")
        with open(target, "w", encoding="utf-8") as fh:
            fh.write(textwrap.dedent(body))
        cache_path = os.path.join(td.name, "cache.json")
        return td, pkg, target, cache_path

    def test_warm_hit_serves_identical_findings(self):
        import contextlib
        import io

        from heat_tpu.analysis.__main__ import main

        td, pkg, target, cache_path = self._fixture(self.CLEAN)
        with td:
            self.assertEqual(main(["--root", pkg, "--cache", cache_path]), 0)
            self.assertTrue(os.path.exists(cache_path))
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = main(["--root", pkg, "--cache", cache_path])
            self.assertEqual(rc, 0)
            self.assertIn("cache hit", buf.getvalue())

    def test_stale_cache_never_masks_an_edit(self):
        from heat_tpu.analysis.__main__ import main

        td, pkg, target, cache_path = self._fixture(self.CLEAN)
        with td:
            self.assertEqual(main(["--root", pkg, "--cache", cache_path]), 0)
            # introduce a violation AFTER the cache was written
            with open(target, "w", encoding="utf-8") as fh:
                fh.write(textwrap.dedent(self.VIOLATING))
            rc = main(["--root", pkg, "--cache", cache_path])
            self.assertEqual(rc, 1, "stale cache served after an edit")
            # and fixing it is seen too (the cache was rewritten above)
            with open(target, "w", encoding="utf-8") as fh:
                fh.write(textwrap.dedent(self.CLEAN))
            self.assertEqual(main(["--root", pkg, "--cache", cache_path]), 0)

    def test_rule_code_change_invalidates(self):
        from heat_tpu.analysis import cache as cache_mod
        from heat_tpu.analysis.__main__ import main

        td, pkg, target, cache_path = self._fixture(self.CLEAN)
        with td:
            self.assertEqual(main(["--root", pkg, "--cache", cache_path]), 0)
            with open(cache_path) as fh:
                payload = json.load(fh)
            payload["code_hash"] = "stale-rules"
            with open(cache_path, "w") as fh:
                json.dump(payload, fh)
            hashes = cache_mod.module_hashes(pkg)
            self.assertIsNone(cache_mod.lookup(
                payload, pkg, cache_mod.code_fingerprint(), hashes
            ))

    def test_cache_stores_per_module_summaries(self):
        from heat_tpu.analysis.__main__ import main

        td, pkg, target, cache_path = self._fixture("""
            def emit(comm, v):
                return comm.psum(v)
        """)
        with td:
            self.assertEqual(main(["--root", pkg, "--cache", cache_path]), 0)
            with open(cache_path) as fh:
                payload = json.load(fh)
            entry = payload["modules"]["heat_tpu/core/x.py"]
            self.assertIn("hash", entry)
            self.assertEqual(
                entry["summaries"]["emit"]["seq"], ["comm.psum"]
            )

    def test_no_cache_flag_bypasses(self):
        import contextlib
        import io

        from heat_tpu.analysis.__main__ import main

        td, pkg, target, cache_path = self._fixture(self.CLEAN)
        with td:
            self.assertEqual(main(["--root", pkg, "--cache", cache_path]), 0)
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = main(["--root", pkg, "--cache", cache_path, "--no-cache"])
            self.assertEqual(rc, 0)
            self.assertNotIn("cache hit", buf.getvalue())

class TestRuntimeImportContract(unittest.TestCase):
    """The dynamic twin of ``import-nonstdlib``: load every stdlib-only module
    by file path (exactly how the driver entry points load them) in a fresh
    interpreter whose meta_path raises on any jax/numpy/jaxlib import."""

    def test_stdlib_only_modules_load_without_jax(self):
        code = textwrap.dedent("""
            import sys

            FORBIDDEN = ("jax", "jaxlib", "numpy", "scipy", "heat_tpu")

            class Guard:
                def find_spec(self, name, path=None, target=None):
                    if name.split(".")[0] in FORBIDDEN:
                        raise ImportError(
                            "forbidden import at module load: " + name
                        )
                    return None

            sys.meta_path.insert(0, Guard())

            import importlib.util
            import os

            root = sys.argv[1]
            rels = [
                os.path.join("heat_tpu", "core", "diagnostics.py"),
                os.path.join("heat_tpu", "core", "profiler.py"),
                os.path.join("heat_tpu", "core", "resilience.py"),
                os.path.join("heat_tpu", "core", "_scheduler.py"),
                os.path.join("heat_tpu", "core", "telemetry.py"),
            ]
            for rel in rels:
                path = os.path.join(root, rel)
                name = "_probe_" + os.path.basename(rel)[:-3]
                spec = importlib.util.spec_from_file_location(name, path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                print("LOADED", rel)
            print("STDLIB_ONLY_OK")
        """)
        env = dict(os.environ)
        env.pop("HEAT_TPU_FAULT_PLAN", None)
        env.pop("HEAT_TPU_DIAG_DUMP", None)
        proc = subprocess.run(
            [sys.executable, "-c", code, REPO_ROOT],
            capture_output=True, text=True, timeout=120, env=env,
        )
        self.assertEqual(
            proc.returncode, 0,
            f"stdlib-only-at-load contract broken:\n{proc.stderr[-2000:]}",
        )
        self.assertIn("STDLIB_ONLY_OK", proc.stdout)
        for rel in ("diagnostics.py", "profiler.py", "resilience.py",
                    "_scheduler.py", "telemetry.py"):
            self.assertIn(rel, proc.stdout)


    def test_no_backend_touch_before_distributed_join(self):
        """The dynamic twin of ``import-backend-touch``: with the launch
        contract set for a one-process job, ``import heat_tpu`` under a
        backend factory that raises until ``jax.distributed``'s client exists.
        A failure's traceback names the module and line that came too early."""
        code = textwrap.dedent("""
            import sys
            import traceback

            import jax
            from jax._src import distributed, xla_bridge

            real_backends = xla_bridge.backends

            def guarded_backends():
                if distributed.global_state.client is None:
                    raise RuntimeError(
                        "XLA backend created before jax.distributed was joined"
                    )
                return real_backends()

            xla_bridge.backends = guarded_backends
            try:
                import heat_tpu
            except BaseException:
                traceback.print_exc()
                sys.exit(1)
            assert jax.process_count() == 1 and heat_tpu.COMM_WORLD.size >= 1
            print("BRING_UP_ORDER_OK")
        """)
        import socket

        with socket.socket() as sock:
            sock.bind(("localhost", 0))
            port = sock.getsockname()[1]
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            HEAT_TPU_COORDINATOR_ADDRESS=f"localhost:{port}",
            HEAT_TPU_NUM_PROCESSES="1",
            HEAT_TPU_PROCESS_ID="0",
        )
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO_ROOT,
            capture_output=True, text=True, timeout=300, env=env,
        )
        self.assertEqual(
            proc.returncode, 0,
            f"bring-up order broken:\n{proc.stderr[-3000:]}",
        )
        self.assertIn("BRING_UP_ORDER_OK", proc.stdout)


class TestCLI(unittest.TestCase):
    def test_explain_known_and_unknown(self):
        from heat_tpu.analysis.__main__ import main

        self.assertEqual(main(["--explain", "silent-except"]), 0)
        self.assertEqual(main(["--explain", "nope"]), 1)

    def test_dump_lockgraph_json_and_dot(self):
        from heat_tpu.analysis.__main__ import main

        with tempfile.TemporaryDirectory() as td:
            jpath = os.path.join(td, "g.json")
            dpath = os.path.join(td, "g.dot")
            self.assertEqual(main(["--dump-lockgraph", jpath]), 0)
            self.assertEqual(main(["--dump-lockgraph", dpath]), 0)
            with open(jpath) as fh:
                payload = json.load(fh)
            self.assertEqual(payload["schema"], "heat-tpu-lockgraph/1")
            with open(dpath) as fh:
                self.assertIn("digraph heat_tpu_locks", fh.read())

    def test_check_exits_zero_on_clean_tree(self):
        from heat_tpu.analysis.__main__ import main

        baseline_path = os.path.join(REPO_ROOT, "analysis_baseline.json")
        self.assertEqual(main(["--check", "--baseline", baseline_path]), 0)

    def test_json_report_carries_per_rule_counts(self):
        from heat_tpu.analysis.__main__ import main

        with tempfile.TemporaryDirectory() as td:
            pkg = os.path.join(td, "heat_tpu")
            os.makedirs(os.path.join(pkg, "core"))
            with open(os.path.join(pkg, "core", "x.py"), "w") as fh:
                fh.write(textwrap.dedent("""
                    def f(comm, v):
                        try:
                            return v
                        except Exception:
                            return comm.all_gather(v)
                """))
            report_path = os.path.join(td, "report.json")
            rc = main(["--root", pkg, "--no-cache", "--json", report_path])
            self.assertEqual(rc, 1)
            with open(report_path) as fh:
                report = json.load(fh)
            counts = report["rule_counts"]
            self.assertEqual(counts.get("silent-except"), 1)
            self.assertEqual(counts.get("spmd-collective-in-except"), 1)
            self.assertFalse(report["cache_hit"])

    def test_explain_covers_new_rule_families(self):
        from heat_tpu.analysis.__main__ import main

        for rule in ("spmd-divergent-collective", "spmd-collective-in-except",
                     "layout-shard-claim-mismatch", "layout-contract"):
            self.assertEqual(main(["--explain", rule]), 0)


if __name__ == "__main__":
    unittest.main()
