"""``ht.ops`` tests (ISSUE 18 tentpole) — the single-process half.

Seven contracts, mirroring ``heat_tpu/core/ops.py`` (the real multi-process
beat/cluster_snapshot path runs in ``tests/test_multiprocess.py`` with 2- and
4-process ``jax.distributed`` jobs):

- **OpenMetrics page**: every page (including the pre-first-sample one) passes
  the strict in-repo parser — ``# TYPE`` before ``# HELP`` per family, counter
  samples suffixed ``_total``, escaped label values, terminating ``# EOF`` —
  and the cumulative counters are monotone across consecutive pages; the
  exported admitted/shed/failed totals reconcile EXACTLY against the
  executor's lifecycle ledger.
- **Burn-rate math**: hand-computed windows (known over/count/bad cells fed
  through a fake cumulative collector) produce the exact SRE burn numbers,
  the 1 m/5 m windows disagree when the bad samples age out of the fast one,
  and a 10x latency regression flips the alert within two windows with
  EXACTLY ONE typed ``slo-burn`` transition (auto-dumping one post-mortem
  with the per-shard breakdown riding in the detail).
- **Ring + delta discipline**: the ring respects ``HEAT_TPU_OPS_RING``; a
  counter or histogram stream that is not a prefix of its predecessor (a
  mid-run stats reset) re-baselines as a ``delta_reset`` sample instead of
  exporting negative rates.
- **Health**: ``/healthz`` flips unhealthy while draining, while any breaker
  is open, and while a supervision abort sentinel is installed — asserted
  both in-process and over the real localhost HTTP endpoint
  (``HEAT_TPU_OPS_PORT=0``).
- **Env knobs**: a subprocess with ``HEAT_TPU_OPS=1`` auto-arms and its
  sampler daemon writes a parseable scrape file.
- **Zero-cost**: compiled HLO is byte-identical with the plane off vs armed
  (armed-idle — the sampler reads report surfaces, it hooks nothing).
- **Beats**: ``telemetry.OPS_BEAT_PREFIX`` agrees with ``ops.BEAT_PREFIX``;
  two Monitors on one LocalCoordinator publish beats the non-blocking
  ``cluster_snapshot`` sweep folds; beat files render through ``telemetry
  top --dir`` and fold into ``merge --from-ops`` without touching the
  cumulative shard counters (the disjointness rule).
"""

import contextlib
import glob
import io
import json
import os
import time
import unittest
import urllib.error
import urllib.request

import numpy as np

import jax

import heat_tpu as ht
from heat_tpu.core import (
    _executor,
    diagnostics,
    ops,
    profiler,
    resilience,
    supervision,
    telemetry,
)
from heat_tpu.testing import TestCase, program_text


class _OpsTestCase(TestCase):
    """Reset + disarm the ops plane (and its feeders) around every test."""

    def setUp(self):
        super().setUp()
        self._reset()

    def tearDown(self):
        self._reset()
        super().tearDown()

    def _reset(self):
        ops.disarm()
        ops.reset()
        telemetry.disable()
        telemetry.reset()
        profiler.disable()
        profiler.reset()
        diagnostics.disable()
        diagnostics.reset()
        resilience.disarm_fault_plan()
        resilience.reset(clear_breakers=True)
        supervision.reset_abort()
        with telemetry._lock:
            telemetry._auto_dumps = 0
            telemetry._last_auto_ns.clear()

    def _tmp(self):
        import shutil
        import tempfile

        d = tempfile.mkdtemp(prefix="ht-ops-test-")
        self.addCleanup(lambda: shutil.rmtree(d, ignore_errors=True))
        return d

    def _env(self, key, value):
        old = os.environ.get(key)
        if value is None:
            os.environ.pop(key, None)
        else:
            os.environ[key] = value

        def restore():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old

        self.addCleanup(restore)

    def _flight_env(self, path):
        self._env("HEAT_TPU_FLIGHT_DIR", path)

    def _install_feed(self, cums):
        """Replace the cumulative collector with a deterministic script of
        snapshots — the hand-computed-windows harness."""
        it = iter(list(cums))
        old = ops._collect_cumulative
        ops._collect_cumulative = lambda: next(it)
        self.addCleanup(lambda: setattr(ops, "_collect_cumulative", old))


def _cum(mono, *, admitted=0, shed=0, failed=0, cache_hits=0, cache_misses=0,
         hists=None, lifecycle=None, queue_depth=0, draining=False,
         breakers=None, per_shard=None, service=None):
    """A hand-built cumulative snapshot with exactly known contents."""
    return {
        "mono": float(mono),
        "t": "2026-08-07T00:00:00Z",
        "admitted": admitted,
        "shed": shed,
        "failed": failed,
        "cache_hits": cache_hits,
        "cache_misses": cache_misses,
        "compile_hits": 0,
        "compile_misses": 0,
        "queue_depth": queue_depth,
        "draining": draining,
        "pressure": {"per_shard": list(per_shard or []),
                     "service_ewma_s": dict(service or {})},
        "tenant_lifecycle": lifecycle or {},
        "request_hists": hists or {},
        "breakers": breakers or {},
        "supervision": {"armed": False, "aborted": None},
    }


def _hist(values):
    h = profiler.Histogram()
    for v in values:
        h.observe(v)
    return h


def _wait_for(predicate, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.05)
    return predicate()


# ------------------------------------------------------------------ count_over
class TestCountOver(_OpsTestCase):
    def test_empty_histogram_counts_zero(self):
        self.assertEqual(profiler.Histogram().count_over(0.005), 0)

    def test_threshold_zero_counts_everything(self):
        h = _hist([0.001] * 5 + [0.1] * 2)
        self.assertEqual(h.count_over(0.0), 7)

    def test_counts_only_buckets_above_threshold(self):
        # 0.1 lives in a bucket whose lower bound (~0.095) >= 5 ms; 0.001's
        # bucket lower bound (~0.00095) is below it — bucket-exact split
        h = _hist([0.001] * 100 + [0.1] * 2)
        self.assertEqual(h.count_over(0.005), 2)

    def test_errs_under_at_a_bucket_boundary(self):
        # a threshold inside an occupied bucket excludes that bucket: the
        # count errs UNDER (conservative for alerting, per the docstring)
        h = _hist([0.01])
        self.assertEqual(h.count_over(0.01), 0)
        self.assertEqual(h.count_over(0.009), 1)


# ------------------------------------------------------------------ exporter
class TestOpenMetricsPage(_OpsTestCase):
    def test_empty_page_is_well_formed(self):
        page = ops.render_openmetrics()
        fams = ops.parse_openmetrics(page)
        self.assertIn("ht_samples", fams)
        self.assertEqual(fams["ht_samples"]["type"], "counter")
        self.assertEqual(fams["ht_samples"]["samples"][0][0],
                         "ht_samples_total")
        self.assertIn("ht_delta_resets", fams)
        self.assertTrue(page.endswith("# EOF\n"))

    def test_type_precedes_help_per_family(self):
        lines = ops.render_openmetrics().splitlines()
        seen_type = set()
        for line in lines:
            if line.startswith("# TYPE "):
                seen_type.add(line.split(" ")[2])
            elif line.startswith("# HELP "):
                self.assertIn(line.split(" ")[2], seen_type, line)

    def test_page_validates_with_live_data_and_counters_monotone(self):
        ops.set_slo("tenantA", p99_ms=5.0, success_ratio=0.99)
        hist_a = _hist([0.001] * 4)
        self._install_feed([
            _cum(0.0, hists={"tenantA": hist_a.snapshot()}),
            _cum(10.0, admitted=8, shed=1, cache_hits=3, cache_misses=1,
                 hists={"tenantA": _hist([0.001] * 4 + [0.002] * 2)
                        .snapshot()},
                 per_shard=[{"shard": 0, "queue_depth": 2,
                             "depth_ewma": 1.5, "shed_rate_ewma": 0.1}],
                 service={"add.f32[8]": 0.0003},
                 breakers={"io.write": "open"}),
            _cum(20.0, admitted=20, shed=1, cache_hits=5, cache_misses=1,
                 hists={"tenantA": _hist([0.001] * 4 + [0.002] * 2)
                        .snapshot()}),
        ])
        self.assertIsNone(ops.sample_once())  # baseline
        self.assertIsNotNone(ops.sample_once())
        page1 = ops.render_openmetrics()
        fams1 = ops.parse_openmetrics(page1)
        for name, mtype in (
                ("ht_samples", "counter"), ("ht_requests_admitted", "counter"),
                ("ht_rps", "gauge"), ("ht_shed_rate", "gauge"),
                ("ht_cache_hit_rate", "gauge"), ("ht_queue_depth", "gauge"),
                ("ht_queue_depth_ewma", "gauge"),
                ("ht_shed_rate_ewma", "gauge"),
                ("ht_service_ewma_seconds", "gauge"),
                ("ht_breaker_open", "gauge"), ("ht_draining", "gauge"),
                ("ht_slo_burn_rate", "gauge"), ("ht_slo_alert", "gauge")):
            self.assertIn(name, fams1, page1)
            self.assertEqual(fams1[name]["type"], mtype)
            self.assertIsNotNone(fams1[name]["help"])
        # labelled series carry their labels through the strict parser
        _, labels, v = fams1["ht_breaker_open"]["samples"][0]
        self.assertEqual((labels, v), ({"site": "io.write"}, 1.0))
        _, labels, _ = fams1["ht_service_ewma_seconds"]["samples"][0]
        self.assertEqual(labels, {"signature": "add.f32[8]"})
        burn_labels = {tuple(sorted(s[1].items()))
                       for s in fams1["ht_slo_burn_rate"]["samples"]}
        self.assertEqual(burn_labels, {
            (("tenant", "tenantA"), ("window", "1m")),
            (("tenant", "tenantA"), ("window", "5m")),
        })
        # counters are CUMULATIVE totals: monotone across consecutive pages
        self.assertIsNotNone(ops.sample_once())
        fams2 = ops.parse_openmetrics(ops.render_openmetrics())
        for name in ("ht_samples", "ht_requests_admitted", "ht_requests_shed",
                     "ht_requests_failed", "ht_delta_resets"):
            v1 = fams1[name]["samples"][0][2]
            v2 = fams2[name]["samples"][0][2]
            self.assertGreaterEqual(v2, v1, name)
        self.assertEqual(fams2["ht_requests_admitted"]["samples"][0][2], 20.0)

    def test_label_escaping_round_trips(self):
        nasty = 'a\\b"c\nd'
        fam = ops._Family("ht_t", "gauge", "escaping probe")
        fam.add(1.0, tenant=nasty)
        page = "\n".join(fam.render() + ["# EOF"]) + "\n"
        fams = ops.parse_openmetrics(page)
        self.assertEqual(fams["ht_t"]["samples"][0][1], {"tenant": nasty})

    def test_parser_rejects_malformed_pages(self):
        for bad in (
            "ht_x 1\n",                                  # no EOF
            "# TYPE ht_x gauge\n# HELP ht_x h\nht_x 1\n# EOF\nht_x 2\n",
            "ht_x 1\n# EOF\n",                           # sample before TYPE
            "# TYPE ht_x counter\n# HELP ht_x h\nht_x 1\n# EOF\n",  # no _total
            "# TYPE ht_x gauge\n# HELP ht_x h\n\nht_x 1\n# EOF\n",  # blank
            "# TYPE ht_x gauge\n# HELP ht_x h\nht_x one\n# EOF\n",  # value
            '# TYPE ht_x gauge\n# HELP ht_x h\nht_x{t="a\\q"} 1\n# EOF\n',
            "# TYPE ht_x bogus\n# HELP ht_x h\n# EOF\n",  # bad type
            "# TYPE ht_x gauge\n# TYPE ht_x gauge\n# EOF\n",  # dup TYPE
        ):
            with self.assertRaises(ValueError, msg=bad):
                ops.parse_openmetrics(bad)

    def test_totals_reconcile_against_the_executor_ledger(self):
        # the acceptance identity: exported admitted/shed/failed == the exact
        # lifecycle ledger the serving gate asserts on
        self.assertIsNone(ops.sample_once())  # baseline off the live executor
        x = ht.array(np.arange(8, dtype=np.float32), split=0)
        for _ in range(3):
            (x + 1.0).sum().parray
        s = ops.sample_once()
        ex = _executor.executor_stats()
        self.assertEqual(
            s["totals"]["admitted"],
            ex.get("inline_dispatches", 0) + ex.get("queued_dispatches", 0))
        self.assertEqual(s["totals"]["shed"], ex.get("shed_requests", 0))
        self.assertEqual(
            s["totals"]["failed"],
            ex.get("expired_requests", 0) + ex.get("cancelled_requests", 0))
        fams = ops.parse_openmetrics(ops.render_openmetrics())
        self.assertEqual(fams["ht_requests_admitted"]["samples"][0][2],
                         float(s["totals"]["admitted"]))


# ------------------------------------------------------------------ burn rates
class TestBurnRate(_OpsTestCase):
    def test_slo_validation(self):
        with self.assertRaises(ValueError):
            ops.set_slo("t")
        with self.assertRaises(ValueError):
            ops.set_slo("t", p99_ms=-1.0)
        with self.assertRaises(ValueError):
            ops.set_slo("t", success_ratio=0.0)
        with self.assertRaises(ValueError):
            ops.set_slo("t", success_ratio=1.5)
        ops.set_slo("t", p99_ms=5.0)
        self.assertEqual(ops.slo_status()["t"]["objectives"],
                         {"p99_ms": 5.0})
        ops.clear_slo("t")
        self.assertEqual(ops.slo_status(), {})

    def test_p99_burn_matches_hand_computed_window(self):
        # 102 requests, 2 over the 5 ms objective (bucket-exact: 0.1 s and
        # 0.001 s land entire buckets apart) -> frac 2/102, budget 1% ->
        # burn = (2/102)/0.01 on both windows
        ops.set_slo("tenantA", p99_ms=5.0)
        self._install_feed([
            _cum(0.0, hists={"tenantA": profiler.Histogram().snapshot()}),
            _cum(10.0, hists={"tenantA": _hist([0.001] * 100 + [0.1] * 2)
                              .snapshot()}),
        ])
        ops.sample_once()
        s = ops.sample_once()
        expected = round((2 / 102) / 0.01, 6)
        self.assertEqual(s["slo"]["tenantA"]["burn"],
                         {"1m": expected, "5m": expected})
        self.assertEqual(s["tenants"]["tenantA"]["count"], 102)
        self.assertEqual(s["tenants"]["tenantA"]["over"], 2)
        self.assertTrue(s["slo"]["tenantA"]["alert"])  # 1.96 > 1 both windows

    def test_success_burn_matches_hand_computed_window(self):
        # 7 completed + 3 shed -> bad frac 0.3; success_ratio 0.9 budgets
        # 0.1 -> burn exactly 3.0
        ops.set_slo("tenantB", success_ratio=0.9)
        self._install_feed([
            _cum(0.0),
            _cum(10.0, hists={"tenantB": _hist([0.001] * 7).snapshot()},
                 lifecycle={"tenantB": {"shed": 3}}),
        ])
        ops.sample_once()
        s = ops.sample_once()
        self.assertEqual(s["slo"]["tenantB"]["burn"], {"1m": 3.0, "5m": 3.0})
        self.assertEqual(s["tenants"]["tenantB"]["bad"], 3)
        status = ops.slo_status()["tenantB"]
        self.assertTrue(status["alert"])
        self.assertIsNotNone(status["since"])

    def test_worse_objective_wins_when_both_declared(self):
        # healthy latency but failing success objective: the alert must not
        # hide behind the healthier objective
        ops.set_slo("tenantC", p99_ms=1000.0, success_ratio=0.9)
        self._install_feed([
            _cum(0.0),
            _cum(10.0, hists={"tenantC": _hist([0.001] * 7).snapshot()},
                 lifecycle={"tenantC": {"shed": 3}}),
        ])
        ops.sample_once()
        s = ops.sample_once()
        self.assertEqual(s["slo"]["tenantC"]["burn"]["1m"], 3.0)

    def test_fast_window_forgets_what_the_slow_window_remembers(self):
        # bad sample at t=10, good ones at t=250/260: the 1 m window holds
        # only the good samples (burn 0), the 5 m window still burns -> no
        # alert (BOTH windows must burn)
        ops.set_slo("tenantD", p99_ms=5.0)
        h = profiler.Histogram()
        feeds = [_cum(0.0, hists={"tenantD": h.snapshot()})]
        for _ in range(10):
            h.observe(0.1)
        feeds.append(_cum(10.0, hists={"tenantD": h.snapshot()}))
        for _ in range(10):
            h.observe(0.001)
        feeds.append(_cum(250.0, hists={"tenantD": h.snapshot()}))
        for _ in range(10):
            h.observe(0.001)
        feeds.append(_cum(260.0, hists={"tenantD": h.snapshot()}))
        self._install_feed(feeds)
        ops.sample_once()
        for _ in range(2):
            ops.sample_once()
        s = ops.sample_once()
        burns = s["slo"]["tenantD"]["burn"]
        self.assertEqual(burns["1m"], 0.0)
        self.assertGreater(burns["5m"], 1.0)
        self.assertFalse(s["slo"]["tenantD"]["alert"])

    def test_10x_regression_flips_alert_within_two_windows_one_typed_event(self):
        out = os.path.join(self._tmp(), "flight")
        self._flight_env(out)
        ops.set_slo("tenantE", p99_ms=5.0)
        h = profiler.Histogram()
        feeds = [_cum(0.0, hists={"tenantE": h.snapshot()})]
        mono = 0.0
        for _ in range(3):  # healthy baseline: 1 ms, well under 5 ms
            mono += 10.0
            for _ in range(10):
                h.observe(0.001)
            feeds.append(_cum(mono, hists={"tenantE": h.snapshot()}))
        for _ in range(2):  # the 10x regression: 10 ms > 5 ms
            mono += 10.0
            for _ in range(10):
                h.observe(0.010)
            feeds.append(_cum(mono, hists={"tenantE": h.snapshot()}))
        self._install_feed(feeds)
        ops.sample_once()
        for _ in range(3):
            s = ops.sample_once()
            self.assertFalse(s["slo"]["tenantE"]["alert"], s)
        flipped_at = None
        for i in range(2):
            s = ops.sample_once()
            if s["slo"]["tenantE"]["alert"]:
                flipped_at = i
                break
        self.assertIsNotNone(flipped_at, "alert did not flip within 2 windows")
        # exactly ONE typed slo-burn transition on the flight ring...
        burns = [e for e in telemetry.flight_events()
                 if e["kind"] == "slo-burn" and e["site"] == "ops.slo.tenantE"]
        self.assertEqual(len(burns), 1, burns)
        detail = json.loads(burns[0]["detail"])
        self.assertIn("per_shard", detail)
        self.assertIn("burn", detail)
        # ...which auto-dumped exactly one post-mortem
        self.assertTrue(
            _wait_for(lambda: glob.glob(os.path.join(out, "*.json"))),
            "no flight dump after the slo-burn transition")
        time.sleep(0.3)
        dumps = glob.glob(os.path.join(out, "*.json"))
        self.assertEqual(len(dumps), 1, dumps)
        self.assertIn("slo-burn", dumps[0])

    def test_recovery_emits_cleared_not_a_second_dump(self):
        out = os.path.join(self._tmp(), "flight")
        self._flight_env(out)
        ops.set_slo("tenantF", p99_ms=5.0)
        h = profiler.Histogram()
        feeds = [_cum(0.0, hists={"tenantF": h.snapshot()})]
        for _ in range(10):
            h.observe(0.1)
        feeds.append(_cum(10.0, hists={"tenantF": h.snapshot()}))
        # 590 s later: the bad window has aged out of BOTH windows
        for _ in range(10):
            h.observe(0.001)
        feeds.append(_cum(600.0, hists={"tenantF": h.snapshot()}))
        self._install_feed(feeds)
        ops.sample_once()
        s = ops.sample_once()
        self.assertTrue(s["slo"]["tenantF"]["alert"])
        s = ops.sample_once()
        self.assertFalse(s["slo"]["tenantF"]["alert"])
        kinds = [e["kind"] for e in telemetry.flight_events()
                 if e["site"] == "ops.slo.tenantF"]
        self.assertEqual(kinds, ["slo-burn", "slo-burn-cleared"])
        self.assertTrue(_wait_for(
            lambda: glob.glob(os.path.join(out, "*.json"))))
        time.sleep(0.3)
        self.assertEqual(len(glob.glob(os.path.join(out, "*.json"))), 1)


# ------------------------------------------------------------------ ring/delta
class TestRingAndDelta(_OpsTestCase):
    def test_ring_respects_env_capacity(self):
        self._env("HEAT_TPU_OPS_RING", "8")
        self.addCleanup(ops.reload)  # re-read after the env restore
        ops.reload()
        self._install_feed([_cum(float(i)) for i in range(25)])
        ops.sample_once()
        for _ in range(24):
            ops.sample_once()
        self.assertEqual(len(ops.samples()), 8)
        self.assertEqual(ops.ops_stats()["ring_cap"], 8)
        self.assertEqual(ops.ops_stats()["samples"], 24)

    def test_counter_reset_rebaselines_as_delta_reset(self):
        self._install_feed([
            _cum(0.0, admitted=100),
            _cum(10.0, admitted=150),
            _cum(20.0, admitted=3),  # mid-run stats reset: not a prefix
            _cum(30.0, admitted=7),  # …and the stream continues cleanly
        ])
        ops.sample_once()
        s1 = ops.sample_once()
        self.assertFalse(s1["delta_reset"])
        self.assertEqual(s1["deltas"]["admitted"], 50)
        s2 = ops.sample_once()
        self.assertTrue(s2["delta_reset"])
        self.assertEqual(s2["deltas"]["admitted"], 0)
        self.assertEqual(s2["rates"]["rps"], 0.0)  # never a negative rate
        s3 = ops.sample_once()
        self.assertFalse(s3["delta_reset"])
        self.assertEqual(s3["deltas"]["admitted"], 4)
        fams = ops.parse_openmetrics(ops.render_openmetrics())
        self.assertEqual(fams["ht_delta_resets"]["samples"][0][2], 1.0)

    def test_histogram_reset_rebaselines_as_delta_reset(self):
        big = _hist([0.001] * 10)
        small = _hist([0.001] * 2)  # fewer counts: not a prefix of `big`
        self._install_feed([
            _cum(0.0, hists={"t": big.snapshot()}),
            _cum(10.0, hists={"t": small.snapshot()}),
        ])
        ops.sample_once()
        s = ops.sample_once()
        self.assertTrue(s["delta_reset"])
        self.assertEqual(s["tenants"], {})
        self.assertEqual(ops.ops_stats()["delta_resets"], 1)

    def test_lifecycle_going_backwards_rebaselines(self):
        self._install_feed([
            _cum(0.0, lifecycle={"t": {"shed": 5}}),
            _cum(10.0, lifecycle={"t": {"shed": 2}}),
        ])
        ops.sample_once()
        self.assertTrue(ops.sample_once()["delta_reset"])


# ------------------------------------------------------------------ health
class _FakeDrainingScheduler:
    def draining(self):
        return True


class TestHealthz(_OpsTestCase):
    def test_healthy_by_default(self):
        ok, payload = ops.healthz()
        self.assertTrue(ok)
        self.assertEqual(payload["open_breakers"], [])
        self.assertIsNone(payload["abort"])

    def test_open_breaker_flips_unhealthy_then_reset_recovers(self):
        br = resilience.breaker("ops.test.breaker",
                                 failure_threshold=1, cooldown_s=60.0)
        br.record_failure("boom")
        ok, payload = ops.healthz()
        self.assertFalse(ok)
        self.assertIn("ops.test.breaker", payload["open_breakers"])
        resilience.reset(clear_breakers=True)
        ok, _ = ops.healthz()
        self.assertTrue(ok)

    def test_abort_sentinel_flips_unhealthy(self):
        supervision.post_abort("peer-failed", site="test.ops", rank=1)
        ok, payload = ops.healthz()
        self.assertFalse(ok)
        self.assertEqual(payload["abort"]["kind"], "peer-failed")
        supervision.reset_abort()
        self.assertTrue(ops.healthz()[0])

    def test_draining_flips_unhealthy(self):
        old = _executor._dispatch_scheduler
        _executor._dispatch_scheduler = _FakeDrainingScheduler()
        try:
            ok, payload = ops.healthz()
        finally:
            _executor._dispatch_scheduler = old
        self.assertFalse(ok)
        self.assertTrue(payload["draining"])


class TestHttpEndpoint(_OpsTestCase):
    def _serve(self):
        self.addCleanup(ops.reload)  # re-read knobs after the env restore
        self._env("HEAT_TPU_OPS_PORT", "0")
        ops.reload()
        ops.arm(start_thread=False)
        self.addCleanup(ops.disarm)
        addr = ops.http_address()
        self.assertIsNotNone(addr, "no HTTP endpoint with the port knob set")
        return addr

    def test_metrics_and_healthz_transitions_over_http(self):
        host, port = self._serve()
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10) as resp:
            self.assertEqual(resp.status, 200)
            self.assertIn("openmetrics-text",
                          resp.headers["Content-Type"])
            body = resp.read().decode()
        self.assertIn("ht_samples", ops.parse_openmetrics(body))

        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=10) as resp:
            self.assertEqual(resp.status, 200)
            self.assertTrue(json.loads(resp.read())["ok"])

        # breaker opens -> 503; breaker reset -> 200 again
        br = resilience.breaker("ops.test.http",
                                 failure_threshold=1, cooldown_s=60.0)
        br.record_failure("boom")
        with self.assertRaises(urllib.error.HTTPError) as ctx:
            urllib.request.urlopen(f"http://{host}:{port}/healthz", timeout=10)
        self.assertEqual(ctx.exception.code, 503)
        payload = json.loads(ctx.exception.read())
        self.assertIn("ops.test.http", payload["open_breakers"])
        resilience.reset(clear_breakers=True)
        with urllib.request.urlopen(
                f"http://{host}:{port}/healthz", timeout=10) as resp:
            self.assertEqual(resp.status, 200)

        with self.assertRaises(urllib.error.HTTPError) as ctx:
            urllib.request.urlopen(f"http://{host}:{port}/nope", timeout=10)
        self.assertEqual(ctx.exception.code, 404)


# ------------------------------------------------------------------ env knob
class TestEnvKnob(_OpsTestCase):
    def test_heat_tpu_ops_env_arms_and_writes_a_scrape_file(self):
        import subprocess
        import sys

        out = self._tmp()
        scrape = os.path.join(out, "metrics.prom")
        code = (
            "import os, sys, time\n"
            "from heat_tpu.core import ops\n"
            "print('ARMED', ops.armed())\n"
            "deadline = time.monotonic() + 20\n"
            "while time.monotonic() < deadline and not os.path.exists("
            f"{scrape!r}):\n"
            "    time.sleep(0.05)\n"
            f"print('SCRAPE', os.path.exists({scrape!r}))\n"
        )
        env = dict(os.environ)
        env.update(HEAT_TPU_OPS="1", HEAT_TPU_OPS_INTERVAL_S="0.05",
                   HEAT_TPU_OPS_SCRAPE=scrape, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        self.assertIn("ARMED True", proc.stdout)
        self.assertIn("SCRAPE True", proc.stdout)
        with open(scrape) as f:
            self.assertIn("ht_samples", ops.parse_openmetrics(f.read()))

    def test_heat_tpu_ops_slo_declares_objectives_from_env(self):
        # the CI shape: objectives on an unmodified workload, env only.
        # LIFO cleanups: disarm -> env restore -> reload (knobs end clean)
        self.addCleanup(ops.reload)
        self._env("HEAT_TPU_OPS_SLO",
                  "tenantA:p99_ms=50,success_ratio=0.999;"
                  "tenantB:p99_ms=10;"
                  "broken:p99_ms=oops;"       # skipped: non-numeric value
                  "noobjectives;"             # skipped: no colon
                  "negatives:p99_ms=-1")      # parses, set_slo rejects typed
        ops.reload()
        ops.arm(start_thread=False)
        self.addCleanup(ops.disarm)
        status = ops.slo_status()
        self.assertEqual(
            status["tenantA"]["objectives"],
            {"p99_ms": 50.0, "success_ratio": 0.999})
        self.assertEqual(status["tenantB"]["objectives"], {"p99_ms": 10.0})
        self.assertNotIn("broken", status)
        self.assertNotIn("noobjectives", status)
        self.assertNotIn("negatives", status)  # degraded, never raised
        # a declared-but-idle tenant still exports its burn series (0.0) —
        # the serving CI gate scrapes for the family mid-harness
        self.assertIsNotNone(ops.sample_once())
        fams = ops.parse_openmetrics(ops.render_openmetrics())
        burn_tenants = {labels["tenant"]
                        for _, labels, _ in fams["ht_slo_burn_rate"]["samples"]}
        self.assertEqual(burn_tenants, {"tenantA", "tenantB"})


# ------------------------------------------------------------------ zero-cost
class TestZeroCost(_OpsTestCase):
    def test_hlo_byte_parity_armed_idle_vs_off(self):
        # same proof shape as diagnostics/profiler/telemetry: the plane hooks
        # nothing, so compiled HLO is byte-identical off vs armed-idle
        def chain_hlos():
            _executor.clear_executor_cache()
            x = ht.array(np.arange(8, dtype=np.float32), split=0)
            y = ht.array(np.full(8, 0.5, dtype=np.float32), split=0)
            for _ in range(2):  # past the conftest warm-up threshold (2)
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

        baseline = chain_hlos()
        self.assertGreaterEqual(len(baseline), 1, list(baseline))
        ops.set_slo("parity", p99_ms=1.0)
        ops.arm(start_thread=False)
        try:
            ops.sample_once()
            armed = chain_hlos()
            ops.sample_once()
        finally:
            ops.disarm()
        self.assertEqual(armed, baseline,
                         "an armed ops plane changed compiled HLO")


# ------------------------------------------------------------------ beats
class TestBeatsAndTop(_OpsTestCase):
    def test_beat_prefix_agrees_with_telemetry(self):
        # telemetry duplicates the prefix for standalone file-path loads;
        # this is the one place the two constants are pinned together
        self.assertEqual(telemetry.OPS_BEAT_PREFIX, ops.BEAT_PREFIX)

    def test_monitor_tee_publishes_only_while_armed(self):
        co = supervision.LocalCoordinator()
        mon = supervision.Monitor(co, 0, 2, generation=990,
                                  peer_timeout_s=1000.0, clock=lambda: 0.0)
        mon.step(0.0)
        self.assertEqual(co.get_dir(f"{mon.ns}/ops/"), [])
        ops.arm(start_thread=False)
        self.addCleanup(ops.disarm)
        mon.step(0.0)
        found = co.get_dir(f"{mon.ns}/ops/")
        self.assertEqual(len(found), 1)
        beat = json.loads(found[0][1])
        self.assertEqual(beat["schema"], ops.BEAT_SCHEMA)
        self.assertEqual(beat["rank"], 0)

    def test_cluster_snapshot_folds_two_monitors_nonblocking(self):
        co = supervision.LocalCoordinator()
        mons = [supervision.Monitor(co, r, 2, generation=991,
                                    peer_timeout_s=1000.0, clock=lambda: 0.0)
                for r in range(2)]
        ops.arm(start_thread=False)
        self.addCleanup(ops.disarm)
        ops.sample_once()
        # rank 1 is "mid-drain": it has NOT beaten yet — the sweep must
        # return immediately with rank 0 only, never wait for it
        mons[0].step(0.0)
        t0 = time.monotonic()
        snap = ops.cluster_snapshot(co, mons[0].ns)
        self.assertLess(time.monotonic() - t0, 5.0)
        self.assertEqual(list(snap["ranks"]), ["0"])
        mons[1].step(0.0)
        snap = ops.cluster_snapshot(co, mons[0].ns)
        self.assertEqual(list(snap["ranks"]), ["0", "1"])
        for rank, beat in snap["ranks"].items():
            self.assertEqual(beat["schema"], ops.BEAT_SCHEMA)
            self.assertEqual(str(beat["rank"]), rank)

    def test_cluster_snapshot_single_process_fallback(self):
        snap = ops.cluster_snapshot()
        self.assertEqual(snap["schema"], ops.SCHEMA)
        self.assertEqual(len(snap["ranks"]), 1)
        (beat,) = snap["ranks"].values()
        self.assertEqual(beat["schema"], ops.BEAT_SCHEMA)

    def test_unparseable_beat_surfaces_as_error_row(self):
        co = supervision.LocalCoordinator()
        co.set("ns/ops/0", "{not json", True)
        snap = ops.cluster_snapshot(co, "ns")
        self.assertEqual(snap["ranks"]["0"]["error"], "unparseable beat")

    def test_beat_files_render_through_telemetry_top(self):
        d = self._tmp()
        self._install_feed([_cum(0.0), _cum(10.0, admitted=42,
                                             queue_depth=3)])
        ops.sample_once()
        ops.sample_once()
        ops.write_beat_file(d, rank=0)
        ops.write_beat_file(d, rank=1)
        beats = telemetry.load_ops_beats(d)
        self.assertEqual(sorted(beats), ["0", "1"])
        self.assertEqual(beats["0"]["schema"], ops.BEAT_SCHEMA)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = telemetry.main(["top", "--dir", d])
        out = buf.getvalue()
        self.assertEqual(rc, 0, out)
        self.assertIn("RANK", out)
        self.assertIn("RPS", out)
        self.assertEqual(len([ln for ln in out.splitlines()
                              if ln.strip().startswith(("0 ", "1 "))]), 2)

    def test_top_without_beats_fails_typed(self):
        d = self._tmp()
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = telemetry.main(["top", "--dir", d])
        self.assertEqual(rc, 1)
        self.assertIn(telemetry.OPS_BEAT_PREFIX, buf.getvalue())

    def test_merge_from_ops_folds_disjoint_section(self):
        d = self._tmp()
        shards = os.path.join(d, "shards")
        beats = os.path.join(d, "beats")
        report_path = os.path.join(d, "report.json")
        telemetry.dump_shard(shards)
        self._install_feed([_cum(0.0), _cum(10.0, admitted=50, shed=10,
                                             queue_depth=2)])
        ops.sample_once()
        ops.sample_once()
        ops.write_beat_file(beats, rank=0)
        ops.write_beat_file(beats, rank=1)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = telemetry.main(["merge", "--dir", shards, "--from-ops",
                                 beats, "--out", report_path])
        self.assertEqual(rc, 0, buf.getvalue())
        with open(report_path) as f:
            report = json.load(f)
        sec = report["ops"]
        self.assertEqual(sec["schema"], "heat-tpu-ops-merged/1")
        self.assertEqual(sorted(sec["ranks"]), ["0", "1"])
        # the disjointness rule: windowed ops rates live ONLY in the `ops`
        # section; the cumulative counter/executor sections are untouched
        self.assertEqual(sec["totals"]["rps"], 2 * (50 / 10.0))
        self.assertEqual(sec["totals"]["queue_depth"], 4)
        self.assertNotIn("rps", report.get("counters", {}))
        # and the same merge WITHOUT --from-ops has no ops section at all
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = telemetry.main(["merge", "--dir", shards, "--out",
                                 report_path])
        self.assertEqual(rc, 0)
        with open(report_path) as f:
            self.assertNotIn("ops", json.load(f))


# ------------------------------------------------------------------ reporting
class TestOpsStats(_OpsTestCase):
    def test_ops_section_rides_the_diagnostics_report(self):
        stats = ops.ops_stats()
        self.assertEqual(stats["schema"], ops.SCHEMA)
        self.assertFalse(stats["armed"])
        rep = diagnostics.report()
        self.assertEqual(rep["ops"]["schema"], ops.SCHEMA)

    def test_arm_is_idempotent_and_disarm_keeps_the_ring(self):
        ops.arm(start_thread=False)
        ops.arm(start_thread=False)
        self.assertTrue(ops.armed())
        self._install_feed([_cum(10.0, admitted=5)])
        s = ops.sample_once()  # arm() installed the baseline already
        self.assertIsNotNone(s)
        ops.disarm()
        self.assertFalse(ops.armed())
        self.assertEqual(len(ops.samples()), 1)  # post-mortem reads survive


if __name__ == "__main__":
    unittest.main()
