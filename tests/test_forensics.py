"""Request-forensics suite (ISSUE 19 tentpole).

Covers the per-request lifecycle records (stage decomposition, critical-path
reduction, admission verdicts, failure-path legs), the slowest-K exemplar
reservoirs, the per-tenant cost meters and their exact reconciliation rule,
the zero-cost-when-disabled contract (HLO byte-parity off vs armed-idle), and
the consumer surfaces (diagnostics provider, ops exporter families,
``telemetry slow`` / ``merge --from-ops`` folds).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import (
    _executor, diagnostics, forensics, ops, profiler, resilience, telemetry,
)
from heat_tpu.testing import TestCase, program_text

_OLD_THRESHOLD = None


def setUpModule():
    # forensics bills compile-vs-execute per program call: assert against the
    # production compile-on-first-miss behaviour (the suite conftest raises
    # the warm-up threshold for signature-diverse tests)
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


class _ForensicsCase(TestCase):
    """Isolation: every test starts disarmed with empty stores and restores
    the switches (and env knobs) it flips."""

    _KNOBS = ("HEAT_TPU_FORENSICS", "HEAT_TPU_FORENSICS_RING",
              "HEAT_TPU_FORENSICS_EXEMPLARS")

    def setUp(self):
        self._env = {k: os.environ.get(k) for k in self._KNOBS}
        for k in self._KNOBS:
            os.environ.pop(k, None)
        self._was_enabled = diagnostics._enabled
        resilience.disarm_fault_plan()
        resilience.reset(clear_breakers=True)
        diagnostics.reset()
        forensics.disarm()
        forensics.reset()
        forensics.reload()

    def tearDown(self):
        forensics.disarm()
        forensics.reset()
        resilience.disarm_fault_plan()
        resilience.reset(clear_breakers=True)
        diagnostics._enabled = self._was_enabled
        for k, v in self._env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        forensics.reload()

    @staticmethod
    def _chain(np_a):
        x = ht.array(np_a, split=0)
        return ((x + 1.0) * 2.0 - 0.5).numpy()


# ------------------------------------------------------------------ contract
class TestDisabledContract(_ForensicsCase):
    def test_disarmed_records_nothing(self):
        self.assertFalse(forensics.armed())
        with profiler.request("quiet"):
            self._chain(np.arange(8, dtype=np.float32))
        self.assertEqual(forensics.records(), [])
        self.assertEqual(forensics.tenant_cost(), {})
        # producers are no-ops, not errors, while off
        forensics.note_program("x", 1.0, "execute", rid=123)
        forensics.note_event("typed-failure", "x", rid=123)
        self.assertEqual(forensics.records(), [])

    def test_hlo_byte_parity_off_vs_armed_idle(self):
        """Arming the plane (without any request traffic) must not change a
        single compiled byte — forensics lives strictly outside traced
        bodies."""
        def chain_hlos():
            _executor.clear_executor_cache()
            np_x = np.arange(8, dtype=np.float32)
            np_y = np.full(8, 0.25, dtype=np.float32)
            x = ht.array(np_x, split=0)
            y = ht.array(np_y, split=0)
            (x * y + 1.0).sum().parray
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
        forensics.arm()
        armed = chain_hlos()
        self.assertEqual(armed, baseline, "arming forensics changed compiled HLO")
        forensics.disarm()
        again = chain_hlos()
        self.assertEqual(again, baseline, "disarming did not restore HLO")


# ------------------------------------------------------------------ records
class TestLifecycleRecord(_ForensicsCase):
    def test_stage_decomposition_sums_to_measured_latency(self):
        forensics.arm()
        _executor.clear_executor_cache()
        with profiler.request("tenantA"):
            self._chain(np.linspace(0.0, 1.0, 9, dtype=np.float32))
        recs = forensics.records(tag="tenantA")
        self.assertEqual(len(recs), 1)
        rec = recs[0]
        total = rec["total_s"]
        stage_sum = sum(rec["stages"].values())
        # acceptance contract: decomposition within 5% of the measured wall
        # latency (the `host` residual makes it exact up to rounding)
        self.assertLessEqual(abs(stage_sum - total), max(1e-6, 0.05 * total),
                             rec["stages"])
        self.assertTrue(rec["critical_path"], rec)
        self.assertEqual(rec["dominant"], rec["critical_path"][0]["stage"])
        timed = [leg for leg in rec["critical_path"] if "seconds" in leg]
        self.assertAlmostEqual(sum(leg["share"] for leg in timed), 1.0,
                               places=3)
        # first-touch traffic: the compile split must be visible
        self.assertIn("compile", rec["stages"])

    def test_execute_split_and_device_meter_on_replay(self):
        forensics.arm()
        _executor.clear_executor_cache()
        np_a = np.arange(16, dtype=np.float32)
        with profiler.request("tenantB"):
            self._chain(np_a)  # first call: compile
        with profiler.request("tenantB"):
            self._chain(np_a)  # same signature: compiled replay
        recs = forensics.records(tag="tenantB")
        self.assertEqual(len(recs), 2)
        replay = recs[-1]
        self.assertIn("execute", replay["stages"], replay["stages"])
        self.assertGreater(replay["device_s"], 0.0)
        cost = forensics.tenant_cost()["tenantB"]
        self.assertEqual(cost["requests"], 2)
        self.assertGreater(cost["device_seconds"], 0.0)
        # executor_stats surfaces the same meters
        self.assertEqual(ht.executor_stats()["tenant_cost"]["tenantB"], cost)

    def test_admission_verdict_and_headroom_on_expired_deadline(self):
        forensics.arm()
        _executor.clear_executor_cache()
        np_a = np.arange(8, dtype=np.float32)
        with pytest.raises(resilience.DeadlineExceeded):
            with profiler.request("tenantD", deadline_s=0.0):
                self._chain(np_a)
        rec = forensics.records(tag="tenantD")[-1]
        # an already-expired request dies at its earliest checkpoint (defer
        # here; force/staged when the deadline expires later in the life)
        verdicts = {a["verdict"] for a in rec["admission"]}
        self.assertIn("deadline-expired", verdicts, rec["admission"])
        self.assertIn(rec["admission"][0]["checkpoint"],
                      ("defer", "force", "staged"))
        expired = [a for a in rec["admission"]
                   if a["verdict"] == "deadline-expired"]
        self.assertTrue(all(a["headroom_s"] <= 0.0 for a in expired), expired)
        self.assertIsNotNone(rec["deadline_headroom_s"])

    def test_result_cache_outcome_reasons(self):
        forensics.arm()
        _executor.clear_executor_cache()
        with profiler.request("tenantC"):
            self._chain(np.arange(8, dtype=np.float32))
        rec = forensics.records(tag="tenantC")[-1]
        rc = rec["result_cache"]
        # the plane always records an outcome per consult: hit, miss, or a
        # reasoned bypass (result cache disabled by default -> bypasses/misses)
        self.assertTrue(
            rc["hits"] or rc["misses"] or rc["bypass"],
            rc,
        )


# ------------------------------------------------------------------ failure legs
class TestFailureLegs(_ForensicsCase):
    def test_fault_plan_record_carries_eager_replay_leg(self):
        forensics.arm()
        _executor.clear_executor_cache()
        np_a = np.linspace(0.0, 1.0, 11, dtype=np.float32)
        expected = (np_a + 1.0) * 2.0 - 0.5
        resilience.arm_fault_plan(
            [{"site": "executor.compile", "on_call": 1, "count": 99,
              "kind": "raise"}]
        )
        with profiler.request("chaos"):
            got = self._chain(np_a)
        np.testing.assert_array_equal(got, expected)
        rec = forensics.records(tag="chaos")[-1]
        kinds = {e["kind"] for e in rec["events"]}
        self.assertIn("eager-replay", kinds, rec["events"])
        legs = [leg["stage"] for leg in rec["critical_path"]]
        self.assertIn("eager-replay", legs, rec["critical_path"])

    def test_transient_fault_record_carries_retry_leg(self):
        forensics.arm()
        _executor.clear_executor_cache()
        np_a = np.linspace(-1.0, 1.0, 9, dtype=np.float32)
        resilience.arm_fault_plan(
            [{"site": "executor.execute", "on_call": 1, "count": 1,
              "kind": "raise"}]
        )
        with profiler.request("flaky"):
            got = self._chain(np_a)
        np.testing.assert_array_equal(got, (np_a + 1.0) * 2.0 - 0.5)
        rec = forensics.records(tag="flaky")[-1]
        kinds = {e["kind"] for e in rec["events"]}
        # the diagnostics resilience-event tee lands the retry on the record
        self.assertIn("retry", kinds, rec["events"])
        self.assertIn("retry", [leg["stage"] for leg in rec["critical_path"]])

    def test_typed_failure_leg_in_critical_path(self):
        forensics.arm()
        forensics.begin_request(90001, "t9")
        forensics.note_event("typed-failure", "deadline_expired: op",
                             rid=90001)
        forensics.finish_request(90001, 0.010)
        rec = forensics.records(tag="t9")[-1]
        legs = [leg["stage"] for leg in rec["critical_path"]]
        self.assertIn("typed-failure", legs, rec["critical_path"])
        # event legs never displace the non-empty timed/dominant head
        self.assertTrue(rec["critical_path"][0].get("stage"), rec)


# ------------------------------------------------------------------ reservoirs
class TestExemplarReservoir(_ForensicsCase):
    def test_reservoir_bound_and_deterministic_slowest_k_order(self):
        os.environ["HEAT_TPU_FORENSICS_EXEMPLARS"] = "3"
        forensics.arm()  # re-reads the knob
        for i in range(10):
            rid = 1000 + i
            forensics.begin_request(rid, "zipf")
            forensics.finish_request(rid, 0.010 * (i + 1))
        ex = forensics.exemplars("zipf")["zipf"]
        self.assertEqual([round(r["total_s"], 3) for r in ex],
                         [0.100, 0.090, 0.080])
        # ties break by rid ascending — deterministic, not insertion order
        forensics.reset()
        for rid in (7, 3, 5):
            forensics.begin_request(rid, "tie")
            forensics.finish_request(rid, 0.050)
        ex = forensics.exemplars("tie")["tie"]
        self.assertEqual([r["rid"] for r in ex], [3, 5, 7])

    def test_exemplar_refs_compact_shape(self):
        forensics.arm()
        for i in range(4):
            forensics.begin_request(2000 + i, "refs")
            forensics.finish_request(2000 + i, 0.010 * (i + 1))
        refs = forensics.exemplar_refs("refs", k=2)
        self.assertEqual(len(refs), 2)
        for ref in refs:
            self.assertEqual(sorted(ref), ["dominant", "rid", "tenant",
                                           "total_ms"])
        self.assertEqual(refs[0]["total_ms"], 40.0)

    def test_ring_bound_counts_drops(self):
        os.environ["HEAT_TPU_FORENSICS_RING"] = "16"
        forensics.arm()
        for i in range(20):
            forensics.begin_request(3000 + i, "ring")
            forensics.finish_request(3000 + i, 0.001)
        self.assertEqual(len(forensics.records(limit=1000)), 16)
        stats = forensics.forensics_stats()
        self.assertEqual(stats["finished"], 20)
        self.assertEqual(stats["dropped"], 4)


# ------------------------------------------------------------------ meters
class TestCostMeters(_ForensicsCase):
    def test_totals_reconcile_exactly_with_tenant_fold(self):
        forensics.arm()
        _executor.clear_executor_cache()
        np_a = np.arange(12, dtype=np.float32)
        for tenant in ("alpha", "beta", "alpha"):
            with profiler.request(tenant):
                self._chain(np_a)
        cost = forensics.tenant_cost()
        totals = forensics.totals()
        # the reconciliation rule is EXACT equality, not approximate: totals
        # are defined as the fold over the per-tenant meters
        agg_requests = sum(m["requests"] for m in cost.values())
        agg_device = sum(m["device_seconds"] for m in cost.values())
        agg_flops = sum(m["flops"] for m in cost.values())
        self.assertEqual(totals["requests"], agg_requests)
        self.assertEqual(totals["device_seconds"], agg_device)
        self.assertEqual(totals["flops"], agg_flops)
        self.assertEqual(agg_requests, 3)
        self.assertEqual(cost["alpha"]["requests"], 2)
        self.assertEqual(cost["beta"]["requests"], 1)

    def test_batch_execute_splits_device_time_by_width(self):
        forensics.arm()
        forensics.begin_request(41, "w1")
        forensics.begin_request(42, "w2")
        forensics.note_batch_execute([41, 42], "batched", 0.080,
                                     flops_each=100.0)
        forensics.finish_request(41, 0.1)
        forensics.finish_request(42, 0.1)
        cost = forensics.tenant_cost()
        self.assertAlmostEqual(cost["w1"]["device_seconds"], 0.040, places=9)
        self.assertAlmostEqual(cost["w2"]["device_seconds"], 0.040, places=9)
        self.assertEqual(cost["w1"]["flops"], 100.0)

    def test_unattributed_work_meters_under_dash(self):
        forensics.arm()
        forensics.note_program("orphan", 0.020, "execute")
        cost = forensics.tenant_cost()
        self.assertIn("-", cost)
        self.assertAlmostEqual(cost["-"]["device_seconds"], 0.020, places=9)


# ------------------------------------------------------------------ surfaces
class TestConsumerSurfaces(_ForensicsCase):
    def test_diagnostics_report_carries_forensics_provider(self):
        forensics.arm()
        forensics.begin_request(51, "prov")
        forensics.finish_request(51, 0.005)
        section = diagnostics.report()["forensics"]
        self.assertEqual(section["schema"], forensics.SCHEMA)
        self.assertTrue(section["armed"])
        self.assertEqual(section["finished"], 1)
        self.assertIn("prov", section["exemplars"])

    def test_explain_names_dominants_and_slowest(self):
        forensics.arm()
        forensics.begin_request(61, "why")
        forensics.note_program("p", 0.030, "compile", rid=61)
        forensics.finish_request(61, 0.040)
        out = ht.explain("why")
        self.assertEqual(out["records"], 1)
        self.assertEqual(out["dominant_stages"], {"compile": 1})
        self.assertEqual(len(out["slowest"]), 1)
        self.assertEqual(out["slowest"][0]["dominant"], "compile")

    def test_ops_exporter_emits_tenant_cost_families(self):
        forensics.arm()
        forensics.begin_request(71, "exported")
        forensics.note_program("p", 0.010, "execute", flops=500.0, rid=71)
        forensics.finish_request(71, 0.012)
        ops.reset()
        self.assertIsNone(ops.sample_once())  # baseline
        sample = ops.sample_once()
        self.assertIsNotNone(sample)
        self.assertIn("exported", sample["tenant_cost"])
        fams = ops.parse_openmetrics(ops.render_openmetrics())
        for fam in ("ht_tenant_device_seconds", "ht_tenant_flops",
                    "ht_tenant_collective_bytes", "ht_tenant_stage_share"):
            self.assertIn(fam, fams, sorted(fams))
        rows = {labels["tenant"]: value for _, labels, value in
                fams["ht_tenant_flops"]["samples"]}
        self.assertEqual(rows["exported"], 500.0)
        # the compact beat carries the cost cells telemetry folds
        beat = ops._compact_beat(0)
        cell = beat["tenants"]["exported"]
        self.assertGreater(cell["device_s"], 0.0)
        self.assertEqual(cell["flops"], 500.0)
        ops.reset()

    def test_telemetry_fold_ops_sums_cost_across_ranks(self):
        beats = {
            "0": {"rank": 0, "rps": 1.0, "shed_rate": 0.0, "queue_depth": 0,
                  "tenants": {"t": {"device_s": 0.25, "flops": 10.0,
                                    "collective_bytes": 4.0}}},
            "1": {"rank": 1, "rps": 1.0, "shed_rate": 0.0, "queue_depth": 0,
                  "tenants": {"t": {"device_s": 0.5, "flops": 30.0,
                                    "collective_bytes": 4.0}}},
        }
        section = telemetry._fold_ops_section(beats)
        self.assertEqual(section["tenant_cost"]["t"],
                         {"device_s": 0.75, "flops": 40.0,
                          "collective_bytes": 8.0})

    def test_telemetry_slow_renders_critical_paths(self):
        shard = {
            "process": {"index": 0},
            "diagnostics": {"forensics": {"exemplars": {"slowpoke": [{
                "rid": 9, "tenant": "slowpoke", "total_s": 0.5,
                "dominant": "compile",
                "critical_path": [
                    {"stage": "compile", "seconds": 0.4, "share": 0.8},
                    {"stage": "host", "seconds": 0.1, "share": 0.2},
                ],
            }]}}},
        }
        rc, text = telemetry._render_slow([shard], None, 10)
        self.assertEqual(rc, 0, text)
        self.assertIn("#9", text)
        self.assertIn("dominant=compile", text)
        self.assertIn("compile 80%", text)
        rc, text = telemetry._render_slow([shard], "nobody", 10)
        self.assertEqual(rc, 1)
        self.assertIn("HEAT_TPU_FORENSICS", text)

    def test_slo_burn_detail_names_exemplars(self):
        """The slo-burn post-mortem detail references the offending tenant's
        slowest-K forensic exemplars (attached outside ops._lock)."""
        forensics.arm()
        forensics.begin_request(81, "burny")
        forensics.finish_request(81, 0.2)
        refs = forensics.exemplar_refs("burny", 3)
        self.assertEqual(len(refs), 1)
        self.assertEqual(refs[0]["rid"], 81)
        self.assertEqual(refs[0]["total_ms"], 200.0)
