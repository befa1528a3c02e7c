"""``ht.telemetry`` tests (ISSUE 11 tentpole) — the single-process half.

Five contracts, mirroring ``heat_tpu/core/telemetry.py`` (the real
multi-process shard/merge/skew/straggler path runs in
``tests/test_multiprocess.py`` with 2- and 4-process ``jax.distributed``
jobs):

- **Collective windows**: ``MeshCommunication._guarded`` times every
  collective/layout invocation into per-(site, seq) windows and per-site
  duration histograms when collection is on, records nothing when off, and
  never changes compiled HLO either way.
- **Shard/merge math** on synthetic shards with known contents: exact counter
  sums, span folds, associativity-independent histogram quantiles, summed
  executor stats, preserved per-process breakdowns.
- **Skew & straggler attribution**: hand-built windows with known anchors
  produce the expected cross-rank skew values, ``skew.<op>`` histograms, and
  a scoreboard naming the hand-planted straggler; clock anchors shift
  per-process timestamps onto one timeline.
- **Merged trace namespacing**: every process's events land in its own
  disjoint pid range (request tracks AND counter tracks — two ranks'
  cumulative counters must never sum onto one track), timestamps are aligned
  and non-negative, and flow arrows link the same collective across process
  tracks.
- **Flight recorder**: the always-on ring records resilience/fallback/
  lifecycle events; the typed failure kinds auto-dump a post-mortem artifact
  (rate-limited, thread-offloaded); dumps and shard/report writes all go
  through ``resilience.atomic_write`` so a crash mid-dump cannot leave a
  torn artifact.
"""

import glob
import json
import os
import time
import unittest

import numpy as np

import jax

import heat_tpu as ht
from heat_tpu.core import _executor, diagnostics, profiler, resilience, telemetry
from heat_tpu.testing import TestCase, program_text


class _TelTestCase(TestCase):
    """Reset + disable the telemetry plane (and its feeders) around every
    test; give each test a fresh auto-dump budget."""

    def setUp(self):
        super().setUp()
        self._reset()

    def tearDown(self):
        self._reset()
        super().tearDown()

    def _reset(self):
        telemetry.disable()
        telemetry.reset()
        profiler.disable()
        profiler.reset()
        diagnostics.disable()
        diagnostics.reset()
        resilience.disarm_fault_plan()
        resilience.reset()
        with telemetry._lock:
            telemetry._auto_dumps = 0
            telemetry._last_auto_ns.clear()

    def _tmp(self):
        import shutil
        import tempfile

        d = tempfile.mkdtemp(prefix="ht-telemetry-test-")
        self.addCleanup(lambda: shutil.rmtree(d, ignore_errors=True))
        return d

    def _flight_env(self, path):
        old = os.environ.get("HEAT_TPU_FLIGHT_DIR")
        os.environ["HEAT_TPU_FLIGHT_DIR"] = path

        def restore():
            if old is None:
                os.environ.pop("HEAT_TPU_FLIGHT_DIR", None)
            else:
                os.environ["HEAT_TPU_FLIGHT_DIR"] = old

        self.addCleanup(restore)


def _wait_for(predicate, timeout_s=10.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.05)
    return predicate()


# --------------------------------------------------------------------------- windows
class TestCollectiveWindows(_TelTestCase):
    def test_window_seq_and_duration_histogram(self):
        with telemetry.collective_window("comm.test"):
            time.sleep(0.002)
        with telemetry.collective_window("comm.test"):
            pass
        with telemetry.collective_window("comm.other"):
            pass
        wins = telemetry.windows()
        self.assertEqual([(w[0], w[1]) for w in wins],
                         [("comm.test", 1), ("comm.test", 2), ("comm.other", 1)])
        for _, _, t0, t1, tag in wins:
            self.assertGreaterEqual(t1, t0)
            self.assertIsNone(tag)  # no ambient request scope in this test
        durs = telemetry.duration_snapshots()
        self.assertEqual(durs["comm.test"]["count"], 2)
        self.assertGreaterEqual(durs["comm.test"]["max_s"], 0.002)

    def test_seq_is_per_request_tag(self):
        # two tenants interleaving must not share a sequence: the identity
        # the merge matches on is (site, tag, seq), so ranks that interleave
        # tenants in a different order still pair the RIGHT collectives
        profiler.enable()
        with profiler.request("tenantA"):
            with telemetry.collective_window("comm.psum"):
                pass
        with profiler.request("tenantB"):
            with telemetry.collective_window("comm.psum"):
                pass
        with profiler.request("tenantA"):
            with telemetry.collective_window("comm.psum"):
                pass
        keyed = [(w[4], w[1]) for w in telemetry.windows()]
        self.assertEqual(keyed, [("tenantA", 1), ("tenantB", 1), ("tenantA", 2)])

    def test_skew_matches_by_tag_across_interleaved_ranks(self):
        # rank 0 runs A then B; rank 1 runs B then A. A bare per-site counter
        # would pair A(rank0) with B(rank1); the tag-keyed identity pairs
        # like with like and measures ~zero skew
        def win(tag, enter_us):
            return ["comm.psum", 1, enter_us * 1000, (enter_us + 5) * 1000, tag]

        shards = [
            _synthetic_shard(0, 2, anchor_ns=0,
                             windows=[win("A", 100), win("B", 9000)]),
            _synthetic_shard(1, 2, anchor_ns=0,
                             windows=[win("B", 9010), win("A", 108)]),
        ]
        skew = telemetry.merge(shards)["skew"]
        self.assertEqual(skew["collectives_measured"], 2)
        self.assertLessEqual(skew["sites"]["comm.psum"]["max_skew_us"], 20)

    def test_guarded_chokepoint_records_only_when_collecting(self):
        x = ht.array(np.arange(12, dtype=np.float32), split=0)
        self.assertEqual(telemetry.windows(), [])  # collection off: nothing
        telemetry.enable()
        y = ht.array(np.arange(12, dtype=np.float32) * 2, split=0)
        del x, y
        sites = {w[0] for w in telemetry.windows()}
        self.assertIn("comm.shard", sites)

    def test_hlo_byte_parity_with_collection_on(self):
        # same proof shape as diagnostics/profiler/resilience: nothing enters
        # traced bodies, so compiled HLO is byte-identical on/off
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
        telemetry.enable()
        try:
            collected = chain_hlos()
        finally:
            telemetry.disable()
        self.assertEqual(collected, baseline,
                         "telemetry collection changed compiled HLO")


# --------------------------------------------------------------------------- shards
def _synthetic_shard(index, count, *, anchor_ns, counters=None, hists=None,
                     windows=(), trace=None, executor=None):
    """A hand-built shard with exactly known contents."""
    prof = {"histograms": hists or {}, "requests_total": 0}
    diag = {
        "counters": dict(counters or {}),
        "spans": {},
        "collectives": [],
        "profiler": prof,
    }
    if executor is not None:
        diag["executor"] = executor
    return {
        "schema": telemetry.SCHEMA,
        "generated_at": "2026-08-04T00:00:00Z",
        "process": {"index": index, "count": count, "pid": 1000 + index,
                    "host": "testhost"},
        "clock": {
            "anchor_monotonic_ns": anchor_ns,
            "anchors_monotonic_ns": None,
            "aligned": True,
            "profiler_origin_monotonic_us": anchor_ns / 1e3,  # profiler t0 ==
            "dumped_at_monotonic_ns": anchor_ns + 10**9,      # the anchor
        },
        "collectives": {"windows": [list(w) for w in windows], "durations": {}},
        "flight": {"events": [], "dumps": []},
        "diagnostics": diag,
        "trace": trace or {"requests": [], "slices": [], "counter_events": []},
    }


def _hist_snap(values):
    h = profiler.Histogram()
    for v in values:
        h.observe(v)
    return h.snapshot()


class TestShardMerge(_TelTestCase):
    def test_dump_shard_roundtrip(self):
        diagnostics.enable()
        diagnostics.counter("t.mark", 7)
        profiler.enable()
        profiler.observe("t.lat", 0.01)
        out = self._tmp()
        path = telemetry.dump_shard(out)
        self.assertTrue(os.path.exists(path))
        with open(path) as f:
            shard = json.load(f)
        self.assertEqual(shard["schema"], telemetry.SCHEMA)
        self.assertEqual(shard["diagnostics"]["counters"]["t.mark"], 7)
        merged = telemetry.merge(out)
        self.assertEqual(merged["schema"], telemetry.MERGED_SCHEMA)
        self.assertEqual(merged["processes"], 1)
        self.assertEqual(merged["counters"]["t.mark"], 7)
        self.assertEqual(merged["histograms"]["t.lat"]["count"], 1)

    def test_exact_counter_sums_and_per_process_breakdown(self):
        shards = [
            _synthetic_shard(0, 3, anchor_ns=0, counters={"a": 1, "b": 10}),
            _synthetic_shard(1, 3, anchor_ns=0, counters={"a": 2}),
            _synthetic_shard(2, 3, anchor_ns=0, counters={"a": 4, "c": 0.5}),
        ]
        merged = telemetry.merge(shards)
        self.assertEqual(merged["counters"], {"a": 7, "b": 10, "c": 0.5})
        self.assertEqual(merged["processes"], 3)
        self.assertEqual(merged["per_process"]["1"]["counters"], {"a": 2})

    def test_histogram_merge_is_order_independent(self):
        rng = np.random.RandomState(5)
        streams = [rng.lognormal(-6, 1.0, 200) for _ in range(3)]
        shards = [
            _synthetic_shard(i, 3, anchor_ns=0,
                             hists={"lat": _hist_snap(streams[i])})
            for i in range(3)
        ]
        fwd = telemetry.merge(shards)["histograms"]["lat"]
        rev = telemetry.merge(list(reversed(shards)))["histograms"]["lat"]
        self.assertEqual(fwd["buckets"], rev["buckets"])
        self.assertEqual(fwd["count"], 600)
        for q in ("p50_s", "p95_s", "p99_s"):
            self.assertEqual(fwd[q], rev[q])
        # equivalent to having observed the union stream
        union = _hist_snap(np.concatenate(streams))
        self.assertEqual(fwd["buckets"], union["buckets"])

    def test_executor_stats_sum_and_peak_fold(self):
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0,
                             executor={"hits": 10, "misses": 2, "draining": False,
                                       "queue_depth_peak": 10,
                                       "batch_width_hist": {"2": 3}}),
            _synthetic_shard(1, 2, anchor_ns=0,
                             executor={"hits": 5, "misses": 1, "draining": False,
                                       "queue_depth_peak": 7,
                                       "batch_width_hist": {"2": 1, "4": 2}}),
        ]
        merged = telemetry.merge(shards)
        self.assertEqual(merged["executor"]["hits"], 15)
        self.assertEqual(merged["executor"]["misses"], 3)
        self.assertEqual(merged["executor"]["batch_width_hist"],
                         {"2": 4, "4": 2})
        # peaks max-fold: no rank ever saw a depth-17 queue
        self.assertEqual(merged["executor"]["queue_depth_peak"], 10)
        self.assertIs(merged["executor"]["draining"], False)

    def test_inconsistent_process_count_rejected(self):
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0),
            _synthetic_shard(1, 3, anchor_ns=0),
        ]
        with self.assertRaises(ValueError):
            telemetry.merge(shards)

    def test_merge_empty_rejected(self):
        with self.assertRaises(ValueError):
            telemetry.merge([])

    def test_duplicate_shard_list_rejected(self):
        # same contract as load_shards: rank 0 twice would double-count sums
        shard = _synthetic_shard(0, 2, anchor_ns=0, counters={"a": 1})
        with self.assertRaises(ValueError):
            telemetry.merge([shard, dict(shard)])

    def test_cli_check_gates_job_completeness(self):
        out = self._tmp()
        diagnostics.enable()
        diagnostics.counter("t.mark", 1)
        telemetry.dump_shard(out)
        # rewrite the shard to claim a 2-process job: one shard of two
        path = os.path.join(out, os.listdir(out)[0])
        with open(path) as f:
            shard = json.load(f)
        shard["process"]["count"] = 2
        with open(path, "w") as f:
            json.dump(shard, f)
        self.assertEqual(telemetry.main(["merge", "--dir", out]), 0)
        self.assertEqual(telemetry.main(["merge", "--dir", out, "--check"]), 1)


# --------------------------------------------------------------------------- skew
class TestSkewAttribution(_TelTestCase):
    def _skewed_shards(self):
        # 3 ranks; anchors deliberately far apart (different "boot offsets")
        # so only ALIGNED math can see the true skew. Rank 2 enters seq 2 of
        # comm.psum 50 ms late — the planted straggler.
        us = 1000  # ns per µs
        # window tuples: (site, seq, enter_ns, exit_ns) in each rank's OWN clock
        shards = []
        anchors = [10**12, 5 * 10**12, 9 * 10**12]
        enters_us = {  # aligned enter times per (seq, rank)
            1: [100, 110, 105],
            2: [200, 210, 50_200],   # rank 2: +50 ms
            3: [60_300, 60_290, 60_310],
        }
        for rank in range(3):
            wins = []
            for seq in (1, 2, 3):
                t0 = anchors[rank] + enters_us[seq][rank] * us
                wins.append(("comm.psum", seq, t0, t0 + 500 * us))
            shards.append(_synthetic_shard(rank, 3, anchor_ns=anchors[rank],
                                           windows=wins))
        return shards

    def test_skew_values_scoreboard_and_straggler(self):
        merged = telemetry.merge(self._skewed_shards())
        skew = merged["skew"]
        self.assertEqual(skew["collectives_measured"], 3)
        site = skew["sites"]["comm.psum"]
        self.assertEqual(site["collectives"], 3)
        self.assertAlmostEqual(site["max_skew_us"], 50_000, delta=1)
        self.assertEqual(site["max_skew_seq"], 2)
        self.assertEqual(site["slowest_rank"], 2)
        board = skew["scoreboard"]
        self.assertEqual(board["2"]["straggler_count"], 2)  # seq 2 and 3
        self.assertEqual(board["2"]["worst_site"], "comm.psum")
        self.assertEqual(board["2"]["worst_seq"], 2)
        self.assertEqual(skew["slowest_rank"], 2)
        # the skew.<op> histogram rides the merged histogram table
        self.assertIn("skew.psum", merged["histograms"])
        self.assertEqual(merged["histograms"]["skew.psum"]["count"], 3)
        self.assertGreaterEqual(merged["histograms"]["skew.psum"]["max_s"], 0.049)

    def test_single_rank_windows_have_no_skew(self):
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0,
                             windows=[("comm.psum", 1, 1000, 2000)]),
            _synthetic_shard(1, 2, anchor_ns=0),
        ]
        skew = telemetry.merge(shards)["skew"]
        self.assertEqual(skew["collectives_measured"], 0)
        self.assertIsNone(skew["slowest_rank"])

    def test_unaligned_clocks_invalidate_skew_and_flows(self):
        # no handshake: per-process anchors are arbitrary boot offsets, so
        # cross-rank deltas are meaningless — no phantom straggler, no arrows
        shards = self._skewed_shards()
        for shard in shards:
            shard["clock"]["aligned"] = False
        merged = telemetry.merge(shards)
        skew = merged["skew"]
        self.assertFalse(skew["valid"])
        self.assertEqual(skew["collectives_measured"], 0)
        self.assertIsNone(skew["slowest_rank"])
        self.assertNotIn("skew.psum", merged["histograms"])
        trace = telemetry.merged_trace(shards)
        flows = [ev for ev in trace["traceEvents"]
                 if ev.get("cat") == "collective-skew"]
        self.assertEqual(flows, [])
        # aligned shards report valid attribution (the inverse contract)
        self.assertTrue(
            telemetry.merge(self._skewed_shards())["skew"]["valid"]
        )


# --------------------------------------------------------------------------- trace
class TestMergedTrace(_TelTestCase):
    def _traced_shards(self):
        trace0 = {
            "requests": [{"id": 1, "tag": "w", "t0_us": 10.0, "t1_us": 500.0}],
            "slices": [[1, 7, "request", "w", 10.0, 500.0],
                       [1, 7, "dispatch", "add", 20.0, 100.0]],
            "counter_events": [["queue_depth", 15.0, 3.0]],
        }
        trace1 = {
            "requests": [{"id": 1, "tag": "w", "t0_us": 12.0, "t1_us": 480.0}],
            "slices": [[1, 9, "request", "w", 12.0, 480.0]],
            "counter_events": [["queue_depth", 18.0, 5.0]],
        }
        s0 = _synthetic_shard(0, 2, anchor_ns=10**12, trace=trace0,
                              windows=[("comm.psum", 1, 10**12 + 50_000_000,
                                        10**12 + 51_000_000)])
        s1 = _synthetic_shard(1, 2, anchor_ns=2 * 10**12, trace=trace1,
                              windows=[("comm.psum", 1, 2 * 10**12 + 70_000_000,
                                        2 * 10**12 + 71_000_000)])
        return [s0, s1]

    def test_pid_namespacing_and_counter_tracks(self):
        obj = telemetry.merged_trace(self._traced_shards())
        self.assertEqual(obj["schema"], telemetry.TRACE_SCHEMA)
        events = obj["traceEvents"]
        stride = telemetry.PID_STRIDE
        ranges = {0: range(stride, 2 * stride), 1: range(2 * stride, 3 * stride)}
        for ev in events:
            self.assertIn(ev["pid"] // stride, (1, 2),
                          f"pid {ev['pid']} outside any process range")
        # the two ranks' queue_depth counters sit on DIFFERENT tracks (pids):
        counter_pids = {ev["pid"] for ev in events
                        if ev.get("ph") == "C" and ev["name"] == "queue_depth"}
        self.assertEqual(len(counter_pids), 2)
        self.assertTrue(any(p in ranges[0] for p in counter_pids))
        self.assertTrue(any(p in ranges[1] for p in counter_pids))
        # request tracks are namespaced with the process label
        names = {ev["args"]["name"] for ev in events
                 if ev.get("ph") == "M" and ev["name"] == "process_name"}
        self.assertIn("p0/request 1: w", names)
        self.assertIn("p1/request 1: w", names)
        self.assertIn("p0/collectives", names)

    def test_timestamps_aligned_monotone_nonnegative(self):
        obj = telemetry.merged_trace(self._traced_shards())
        events = [ev for ev in obj["traceEvents"] if "ts" in ev]
        self.assertTrue(events)
        for ev in events:
            self.assertGreaterEqual(ev["ts"], 0.0, ev)
        # per-(pid, tid) streams stay monotone for B/E pairs (nesting order)
        last = {}
        for ev in obj["traceEvents"]:
            if ev.get("ph") in ("B", "E"):
                key = (ev["pid"], ev["tid"])
                self.assertGreaterEqual(ev["ts"], last.get(key, -1.0), ev)
                last[key] = ev["ts"]
        # alignment: the two ranks' collective windows land 20 ms apart on the
        # SHARED clock even though their raw anchors differ by a full second
        xs = [ev for ev in obj["traceEvents"] if ev.get("cat") == "collective"]
        self.assertEqual(len(xs), 2)
        delta = abs(xs[0]["ts"] - xs[1]["ts"])
        self.assertAlmostEqual(delta, 20_000, delta=5)

    def test_huge_request_ids_stay_inside_pid_range(self):
        # a long-lived process's rid counter can exceed PID_STRIDE: the
        # merger renumbers densely so tracks never bleed into another
        # process's pid range (the original rid stays visible in the tag)
        big = telemetry.PID_STRIDE + 12345
        trace = {
            "requests": [{"id": big, "tag": "w", "t0_us": 1.0, "t1_us": 9.0}],
            "slices": [[big, 7, "request", "w", 1.0, 9.0]],
            "counter_events": [],
        }
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0, trace=trace),
            _synthetic_shard(1, 2, anchor_ns=0),
        ]
        obj = telemetry.merged_trace(shards)
        stride = telemetry.PID_STRIDE
        for ev in obj["traceEvents"]:
            self.assertIn(ev["pid"] // stride, (1, 2), ev)
        names = {ev["args"]["name"] for ev in obj["traceEvents"]
                 if ev.get("ph") == "M" and ev["name"] == "process_name"}
        self.assertIn(f"p0/request 1: w (rid {big})", names)

    def test_flow_arrows_link_collectives_across_ranks(self):
        obj = telemetry.merged_trace(self._traced_shards())
        flows = [ev for ev in obj["traceEvents"]
                 if ev.get("cat") == "collective-skew"]
        self.assertEqual({ev["ph"] for ev in flows}, {"s", "f"})
        self.assertEqual(len({ev["pid"] for ev in flows}), 2)
        self.assertEqual({ev["name"] for ev in flows}, {"comm.psum"})


# --------------------------------------------------------------------------- flight
class TestFlightRecorder(_TelTestCase):
    def test_ring_records_and_is_bounded(self):
        for i in range(telemetry._flight.maxlen + 10):
            telemetry.flight_record("manual", f"site{i}", "d", kind="k")
        events = telemetry.flight_events()
        self.assertEqual(len(events), telemetry._flight.maxlen)
        self.assertEqual(events[-1]["site"],
                         f"site{telemetry._flight.maxlen + 9}")

    def test_fault_firing_auto_dumps_postmortem(self):
        out = os.path.join(self._tmp(), "flight")
        self._flight_env(out)
        resilience.arm_fault_plan(
            [{"site": "test.flight", "kind": "raise", "on_call": 1}]
        )
        with self.assertRaises(resilience.FaultInjected):
            resilience.maybe_fault("test.flight")
        self.assertTrue(
            _wait_for(lambda: glob.glob(os.path.join(out, "*.json"))),
            "no flight dump after an injected fault",
        )
        path = glob.glob(os.path.join(out, "*.json"))[0]
        with open(path) as f:
            dump = json.load(f)
        self.assertEqual(dump["schema"], telemetry.FLIGHT_SCHEMA)
        self.assertEqual(dump["reason"], "fault")
        self.assertTrue(any(
            e["kind"] == "fault" and e["site"] == "test.flight"
            for e in dump["events"]
        ))

    def test_breaker_open_auto_dumps(self):
        out = os.path.join(self._tmp(), "flight")
        self._flight_env(out)
        br = resilience.CircuitBreaker("test.breaker", failure_threshold=2,
                                       cooldown_s=60.0)
        br.record_failure("boom")
        br.record_failure("boom")
        self.assertEqual(br.state, resilience.OPEN)
        self.assertTrue(
            _wait_for(lambda: any("breaker-open" in p for p in
                                  glob.glob(os.path.join(out, "*.json")))),
            "no flight dump after a breaker opened",
        )

    def test_drain_timeout_kind_auto_dumps(self):
        out = os.path.join(self._tmp(), "flight")
        self._flight_env(out)
        diagnostics.record_resilience_event(
            "scheduler.drain", "drain-timeout", "synthetic"
        )
        self.assertTrue(
            _wait_for(lambda: glob.glob(os.path.join(out, "*.json"))),
            "no flight dump after a drain timeout event",
        )

    def test_auto_dump_disabled_by_env(self):
        out = os.path.join(self._tmp(), "flight")
        self._flight_env(out)
        os.environ["HEAT_TPU_FLIGHT"] = "0"
        self.addCleanup(lambda: os.environ.pop("HEAT_TPU_FLIGHT", None))
        diagnostics.record_resilience_event("x", "fault", "synthetic")
        time.sleep(0.3)
        self.assertEqual(glob.glob(os.path.join(out, "*.json")), [])
        # the ring still recorded; the on-demand dump still works
        self.assertTrue(any(e["kind"] == "fault"
                            for e in telemetry.flight_events()))
        self.assertIsNotNone(telemetry.flight_dump("on-demand"))

    def test_rate_limit_one_dump_per_trigger(self):
        out = os.path.join(self._tmp(), "flight")
        self._flight_env(out)
        for _ in range(5):
            diagnostics.record_resilience_event("x", "quarantine", "synthetic")
        self.assertTrue(_wait_for(
            lambda: glob.glob(os.path.join(out, "*.json"))))
        time.sleep(0.3)
        self.assertEqual(len(glob.glob(os.path.join(out, "*.json"))), 1)


# --------------------------------------------------------------------------- atomic dumps
class TestAtomicArtifacts(_TelTestCase):
    def test_diagnostics_dump_never_leaves_torn_artifact(self):
        path = os.path.join(self._tmp(), "diag.json")
        resilience.arm_fault_plan([
            {"site": "diagnostics.dump", "kind": "raise", "on_call": 1,
             "count": 10},
        ])
        with self.assertRaises(resilience.FaultInjected):
            diagnostics.dump(path)
        self.assertFalse(os.path.exists(path),
                         "a failed dump must not commit a partial file")
        resilience.disarm_fault_plan()
        diagnostics.dump(path)
        with open(path) as f:
            self.assertEqual(json.load(f)["schema"], diagnostics.SCHEMA)

    def test_profiler_trace_dump_is_atomic(self):
        path = os.path.join(self._tmp(), "trace.json")
        resilience.arm_fault_plan([
            {"site": "profiler.trace", "kind": "raise", "on_call": 1,
             "count": 10},
        ])
        with self.assertRaises(resilience.FaultInjected):
            profiler.dump_trace(path)
        self.assertFalse(os.path.exists(path))
        resilience.disarm_fault_plan()
        obj = profiler.dump_trace(path)
        self.assertEqual(obj["schema"], profiler.TRACE_SCHEMA)
        with open(path) as f:
            json.load(f)

    def test_shard_dump_is_atomic(self):
        out = self._tmp()
        resilience.arm_fault_plan([
            {"site": "telemetry.shard", "kind": "raise", "on_call": 1,
             "count": 10},
        ])
        with self.assertRaises(resilience.FaultInjected):
            telemetry.dump_shard(out)
        self.assertEqual(
            [n for n in os.listdir(out) if n.startswith(telemetry.SHARD_PREFIX)],
            [],
        )
        resilience.disarm_fault_plan()
        path = telemetry.dump_shard(out)
        with open(path) as f:
            self.assertEqual(json.load(f)["schema"], telemetry.SCHEMA)


# --------------------------------------------------------------------------- env knob
class TestEnvKnob(_TelTestCase):
    def test_heat_tpu_telemetry_env_enables_collection(self):
        import subprocess
        import sys

        code = (
            "from heat_tpu.core import telemetry; "
            "print('COLLECTING', telemetry.collecting())"
        )
        env = dict(os.environ)
        env["HEAT_TPU_TELEMETRY"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        self.assertEqual(proc.returncode, 0, proc.stderr[-2000:])
        self.assertIn("COLLECTING True", proc.stdout)


if __name__ == "__main__":
    unittest.main()


# ----------------------------------------------------------------- sequence gate
class TestSequenceConsistency(_TelTestCase):
    """The runtime twin of the static ``spmd-divergent-collective`` rule:
    ``merge`` compares every rank's per-tag ordered site list against the
    lowest rank and ``--check`` fails naming the first diverging rank/site."""

    def _win(self, site, seq, t, tag=None):
        return (site, seq, t, t + 1000, tag)

    def test_consistent_sequences_pass(self):
        wins = [self._win("comm.shard", i + 1, i * 10_000) for i in range(3)]
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0, windows=wins),
            _synthetic_shard(1, 2, anchor_ns=0, windows=wins),
        ]
        seq = telemetry.merge(shards)["sequence"]
        self.assertTrue(seq["valid"])
        self.assertTrue(seq["consistent"])
        self.assertEqual(seq["windows_checked"], 6)
        self.assertEqual(seq["divergences"], [])

    def test_extra_collective_names_rank_and_site(self):
        base = [self._win("comm.shard", i + 1, i * 10_000) for i in range(3)]
        extra = base + [self._win("comm.shard", 4, 40_000)]
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0, windows=base),
            _synthetic_shard(1, 2, anchor_ns=0, windows=extra),
        ]
        seq = telemetry.merge(shards)["sequence"]
        self.assertFalse(seq["consistent"])
        d = seq["divergences"][0]
        self.assertEqual(d["rank"], 1)
        self.assertEqual(d["reference_rank"], 0)
        self.assertEqual(d["index"], 3)
        self.assertIsNone(d["expected"])
        self.assertEqual(d["actual"], "comm.shard")
        self.assertEqual((d["expected_len"], d["actual_len"]), (3, 4))

    def test_mid_sequence_site_mismatch(self):
        a = [self._win("comm.shard", 1, 0), self._win("comm.psum", 1, 10_000)]
        b = [self._win("comm.shard", 1, 0), self._win("comm.all_gather", 1, 10_000)]
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0, windows=a),
            _synthetic_shard(1, 2, anchor_ns=0, windows=b),
        ]
        d = telemetry.merge(shards)["sequence"]["divergences"][0]
        self.assertEqual(d["index"], 1)
        self.assertEqual(d["expected"], "comm.psum")
        self.assertEqual(d["actual"], "comm.all_gather")

    def test_tag_keyed_identity_tolerates_tenant_interleaving(self):
        # tenant A then B on rank 0; B then A on rank 1 — per-tag sequences
        # are identical, so concurrent tenants interleaving differently per
        # process must NOT read as divergence (the async executor's default)
        r0 = [self._win("comm.psum", 1, 0, "A"), self._win("comm.shard", 1, 10_000, "B")]
        r1 = [self._win("comm.shard", 1, 0, "B"), self._win("comm.psum", 1, 10_000, "A")]
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0, windows=r0),
            _synthetic_shard(1, 2, anchor_ns=0, windows=r1),
        ]
        seq = telemetry.merge(shards)["sequence"]
        self.assertTrue(seq["consistent"], seq["divergences"])
        self.assertEqual(seq["tags_checked"], 2)

    def test_sequence_checked_even_with_unaligned_clocks(self):
        # the skew math refuses unaligned clocks; the sequence gate needs
        # only per-rank LOCAL ordering, so it still detects the divergence
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0,
                             windows=[self._win("comm.shard", 1, 0)]),
            _synthetic_shard(1, 2, anchor_ns=999,
                             windows=[self._win("comm.psum", 1, 0)]),
        ]
        for s in shards:
            s["clock"]["aligned"] = False
        merged = telemetry.merge(shards)
        self.assertFalse(merged["skew"]["valid"])
        self.assertFalse(merged["sequence"]["consistent"])

    def test_overflowed_window_ring_invalidates_and_check_fails_loudly(self):
        import contextlib
        import io

        wins = [self._win("comm.shard", i + 1, i * 1000) for i in range(3)]
        shards = [
            _synthetic_shard(0, 2, anchor_ns=0, windows=wins),
            _synthetic_shard(1, 2, anchor_ns=0, windows=wins[:2]),
        ]
        for s in shards:
            s["collectives"]["windows_cap"] = 3
        seq = telemetry.merge(shards)["sequence"]
        self.assertFalse(seq["valid"])
        self.assertIn("HEAT_TPU_TELEMETRY_WINDOWS", seq["reason"])
        self.assertTrue(seq["consistent"])  # no confident phantom divergence
        # a gate that cannot check must not pass as one that checked: the
        # CLI --check FAILS, and the summary never affirms consistency
        d = self._tmp()
        for s in shards:
            p = os.path.join(
                d, f"{telemetry.SHARD_PREFIX}p{s['process']['index']:04d}.json"
            )
            with open(p, "w") as f:
                json.dump(s, f)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = telemetry.main(["merge", "--dir", d, "--check"])
        out = buf.getvalue()
        self.assertEqual(rc, 1, out)
        self.assertIn("could not run", out)
        self.assertIn('"sequence_consistent": null', out)
        # report-only mode still merges
        self.assertEqual(telemetry.main(["merge", "--dir", d]), 0)

    def test_windows_capacity_env_knob_applies_at_reset(self):
        old = os.environ.get("HEAT_TPU_TELEMETRY_WINDOWS")
        os.environ["HEAT_TPU_TELEMETRY_WINDOWS"] = "300"

        def restore():
            if old is None:
                os.environ.pop("HEAT_TPU_TELEMETRY_WINDOWS", None)
            else:
                os.environ["HEAT_TPU_TELEMETRY_WINDOWS"] = old
            telemetry.reset()

        self.addCleanup(restore)
        telemetry.reset()
        self.assertEqual(telemetry._windows.maxlen, 300)
        payload = telemetry.shard_payload()
        self.assertEqual(payload["collectives"]["windows_cap"], 300)

    def test_single_shard_trivially_consistent(self):
        shards = [_synthetic_shard(0, 1, anchor_ns=0,
                                   windows=[self._win("comm.shard", 1, 0)])]
        seq = telemetry.merge(shards)["sequence"]
        self.assertTrue(seq["valid"])
        self.assertTrue(seq["consistent"])

    def test_cli_check_fails_on_divergence_and_passes_clean(self):
        import contextlib
        import io

        base = [self._win("comm.shard", 1, 0)]
        extra = base + [self._win("comm.ppermute", 1, 5_000)]

        def write_dir(shards):
            d = self._tmp()
            for s in shards:
                path = os.path.join(
                    d, f"{telemetry.SHARD_PREFIX}p{s['process']['index']:04d}.json"
                )
                with open(path, "w") as f:
                    json.dump(s, f)
            return d

        clean = write_dir([
            _synthetic_shard(0, 2, anchor_ns=0, windows=base),
            _synthetic_shard(1, 2, anchor_ns=0, windows=base),
        ])
        self.assertEqual(
            telemetry.main(["merge", "--dir", clean, "--expect", "2",
                            "--check"]), 0)

        bad = write_dir([
            _synthetic_shard(0, 2, anchor_ns=0, windows=base),
            _synthetic_shard(1, 2, anchor_ns=0, windows=extra),
        ])
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = telemetry.main(["merge", "--dir", bad, "--expect", "2",
                                 "--check"])
        out = buf.getvalue()
        self.assertEqual(rc, 1, out)
        self.assertIn("rank 1", out)
        self.assertIn("comm.ppermute", out)
        # report-only mode still merges (the gate is --check's)
        self.assertEqual(telemetry.main(["merge", "--dir", bad]), 0)
