"""Cross-request result cache tests (ISSUE 17 tentpole).

Covers the memoization tier's correctness contract in-process:

- **default-off parity** — with ``HEAT_TPU_RESULT_CACHE`` unset the tier is
  disabled, holds no shards, and records nothing under traffic;
- **store/hit round trip** — a repeated fused force over generation-registered
  leaves stores once and then hits, bit-identical values;
- **post-clear recompute** — ``ht.clear_executor_cache()`` drops every entry
  and the first post-clear read of any key is a guaranteed recompute
  (satellite: the documented clear contract);
- **donation-epoch invalidation is exact** — donating one registered buffer
  invalidates exactly the entries that alias it, neighbours keep hitting;
- **generation-bump invalidation** — re-registering a tag at a higher
  generation makes entries keyed on the old generation fail validation
  closed (the ``StagedBatch``/``restage`` contract);
- **swap hammer vs cache-off bit-parity** — the same request sequence
  interleaved with ``swap_state`` swaps produces IDENTICAL values with the
  cache on and off, and a threaded hammer never observes a torn or stale
  value;
- **poisoned entry** — a corrupted entry is a typed ``cache-corrupt``
  rejection on the always-on resilience stream and a correct recompute,
  never a served value;
- **uncacheable bypass** — RNG-labelled programs and unregistered operands
  never consult or fill.
"""

import itertools
import os
import shutil
import tempfile
import threading

import numpy as np

import heat_tpu as ht
from heat_tpu.core import _executor, _result_cache, diagnostics
from heat_tpu.testing import TestCase

_OLD = {}

N = 1024

# The generation table is MONOTONIC by contract (``max(prev, gen)``) and
# survives ``clear()`` — identity metadata, not cache contents — so each test
# case registers under its own tag family, exactly like production callers
# draw ids from one process-wide counter (``workloads._GEN_COUNTER``).
_TAG_SEQ = itertools.count()


def setUpModule():
    # compile-on-first-miss so the first dispatch already has a program spec
    # (the program half of the cache key); conftest's threshold-2 would make
    # every first call eager and shift the store to the second call
    for knob, val in (("HEAT_TPU_JIT_THRESHOLD", "1"),):
        _OLD[knob] = os.environ.get(knob)
        os.environ[knob] = val
    _executor.reload_env_knobs()


def tearDownModule():
    for knob, old in _OLD.items():
        if old is None:
            os.environ.pop(knob, None)
        else:
            os.environ[knob] = old
    _executor.reload_env_knobs()


def _cache_corrupt_events():
    with diagnostics._lock:
        return [
            e for e in diagnostics._resilience_events
            if e.get("kind") == "cache-corrupt"
            and e.get("site") == "executor.result_cache"
        ]


class _CacheCase(TestCase):
    """Arms the tier, registers two staged leaves, restores everything."""

    def setUp(self):
        super().setUp()
        _executor.clear_executor_cache()
        old = os.environ.get("HEAT_TPU_RESULT_CACHE")

        def restore():
            if old is None:
                os.environ.pop("HEAT_TPU_RESULT_CACHE", None)
            else:
                os.environ["HEAT_TPU_RESULT_CACHE"] = old
            _executor.clear_executor_cache()  # also re-reads the knob

        os.environ["HEAT_TPU_RESULT_CACHE"] = "1"
        _executor.reload_env_knobs()
        self.addCleanup(restore)
        self.tag = f"t{next(_TAG_SEQ)}"
        self.a = ht.array(np.arange(N, dtype=np.float32), split=0)
        self.b = ht.array(np.full(N, 2.0, np.float32), split=0)
        _result_cache.register_generation(self.a.parray, f"{self.tag}:a", 1)
        _result_cache.register_generation(self.b.parray, f"{self.tag}:b", 1)

    def _force(self, x, y):
        out = x * y + y
        return out.numpy()

    def _rc(self):
        return ht.executor_stats()["result_cache"]


class TestDefaultOff(TestCase):
    def test_off_by_default_and_records_nothing(self):
        _executor.clear_executor_cache()  # re-reads the (unset) knob
        self.assertFalse(_result_cache.enabled())
        rc = ht.executor_stats()["result_cache"]
        self.assertFalse(rc["enabled"])
        self.assertEqual(rc["shards"], 0)
        a = ht.array(np.arange(64, dtype=np.float32), split=0)
        _result_cache.register_generation(a.parray, "off:a", 1)
        for _ in range(3):
            (a + 1.0).numpy()
        rc = ht.executor_stats()["result_cache"]
        self.assertEqual(
            (rc["hits"], rc["misses"], rc["stores"], rc["entries"]),
            (0, 0, 0, 0),
        )
        # the fold-out aliases ride executor_stats unconditionally
        stats = ht.executor_stats()
        for k in ("cache_hits", "cache_misses", "cache_bytes_saved",
                  "cache_invalidations"):
            self.assertEqual(stats[k], 0)


class TestStoreHit(_CacheCase):
    def test_repeat_is_store_then_hits_bit_identical(self):
        first = self._force(self.a, self.b)
        rc0 = self._rc()
        self.assertGreaterEqual(rc0["stores"], 1)
        again = self._force(self.a, self.b)
        rc1 = self._rc()
        self.assertGreater(rc1["hits"], rc0["hits"])
        self.assertEqual(rc1["stores"], rc0["stores"])
        self.assertGreater(rc1["bytes_saved"], 0)
        self.assertEqual(first.tobytes(), again.tobytes())

    def test_clear_executor_cache_guarantees_recompute(self):
        self._force(self.a, self.b)
        self._force(self.a, self.b)
        self.assertGreaterEqual(self._rc()["entries"], 1)
        ht.clear_executor_cache()
        rc = self._rc()
        self.assertEqual(rc["entries"], 0)
        self.assertEqual(rc["bytes"], 0)
        # the first post-clear read recomputes (a fresh store, not a hit)
        value = self._force(self.a, self.b)
        rc = self._rc()
        self.assertEqual(rc["hits"], 0)
        self.assertGreaterEqual(rc["stores"], 1)
        expect = np.arange(N, dtype=np.float32) * 2.0 + 2.0
        self.assertEqual(value.tobytes(), expect.tobytes())


class TestInvalidation(_CacheCase):
    def test_donation_invalidates_exactly_the_aliasing_entries(self):
        self._force(self.a, self.b)            # entry keyed on (tag:a, tag:b)
        c = ht.array(np.full(N, 5.0, np.float32), split=0)
        _result_cache.register_generation(c.parray, f"{self.tag}:c", 1)
        (c + 1.0).numpy()                      # entry keyed on (t:c) only
        rc0 = self._rc()
        dropped = _result_cache.note_donation([id(self.a.parray)])
        self.assertEqual(dropped, 1)           # exact: only the a-entry dies
        self.assertEqual(self._rc()["invalidations"],
                         rc0["invalidations"] + 1)
        hits0 = self._rc()["hits"]
        (c + 1.0).numpy()                      # the c-entry still serves
        self.assertGreater(self._rc()["hits"], hits0)
        stores0 = self._rc()["stores"]
        self._force(self.a, self.b)            # the a-entry recomputes
        self.assertGreaterEqual(self._rc()["stores"], stores0)

    def test_generation_bump_fails_stale_entries_closed(self):
        first = self._force(self.a, self.b)
        self._force(self.a, self.b)
        self.assertGreaterEqual(self._rc()["hits"], 1)
        # the restage event: the SAME buffer re-registers at a higher
        # generation, so the old entry's (tag, gen) pairs no longer validate
        _result_cache.register_generation(self.a.parray, f"{self.tag}:a", 2)
        rc0 = self._rc()
        again = self._force(self.a, self.b)    # digests at gen 2: fresh key
        rc1 = self._rc()
        self.assertEqual(rc1["hits"], rc0["hits"])
        self.assertGreater(rc1["stores"], rc0["stores"])
        self.assertEqual(first.tobytes(), again.tobytes())
        # the stale gen-1 entry is swept (never serveable either way)
        self.assertGreaterEqual(
            _result_cache.invalidate_prefix(f"{self.tag}:a"), 1
        )


class TestPoisonedEntry(_CacheCase):
    def test_poisoned_entry_rejects_typed_and_recomputes(self):
        clean = self._force(self.a, self.b)
        self._force(self.a, self.b)
        ev0 = len(_cache_corrupt_events())
        self.assertGreaterEqual(_result_cache._poison_one(), 1)
        rc0 = self._rc()
        value = self._force(self.a, self.b)
        rc1 = self._rc()
        self.assertEqual(value.tobytes(), clean.tobytes())
        self.assertEqual(rc1["rejects"], rc0["rejects"] + 1)
        events = _cache_corrupt_events()
        self.assertEqual(len(events), ev0 + 1)
        self.assertIn("ResultCacheCorrupt", events[-1]["detail"])


class TestUncacheable(_CacheCase):
    def test_rng_labels_never_consult(self):
        for label in ("rand[2]", "defer:normal..add[3]", "dropout"):
            self.assertTrue(_result_cache.uncacheable_label(label))
        self.assertFalse(_result_cache.uncacheable_label("defer:mul..add[2]"))

    def test_unregistered_operand_is_uncacheable(self):
        big = ht.array(np.zeros((256, 256), np.float32), split=0)
        stores0 = self._rc()["stores"]
        for _ in range(2):
            (big + 1.0).numpy()
        self.assertEqual(self._rc()["stores"], stores0)
        self.assertIsNone(
            _result_cache.digest_args((big.parray,))
        )

    def test_scalar_and_registered_digests(self):
        d = _result_cache.digest_args((1.5, self.a.parray))
        self.assertEqual(d[0], ("s", "float", "1.5"))
        self.assertEqual(d[1], ("g", f"{self.tag}:a", 1))


class TestSwapHammer(TestCase):
    """``swap_state`` under the cache: bit-parity with cache-off, and a
    threaded hammer that must never observe a torn or stale value."""

    SCALES = {"a": 1.0, "b": 3.0}

    def setUp(self):
        super().setUp()
        self.tmp = tempfile.mkdtemp(prefix="ht-result-cache-swap-")
        self.addCleanup(shutil.rmtree, self.tmp, ignore_errors=True)
        self.gen = {}
        for name, scale in self.SCALES.items():
            w = ht.array(np.full(N, scale, np.float32), split=0)
            self.gen[name] = os.path.join(self.tmp, f"gen_{name}")
            ht.save_checkpoint({"w": w}, self.gen[name])
        old = os.environ.get("HEAT_TPU_RESULT_CACHE")

        def restore():
            if old is None:
                os.environ.pop("HEAT_TPU_RESULT_CACHE", None)
            else:
                os.environ["HEAT_TPU_RESULT_CACHE"] = old
            _executor.clear_executor_cache()
            sched = _executor._get_scheduler()
            sched.resume()
            sched.reopen()

        self.addCleanup(restore)

    def _arm(self, on: bool):
        os.environ["HEAT_TPU_RESULT_CACHE"] = "1" if on else "0"
        _executor.clear_executor_cache()

    def _sequence(self, pool, batches, swaps_at):
        """Serve a deterministic slot rotation, swapping generations at the
        given request indices; returns the value list."""
        values = []
        order = ["b", "a", "b"]
        for i in range(24):
            if i in swaps_at:
                ht.serving.swap_state(pool, self.gen[order[len(values) % 3]])
            x = batches[i % len(batches)]
            y = x * pool.state["w"] + pool.state["w"]
            values.append(float(np.asarray(y.parray)[0]))
        return values

    def _build(self, name):
        pool = ht.serving.ModelPool(
            {"w": ht.zeros((N,), split=0)}, name=name
        ).load(self.gen["a"])
        batches = []
        for s in range(4):
            v = ht.array(np.full(N, float(s + 1), np.float32), split=0)
            _result_cache.register_generation(v.parray, f"{name}:x:{s}", 1)
            batches.append(v)
        return pool, batches

    def test_swap_sequence_bit_parity_with_cache_off(self):
        swaps_at = {6, 13, 19}
        self._arm(False)
        pool, batches = self._build("hammer-off")
        baseline = self._sequence(pool, batches, swaps_at)
        self._arm(True)
        pool, batches = self._build("hammer-on")
        cached = self._sequence(pool, batches, swaps_at)
        self.assertEqual(baseline, cached)
        rc = ht.executor_stats()["result_cache"]
        self.assertGreater(rc["hits"], 0)          # the cache actually served
        self.assertGreater(rc["invalidations"], 0)  # the swaps actually swept

    def test_threaded_hammer_never_serves_stale_or_torn(self):
        self._arm(True)
        pool, batches = self._build("hammer-t")
        stop = threading.Event()
        bad = []
        valid = {s: {scale * (s + 2) for scale in self.SCALES.values()}
                 for s in range(len(batches))}

        from heat_tpu.core import resilience

        def worker(seed):
            i = seed
            while not stop.is_set():
                s = i % len(batches)
                i += 1
                try:
                    # a request binds the state ONCE: the swap is one atomic reference
                    # swap, so two reads of ``pool.state`` may straddle it (2 * 1 + 3)
                    w = pool.state["w"]
                    y = batches[s] * w + w
                    v = float(np.asarray(y.parray)[0])
                except (resilience.Shed, resilience.DeadlineExceeded,
                        resilience.RequestCancelled,
                        resilience.DrainTimeout):
                    continue  # typed lifecycle errors during quiesce are fine
                if v not in valid[s]:
                    bad.append((s, v))
                    return

        threads = [threading.Thread(target=worker, args=(k,), daemon=True)
                   for k in range(3)]
        for t in threads:
            t.start()
        try:
            for gen in ("b", "a", "b"):
                ht.serving.swap_state(pool, self.gen[gen],
                                      drain_timeout_s=30.0)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        self.assertEqual(bad, [])
        # post-quiesce: every request now sees the final generation only
        final = self.SCALES["b"]
        for s in range(len(batches)):
            y = batches[s] * pool.state["w"] + pool.state["w"]
            self.assertEqual(float(np.asarray(y.parray)[0]),
                             final * (s + 2))
