"""CPU-only checks of the chip benchmark's harness: ``pytest benchmarks/chip``.

Sizes are passed as arguments (``run_cell(..., sizes=...)``); nothing here needs or
describes a TPU. Four virtual CPU devices stand in for the four-chip host.
"""

import json
import math
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, os.path.join(HERE, "drivers")):
    if path not in sys.path:
        sys.path.insert(0, path)

import rooflines  # noqa: E402
import run  # noqa: E402
import serve_loop  # noqa: E402
import trace_reduce  # noqa: E402

MANIFEST = run.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

SERVING_SIZES = {
    "requests.kmeans_assign.batch": 512, "requests.kmeans_assign.fit_rows": 4096,
    "requests.cdist_knn.batch": 64, "requests.cdist_knn.corpus_rows": 2048,
    "requests.mlp_infer.batch": 256, "requests.mlp_infer.features": 64,
    "requests.mlp_infer.hidden": 128}
SIZES = {
    "kmeans-fit": {"rows": 8192, "reference_block_rows": 2048},
    "matmul-ring-4chip": {"n": 256, "reference_rows": 64},
    "serve-saturated": SERVING_SIZES,
}
CONTROL = {"kmeans-fit": "bfloat16", "matmul-ring-4chip": "float8",
           "serve-saturated": "bfloat16"}


def tiny(cell, **kw):
    return run.run_cell(cell, 2**31 + 11, 0.4, False, on_chip=False, sizes=SIZES[cell], **kw)


# ------------------------------------------------------------------ the data files
@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_manifest_names_resolve(section):
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for entry in MANIFEST[section]:
        assert NAME.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        for cell in entry.get("workloads", []):
            assert cell in cells, (entry["name"], cell)
    if section == "configs":
        for c in MANIFEST["configs"]:
            cfg = run.load_json(ROOT, c["file"])
            assert c["file"].startswith(MANIFEST["paths"][0] + "/")
            assert cfg["reduced"] == c["reduced"] and cfg["source"] and cfg["assumed"]
            assert cfg["guarantees"] and cfg["limits"]
            assert os.path.exists(os.path.join(HERE, "drivers", cfg["driver"] + ".py"))
            named = [cfg["problem"]] if "problem" in cfg else [
                r[k] for r in cfg["requests"].values() for k in ("builder", "reference")]
            assert named and all(callable(run.resolve(ref)) for ref in named)
    if section == "workloads":
        four = [w for w in cells.values() if w["chips"] == 4]
        assert len(four) <= max(1, len(cells) // 4)
        for w in cells.values():
            assert NAME.match(w["traffic"]) and len(w["why"]) <= 200
            cell = run.load_cell(w["name"])  # config, traffic and metric files all load
            names = {m["name"] for m in cell["end_to_end"]}
            assert "setup_s" in names and len(names) >= 2 and cell["per_layer"]
    if section == "end_to_end":
        assert all(0.01 <= m["bound"] <= 0.1 for m in MANIFEST["end_to_end"])
    if section == "per_layer":
        for m in MANIFEST["per_layer"]:
            spec = run.load_json(HERE, "metrics", m["name"] + ".json")
            assert os.path.exists(os.path.join(HERE, "readers", spec["reader"] + ".py"))
            moved = e2e[m["moves"]]  # every cell of the metric reports what it moves
            assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))


# ------------------------------------------------------------- the load generator
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3])
def test_every_seed_offers_the_same_blocks_and_gaps_in_another_order(seed):
    types, n = ["a", "b", "c"], 24 * 20
    reqs, due = serve_loop.schedule(types, 8, n, seed, rate_rps=100.0)
    for b in range(20):  # every block: each (type, slot) pair exactly once
        assert sorted(reqs[24 * b:24 * (b + 1)]) == sorted((t, s) for t in types for s in range(8))
    other, other_due = serve_loop.schedule(types, 8, n, seed + 1, 100.0)
    assert reqs != other and due != other_due

    def gaps(d):
        return sorted(round(b - a, 9) for a, b in zip([0.0] + d[:-1], d))

    assert gaps(due) == gaps(other_due) and due == sorted(due)
    assert math.isclose(due[-1], 4.8)  # 480 arrivals at 100 req/s, whatever the order
    closed, none = serve_loop.schedule(types, 8, 100, seed)
    assert none is None and len(closed) == 100


def test_latency_counts_from_the_due_time():
    class NoProfiler:
        @staticmethod
        def request(tag):
            import contextlib
            return contextlib.nullcontext()

    def slow(name, slot):
        time.sleep(0.05)
        return slot

    # one worker, three requests due at once: the later ones wait for the earlier
    records, answers, wall = serve_loop.load_loop(
        NoProfiler, [("a", 0)] * 3, slow, workers=1, seconds=1.0, due=[0.0, 0.0, 0.0],
        keep=frozenset([2]))
    lat = [r[2] for r in records]
    assert lat[0] >= 0.05 and lat[1] >= 0.10 and lat[2] >= 0.15 and answers == {2: 0}
    assert records[2][3] >= 0.09  # and the generator says how late it sent them
    assert serve_loop.percentile_ms([0.001, 0.002, math.inf], 0.95) == math.inf


# ------------------------------------------------------------------- the yardstick
def test_roofline_floors():
    peak = rooflines.peaks("TPU v5 lite")
    kmeans = run.load_json(HERE, "configs", "kmeans-bigdata2020.json")
    matmul = run.load_json(HERE, "configs", "matmul-split01-bf16.json")
    assert math.isclose(rooflines.kmeans_fit_floor_s(kmeans, peak, 1), 0.1573, rel_tol=1e-3)
    assert math.isclose(rooflines.kmeans_fit_floor_s(dict(kmeans, rows=2**25), peak, 1),
                        0.3146, rel_tol=1e-3)
    assert math.isclose(rooflines.matmul_chain_floor_s(matmul, peak, 4), 0.7144, rel_tol=1e-3)
    with pytest.raises(SystemExit):
        rooflines.peaks("TPU v9 imaginary")


TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 100
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "%while = (s32[]) while(%t)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = f32[8,64]{1,0} fusion(%p)" } }
  event_metadata { key: 3 value { id: 3 name: "%collective-permute-done.1 = bf16[8]{0} collective-permute-done(%s)" } } }
planes { name: "/host:CPU"
  lines { name: "main" timestamp_ns: 100
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 12000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.solve" } } }
"""


def test_trace_reduction_on_a_hand_built_trace():
    from jax.profiler import ProfileData

    trace = trace_reduce.from_profile(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TRACE)))
    w = trace_reduce.window_of(trace)
    assert w == (100, 30100)
    assert math.isclose(trace_reduce.busy_s(trace, [w]), 15e-6)  # the while covers its body
    solves = trace_reduce.spans_named(trace, "bench.solve")
    assert math.isclose(trace_reduce.busy_s(trace, solves), 9e-6)  # clipped to the span
    assert trace_reduce.top_ops(trace, w)[0] == ["while", 7e-6]  # self time, body taken out
    assert trace_reduce.idle_gaps(trace, w) == [["bench.solve", 10e-6], ["none", 5e-6]]
    assert math.isclose(trace_reduce.exposed_collective_s(trace, w), 5e-6)
    assert trace_reduce.union([(5, 9), (1, 3), (2, 6)]) == [(1, 9)]


# ------------------------------------------------- a whole run, at a tiny size
@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_agrees_with_the_reference_and_the_control_does_not(cell):
    out = tiny(cell)
    assert out["correct"], out["compared"]
    wanted = {m["name"] for m in run.load_cell(cell)["end_to_end"]}
    assert set(out["metrics"]) == wanted and out["failed"] == 0
    assert list(out)[-1] == "compared" and json.loads(json.dumps(out)) == out
    if "by_type" in out["info"]:  # whole blocks hold equal thirds; the last may be partial
        counts = out["info"]["by_type"].values()
        assert max(counts) - min(counts) <= 8
    control = tiny(cell, control=CONTROL[cell])
    assert not control["correct"], control["compared"]


def _fit_returns_its_start(state):
    import heat_tpu as ht

    p, solve = state["problem"], state["problem"].solve
    p.solve = lambda: (solve(), setattr(p.km, "_cluster_centers", ht.array(p.centers0)))


def _fit_leaves_half_out(state):
    import heat_tpu as ht
    import jax.numpy as jnp

    p = state["problem"]
    half = p.x_raw[: p.x_raw.shape[0] // 2]
    p.x = ht.array(jnp.concatenate([half, half]), split=0)


def _fit_answer_altered(state):
    p, solve = state["problem"], state["problem"].solve

    def altered():
        solve()
        p.km._cluster_centers = p.km._cluster_centers + 0.01

    p.solve = altered


def _matmul_without_the_exchange(state):
    import heat_tpu as ht
    import jax.numpy as jnp

    p = state["problem"]
    n, chips = p.b_raw.shape[0], 4
    own = jnp.arange(n)[:, None] // (n // chips) == jnp.arange(n)[None, :] // (n // chips)
    p.b = ht.array(jnp.where(own, p.b_raw, 0).astype(p.b_raw.dtype), split=1)


def _one_request_type_answers_wrongly(state):
    w = state["workloads"]["cdist_knn"]
    state["workloads"]["cdist_knn"] = w._replace(request=lambda slot: w.request(slot) + 1)


@pytest.mark.parametrize("cell,fault", [
    ("kmeans-fit", _fit_returns_its_start), ("kmeans-fit", _fit_leaves_half_out),
    ("kmeans-fit", _fit_answer_altered), ("matmul-ring-4chip", _matmul_without_the_exchange),
    ("serve-saturated", _one_request_type_answers_wrongly),
], ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_a_broken_timed_path_is_not_correct(cell, fault):
    out = tiny(cell, tamper=fault)
    assert not out["correct"], out["compared"]
