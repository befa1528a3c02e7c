"""CPU-only checks of the per-request-type metrics read from the program's host spans
(``readers/span_ms.py``, ``metrics/req_ms.*.json``, ``metrics/req_host_ms.*.json``).

A new file beside ``test_chip_benchmark.py`` (whose sizes it borrows): a PR that adds
metrics may add benchmark files and edit none.
"""

import math

import pytest

import test_chip_benchmark as harness  # sets the CPU platform and the import paths first

run, trace_reduce = harness.run, harness.trace_reduce
TYPES = ["kmeans_assign", "cdist_knn", "mlp_infer"]
NEW = [f"{kind}.{t}" for kind in ("req_ms", "req_host_ms") for t in TYPES]


def read(name, counters):
    spec = run.load_json(harness.HERE, "metrics", f"{name}.json")
    return run.load_module("readers", spec["reader"]).read({"counters": counters},
                                                           spec["params"])


@pytest.mark.parametrize("kind,expected_ms", [("req_ms", 2.5), ("req_host_ms", 2.0)])
def test_span_ms_on_hand_built_counters(kind, expected_ms):
    span = "diagnostics.{}.request.bench.cdist_knn"
    counters = {span.format("span_n"): 4, span.format("span_s"): 0.010,
                span.format("span_self_s"): 0.002,
                "diagnostics.span_n.request.bench.mlp_infer": 0}
    assert math.isclose(read(f"{kind}.cdist_knn", counters), expected_ms)
    assert read(f"{kind}.mlp_infer", counters) is None  # counted, never entered
    assert read(f"{kind}.kmeans_assign", counters) is None  # a program without the span


def test_the_manifest_gained_the_six_metrics_at_its_end():
    tail = harness.MANIFEST["per_layer"][-6:]
    assert [m["name"] for m in tail] == NEW
    assert all(m["workloads"] == ["serve-saturated"] and m["moves"] == "goodput_rps"
               and m["source"] == "program_counter" for m in tail)
    assert len({m["layer"] for m in tail}) == 1


def test_tiny_traced_serving_run_reports_the_six_metrics(monkeypatch):
    # the CPU's trace has no device plane: the three reductions that need one stand aside
    monkeypatch.setattr(trace_reduce, "busy_s", lambda trace, windows: 0.0)
    monkeypatch.setattr(trace_reduce, "top_ops", lambda trace, window: [])
    monkeypatch.setattr(trace_reduce, "idle_gaps", lambda trace, window: [])
    from heat_tpu.core import diagnostics

    was_on = diagnostics.enabled()
    try:
        out = run.run_cell("serve-saturated", 2**31 + 12, 0.4, True, on_chip=False,
                           sizes=harness.SERVING_SIZES)
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    assert out["correct"], out["compared"]
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert set(NEW) <= set(metrics) and "sat_lat_p95_ms" in metrics
    for t in TYPES:
        assert 0 < metrics[f"req_host_ms.{t}"] <= metrics[f"req_ms.{t}"]
        assert all(math.isfinite(metrics[f"{kind}.{t}"]) for kind in ("req_ms", "req_host_ms"))
