"""CPU-only checks of the six set-up metrics read from the program's own totals
(``readers/program_total.py``; ``metrics/startup_*.json``, ``metrics/setup_*.json``).

A new file beside ``test_chip_benchmark.py`` (whose sizes it borrows), as
``test_span_metrics.py`` is. Under pytest ``heat_tpu`` is imported long before ``run.py``,
so the identity with ``info.imported_s`` is the chip's criterion, not this file's.
"""

import math

import test_chip_benchmark as harness  # sets the CPU platform and the import paths first

run, trace_reduce = harness.run, harness.trace_reduce
STARTUP = ["startup_before_import_s", "startup_import_s", "startup_world_s"]
SETUP = ["setup_trace_lower_s", "setup_cache_read_s", "setup_cache_miss_n"]


def reader(params, counters):
    return run.load_module("readers", "program_total").read({"counters": counters}, params)


def read(name, counters):
    spec = run.load_json(harness.HERE, "metrics", f"{name}.json")
    assert spec["reader"] == "program_total"
    return reader(spec["params"], counters)


def test_the_manifest_gained_the_six_metrics_for_every_cell():
    added = {m["name"]: m for m in harness.MANIFEST["per_layer"] if m["name"] in STARTUP + SETUP}
    assert list(added) == STARTUP + SETUP
    assert all(m["workloads"] == harness.CELLS and m["moves"] == "setup_s"
               and m["better"] == "lower" and m["source"] == "program_counter"
               for m in added.values())
    assert len({added[n]["layer"] for n in STARTUP}) == 1
    assert {added[n]["layer"] for n in SETUP} == {"compile caches core/_compile_cache.py"}


def test_program_total_less_window_is_the_total_minus_the_windows_delta():
    from heat_tpu.core import diagnostics

    was_on = diagnostics.enabled()
    diagnostics.enable()
    try:
        diagnostics.reset()
        diagnostics.counter("setup.test.a", 5.0)
        diagnostics.counter("setup.test.b", 2.5)
        window = {"diagnostics.setup.test.a": 2.0, "diagnostics.setup.test.b": 0.5}
        both = ["setup.test.a", "setup.test.b"]
        assert reader({"counter": "setup.test.a"}, window) == 5.0
        assert reader({"counter": "setup.test.a", "less_window": True}, window) == 3.0
        assert reader({"counter": both, "less_window": True}, window) == 5.0
        assert reader({"counter": both, "less_window": True}, {}) == 7.5
        assert reader({"counter": "setup.test.never"}, window) is None  # a program without it
        assert reader({"path": ["startup", "no.such.phase", "seconds"]}, window) is None
        assert reader({"path": ["startup", "wall_start"]}, window) is None  # not a number
        assert reader({"path": ["startup", "import_s"]}, window) > 0.0
        assert read("setup_cache_miss_n", {}) == 0  # enabled: counted, and none
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    if not was_on:
        assert read("setup_cache_miss_n", {}) is None  # off: not counted


def test_tiny_traced_fit_reports_the_six_metrics(monkeypatch):
    # the CPU's trace has no device plane: the three reductions that need one stand aside
    monkeypatch.setattr(trace_reduce, "busy_s", lambda trace, windows: 0.0)
    monkeypatch.setattr(trace_reduce, "top_ops", lambda trace, window: [])
    monkeypatch.setattr(trace_reduce, "idle_gaps", lambda trace, window: [])
    from heat_tpu.core import diagnostics

    was_on = diagnostics.enabled()
    try:
        out = run.run_cell("kmeans-fit", 2**31 + 35, 0.4, True, on_chip=False,
                           sizes=harness.SIZES["kmeans-fit"])
        counters = diagnostics.report()["counters"]
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    assert out["correct"], out["compared"]
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert set(STARTUP + SETUP) <= set(metrics), sorted(metrics)
    for name in STARTUP + SETUP:
        assert math.isfinite(metrics[name]) and metrics[name] >= 0.0, (name, metrics[name])
    assert metrics["startup_world_s"] <= metrics["startup_import_s"]
    assert metrics["setup_cache_read_s"] <= out["info"]["compile_s"]["setup"]
    assert metrics["setup_trace_lower_s"] > 0.0  # the fit's program was traced in set-up
    assert metrics["setup_cache_miss_n"] == int(metrics["setup_cache_miss_n"])
    # a warmed window traces, lowers and compiles nothing: set-up is the whole total
    assert out["info"]["compiles"]["window"] == 0
    assert metrics["setup_trace_lower_s"] <= counters["jit.trace_s"] + counters["jit.lower_s"]
