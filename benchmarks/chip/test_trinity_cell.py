"""The ``trinity-score-32k`` cell at a tiny size on the CPU: ``pytest benchmarks/chip``.

``run.run_cell(..., on_chip=False, sizes=...)`` with hidden 64, 4 / 2 heads of 16, a
window of 32, 8 experts top-2 and 256 tokens is ``correct``; with the float8 control in
the program's place, or with the window taken off the window layers of the model that the
window times, it is not.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.join(HERE, "drivers")):
    if path not in sys.path:
        sys.path.insert(0, path)

import rooflines  # noqa: E402
import rooflines_trinity  # noqa: E402
import run  # noqa: E402

CELL = "trinity-score-32k"
CONFIG = "trinity-mini-26b-a3b"
SIZES = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 32,
    "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
    "tokens": 256, "continuation": 64,
    # the chip's limits are set from readings at 32,768 tokens of hidden 2048. At this
    # size, over five seeds, the program reads logits <= 0.108, routes <= 0.023; the
    # float8 control >= 0.29 and >= 0.17; the model without its window 1.17 and 0.69. On
    # the CPU every layer's core is the XLA path (5 fallbacks a trace), which the chip's
    # limit of 0 refuses.
    "limits.logits_rms_gap": 0.2, "limits.route_mismatch_share": 0.09,
    "limits.attention_fallbacks": 5,
}


def tiny(seed=2**31 + 31, **kw):
    return run.run_cell(CELL, seed, 0.2, False, on_chip=False, sizes=SIZES, **kw)


def no_window(state):
    """The planted fault: the model that the window times loses the window on its window
    layers (a band as long as the document cuts nothing); the driver's own weights and
    configuration, which the reference reads, stay. On the chip too (PERF.md, PR 31)."""
    import heat_tpu as ht

    problem = state["problem"]
    cfg = problem.cfg
    model = ht.nn.Trinity(dict(cfg, sliding_window=cfg["tokens"]),
                          continuation=cfg["continuation"], dtype=cfg["dtype"])
    model.params = problem.params
    problem.model = model


@pytest.mark.parametrize("case", ["program", "float8_control", "no_window"])
def test_tiny_cell(case):
    if case == "program":
        out = tiny()
        assert out["correct"], out["compared"]
        assert set(out["metrics"]) == {"solve_s", "setup_s"} and out["failed"] == 0
        assert json.loads(json.dumps(out)) == out
        assert set(out["compared"]) == {"logits_rms_gap", "route_mismatch_share",
                                        "attention_fallbacks"}
        assert out["compared"]["attention_fallbacks"]["value"] == 5  # the CPU's XLA path
    elif case == "float8_control":
        out = tiny(control="float8")
        assert not out["correct"], out["compared"]
        assert out["compared"]["logits_rms_gap"]["value"] > SIZES["limits.logits_rms_gap"]
    else:
        out = tiny(tamper=no_window)
        assert not out["correct"], out["compared"]


def test_a_fallback_alone_is_not_correct():
    """The chip's limit on ``attention_fallbacks`` is 0: a program whose attention took the
    XLA path, as every CPU run's does, is not ``correct`` whatever its logits."""
    sizes = dict(SIZES)
    del sizes["limits.attention_fallbacks"]
    out = run.run_cell(CELL, 2**31 + 32, 0.2, False, on_chip=False, sizes=sizes)
    compared = out["compared"]
    assert not out["correct"]
    assert all(c["value"] <= c["limit"] for k, c in compared.items() if k != "attention_fallbacks")


def test_tiny_traced_run_reads_the_program_counters(monkeypatch):
    """``--trace 1`` on the CPU: the trace has no device plane, so the reductions that
    need one stand aside and the three shares find nothing to read; the two metrics that
    read the program's counters are there."""
    import trace_reduce
    from heat_tpu.core import diagnostics

    monkeypatch.setattr(trace_reduce, "busy_s", lambda trace, windows: 0.0)
    monkeypatch.setattr(trace_reduce, "top_ops", lambda trace, window: [])
    monkeypatch.setattr(trace_reduce, "idle_gaps", lambda trace, window: [])
    was_on = diagnostics.enabled()
    try:
        out = run.run_cell(CELL, 2**31 + 33, 0.2, True, on_chip=False, sizes=SIZES)
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    assert out["correct"], out["compared"]
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert set(metrics) == {"fwd_traces_in_window.trinity", "moe_load_max_over_mean.trinity"}
    assert metrics["fwd_traces_in_window.trinity"] == 0
    assert 1.0 <= metrics["moe_load_max_over_mean.trinity"] <= 8.0  # 8 experts at this size


def test_kernel_shares_on_a_hand_built_trace():
    """``solve_share`` with the two kernels' names: each floor over the self time of the
    operations named so, one kernel's time not read as the other's."""
    solve_share = run.load_module("readers", "solve_share")
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    peak = rooflines.peaks("TPU v5 lite")
    swa_ns = 1e9 * rooflines_trinity.window_core_floor_s(cfg, peak, 1)
    gqa_ns = 1e9 * rooflines_trinity.full_core_floor_s(cfg, peak, 1)
    end = int(10 * gqa_ns)
    events = [(0, end, "while"), (10, 10 + int(4 * swa_ns), "swa_flash_fwd.3"),
              (end // 2, end // 2 + int(2 * gqa_ns), "gqa_flash_fwd.7")]
    ctx = {"trace": {"devices": {"/device:TPU:0": events}, "spans": [(0, end, "bench.solve")]},
           "window": (0, end), "config": cfg, "peak": peak, "chips": 1}

    def share(metric):
        spec = run.load_json(HERE, "metrics", f"{metric}.json")
        return solve_share.read(ctx, spec["params"])

    assert abs(share("swa_flash_roofline_share") - 25.0) < 1e-3
    assert abs(share("gqa_flash_roofline_share") - 50.0) < 1e-3
    whole = 100.0 * 1e9 * rooflines_trinity.forward_floor_s(cfg, peak, 1) / end
    assert abs(share("fwd_mfu.trinity") - whole) < 1e-3
    ctx["trace"]["devices"]["/device:TPU:0"] = events[:1]  # no such operation ran
    assert share("swa_flash_roofline_share") is None


def test_forward_flops_by_count():
    """ISSUE 31's count: 39.5 TFLOP a solve; the band's pairs exactly; the three floors."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    t, w = 32768, 2048
    assert rooflines_trinity.window_pairs(cfg) == w * (w + 1) / 2 + (t - w) * w == 65_012_736
    assert abs(rooflines_trinity.window_core_flops(cfg) / 4 - 1.065e12) < 0.001e12
    assert abs(rooflines_trinity.full_core_flops(cfg) - 8.796e12) < 0.001e12
    assert 39.3e12 < rooflines_trinity.forward_flops(cfg) < 39.7e12
    # a window as long as the document is the causal half
    assert rooflines_trinity.window_pairs(dict(cfg, sliding_window=10**6)) == t * (t + 1) / 2
    peak = rooflines.peaks("TPU v5 lite")
    assert 0.19 < rooflines_trinity.forward_floor_s(cfg, peak, 1) < 0.21
    assert rooflines_trinity.window_core_floor_s(cfg, peak, 1) < \
        rooflines_trinity.full_core_floor_s(cfg, peak, 1) < \
        rooflines_trinity.forward_floor_s(cfg, peak, 1)


def test_configuration_keeps_the_catalog_row():
    """Every number of the catalog's config under the same key, but what ``reduced``
    names; the manifest's ``reduced`` equals the file's; the weights by count."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                                  "layer_types"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    published = {"global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
                 "hidden_size": 2048, "intermediate_size": 6144, "load_balance_coeff": 0.001,
                 "max_position_embeddings": 131072, "model_type": "afmoe",
                 "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
                 "num_attention_heads": 32, "num_expert_groups": 1, "num_experts": 128,
                 "num_experts_per_tok": 8, "num_key_value_heads": 4, "num_limited_groups": 1,
                 "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
                 "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
                 "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
                 "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"]["num_hidden_layers"] == 32 and cfg["published"]["num_dense_layers"] == 2
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert set(cfg["limits"]) == {"logits_rms_gap", "route_mismatch_share", "attention_fallbacks"}
    assert cfg["limits"]["attention_fallbacks"] == 0
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "back-to-back", 1)

    import jax

    import heat_tpu as ht

    model = ht.nn.Trinity(cfg, continuation=cfg["continuation"], dtype=cfg["dtype"])
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(model.init, jax.random.key(0)))
    assert 4.24e9 < sum(x.size for x in leaves) < 4.25e9
    assert sum(x.size * x.dtype.itemsize for x in leaves) >= 8.4e9
