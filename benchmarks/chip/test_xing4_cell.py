"""The ``xing4-score-32k`` cell at a tiny size on the CPU: ``pytest benchmarks/chip``.

``run.run_cell(..., on_chip=False, sizes=...)`` with hidden 64, 8 experts and 256 tokens
is ``correct``; with the float8 control in the program's place, or with a weight of the
timed path tampered with after set-up, it is not.
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
import rooflines_xing4  # noqa: E402
import run  # noqa: E402

CELL = "xing4-score-32k"
SIZES = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
    "rope_scaling.original_max_position_embeddings": 32, "rope_scaling.factor": 8,
    "tokens": 256, "continuation": 64,
    # the chip's limits are set from readings at 32,768 tokens of hidden 3584. At this
    # size, over three seeds, the program reads logits <= 0.039, MTP logits <= 0.059 (one
    # token routed otherwise moves them), routes <= 0.024; the float8 control >= 0.116,
    # >= 0.22, >= 0.080. (The log-likelihoods do not tell the two apart, here or on the
    # chip, and are not compared: the configuration's limits_why.)
    "limits.logits_rms_gap": 0.08, "limits.mtp_logits_rms_gap": 0.12,
    "limits.route_mismatch_share": 0.05,
}


def tiny(**kw):
    return run.run_cell(CELL, 2**31 + 27, 0.2, False, on_chip=False, sizes=SIZES, **kw)


def _tamper_one_weight(state):
    """Every expert's down-projection of the first expert layer, doubled, in the model
    that the window times; the driver's own weights, which the reference reads, stay."""
    import jax

    problem = state["problem"]
    params = jax.tree_util.tree_map(lambda x: x, problem.params)
    ffn = dict(params["layers"][1]["ffn"])
    ffn["experts"] = dict(ffn["experts"], w_down=ffn["experts"]["w_down"] * 2)
    params["layers"][1] = dict(params["layers"][1], ffn=ffn)
    problem.model.params = params


@pytest.mark.parametrize("case", ["program", "float8_control", "tampered_weight"])
def test_tiny_cell(case):
    if case == "program":
        out = tiny()
        assert out["correct"], out["compared"]
        assert set(out["metrics"]) == {"solve_s", "setup_s"} and out["failed"] == 0
        assert json.loads(json.dumps(out)) == out
        assert set(out["compared"]) == {"logits_rms_gap", "mtp_logits_rms_gap",
                                        "route_mismatch_share"}
    elif case == "float8_control":
        out = tiny(control="float8")
        assert not out["correct"], out["compared"]
    else:
        out = tiny(tamper=_tamper_one_weight)
        assert not out["correct"], out["compared"]


def test_tiny_traced_run_reads_the_program_counters(monkeypatch):
    """``--trace 1`` on the CPU: the trace has no device plane, so the reductions that
    need one stand aside and the two shares find nothing to read; the three metrics
    that read the program's spans and counters are there."""
    import trace_reduce
    from heat_tpu.core import diagnostics

    monkeypatch.setattr(trace_reduce, "busy_s", lambda trace, windows: 0.0)
    monkeypatch.setattr(trace_reduce, "top_ops", lambda trace, window: [])
    monkeypatch.setattr(trace_reduce, "idle_gaps", lambda trace, window: [])
    was_on = diagnostics.enabled()
    try:
        out = run.run_cell(CELL, 2**31 + 28, 0.2, True, on_chip=False, sizes=SIZES)
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    assert out["correct"], out["compared"]
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert set(metrics) == {"fwd_host_ms", "fwd_traces_in_window", "moe_load_max_over_mean"}
    assert metrics["fwd_traces_in_window"] == 0 and metrics["fwd_host_ms"] > 0
    assert 1.0 <= metrics["moe_load_max_over_mean"] <= 8.0  # 8 experts at this size


def test_kernel_share_on_a_hand_built_trace():
    """``solve_share`` with ``kernel``: the floor of the solves inside the window over the
    self time of the operations named so; no such operation, nothing read."""
    solve_share = run.load_module("readers", "solve_share")
    cfg = run.load_json(HERE, "configs", "xing4.0-29b-a4b.json")
    peak = rooflines.peaks("TPU v5 lite")
    floor_ns = 1e9 * rooflines_xing4.attention_core_floor_s(cfg, peak, 1)
    events = [(0, int(4 * floor_ns), "while"), (10, 10 + int(2 * floor_ns), "mla_flash_fwd.6")]
    trace = {"devices": {"/device:TPU:0": events},
             "spans": [(0, int(5 * floor_ns), "bench.solve")]}
    ctx = {"trace": trace, "window": (0, int(5 * floor_ns)), "config": cfg, "peak": peak,
           "chips": 1}
    params = {"module": "rooflines_xing4", "floor": "attention_core_floor_s"}
    share = solve_share.read(ctx, dict(params, kernel="mla_flash_fwd"))
    assert abs(share - 50.0) < 1e-3
    assert abs(solve_share.read(ctx, params) - 100.0 * floor_ns / (4 * floor_ns)) < 1e-3
    assert solve_share.read(ctx, dict(params, kernel="no_such_kernel")) is None


def test_forward_flops_by_count():
    """ISSUE 27's count: 103 TFLOP a solve, 64% of it causal attention; the two floors."""
    cfg = run.load_json(HERE, "configs", "xing4.0-29b-a4b.json")
    flops = rooflines_xing4.forward_flops(cfg)
    core = rooflines_xing4.attention_core_flops(cfg)
    assert 100e12 < flops < 106e12 and 0.62 < core / flops < 0.66
    peak = rooflines.peaks("TPU v5 lite")
    assert 0.50 < rooflines_xing4.forward_floor_s(cfg, peak, 1) < 0.54
    assert rooflines_xing4.attention_core_floor_s(cfg, peak, 1) < \
        rooflines_xing4.forward_floor_s(cfg, peak, 1)


def test_configuration_keeps_the_catalog_row():
    """Every number of the catalog's config under the same key, but what ``reduced``
    names; the manifest's ``reduced`` equals the file's."""
    cfg = run.load_json(HERE, "configs", "xing4.0-29b-a4b.json")
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == "xing4.0-29b-a4b")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    published = {"hidden_size": 3584, "intermediate_size": 9216, "moe_intermediate_size": 1024,
                 "n_routed_experts": 64, "num_experts_per_tok": 4, "q_lora_rank": 768,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_attention_heads": 32, "vocab_size": 131072,
                 "hc_mult": 4, "hc_sinkhorn_iters": 20, "n_shared_experts": 1,
                 "routed_scaling_factor": 2, "num_nextn_predict_layers": 1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 2}
    assert set(cfg["limits"]) == {"logits_rms_gap", "mtp_logits_rms_gap",
                                  "route_mismatch_share"}
