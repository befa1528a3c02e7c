"""The ``ling-score-32k`` cell at a tiny size on the CPU: ``pytest benchmarks/chip``.

``run.run_cell(..., on_chip=False, sizes=...)`` with hidden 64, 4 heads of 16, one period
of six layers (five KDA, one latent), 4 of 16 experts held, top-4 in 4 groups of which 2
stay, and 256 tokens is ``correct`` (seeded weights, as on the chip);
with the float8 control in the program's place, or with the log-decay taken off the KDA
layers of the model that the window times, it is not.
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
import rooflines_ling  # noqa: E402
import run  # noqa: E402

CELL = "ling-score-32k"
CONFIG = "ling-3.0-flash-vl"
SIZES = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rotary_dim": 8, "v_head_dim": 16,
    "published.num_experts": 16, "num_experts": 4, "experts_held": [4, 4],
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "vocab_size": 512,
    "tokens": 256, "continuation": 64,
    # the chip's limits are set from readings at 32,768 tokens of hidden 2560. At this
    # size, over seven seeds, the program reads logits <= 0.114, routes <= 0.072; the float8
    # control >= 0.266 and >= 0.318; the model without its decay (five seeds) >= 0.85 and
    # >= 0.77. On the CPU every layer's core is the plain path (6 fallbacks a trace), which
    # the chip's limit of 0 refuses.
    "limits.logits_rms_gap": 0.17, "limits.route_mismatch_share": 0.15,
    "limits.attention_fallbacks": 6,
}


def tiny(seed=2**31 + 33, **kw):
    return run.run_cell(CELL, seed, 0.2, False, on_chip=False, sizes=SIZES, **kw)


def no_decay(state):
    """The planted fault: the model that the window times forgets nothing (``dt_bias`` so
    low that every log-decay is 0) on its KDA layers; the driver's own weights, which the
    reference reads, stay."""
    import jax

    problem = state["problem"]

    def flat(path, leaf):
        return leaf - 100.0 if any(getattr(p, "key", None) == "dt_bias" for p in path) else leaf

    problem.model.params = jax.tree_util.tree_map_with_path(flat, problem.params)


@pytest.mark.parametrize("case", ["program", "float8_control", "no_decay"])
def test_tiny_cell(case):
    if case == "program":
        out = tiny()
        assert out["correct"], out["compared"]
        assert set(out["metrics"]) == {"solve_s", "setup_s"} and out["failed"] == 0
        assert json.loads(json.dumps(out)) == out
        assert set(out["compared"]) == {"logits_rms_gap", "route_mismatch_share",
                                        "attention_fallbacks"}
        assert out["compared"]["attention_fallbacks"]["value"] == 6  # the CPU's plain paths
    elif case == "float8_control":
        out = tiny(control="float8")
        assert not out["correct"], out["compared"]
    else:
        out = tiny(tamper=no_decay)
        assert not out["correct"], out["compared"]


def test_a_fallback_alone_is_not_correct():
    """The chip's limit on ``attention_fallbacks`` is 0: a program whose recurrence or
    attention took the plain path, as every CPU run's does, is not ``correct`` whatever
    its logits."""
    sizes = dict(SIZES)
    del sizes["limits.attention_fallbacks"]
    out = run.run_cell(CELL, 2**31 + 34, 0.2, False, on_chip=False, sizes=sizes)
    compared = out["compared"]
    assert not out["correct"]
    assert all(c["value"] <= c["limit"] for k, c in compared.items() if k != "attention_fallbacks")


def test_tiny_traced_run_reads_the_program_counters(monkeypatch):
    """``--trace 1`` on the CPU: the trace has no device plane, so the reductions that
    need one stand aside and the four shares find nothing to read; the two metrics that
    read the program's counters are there."""
    import trace_reduce
    from heat_tpu.core import diagnostics

    monkeypatch.setattr(trace_reduce, "busy_s", lambda trace, windows: 0.0)
    monkeypatch.setattr(trace_reduce, "top_ops", lambda trace, window: [])
    monkeypatch.setattr(trace_reduce, "idle_gaps", lambda trace, window: [])
    was_on = diagnostics.enabled()
    try:
        out = run.run_cell(CELL, 2**31 + 35, 0.2, True, on_chip=False, sizes=SIZES)
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    assert out["correct"], out["compared"]
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert set(metrics) == {"fwd_traces_in_window.ling", "moe_load_max_over_mean.ling"}
    assert metrics["fwd_traces_in_window.ling"] == 0
    assert 1.0 <= metrics["moe_load_max_over_mean.ling"] <= 4.0  # 4 experts held at this size


def test_kernel_shares_on_a_hand_built_trace():
    """The three kernels' shares: each floor over the self time of the operations named
    so; the experts' floor from the pairs the program counted, not from ``tokens * top-k``."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    peak = rooflines.peaks("TPU v5 lite")
    kda_ns = 1e9 * rooflines_ling.kda_floor_s(cfg, peak, 1)
    mla_ns = 1e9 * rooflines_ling.mla_core_floor_s(cfg, peak, 1)
    pairs = 5 * 60_000.0  # what five expert layers routed to the held experts
    moe_ns = 1e9 * rooflines_ling.routed_floor_s(cfg, peak, 1, pairs)
    end = int(10 * mla_ns)
    events = [(0, end, "while"), (10, 10 + int(20 * kda_ns), "kda_chunk_fwd.3"),
              (end // 2, end // 2 + int(2 * mla_ns), "mla_flash_fwd.7"),
              (3 * end // 4, 3 * end // 4 + int(4 * moe_ns), "moe_grouped_fwd.9")]
    ctx = {"trace": {"devices": {"/device:TPU:0": events}, "spans": [(0, end, "bench.solve")]},
           "window": (0, end), "config": cfg, "peak": peak, "chips": 1,
           "counters": {"diagnostics.nn.moe.tokens": pairs}}

    def share(metric):
        spec = run.load_json(HERE, "metrics", f"{metric}.json")
        return run.load_module("readers", spec["reader"]).read(ctx, spec["params"])

    assert abs(share("kda_roofline_share") - 5.0) < 1e-3
    assert abs(share("mla_flash_roofline_share.ling") - 50.0) < 1e-3
    assert abs(share("moe_gmm_roofline_share.ling") - 25.0) < 1e-3
    whole = 100.0 * 1e9 * rooflines_ling.forward_floor_s(cfg, peak, 1) / end
    assert abs(share("fwd_mfu.ling") - whole) < 1e-3
    ctx["counters"] = {}  # nothing counted: the parent, or a run without diagnostics
    assert share("moe_gmm_roofline_share.ling") is None
    ctx["trace"]["devices"]["/device:TPU:0"] = events[:1]  # no such operation ran
    assert share("kda_roofline_share") is None


def test_counts_at_the_published_widths():
    """ISSUE 33's arithmetic: 4,354 M parameters, the latent layer's pairs, the
    recurrence's operations and bytes, about 40 TFLOP a solve."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    t, h, hd = 32768, 32, 128
    kda = 5 * 2560 * 4096 + 2 * 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128
    mla = 2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560 + 2560 * 32 + 512
    expert_ffn = 128 * 3 * 2560 * 768 + 3 * 2560 * 768 + 2560 * 512 + 512
    total = (5 * kda + mla + 3 * 2560 * 6144 + 5 * expert_ffn + 2 * 39296 * 2560 + 13 * 2560)
    assert (kda, mla, expert_ffn) == (52_646_048, 31_965_696, 762_184_192)
    assert rooflines_ling.parameters(cfg) == total == 4_354_531_616
    assert rooflines_ling.mla_core_flops(cfg) == 2.0 * (t * (t + 1) / 2) * 320 * h
    assert abs(rooflines_ling.mla_core_flops(cfg) - 10.995e12) < 0.001e12
    assert rooflines_ling.kda_flops(cfg) == 6.0 * hd * hd * t * h * 5 == 515_396_075_520
    assert rooflines_ling.kda_bytes(cfg) == t * h * hd * 12 * 5 == 8_053_063_680
    assert rooflines_ling.mean_held_pairs(cfg) == 65536
    assert abs(rooflines_ling.routed_flops_of(cfg, 5 * 65536) - 3.865e12) < 0.001e12
    assert 40.0e12 < rooflines_ling.forward_flops(cfg) < 40.4e12
    peak = rooflines.peaks("TPU v5 lite")
    # the recurrence's operands bound it: 8.05 GB at 819 GB/s against 0.5 TFLOP at 197
    assert abs(rooflines_ling.kda_floor_s(cfg, peak, 1)
               - rooflines_ling.kda_bytes(cfg) / peak["hbm_bytes_per_s"]) < 1e-12
    assert rooflines_ling.kda_floor_s(cfg, peak, 1) > rooflines_ling.kda_flops(cfg) / peak[
        "bf16_flops_per_s"]
    assert 0.20 < rooflines_ling.forward_floor_s(cfg, peak, 1) < 0.21


def test_configuration_keeps_the_catalog_row():
    """Every number of the catalog's config under the same key, but what ``reduced``
    names; the manifest's ``reduced`` equals the file's; the weights by count."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                                  "num_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    published = {
        "hidden_size": 2560, "intermediate_size": 6144, "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 128, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "rope_theta": 6000000, "rms_norm_eps": 1e-06, "partial_rotary_factor": 0.5,
        "rotary_dim": 64, "max_position_embeddings": 131072, "num_experts_per_tok": 8,
        "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
        "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
        "score_function": "sigmoid", "use_qk_norm": True, "layer_group_size": 6,
        "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1, "linear_silu": True,
        "use_mla_nope": False, "short_conv_kernel_size": 4, "use_nGPT": False,
        "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
        "gated_attention_proj_granularity_type": "head_wise", "mtp_use_kda": False,
        "no_kda_lora": True, "use_kda_lora": False, "kda_safe_gate": True,
        "kda_lower_bound": -5, "image_patch_token": 157157, "video_patch_token": 156909,
        "image_start_token": 157158, "video_start_token": 157160}
    assert {k: cfg[k] for k in published} == published
    assert cfg["expert_swiglu_limit_list"] == [0] * 35 + [4] * 7
    assert cfg["share_expert_swiglu_limit_list"] == [0] * 34 + [5] * 6 + [7] * 2
    assert cfg["published"] == {"num_hidden_layers": 42, "first_k_dense_replace": 2,
                                "num_experts": 512, "vocab_size": 157184}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"], cfg["num_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == (6, 1, 128, 39296, [0, 128])
    assert set(cfg["limits"]) == {"logits_rms_gap", "route_mismatch_share", "attention_fallbacks"}
    assert cfg["limits"]["attention_fallbacks"] == 0
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "back-to-back", 1)
    solve_s = next(m for m in manifest["end_to_end"] if m["name"] == "solve_s")
    assert solve_s["workloads"][-1] == CELL

    import jax

    import heat_tpu as ht

    model = ht.nn.Ling(dict(cfg, num_experts=512), continuation=cfg["continuation"],
                       experts_held=tuple(cfg["experts_held"]), dtype=cfg["dtype"])
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(model.init, jax.random.key(0)))
    assert sum(x.size for x in leaves) == rooflines_ling.parameters(cfg)
    assert sum(x.size * x.dtype.itemsize for x in leaves) >= 8.7e9
