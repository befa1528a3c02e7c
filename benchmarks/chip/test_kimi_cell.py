"""The ``kimi-score-32k`` cell at a tiny size on the CPU: ``pytest benchmarks/chip``.

``run.run_cell(..., on_chip=False, sizes=...)`` with hidden 64, KDA and latent attention of 4
heads of 16, the published layers 1-5 (KDA, KDA, KDA, latent, KDA; one dense, four expert
layers), 8 of 16 experts held, top-4, and 256 tokens is ``correct`` (seeded weights, as on
the chip); with the float8 control in the program's place, with the log-decay taken off
the KDA layers of the model that the window times, or with the kernel's floor on a step's
log-decay raised to the bounded kind's -5, it is not.
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
import rooflines_kimi_linear as counts  # noqa: E402
import run  # noqa: E402

CELL = "kimi-score-32k"
CONFIG = "kimi-linear-48b-a3b"
NEW_METRICS = ["fwd_mfu.kimi", "kda_roofline_share.kimi", "moe_gmm_roofline_share.kimi",
               "moe_load_max_over_mean.kimi", "fwd_traces_in_window.kimi",
               "mla_flash_roofline_share.kimi"]
SIZES = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "linear_attn_config.num_heads": 4, "linear_attn_config.head_dim": 16,
    "published.num_experts": 16, "num_experts": 8, "experts_held": [8, 8],
    "num_experts_per_token": 4, "vocab_size": 512, "tokens": 256, "continuation": 64,
    # the chip's limits are set from readings at 32,768 tokens of hidden 2304. At this size,
    # over six seeds, the program reads logits <= 0.155, routes <= 0.075; the float8 control
    # >= 0.289 and >= 0.364; the model without its decay >= 0.98 and >= 0.92. On the CPU every
    # layer's core is the plain path (5 fallbacks a trace), which the chip's limit of 0 refuses.
    "limits.logits_rms_gap": 0.2, "limits.route_mismatch_share": 0.2,
    "limits.attention_fallbacks": 5,
}


def tiny(seed=2**31 + 41, **kw):
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


@pytest.mark.parametrize("case", ["program", "float8_control", "no_decay", "clamped_at_-5"])
def test_tiny_cell(case, monkeypatch):
    if case == "program":
        out = tiny()
        assert out["correct"], out["compared"]
        assert set(out["metrics"]) == {"solve_s", "setup_s"} and out["failed"] == 0
        assert json.loads(json.dumps(out)) == out
        assert set(out["compared"]) == {"logits_rms_gap", "route_mismatch_share",
                                        "attention_fallbacks", "kda_rms_gap"}
        assert out["compared"]["attention_fallbacks"]["value"] == 5  # the CPU's plain paths
        assert out["compared"]["kda_rms_gap"]["value"] < 1e-5
    elif case == "float8_control":
        out = tiny(control="float8")
        assert not out["correct"], out["compared"]
        assert out["compared"]["kda_rms_gap"]["value"] > 1e-2
    elif case == "no_decay":
        out = tiny(tamper=no_decay)
        assert not out["correct"], out["compared"]
    else:  # the bounded kernel's domain on the softplus decays, in the timed model and the check
        from heat_tpu.core.kernels import delta_rule

        monkeypatch.setattr(delta_rule, "FLOOR", -5.0)
        kda = tiny()["compared"]["kda_rms_gap"]
        assert kda["value"] > 10 * kda["limit"], kda


def test_a_fallback_alone_is_not_correct():
    """The chip's limit on ``attention_fallbacks`` is 0: a program whose recurrence or
    attention took the plain path, as every CPU run's does, is not ``correct`` whatever its
    logits."""
    sizes = dict(SIZES)
    del sizes["limits.attention_fallbacks"]
    out = run.run_cell(CELL, 2**31 + 42, 0.2, False, on_chip=False, sizes=sizes)
    compared = out["compared"]
    assert not out["correct"]
    assert all(c["value"] <= c["limit"] for k, c in compared.items() if k != "attention_fallbacks")


def test_tiny_traced_run_reads_the_program_counters(monkeypatch):
    """``--trace 1`` on the CPU: the trace has no device plane, so the reductions that need
    one stand aside and the three shares find nothing to read; the metrics that read the
    program's counters are there."""
    import trace_reduce
    from heat_tpu.core import diagnostics

    monkeypatch.setattr(trace_reduce, "busy_s", lambda trace, windows: 0.0)
    monkeypatch.setattr(trace_reduce, "top_ops", lambda trace, window: [])
    monkeypatch.setattr(trace_reduce, "idle_gaps", lambda trace, window: [])
    was_on = diagnostics.enabled()
    try:
        out = run.run_cell(CELL, 2**31 + 43, 0.2, True, on_chip=False, sizes=SIZES)
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    assert out["correct"], out["compared"]
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert {"fwd_traces_in_window.kimi", "moe_load_max_over_mean.kimi",
            "fwd_host_ms", "compile_s", "peak_hbm_share.solve"} - set(metrics) \
        <= {"peak_hbm_share.solve"}
    assert not {"fwd_mfu.kimi", "kda_roofline_share.kimi", "moe_gmm_roofline_share.kimi",
                "mla_flash_roofline_share.kimi"} & set(metrics)
    assert metrics["fwd_traces_in_window.kimi"] == 0
    # the set-up's balanced selection bias: 1.14-1.16 over two seeds, 1.50-1.67 without it
    assert 1.0 <= metrics["moe_load_max_over_mean.kimi"] < 1.3


def test_kernel_shares_on_a_hand_built_trace():
    """The three kernels' shares and the whole forward's: each floor over the self time of the
    operations named so; the experts' floor from the pairs the program counted; the bounded
    kernel's name is not the unbounded one's; the latent layer's flash kernel over its causal
    pairs."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    peak = rooflines.peaks("TPU v5 lite")
    kda_ns = 1e9 * counts.kda_floor_s(cfg, peak, 1)
    pairs = 4 * 130_000.0  # what four expert layers routed to the held experts
    moe_ns = 1e9 * counts.routed_floor_s(cfg, peak, 1, pairs)
    mla_ns = 1e9 * counts.mla_core_floor_s(cfg, peak, 1)
    end = int(40 * kda_ns)
    events = [(0, end, "while"), (10, 10 + int(4 * kda_ns), "kda_unbounded_fwd.3"),
              (end // 2, end // 2 + int(2 * kda_ns), "kda_chunk_fwd.5"),
              (3 * end // 4, 3 * end // 4 + int(5 * moe_ns), "moe_grouped_fwd.9"),
              (end // 8, end // 8 + int(2 * mla_ns), "mla_flash_fwd.1")]
    ctx = {"trace": {"devices": {"/device:TPU:0": events}, "spans": [(0, end, "bench.solve")]},
           "window": (0, end), "config": cfg, "peak": peak, "chips": 1,
           "counters": {"diagnostics.nn.moe.tokens": pairs}}

    def share(metric):
        spec = run.load_json(HERE, "metrics", f"{metric}.json")
        return run.load_module("readers", spec["reader"]).read(ctx, spec["params"])

    assert abs(share("kda_roofline_share.kimi") - 25.0) < 1e-3
    assert abs(share("moe_gmm_roofline_share.kimi") - 20.0) < 1e-3
    assert abs(share("mla_flash_roofline_share.kimi") - 50.0) < 1e-3
    whole = 100.0 * 1e9 * counts.forward_floor_s(cfg, peak, 1) / end
    assert abs(share("fwd_mfu.kimi") - whole) < 1e-3
    ctx["counters"] = {}  # nothing counted: the parent, or a run without diagnostics
    assert share("moe_gmm_roofline_share.kimi") is None
    ctx["trace"]["devices"]["/device:TPU:0"] = events[:1]  # no such operation ran
    assert share("kda_roofline_share.kimi") is None


def test_counts_at_the_published_widths():
    """The cut's arithmetic: 4,283 M parameters, the recurrence's operations and bytes,
    about 37.4 TFLOP a solve."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    t, h, hd = 32768, 32, 128
    kda = (4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 4 * 4096
           + 32 + 4096 + 128)
    mla = 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304 + 512
    expert_ffn = 129 * 3 * 2304 * 1024 + 2304 * 256 + 256
    total = (4 * kda + mla + 3 * 2304 * 9216 + 4 * expert_ffn + 2 * 81920 * 2304 + 11 * 2304)
    assert (kda, mla, expert_ffn) == (39_514_272, 29_114_880, 913_637_632)
    assert counts.parameters(cfg) == total == 4_282_936_192
    assert counts.kda_flops(cfg) == 6.0 * hd * hd * t * h * 4
    assert counts.kda_bytes(cfg) == t * h * hd * 16 * 4 == 8_589_934_592
    assert counts.mean_held_pairs(cfg) == 131072  # 1,024 tokens a held expert
    assert abs(counts.mla_core_flops(cfg) - 10.995e12) < 0.001e12
    assert 37.2e12 < counts.forward_flops(cfg) < 37.5e12
    peak = rooflines.peaks("TPU v5 lite")
    # the recurrence's operands bound it: 8.59 GB at 819 GB/s, 10.5 ms a solve
    assert abs(counts.kda_floor_s(cfg, peak, 1) - counts.kda_bytes(cfg) / peak["hbm_bytes_per_s"]) < 1e-12
    assert 0.0104 < counts.kda_floor_s(cfg, peak, 1) < 0.0106
    assert 0.18 < counts.forward_floor_s(cfg, peak, 1) < 0.2


def test_configuration_keeps_the_catalog_row():
    """Every number of the catalog's config under the same key, but what ``reduced`` names;
    the manifest's ``reduced`` equals the file's; the new metrics are this cell's; the
    weights by count."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    reduced = ["num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True,
        "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1, "num_experts_per_token": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    lists = {"full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
             "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26]}
    widths = {"head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}
    assert cfg["published"] == {"num_hidden_layers": 27, "linear_attn_config": {**lists, **widths},
                                "num_experts": 256, "vocab_size": 163840}
    assert cfg["linear_attn_config"] == {"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5],
                                         **widths}
    assert [cfg[k] for k in ("num_hidden_layers", "num_experts", "vocab_size")] == [5, 128, 81920]
    assert cfg["experts_held"] == [0, 128]
    assert set(cfg["limits"]) == {"logits_rms_gap", "route_mismatch_share", "attention_fallbacks",
                                  "kda_rms_gap"}
    assert cfg["limits"]["attention_fallbacks"] == 0
    assert set(cfg["limits_why"]) >= set(cfg["limits"])
    for key in ("source", "catalog", "deployment", "assumed", "bytes", "precision", "guarantees"):
        assert cfg[key], key
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "back-to-back", 1)
    for name in NEW_METRICS:
        metric = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "solve_s"
    for name in ("solve_s", "compile_s", "peak_hbm_share.solve", "fwd_host_ms", "setup_cache_miss_n"):
        metric = next(m for m in manifest["end_to_end"] + manifest["per_layer"] if m["name"] == name)
        assert metric["workloads"][-1] == CELL

    import jax

    import heat_tpu as ht

    model = ht.nn.KimiLinear(dict(cfg, num_experts=256), continuation=cfg["continuation"],
                             experts_held=tuple(cfg["experts_held"]), dtype=cfg["dtype"])
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(model.init, jax.random.key(0)))
    assert sum(x.size for x in leaves) == counts.parameters(cfg)
    assert sum(x.size * x.dtype.itemsize for x in leaves) >= 8.56e9
