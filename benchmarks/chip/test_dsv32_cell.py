"""The ``dsv32-score-32k`` cell at a tiny size on the CPU: ``pytest benchmarks/chip``.

``run.run_cell(..., on_chip=False, sizes=...)`` with hidden 64, 4 heads, a lightning indexer
of 8 heads of 32 that keeps 32 of up to 256 keys, one dense and four expert layers, 4 of 16
experts held, top-4 in 4 groups of which 2 stay, and 256 tokens is ``correct`` (seeded
weights, as on the chip); with the float8 control in the program's place, or with the sign
of the indexer's head weights turned in the model that the window times, it is not.
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
import rooflines_deepseek_v32 as counts  # noqa: E402
import run  # noqa: E402

CELL = "dsv32-score-32k"
CONFIG = "deepseek-v3.2-exp"
NEW_METRICS = ["fwd_mfu.dsv32", "dsa_index_roofline_share", "dsa_flash_roofline_share",
               "dsa_selected_over_causal", "moe_load_max_over_mean.dsv32",
               "fwd_traces_in_window.dsv32"]
SIZES = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 8,
    "index_head_dim": 32, "index_topk": 32, "published.n_routed_experts": 16,
    "n_routed_experts": 4, "experts_held": [4, 4], "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "vocab_size": 512, "tokens": 256, "continuation": 64,
    "rope_scaling.original_max_position_embeddings": 64, "head_groups": 2, "ffn_pieces": 2,
    # the chip's limits are set from readings at 32,768 tokens of hidden 7168. At this size,
    # over six seeds, the program reads logits <= 0.385, routes <= 0.213, selections <= 0.108
    # (64 wide and five layers deep: tokens routed otherwise are most of it); the float8
    # control >= 0.624, >= 0.512 and >= 0.267; the model whose head weights are turned >= 1.03,
    # >= 0.77 and >= 0.845. On the CPU every layer's index and core are the plain paths (2
    # fallbacks a layer of a trace), which the chip's limit of 0 refuses.
    "limits.logits_rms_gap": 0.5, "limits.route_mismatch_share": 0.35,
    "limits.select_mismatch_share": 0.18, "limits.attention_fallbacks": 10,
}


def tiny(seed=2**31 + 37, **kw):
    return run.run_cell(CELL, seed, 0.2, False, on_chip=False, sizes=SIZES, **kw)


def turned_weights(state):
    """The planted fault: in the model that the window times the indexer's head weights
    have the other sign, so every query keeps its worst keys; the driver's own weights,
    which the reference reads, stay."""
    import jax

    problem = state["problem"]

    def turn(path, leaf):
        return -leaf if any(getattr(p, "key", None) == "weights_proj" for p in path) else leaf

    problem.model.params = jax.tree_util.tree_map_with_path(turn, problem.params)


@pytest.mark.parametrize("case", ["program", "float8_control", "turned_weights"])
def test_tiny_cell(case):
    if case == "program":
        out = tiny()
        assert out["correct"], out["compared"]
        assert set(out["metrics"]) == {"solve_s", "setup_s"} and out["failed"] == 0
        assert json.loads(json.dumps(out)) == out
        assert set(out["compared"]) == {"logits_rms_gap", "route_mismatch_share",
                                        "select_mismatch_share", "attention_fallbacks"}
        assert out["compared"]["attention_fallbacks"]["value"] == 10  # the CPU's plain paths
    elif case == "float8_control":
        out = tiny(control="float8")
        assert not out["correct"], out["compared"]
    else:
        out = tiny(tamper=turned_weights)
        assert not out["correct"], out["compared"]
        assert out["compared"]["select_mismatch_share"]["value"] > 0.3


def test_a_fallback_alone_is_not_correct():
    """The chip's limit on ``attention_fallbacks`` is 0: a program whose index or attention
    took the plain path, as every CPU run's does, is not ``correct`` whatever its logits."""
    sizes = dict(SIZES)
    del sizes["limits.attention_fallbacks"]
    out = run.run_cell(CELL, 2**31 + 38, 0.2, False, on_chip=False, sizes=sizes)
    compared = out["compared"]
    assert not out["correct"]
    assert all(c["value"] <= c["limit"] for k, c in compared.items() if k != "attention_fallbacks")


def test_tiny_traced_run_reads_the_program_counters(monkeypatch):
    """``--trace 1`` on the CPU: the trace has no device plane, so the reductions that need
    one stand aside and the three shares find nothing to read; the metrics that read the
    program's counters are there, the selection's among them."""
    import trace_reduce
    from heat_tpu.core import diagnostics

    monkeypatch.setattr(trace_reduce, "busy_s", lambda trace, windows: 0.0)
    monkeypatch.setattr(trace_reduce, "top_ops", lambda trace, window: [])
    monkeypatch.setattr(trace_reduce, "idle_gaps", lambda trace, window: [])
    was_on = diagnostics.enabled()
    try:
        out = run.run_cell(CELL, 2**31 + 39, 0.2, True, on_chip=False, sizes=SIZES)
    finally:
        diagnostics.reset()
        if not was_on:
            diagnostics.disable()
    assert out["correct"], out["compared"]
    metrics = {name: m["value"] for name, m in out["metrics"].items()}
    assert {"fwd_traces_in_window.dsv32", "moe_load_max_over_mean.dsv32",
            "dsa_selected_over_causal", "fwd_host_ms", "compile_s",
            "peak_hbm_share.solve"} - set(metrics) <= {"peak_hbm_share.solve"}
    assert not {"fwd_mfu.dsv32", "dsa_index_roofline_share", "dsa_flash_roofline_share"} & set(metrics)
    assert metrics["fwd_traces_in_window.dsv32"] == 0
    assert 1.0 <= metrics["moe_load_max_over_mean.dsv32"] <= 4.0  # 4 experts held at this size
    # 32 of up to 256 keys: sum_t min(32, t + 1) over T (T + 1) / 2
    assert metrics["dsa_selected_over_causal"] == (32 * 33 / 2 + 224 * 32) / (256 * 257 / 2)


def test_kernel_shares_on_a_hand_built_trace():
    """The two kernels' shares and the whole forward's: each floor over the self time of the
    operations named so."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    peak = rooflines.peaks("TPU v5 lite")
    index_ns = 1e9 * counts.index_floor_s(cfg, peak, 1)
    flash_ns = 1e9 * counts.selected_floor_s(cfg, peak, 1)
    end = int(20 * flash_ns)
    events = [(0, end, "while"), (10, 10 + int(2 * index_ns), "dsa_index_fwd.3"),
              (end // 2, end // 2 + int(8 * flash_ns), "dsa_flash_fwd.7")]
    ctx = {"trace": {"devices": {"/device:TPU:0": events}, "spans": [(0, end, "bench.solve")]},
           "window": (0, end), "config": cfg, "peak": peak, "chips": 1, "counters": {}}

    def share(metric):
        spec = run.load_json(HERE, "metrics", f"{metric}.json")
        return run.load_module("readers", spec["reader"]).read(ctx, spec["params"])

    assert abs(share("dsa_index_roofline_share") - 50.0) < 1e-3
    assert abs(share("dsa_flash_roofline_share") - 12.5) < 1e-3
    whole = 100.0 * 1e9 * counts.forward_floor_s(cfg, peak, 1) / end
    assert abs(share("fwd_mfu.dsv32") - whole) < 1e-3
    ctx["trace"]["devices"]["/device:TPU:0"] = events[:1]  # no such operation ran: the parent
    assert share("dsa_index_roofline_share") is None and share("dsa_flash_roofline_share") is None


def test_counts_at_the_published_widths():
    """ISSUE 37's arithmetic: 4,635 M parameters, 65.0 M of 536.9 M pairs a layer, 8.8 TFLOP
    of index and 5.33 of selected attention a layer, about 180 TFLOP a solve at the least."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    parts = counts.parameters(cfg)
    assert parts["latent_attention"] == (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768
                                         + 16384 * 7168 + 1536 + 512) == 187_107_328
    assert parts["indexer"] == 1536 * 8192 + 7168 * 128 + 7168 * 64 + 256 == 13_959_424
    assert parts["expert_feed_forward"] == 17 * 3 * 7168 * 2048 + 7168 * 256 + 256 == 750_518_528
    assert parts["dense_feed_forward"] == 3 * 7168 * 18432 == 396_361_728
    assert parts["vocabulary"] == 2 * 16160 * 7168 == 231_669_760
    assert parts["total"] == 4_635_518_208
    assert counts.selected_pairs(cfg) == 2048 * 2049 / 2 + 30720 * 2048 == 65_012_736
    assert counts.causal_pairs(cfg) == 32768 * 32769 / 2
    assert abs(counts.selected_pairs(cfg) / counts.causal_pairs(cfg) - 0.1211) < 1e-4
    assert abs(counts.index_flops(cfg) / 5 - 8.80e12) < 0.01e12
    assert abs(counts.selected_flops(cfg) / 5 - 5.33e12) < 0.01e12
    assert counts.mean_held_pairs(cfg) == 16384  # 1,024 tokens an expert
    assert 180.0e12 < counts.forward_flops(cfg) < 180.6e12
    peak = rooflines.peaks("TPU v5 lite")
    assert 0.91 < counts.forward_floor_s(cfg, peak, 1) < 0.92


def test_configuration_keeps_the_catalog_row():
    """Every number of the catalog's config under the same key, but what ``reduced`` names;
    the manifest's ``reduced`` equals the file's; the file's ``bytes`` and the weights by
    count."""
    cfg = run.load_json(HERE, "configs", f"{CONFIG}.json")
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    reduced = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts", "vocab_size",
               "num_nextn_predict_layers"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    published = {
        "attention_bias": False, "ep_size": 1, "hidden_act": "silu", "hidden_size": 7168,
        "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
        "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840,
        "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
        "n_group": 8, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 128, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
        "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == {"num_hidden_layers": 61, "first_k_dense_replace": 3,
                                "n_routed_experts": 256, "vocab_size": 129280,
                                "num_nextn_predict_layers": 1}
    assert [cfg[k] for k in reduced] + [cfg["experts_held"]] == [5, 1, 16, 16160, 0, [0, 16]]
    assert set(cfg["limits"]) == {"logits_rms_gap", "route_mismatch_share",
                                  "select_mismatch_share", "attention_fallbacks"}
    assert cfg["limits"]["attention_fallbacks"] == 0
    assert set(cfg["limits_why"]) >= set(cfg["limits"])
    for key in ("source", "catalog", "deployment", "assumed", "bytes", "precision", "guarantees"):
        assert cfg[key], key
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "back-to-back", 1)
    for name in NEW_METRICS:
        metric = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL] and metric["moves"] == "solve_s"
    # the file's bytes, part by part, are the counts'
    parts = counts.parameters(cfg)
    for part, text in cfg["bytes"].items():
        assert f"{parts[part] / 1e6:,.2f} M" in text, (part, text)

    import jax

    import heat_tpu as ht

    model = ht.nn.DeepseekV32(dict(cfg, n_routed_experts=256), continuation=cfg["continuation"],
                              experts_held=tuple(cfg["experts_held"]), dtype=cfg["dtype"])
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(model.init, jax.random.key(0)))
    assert sum(x.size for x in leaves) == parts["total"]
    assert sum(x.size * x.dtype.itemsize for x in leaves) >= 9.27e9
