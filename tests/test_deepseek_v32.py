"""``ht.nn``'s lightning indexer, the attention over its selection and the DeepseekV32
scoring forward against the plain reference (``reference_deepseek_v32.py``) at a tiny size on
the CPU: hidden 64, 4 heads, an indexer of 8 heads of 32 that keeps 32 keys, one dense and
two expert layers, 16 experts top-4 in 4 groups of which 2 stay, 128 tokens.

In float32 the program must agree with the reference to 1e-5 (rms of the difference over the
reference's rms) and select and route exactly as it does. In bfloat16 (the deployment's
type) the limits lie between what the program reads and what the reference itself reads
when its contractions are rounded to float8, the next precision down: the program passes
them, that control fails them, and so does every planted fault.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import diagnostics
from heat_tpu.core.kernels import sparse_index
from heat_tpu.nn import attention

import reference_deepseek_v32 as R

CFG = {
    "model_type": "deepseek_v32", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 8,
    "index_head_dim": 32, "index_topk": 32, "n_routed_experts": 16, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000, "num_nextn_predict_layers": 0,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 16, "type": "yarn"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
    "hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False,
    "moe_layer_freq": 1,
}
T, D, CONT, TOPK = 128, 64, 16, 32
# bfloat16: (limit, the program's largest reading, the float8 control's smallest) over the
# seeds 5, 6, 7 on the CPU; every planted fault below passes at least one limit on each
LIMITS = {"logits": (0.37, 0.31, 0.42), "routes": (0.2, 0.105, 0.31), "selection": (0.05, 0.023, 0.087)}


def gap(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def model_of(dtype, cfg=CFG, **kw):
    return ht.nn.DeepseekV32(cfg, continuation=CONT, dtype=dtype, block_rows=16, **kw)


@functools.lru_cache(maxsize=None)
def document(dtype: str, seed: int = 5):
    """Seeded weights in ``dtype``, a document, the sampled queries and the reference."""
    model = model_of(dtype)
    params = model.init(jax.random.key(seed))
    tokens = jax.random.randint(jax.random.key(seed + 100), (T,), 0, CFG["vocab_size"], jnp.int32)
    sample = model.sampled_queries(T)
    return params, tokens, sample, R.forward(params, tokens, CFG, CONT, "float32", None, sample)


def readings(got: dict, ref: dict) -> dict:
    """The cell's three comparisons: logits, sets of experts, kept keys the other side lacks."""
    differ = [(np.sort(np.asarray(a), 1) != np.sort(np.asarray(b), 1)).any(axis=1)
              for a, b in zip(got["routes"], ref["routes"])]
    missed = [1.0 - (np.asarray(a) & np.asarray(b)).sum(axis=1) / np.asarray(b).sum(axis=1)
              for a, b in zip(got["selections"], ref["selections"])]
    return {"logits": gap(got["logits"], ref["logits"]), "routes": float(np.mean(differ)),
            "selection": float(np.mean(missed))}


def of_program(out) -> dict:
    return {"logits": out.logits, "routes": out.chosen,
            "selections": [sparse_index.unpack_mask(words, T) for words in out.selected]}


# ------------------------------------------------------------------ the model
def test_model_scores_routes_and_selects_as_the_reference():
    params, tokens, sample, ref = document("float32")
    model = model_of(jnp.float32)
    model.params = params
    out = model(tokens)
    assert out.logits.shape == (CONT, CFG["vocab_size"])
    assert readings(of_program(out), ref) == {"logits": pytest.approx(0, abs=1e-5), "routes": 0.0,
                                              "selection": 0.0}
    (loglik,) = model.readback(out)
    assert abs(loglik - float(ref["loglik"])) < 1e-4 * abs(loglik)
    assert out.chosen.shape == (2, T, 4) and out.load.shape == (2, 16)
    assert out.selected.shape == (3, len(sample), sparse_index.mask_words(T))
    assert sample.tolist() == [0, 64] + list(range(T - 1 - CONT, T - 1))
    for got, want in zip(out.selected, ref["selections"]):  # the same sets, not only as large
        assert np.array_equal(np.asarray(sparse_index.unpack_mask(got, T)), np.asarray(want))
    # every query keeps min(topk, t + 1) keys: 3,600 pairs a layer of 8,256 causal ones
    assert out.kept.tolist() == [TOPK * (TOPK + 1) // 2 + (T - TOPK) * TOPK] * 3
    assert [int(load.sum()) for load in out.load] == [T * 4] * 2  # no token dropped
    assert [type(layer.ffn.module).__name__ for layer in model.layers] == ["GatedMLP", "MoE", "MoE"]


@pytest.mark.parametrize("cut", [{"head_groups": 2}, {"ffn_pieces": 4},
                                 {"head_groups": 4, "ffn_pieces": 2}], ids=str)
def test_heads_in_groups_and_tokens_in_pieces_are_the_uncut_forward(cut):
    params, tokens, _, ref = document("float32")
    model = model_of(jnp.float32, **cut)
    model.params = params
    assert readings(of_program(model(tokens)), ref) == {
        "logits": pytest.approx(0, abs=1e-5), "routes": 0.0, "selection": 0.0}


def test_model_with_a_share_of_the_experts_and_in_bfloat16():
    """The cell's cut, scaled down: 2 of 16 experts held (half a group of four), the reference
    given the same share; then the deployment's type against its float8 control."""
    params, tokens, sample, whole = document("float32")
    held = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf[4:6] if any(getattr(p, "key", None) == "experts" for p in path)
        else leaf, params)
    model = model_of(jnp.float32, experts_held=(4, 2))
    model.params = held
    out = model(tokens)
    ref = R.forward(held, tokens, CFG, CONT, experts_held=(4, 2), sample=sample)
    assert gap(out.logits, ref["logits"]) < 1e-5 and out.load.shape == (2, 2)
    assert all(0 < int(load.sum()) < T * 4 for load in out.load)
    assert gap(ref["logits"], whole["logits"]) > 1e-2  # the absent experts are left out
    params, tokens, sample, ref = document("bfloat16")
    model = model_of(jnp.bfloat16)
    model.params = params
    program = readings(of_program(model(tokens)), ref)
    control = readings(R.forward(params, tokens, CFG, CONT, "float8", None, sample), ref)
    for name, (limit, _, _) in LIMITS.items():
        assert program[name] < limit < control[name], (name, program, control)


def faulty(fault: str, monkeypatch):
    """The bfloat16 model with one thing wrong, on the weights the reference reads."""
    params, tokens, _, ref = document("bfloat16")
    cfg = dict(CFG)
    if fault == "no_selection":  # dense causal attention
        cfg["index_topk"] = T
    elif fault == "topk_halved":
        cfg["index_topk"] = TOPK // 2
    elif fault == "no_relu":
        monkeypatch.setattr(sparse_index, "index_scores", lambda q, k, w: jnp.sum(
            jnp.einsum("htd,sd->hts", q, k, preferred_element_type=jnp.float32) * w[:, :, None], 0))
    elif fault == "no_head_weights":
        scores = sparse_index.index_scores
        monkeypatch.setattr(sparse_index, "index_scores",
                            lambda q, k, w: scores(q, k, jnp.ones_like(w)))
    elif fault == "no_rotary":
        monkeypatch.setattr(attention.LightningIndexer, "_rotate", lambda self, x: x)
    elif fault == "no_norm_bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, leaf: jnp.zeros_like(leaf)
            if [getattr(p, "key", None) for p in path][-2:] == ["k_norm", "bias"] else leaf, params)
    model = model_of(jnp.bfloat16, cfg)
    model.params = params
    return readings(of_program(model(tokens)), ref)


@pytest.mark.parametrize("fault", ["no_selection", "topk_halved", "no_relu", "no_head_weights",
                                   "no_rotary", "no_norm_bias"])
def test_a_planted_fault_passes_a_limit(fault, monkeypatch):
    got = faulty(fault, monkeypatch)
    assert any(got[name] > limit for name, (limit, _, _) in LIMITS.items()), got
    if fault == "no_selection":  # a superset lacks none of the reference's keys: the logits say it
        assert got["selection"] == 0.0 and got["logits"] > 2 * LIMITS["logits"][0]
    else:
        assert got["selection"] > LIMITS["selection"][0]


# ------------------------------------------------------------------ the selection alone
@pytest.mark.parametrize("rows", ["t<k", "t=k", "t>k"])
def test_selection_against_top_k(rows):
    """The indexer's packed words against the reference's ``lax.top_k`` mask, row by row:
    a query with fewer earlier tokens than it may keep keeps them all, the one with exactly
    as many too, a later one the 32 best."""
    params, tokens, _, _ = document("float32")
    p = params["layers"][1]["attn"]
    layer = model_of(jnp.float32).layers[1].attn
    u = jax.random.normal(jax.random.key(21), (T, D), jnp.float32)
    c_q = R.rms_norm(R._mm(u, p["wq_a"]), p["q_norm"]["weight"], CFG["rms_norm_eps"])
    got = np.asarray(sparse_index.unpack_mask(layer.indexer.apply(p["indexer"], (u, c_q)), T))
    want = np.asarray(R.index_mask(p["indexer"], u, c_q, CFG))
    span = {"t<k": range(0, TOPK - 1), "t=k": range(TOPK - 1, TOPK), "t>k": range(TOPK, T)}[rows]
    for t in span:
        assert np.array_equal(got[t], want[t]), t
        assert got[t].sum() == min(TOPK, t + 1) and not got[t, t + 1:].any()
    if rows != "t>k":
        assert all(got[t, :t + 1].all() for t in span)  # plain causal attention


@pytest.mark.parametrize("t,topk", [(96, 40), (4096 + 128, 7)])
def test_mask_words_hold_what_was_packed(t, topk):
    mask = sparse_index.select_plain(jax.random.normal(jax.random.key(3), (t, t)), topk)
    words = sparse_index.pack_mask(mask)
    assert words.shape == (t, sparse_index.mask_words(t)) and words.dtype == jnp.int32
    assert np.array_equal(np.asarray(sparse_index.unpack_mask(words, t)), np.asarray(mask))
    assert int(jnp.sum(jax.lax.population_count(words))) == int(mask.sum())


# ------------------------------------------------------------------ the shares of one layer
@pytest.mark.parametrize("first", range(0, 32, 2))
def test_expert_share_is_its_part_of_the_layer(first):
    """One expert layer scaled down, 32 experts in 8 groups of which 4 stay, top-8:
    ``experts_held=(first, 2)`` (half a group, as the cell's 16 of 256) gives the reference's
    part for the same share, and the 16 shares' parts, with the shared expert and the
    attention counted once, add up to the uncut reference layer."""
    uncut, shares, refs = sixteen_shares()
    share, ref = shares[first // 2], refs[first // 2]
    assert gap(share, ref) < 1e-5
    x_after_attention = uncut["attended"]
    total = x_after_attention + uncut["shared"] + sum(s - x_after_attention - uncut["shared"]
                                                      for s in shares)
    assert gap(total, uncut["layer"]) < 1e-5


@functools.lru_cache(maxsize=None)
def sixteen_shares():
    cfg = dict(CFG, n_routed_experts=32, num_experts_per_tok=8, n_group=8, topk_group=4)
    config = ht.nn.DeepseekV32Config.from_dict(cfg)
    full = ht.nn.DeepseekV32Block(config, False, None, jnp.float32, 16)
    p = full.init(jax.random.key(14))
    x = jax.random.normal(jax.random.key(15), (T, D), jnp.float32)
    layer, _, _ = R.layer(p, x, cfg)
    attended = x + R.mla(p["attn"], R.rms_norm(x, p["attn_norm"]["weight"], 1e-6), cfg)[0]
    shared = R.gated_mlp(p["ffn"]["shared"], R.rms_norm(attended, p["ffn_norm"]["weight"], 1e-6))
    assert gap(full.apply(p, x)[0], layer) < 1e-5
    shares, refs = [], []
    for first in range(0, 32, 2):
        held = dict(p, ffn=dict(p["ffn"], experts={k: v[first:first + 2]
                                                   for k, v in p["ffn"]["experts"].items()}))
        block = ht.nn.DeepseekV32Block(config, False, (first, 2), jnp.float32, 16)
        y, aux = block.apply(held, x)
        assert aux["load"].shape == (2,) and set(aux) == {"selection", "chosen", "load"}
        shares.append(y)
        refs.append(R.layer(held, x, cfg, (first, 2))[0])
    return {"layer": layer, "attended": attended, "shared": shared}, shares, refs


# ------------------------------------------------------------------ the program and its config
@pytest.mark.parametrize("key,value", [
    ("model_type", "deepseek_v3"), ("scoring_func", "softmax"), ("topk_method", "greedy"),
    ("norm_topk_prob", False), ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("moe_layer_freq", 2), ("num_nextn_predict_layers", 1),
    ("num_key_value_heads", 2), ("index_head_dim", 4)])
def test_config_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match="only|whole"):
        ht.nn.DeepseekV32Config.from_dict(dict(CFG, **{key: value}))


def test_one_trace_for_repeated_calls_and_the_counters():
    params, tokens, _, _ = document("float32")
    model = model_of(jnp.float32)
    model.params = params
    diagnostics.enable()
    try:
        diagnostics.reset()
        for _ in range(3):
            out = model(tokens)
        model.readback(out)
        counters = diagnostics.report()["counters"]
        assert counters["nn.dsv32.traces"] == 1
        # on the CPU every layer's index and core take the plain paths, and say so
        assert counters["fallback.nn.dsa"] == 3 and counters["fallback.nn.mla"] == 3
        assert counters["nn.dsa.selected"] == 3 * 3600 and counters["nn.dsa.causal"] == 3 * 8256
        assert counters["nn.moe.tokens"] == 2 * T * 4
        assert counters.get("kernels.flash.fwd.latent", 0) == 0  # the XLA path on the CPU
    finally:
        diagnostics.disable()
        diagnostics.reset()
    with pytest.raises(ValueError, match="DeepseekV32 scores one document"):
        model(tokens[None])
    with pytest.raises(ValueError, match="one document"):
        model.layers[0].attn.apply(params["layers"][0]["attn"], jnp.zeros((2, T, D)))


def test_latent_form_through_the_interpreted_kernel(monkeypatch):
    """The attention layer at the published head widths (128 + 64 / 128), with its indexer's
    selection, YaRN and two head groups, on the TPU path with the flash kernel interpreted: it
    takes the latent operand form (``kernels.flash.fwd.latent`` 1 after a first call, no new
    trace after a second, ``fallback.nn.mla`` 0) and gives what the XLA path on the concatenated
    operands gives, in float32 to 1e-5."""
    m = attention.MultiheadLatentAttention(64, 4, 48, 32, 128, 64, 128, CFG["rope_theta"],
                                           CFG["rope_scaling"], 1e-6, jnp.float32, 0.1,
                                           index=(8, 128, 64), head_groups=2)
    params = m.init(jax.random.key(31))
    x = jax.random.normal(jax.random.key(32), (1024, 64), jnp.float32)
    want, plain = m.apply(params, x)  # the CPU: the XLA path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sparse_index, "available", lambda interpret=False: False)
    monkeypatch.setattr(attention, "flash_latent",
                        functools.partial(attention.flash_latent, interpret=True))
    diagnostics.enable()
    try:
        diagnostics.reset()
        got, chosen = m.apply(params, x)
        counters = diagnostics.report()["counters"]
        assert counters["kernels.flash.fwd.latent"] == 1
        assert counters.get("fallback.nn.mla", 0) == 0
        diagnostics.reset()
        m.apply(params, x)
        assert diagnostics.report()["counters"].get("kernels.flash.fwd.latent", 0) == 0
    finally:
        diagnostics.disable()
        diagnostics.reset()
    assert np.array_equal(np.asarray(chosen["selection"]), np.asarray(plain["selection"]))
    assert gap(got, want) < 1e-5


def test_expert_layers_through_the_interpreted_kernels_walked_in_slabs(monkeypatch):
    """The expert layers through ``moe_grouped_fwd`` and ``moe_combine_fwd``, interpreted, with
    VMEM cut to what the combine holds, so that an expert of 128 x 4096 is walked in two slabs of
    its hidden width, as the cell's 7168 x 2048 is in four on the chip: the forward is the
    ``jnp`` loop's within the file's float32 tolerance, routes and selections equal; the first
    call takes the kernels in both layers (``fallback.nn.moe`` 0) through one trace of the slab
    walk."""
    from heat_tpu.core.kernels import grouped_matmul

    cfg = dict(CFG, hidden_size=128, moe_intermediate_size=4096)
    model = model_of(jnp.float32, cfg)
    params = model.params = model.init(jax.random.key(21))
    tokens = jax.random.randint(jax.random.key(22), (T,), 0, cfg["vocab_size"], jnp.int32)
    loop = of_program(model(tokens))  # on the CPU the gate declines: the jnp loop
    need = grouped_matmul._combine_footprint(128, 4, 4)
    monkeypatch.setattr(grouped_matmul, "_VMEM_CAP", need + grouped_matmul._VMEM_MARGIN)
    assert grouped_matmul._slab(128, 4096, 16, 4, 4) == 2048
    monkeypatch.setattr(grouped_matmul, "available", lambda interpret=False: True)
    for name in ("grouped_gated_silu", "combine"):
        monkeypatch.setattr(grouped_matmul, name,
                            functools.partial(getattr(grouped_matmul, name), interpret=True))
    grouped_matmul._grouped_pallas.clear_cache()  # its traces read the cap
    model = model_of(jnp.float32, cfg)
    model.params = params
    diagnostics.enable()
    try:
        diagnostics.reset()
        kernels = of_program(model(tokens))
        counters = diagnostics.report()["counters"]
        assert counters.get("fallback.nn.moe", 0) == 0
        assert counters["kernels.gmm.fwd.slabs"] == 1 and counters["kernels.gmm.combine"] == 1
    finally:
        diagnostics.disable()
        diagnostics.reset()
        grouped_matmul._grouped_pallas.clear_cache()
    assert gap(kernels["logits"], loop["logits"]) < 1e-5
    for name in ("routes", "selections"):
        for got, want in zip(kernels[name], loop[name]):
            assert np.array_equal(np.asarray(got), np.asarray(want))
