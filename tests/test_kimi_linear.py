"""``ht.nn``'s Kimi Delta Attention with fla's unbounded softplus decay, its low-rank decay and
channel-wise output gate, the latent attention without positions, and the KimiLinear scoring
forward against the plain reference (``reference_kimi_linear.py``) at a tiny size on the CPU:
hidden 64, KDA and latent attention of 4 heads of 16, five layers (published layers 1-5: KDA,
KDA, KDA, latent, KDA; one dense, four expert layers), 16 experts top-4 with no group limit,
128 tokens. The seeded decays reach below -50 a step, past the kernel's floor of -17.

Every sub-block is compared twice, as ``test_ling.py`` does. In float32 the program must
agree with the reference to 1e-5 (rms of the difference over the reference's rms). In
bfloat16 (the deployment's type) the tolerance is set between what the program reads and
what the reference itself reads when its contractions are rounded to float8, the next
precision down: the program passes it, that control fails it. Four planted faults, each a
model that computes something else on the same weights, fail the float32 limit by four orders
of magnitude and more (logits off by 0.11 with every step floored at -5, 0.18 with rotary
positions on the latent layer, 0.76 with one gate a head, 1.03 with Ling's sigmoid decay).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import diagnostics
from heat_tpu.core.kernels import delta_rule
from heat_tpu.nn import attention

import reference_kimi_linear as R

CFG = {
    "model_type": "kimi_linear", "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 72, "q_lora_rank": None,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_use_nope": True, "rope_theta": 10000, "rope_scaling": None,
    "linear_attn_config": {"full_attn_layers": [4], "kda_layers": [1, 2, 3, 5], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "num_experts": 16, "num_experts_per_token": 4, "num_shared_experts": 1,
    "num_expert_group": 1, "topk_group": 1, "use_grouped_topk": True, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
    "routed_scaling_factor": 2.446, "vocab_size": 512, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0, "hidden_act": "silu",
}
T, D, CONT = 128, 64, 16
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# bfloat16 tolerances, (limit, the program's reading, the float8 control's reading) as
# measured on the CPU with the seeds below; the limit lies between the two readings
BF16 = {
    "kda": (2.5e-2, 5.9e-3, 1.0e-1),
    "mla": (2e-2, 4.6e-3, 9.2e-2),
    "layer": (5e-2, 2.5e-2, 1.2e-1),
}
LIMIT = 1e-5  # float32


def gap(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def tokens_in(dtype, seed=1, t=T):
    return jax.random.normal(jax.random.key(seed), (t, D), jnp.float32).astype(dtype)


def config():
    return ht.nn.KimiLinearConfig.from_dict(CFG)


def block_of(latent: bool, dense: bool, dtype, **kw):
    return ht.nn.KimiLinearBlock(config(), latent, dense, dtype=dtype, block_rows=16, **kw)


def mixing_case(latent: bool):
    def case(dtype):
        m = block_of(latent, True, dtype).attn
        p, u = m.init(jax.random.key(3)), tokens_in(dtype)
        return m.apply(p, u), lambda precision: (R.mla if latent else R.kda)(
            p, u, CFG, precision)
    return case


def layer_case(dtype):
    """An expert layer that mixes by KDA: two norms, the recurrence, routed and shared experts."""
    blk = block_of(False, False, dtype)
    p, x = blk.init(jax.random.key(7)), tokens_in(dtype, 2)
    return blk.apply(p, x)[0], lambda precision: R.layer(
        p, x.astype(jnp.float32), CFG, 1, None, precision)[0]


CASES = {"kda": mixing_case(False), "mla": mixing_case(True), "layer": layer_case}


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("sub_block", list(CASES))
def test_sub_block_against_reference(sub_block, dtype):
    got, reference = CASES[sub_block](DTYPES[dtype])
    want = reference("float32")
    assert got.dtype == DTYPES[dtype] and got.shape == want.shape
    if dtype == "float32":
        assert gap(got, want) < LIMIT
    else:
        limit = BF16[sub_block][0]
        assert gap(got, want) < limit
        assert gap(reference("float8"), want) > limit  # one precision down fails it


# ------------------------------------------------------------------ the recurrence, unbounded
EPS = 1e-5


def mix_inputs(t, heads, d, case, seed=0):
    """What ``kda_mix`` takes for the softplus kind with a gate a channel, float32: three
    projections, their taps, the decay's pre-activation and rate, beta after its sigmoid, the
    gate before it, the head norm's weight. ``seeded`` reaches below -50 a step."""
    ks = jax.random.split(jax.random.key(seed), 11)
    xq, xk, xv = (jax.random.normal(k, (t, heads * d), jnp.float32) for k in ks[:3])
    taps = tuple(0.5 * jax.random.normal(k, (4, heads * d), jnp.float32) for k in ks[3:6])
    pre = 2.0 * jax.random.normal(ks[6], (t, heads * d), jnp.float32) - 2.0
    rate = jax.random.uniform(ks[10], (heads * d,), jnp.float32, 1.0, 16.0)
    if case == "steep_for_a_whole_chunk":  # -60 on every channel over positions 64..127
        pre = pre.at[delta_rule.CHUNK:2 * delta_rule.CHUNK].set(60.0 / rate)
    elif case == "floor_inside_sub_chunks":  # one step a sub-chunk at -60, the rest slow
        pre = jnp.full_like(pre, -6.0).at[3::delta_rule.WIDE_SUB].set(60.0 / rate)
    elif case == "steep_on_half_the_channels":  # a channel that forgets beside one that keeps
        pre = pre.at[:, ::2].set(60.0 / rate[::2]).at[:, 1::2].set(-8.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[7], (t, heads), jnp.float32))
    gate = jax.random.normal(ks[8], (t, heads * d), jnp.float32)
    norm_w = 1.0 + 0.1 * jax.random.normal(ks[9], (d,), jnp.float32)
    return xq, xk, xv, taps, pre, rate, beta, gate, norm_w


def token_by_token(xq, xk, xv, taps, pre, rate, beta, gate, norm_w, heads, bound=None):
    """The same mixing by the reference's pieces: four shifted multiply-adds, the norms, the
    unbounded decay (or the bounded one), the recurrence one position after another, the
    gated head norm."""
    t, d = xq.shape[0], xq.shape[1] // heads

    def branch(x, w):
        return R.short_conv(x.astype(jnp.float32), w).reshape(t, heads, d)

    q = R.l2_norm(branch(xq, taps[0])) * d ** -0.5
    k, v = R.l2_norm(branch(xk, taps[1])), branch(xv, taps[2])
    g = -rate * jax.nn.softplus(pre) if bound is None else bound * jax.nn.sigmoid(rate * pre)
    o = R.delta_rule(q, k, v, g.reshape(t, heads, d), beta)
    gated = R.rms_norm(o, norm_w, EPS) * jax.nn.sigmoid(gate).reshape(t, heads, d)
    return gated.reshape(t, heads * d)


def mixed(form, xq, xk, xv, taps, pre, rate, beta, gate, norm_w, heads, dtype=jnp.float32,
          bound=None):
    args = (xq.astype(dtype), xk.astype(dtype), xv.astype(dtype),
            tuple(w.astype(dtype) for w in taps), pre, rate, beta, gate, norm_w, heads, bound, EPS)
    if form == "kernel":
        return delta_rule.kda_mix(*args, interpret=True)
    return delta_rule.kda_mix_reference(*args)


@pytest.mark.parametrize("case", ["seeded", "steep_for_a_whole_chunk", "floor_inside_sub_chunks",
                                  "steep_on_half_the_channels"])
@pytest.mark.parametrize("form", ["kernel", "fallback"])
def test_unbounded_chunked_form_against_the_token_by_token_recurrence(form, case):
    """Three chunks of 64, each of eight sub-chunks of 8: every position's output is the
    recurrence's with decays to -60 a step (the kernel floors a step at -17: exact to
    float32), the positions on either side of a chunk's and a sub-chunk's edge one by one."""
    t, heads, d = 3 * delta_rule.CHUNK, 2, 32
    inputs = mix_inputs(t, heads, d, case)
    g = -inputs[5] * jax.nn.softplus(inputs[4])
    assert float(g.min()) < -50.0 and float(g.max()) > -1.0
    want = token_by_token(*inputs, heads)
    got = mixed(form, *inputs, heads)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all()) and gap(got, want) < 5e-6
    for edge in (0, 1, 7, 8, 9, 15, 16, 63, 64, 65, 66, 67, 71, 72, 127, 128, 135, 136, 191):
        assert gap(got[edge], want[edge]) < 2e-5, edge
    half = mixed(form, *inputs, heads, jnp.bfloat16)
    assert half.dtype == jnp.bfloat16 and gap(half, want) < 2.5e-2


@pytest.mark.parametrize("form", ["kernel", "fallback"])
def test_a_bound_below_minus_5_takes_the_wide_form(form):
    """The bounded kind at a bound of -40 (the narrow form holds -5 at most): its log-decays
    reach below the floor of -17 and the wide form is exact for them too."""
    inputs = mix_inputs(2 * delta_rule.CHUNK, 2, 32, "seeded", seed=4)
    got = mixed(form, *inputs, 2, bound=-40.0)
    assert gap(got, token_by_token(*inputs, 2, bound=-40.0)) < LIMIT  # reads 5.1e-6
    if form == "kernel":
        text = str(jax.make_jaxpr(lambda *a: mixed("kernel", *a, 2, bound=-40.0))(*inputs))
        assert "name=kda_unbounded_fwd" in text


def test_the_kernel_names_the_wide_form_and_counts_its_trace():
    """The softplus kind's Pallas call is ``kda_unbounded_fwd`` (its own device time), the
    bounded kind's stays ``kda_chunk_fwd``; a trace of each wrapper is counted apart, and a
    trace whose step lays its two heads side by side counts ``kernels.kda.fwd.paired``."""
    inputs = mix_inputs(64, 2, 16, "seeded")
    diagnostics.enable()
    diagnostics.reset()
    try:
        mixed("kernel", *inputs, 2)  # the first trace of this shape
        counters = diagnostics.report()["counters"]
        assert counters["kernels.kda.fwd.unbounded"] == 1 and "kernels.kda.fwd" not in counters
        assert counters["kernels.kda.fwd.paired"] == 1
    finally:
        diagnostics.disable()
        diagnostics.reset()
    text = str(jax.make_jaxpr(lambda *a: mixed("kernel", *a, 2))(*inputs))
    assert "name=kda_unbounded_fwd" in text and "kda_chunk_fwd" not in text


# ------------------------------------------------------------------ the expert share
@pytest.mark.parametrize("first", [0, 8])
def test_expert_shares_and_the_shared_expert_once_are_the_uncut_layer(first):
    """16 experts top-4 with no group limit: ``experts_held=(first, 8)`` gives the reference's
    part for the same share, and the two shares with the shared expert counted once add up
    to the uncut reference layer."""
    def layer(held):
        return ht.nn.MoE(D, CFG["moe_intermediate_size"], 16, 4, 1, 2.446, held, 16, jnp.float32)

    full = layer(None)
    p, u = full.init(jax.random.key(14)), tokens_in(jnp.float32, 15)
    uncut, _ = R.moe(p, u, CFG)
    shared = R.gated_mlp(p["shared"], u)

    def share(f):
        held = dict(p, experts={k: v[f:f + 8] for k, v in p["experts"].items()})
        return layer((f, 8)).apply(held, u), held

    (y, aux), held = share(first)
    want, chosen = R.moe(held, u, CFG, (first, 8))
    assert gap(y, want) < 1e-5
    assert np.array_equal(np.asarray(aux["chosen"]), np.asarray(chosen))  # over all 16
    mine = (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + 8)
    assert int(aux["load"].sum()) == int(mine.sum()) < T * 4
    total = shared + sum(share(f)[0][0] - shared for f in (0, 8))
    assert gap(total, uncut) < 1e-5
    assert gap(full.apply(p, u)[0], uncut) < 1e-5


# ------------------------------------------------------------------ the model
def model_of(dtype, params=None, **kw):
    model = ht.nn.KimiLinear(CFG, continuation=CONT, dtype=dtype, block_rows=16, **kw)
    model.params = model.init(jax.random.key(10)) if params is None else params
    return model


def document():
    return jax.random.randint(jax.random.key(11), (T,), 0, CFG["vocab_size"], jnp.int32)


def test_model_scores_and_routes_match_reference():
    model, tokens = model_of(jnp.float32), document()
    out = model(tokens)
    ref = R.forward(model.params, tokens, CFG, CONT)
    assert out.logits.shape == (CONT, CFG["vocab_size"])
    assert gap(out.logits, ref["logits"]) < LIMIT
    (loglik,) = model.readback(out)
    assert abs(loglik - float(ref["loglik"])) < 1e-4 * abs(loglik)
    assert out.chosen.shape == (4, T, 4) and out.load.shape == (4, 16)
    for got, want in zip(out.chosen, ref["routes"]):
        assert np.array_equal(np.sort(np.asarray(got), 1), np.sort(np.asarray(want), 1))
    assert [int(load.sum()) for load in out.load] == [T * 4] * 4  # no token dropped
    kinds = [type(layer.attn).__name__ for layer in model.layers]
    assert kinds == ["KimiDeltaAttention"] * 3 + ["MultiheadLatentAttention", "KimiDeltaAttention"]
    assert [type(layer.ffn).__name__ for layer in model.layers] == ["GatedMLP"] + ["MoE"] * 4
    # the seeded decays of the stream the layers see: 5-30% of them below -5, the bounded
    # kind's bound, so the wide form is what the test holds to the reference
    steep = 0
    x = model.params["embed"]["weight"][tokens].astype(jnp.float32)
    for index, p in enumerate(model.params["layers"]):
        if not R.is_latent(CFG, index):
            u = R.rms_norm(x, p["attn_norm"]["weight"], CFG["rms_norm_eps"])
            steep += int(jnp.sum(R.log_decay(p["attn"], u, CFG) < -5.0))
        x = R.layer(p, x, CFG, index)[0]
    assert 0.05 < steep / (4 * T * 64) < 0.3


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_seeded_decays_at_the_published_widths_are_steep_in_part(seed):
    """One KDA layer of the published widths (2304 -> 32 heads of 128, a decay pair of rank
    128) as the model seeds it, on RMS-normed inputs: 5-30% of the (position, channel)
    log-decays lie below -5, where the bounded kind's narrow form could not hold them, and
    some below the wide form's floor of -17."""
    cfg = {"linear_attn_config": {"num_heads": 32, "head_dim": 128}}
    m = ht.nn.KimiDeltaAttention(2304, 32, 128, 4, None, 1e-5, jnp.bfloat16,
                                 decay_rank=128, gate_rank=128)
    p = m.init(jax.random.key(seed))
    u = jax.random.normal(jax.random.key(100 + seed), (256, 2304), jnp.float32)
    g = R.log_decay(p, R.rms_norm(u, jnp.ones(2304), 1e-5), cfg)
    assert 0.05 < float(jnp.mean(g < -5.0)) < 0.3
    assert 0.0 < float(jnp.mean(g < delta_rule.FLOOR)) < 0.05


def test_model_with_a_share_of_the_experts_and_in_bfloat16():
    """The cell's cut, scaled down: 8 of 16 experts held, the reference given the same share;
    then the deployment's type against its control."""
    tokens = document()
    whole = model_of(jnp.float32)
    held = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf[8:] if any(getattr(p, "key", None) == "experts" for p in path)
        else leaf, whole.params)
    model = model_of(jnp.float32, held, experts_held=(8, 8))
    out = model(tokens)
    ref = R.forward(held, tokens, CFG, CONT, experts_held=(8, 8))
    assert gap(out.logits, ref["logits"]) < LIMIT and out.load.shape == (4, 8)
    assert all(0 < int(load.sum()) < T * 4 for load in out.load)
    assert gap(ref["logits"], R.forward(whole.params, tokens, CFG, CONT)["logits"]) > 1e-2
    half = model_of(jnp.bfloat16, experts_held=(8, 8))
    want = R.forward(half.params, tokens, CFG, CONT, experts_held=(8, 8))
    control = R.forward(half.params, tokens, CFG, CONT, "float8", (8, 8))
    # the program reads 0.10 of the logits' rms off, the float8 control 0.44
    assert gap(half(tokens).logits, want["logits"]) < 0.2 < gap(control["logits"], want["logits"])


def faulty(fault: str, monkeypatch):
    """The float32 model with one thing wrong, on the weights the reference reads."""
    model = model_of(jnp.float32)
    if fault == "clamped_at_-5":  # the bounded kernel's domain: a step floored at -5
        monkeypatch.setattr(delta_rule, "FLOOR", -5.0)
    elif fault == "head_wise_gate":  # one gate a head: the mean of its channels' pre-activations
        plain = delta_rule.kda_mix_reference

        def one_a_head(*args):
            gate, heads = args[7], args[9]
            mean = jnp.mean(gate.reshape(gate.shape[0], heads, -1), axis=2, keepdims=True)
            return plain(*args[:7], jnp.broadcast_to(mean, (gate.shape[0], heads, gate.shape[1]
                                                            // heads)).reshape(gate.shape),
                         *args[8:])

        monkeypatch.setattr(delta_rule, "kda_mix_reference", one_a_head)
    elif fault == "rotary_on_the_latent_layer":
        model.layers[3].attn.inv_freq = attention.yarn_inv_freq(CFG["qk_rope_head_dim"],
                                                                CFG["rope_theta"], None)
    elif fault == "sigmoid_decay":  # Ling's bounded gate, -5 sigmoid(rate pre), for the softplus
        plain = delta_rule.kda_mix_reference
        monkeypatch.setattr(delta_rule, "kda_mix_reference",
                            lambda *args: plain(*args[:10], -5.0, *args[11:]))
    return model


@pytest.mark.parametrize("fault", ["clamped_at_-5", "head_wise_gate", "rotary_on_the_latent_layer",
                                   "sigmoid_decay"])
def test_a_planted_fault_fails_the_limit(fault, monkeypatch):
    tokens = document()
    model = faulty(fault, monkeypatch)
    want = R.forward(model.params, tokens, CFG, CONT)["logits"]
    assert gap(model(tokens).logits, want) > 1e4 * LIMIT


@pytest.mark.parametrize("key,value,words", [
    ("kda_lower_bound", -5, "no lower bound"), ("q_lora_rank", 24, "query latent of rank 24"),
    ("num_expert_group", 4, "no group limit"), ("topk_group", 2, "no group limit"),
    ("mla_use_nope", False, "mla_use_nope=True only"),
    ("moe_router_activation_func", "softmax", "sigmoid"), ("moe_renormalize", False, "moe_renormalize"),
    ("tie_word_embeddings", True, "tie_word_embeddings"), ("num_key_value_heads", 2, "num_key_value_heads"),
    ("num_nextn_predict_layers", 1, "num_nextn_predict_layers"), ("model_type", "deepseek_v3", "kimi_linear"),
    ("num_hidden_layers", 6, "once"), ("first_k_dense_replace", 7, "outside the layers")])
def test_config_refuses_what_it_does_not_compute(key, value, words):
    with pytest.raises(ValueError, match=words):
        ht.nn.KimiLinearConfig.from_dict(dict(CFG, **{key: value}))
    c = config()
    assert [c.is_latent(i) for i in range(5)] == [False, False, False, True, False]
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv) == (4, 16, 4)
    # the published lists run past the cut: the layers beyond it are not built
    deep = dict(CFG, linear_attn_config=dict(CFG["linear_attn_config"], kda_layers=[1, 2, 3, 5, 6, 7],
                                             full_attn_layers=[4, 8]))
    assert ht.nn.KimiLinearConfig.from_dict(deep) == c
    nested = dict(CFG, linear_attn_config=dict(CFG["linear_attn_config"], kda_lower_bound=-5))
    with pytest.raises(ValueError, match="no lower bound"):
        ht.nn.KimiLinearConfig.from_dict(nested)


def test_one_trace_for_repeated_calls_and_the_counters():
    model = model_of(jnp.float32)
    a = document()
    b = jax.random.randint(jax.random.key(18), (T,), 0, CFG["vocab_size"], jnp.int32)
    diagnostics.enable()
    diagnostics.reset()
    try:
        outs = [model(x) for x in (a, b)]
        for out in outs:
            model.readback(out)
        counters = diagnostics.report()["counters"]
        assert counters["nn.kimi_linear.traces"] == 1
        assert "nn.ling.traces" not in counters and "nn.dsv32.traces" not in counters
        assert counters["span_n.nn.forward"] == 2
        assert counters["nn.moe.tokens"] == 2 * 4 * T * 4
        # the CPU takes the plain paths and says so: four KDA layers, one latent layer
        assert counters["fallback.nn.kda"] == 4 and counters["fallback.nn.mla"] == 1
    finally:
        diagnostics.disable()
        diagnostics.reset()
    with pytest.raises(ValueError, match="KimiLinear scores one document"):
        model(a[None])
