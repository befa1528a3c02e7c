"""``ht.nn``'s Kimi Delta Attention, the chunked delta-rule kernel, the latent attention's
direct query and head gate, the router's group limit and the Ling scoring forward against
the plain reference (``reference_ling.py``) at a tiny size on the CPU: hidden 64, 4 heads of
16, one period of six layers (five KDA, one latent; one dense, five expert layers), 16
experts top-4 in 4 groups of which 2 stay, 128 tokens.

Every sub-block is compared twice, as ``test_xing4.py`` does. In float32 the program must
agree with the reference to 1e-5 (rms of the difference over the reference's rms). In
bfloat16 (the deployment's type) the tolerance is set between what the program reads and
what the reference itself reads when its contractions are rounded to float8, the next
precision down: the program passes it, that control fails it.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import diagnostics
from heat_tpu.core.kernels import delta_rule

import reference_ling as R

CFG = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "num_hidden_layers": 6,
    "first_k_dense_replace": 1, "layer_group_size": 6, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rotary_dim": 8, "v_head_dim": 16,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5, "num_experts": 16,
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "routed_scaling_factor": 2.5,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 6000000,
    "score_function": "sigmoid", "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
    "use_nGPT": False, "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "use_mla_nope": False, "mtp_use_kda": False, "use_kda_lora": False, "no_kda_lora": True,
    "kda_safe_gate": True, "linear_silu": True, "num_kv_heads_for_linear_attn": 0,
    "gated_attention_proj_granularity_type": "head_wise",
    "expert_swiglu_limit_list": [0] * 6, "share_expert_swiglu_limit_list": [0] * 6,
}
T, D, CONT = 128, 64, 16
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# bfloat16 tolerances, (limit, the program's reading, the float8 control's reading) as
# measured on the CPU with the seeds below; the limit lies between the two readings
BF16 = {
    "kda": (2.5e-2, 6.3e-3, 9.7e-2),
    "mla": (2e-2, 5.0e-3, 9.7e-2),
    "layer": (7.5e-2, 3.6e-2, 1.3e-1),  # the program's reading is two tokens routed otherwise
}


def gap(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def tokens_in(dtype, seed=1, t=T):
    return jax.random.normal(jax.random.key(seed), (t, D), jnp.float32).astype(dtype)


def block_of(latent: bool, dense: bool, dtype, **kw):
    return ht.nn.LingBlock(ht.nn.LingConfig.from_dict(CFG), latent, dense, dtype=dtype,
                           block_rows=16, **kw)


def mixing_case(latent: bool):
    def case(dtype):
        m = block_of(latent, True, dtype).attn
        p, u = m.init(jax.random.key(3)), tokens_in(dtype)
        return m.apply(p, u), lambda precision: (R.mla if latent else R.kda)(p, u, CFG, precision)
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
        assert gap(got, want) < 1e-5
    else:
        limit = BF16[sub_block][0]
        assert gap(got, want) < limit
        assert gap(reference("float8"), want) > limit  # one precision down fails it


# ------------------------------------------------------------------ the recurrence, chunked
EPS = 1e-6


def mix_inputs(t, heads, d, case, seed=0):
    """What ``kda_mix`` takes, float32: three projections, their taps, the decay's
    pre-activation and rate, beta and the gate (after their sigmoid), the head norm's weight."""
    ks = jax.random.split(jax.random.key(seed), 11)
    xq, xk, xv = (jax.random.normal(k, (t, heads * d), jnp.float32) for k in ks[:3])
    taps = tuple(0.5 * jax.random.normal(k, (4, heads * d), jnp.float32) for k in ks[3:6])
    pre = 2.0 * jax.random.normal(ks[6], (t, heads * d), jnp.float32) - 3.0
    rate = jax.random.uniform(ks[10], (heads * d,), jnp.float32, 0.5, 2.0)
    if case == "bound_for_a_whole_chunk":  # every channel at -5 over positions 64..127
        pre = pre.at[delta_rule.CHUNK:2 * delta_rule.CHUNK].set(100.0)
    elif case == "no_decay":
        pre = jnp.full_like(pre, -200.0)
    elif case == "bound_on_half_the_channels":  # a channel that forgets beside one that keeps
        pre = pre.at[:, ::2].set(100.0).at[:, 1::2].set(-8.0)
    beta, gate = (jax.nn.sigmoid(jax.random.normal(k, (t, heads), jnp.float32)) for k in ks[7:9])
    norm_w = 1.0 + 0.1 * jax.random.normal(ks[9], (d,), jnp.float32)
    return xq, xk, xv, taps, pre, rate, beta, gate, norm_w


def token_by_token(xq, xk, xv, taps, pre, rate, beta, gate, norm_w, heads):
    """The same mixing by the reference's pieces: four shifted multiply-adds, the norms, the
    recurrence one position after another, the gated head norm."""
    t, d = xq.shape[0], xq.shape[1] // heads

    def branch(x, w):
        return R.short_conv(x.astype(jnp.float32), w).reshape(t, heads, d)

    q = R.l2_norm(branch(xq, taps[0])) * d ** -0.5
    k, v = R.l2_norm(branch(xk, taps[1])), branch(xv, taps[2])
    g = delta_rule.LOG_DECAY_BOUND * jax.nn.sigmoid(rate * pre)
    if bool(jnp.all(pre == 100.0, axis=1).any()):
        assert float(g.min()) == delta_rule.LOG_DECAY_BOUND  # the bound itself, not near it
    o = R.delta_rule(q, k, v, g.reshape(t, heads, d), beta)
    return (R.rms_norm(o, norm_w, EPS) * gate[:, :, None]).reshape(t, heads * d)


def mixed(form, xq, xk, xv, taps, pre, rate, beta, gate, norm_w, heads, dtype=jnp.float32):
    args = (xq.astype(dtype), xk.astype(dtype), xv.astype(dtype),
            tuple(w.astype(dtype) for w in taps), pre, rate, beta, gate, norm_w, heads,
            delta_rule.LOG_DECAY_BOUND, EPS)
    if form == "kernel":
        return delta_rule.kda_mix(*args, interpret=True)
    return delta_rule.kda_mix_reference(*args)


@pytest.mark.parametrize("case", ["seeded", "bound_for_a_whole_chunk", "no_decay",
                                  "bound_on_half_the_channels"])
@pytest.mark.parametrize("form", ["kernel", "fallback"])
def test_chunked_form_against_the_token_by_token_recurrence(form, case):
    """Three chunks of 64, each of four sub-chunks of 16: every position's output is the
    recurrence's, the positions on either side of a chunk's and a sub-chunk's edge one by
    one (the convolution reaches back over a chunk's edge too), with the log-decay at its
    bound of -5 on every channel for a whole chunk."""
    t, heads, d = 3 * delta_rule.CHUNK, 2, 32
    inputs = mix_inputs(t, heads, d, case)
    want = token_by_token(*inputs, heads)
    got = mixed(form, *inputs, heads)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert gap(got, want) < 5e-6
    for edge in (0, 1, 2, 3, 15, 16, 17, 63, 64, 65, 66, 67, 79, 80, 127, 128, 143, 144, 191):
        assert gap(got[edge], want[edge]) < 2e-5, edge
    half = mixed(form, *inputs, heads, jnp.bfloat16)
    assert half.dtype == jnp.bfloat16 and gap(half, want) < 2.5e-2  # reads 6e-3 .. 9e-3


def test_kernel_and_fallback_are_one_chunk_step():
    inputs = mix_inputs(128, 4, 16, "seeded", seed=5)
    for dtype, tol in ((jnp.float32, 5e-6), (jnp.bfloat16, 8e-3)):
        assert gap(mixed("kernel", *inputs, 4, dtype), mixed("fallback", *inputs, 4, dtype)) < tol


def chunk_inputs(n, dtype, wide, seed=11):
    """``chunk_step``'s operands for ``n`` heads of 128 over one chunk: unit q (scaled) and k,
    beta in (0, 1), log-decays by the narrow form's bound, or by the softplus kind to below
    the wide form's floor and then floored, a state of 0.3 N(0, 1)."""
    c, d = delta_rule.CHUNK, 128
    ks = jax.random.split(jax.random.key(seed), 6)

    def unit(y):
        return y / jnp.linalg.norm(y, axis=2, keepdims=True)

    q = unit(jax.random.normal(ks[0], (n, c, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (n, c, d)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (n, c, 1)))
    v = jax.random.normal(ks[3], (n, c, d))
    pre = 2.0 * jax.random.normal(ks[4], (n, c, d)) - 2.0
    if wide:
        g = jnp.maximum(-8.0 * jax.nn.softplus(pre), delta_rule.FLOOR)
    else:
        g = delta_rule.LOG_DECAY_BOUND * jax.nn.sigmoid(pre)
    st = 0.3 * jax.random.normal(ks[5], (n, d, d))
    return (q.astype(dtype), k.astype(dtype), (beta * k).astype(dtype), (beta * v).astype(dtype),
            g, st)


@pytest.mark.parametrize("n", [8, 3])
@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("form", ["narrow", "wide"])
def test_the_paired_step_is_the_step_a_head_at_a_time(form, dtype, n):
    """Where the step's head count is even, heads 2p and 2p + 1 lie side by side on the lanes
    and every product of the inverse adds exact zeros beside each head's own terms: the step
    agrees with the same step taken one head at a time (one head is never paired) to float32
    rounding, and within one bfloat16 rounding on bfloat16 operands. An odd count (3) keeps
    the form a head at a time."""
    sub, half = ((delta_rule.SUB, delta_rule._HALF) if form == "narrow"
                 else (delta_rule.WIDE_SUB, delta_rule._WIDE_HALF))
    args = chunk_inputs(n, DTYPES[dtype], form == "wide")
    assert delta_rule._paired(n) == (n == 8) and not delta_rule._paired(1)
    step = jax.jit(lambda *a: delta_rule.chunk_step(*a, sub, half))
    o, st = step(*args)
    alone = [step(*(x[h:h + 1] for x in args)) for h in range(n)]
    tol = 1e-6 if dtype == "float32" else 2.0 ** -8
    assert gap(o, jnp.concatenate([y[0] for y in alone])) <= tol
    assert gap(st, jnp.concatenate([y[1] for y in alone])) <= tol


def test_a_sequence_that_is_no_whole_number_of_chunks():
    """The kernel refuses it in words; the plain form pads it with positions after its end;
    the module takes the plain form and says why."""
    inputs = mix_inputs(100, 2, 32, "seeded", seed=6)
    with pytest.raises(ValueError, match="whole chunks of 64 positions"):
        mixed("kernel", *inputs, 2)
    assert gap(mixed("fallback", *inputs, 2), token_by_token(*inputs, 2)) < 5e-6
    x, w = jnp.ones((100, 256), jnp.bfloat16), jnp.ones((4, 256), jnp.bfloat16)
    assert "no whole number of chunks of 64" in delta_rule.decline_reason(x, w, 2)
    assert "whole lane tiles of 128" in delta_rule.decline_reason(x[:64], w, 4)
    assert "bfloat16 or float32" in delta_rule.decline_reason(x[:64].astype(jnp.float16), w, 2)
    assert "reaches past the 16 rows" in delta_rule.decline_reason(x[:64], jnp.ones((18, 256)), 2)
    assert delta_rule.decline_reason(x[:64], w, 2) is None
    with pytest.raises(ValueError, match="bound below 0"):
        ht.nn.KimiDeltaAttention(64, 4, 16, log_decay_bound=0.5)
    assert ht.nn.KimiDeltaAttention(64, 4, 16, log_decay_bound=-8.0).bound == -8.0  # wide form


def test_the_convolution_sees_zeros_left_of_the_document():
    """Positions 0, 1 and 2 have fewer than four taps; ``w[3]`` is the tap on the position
    itself; a chunk that is not the first takes the rows before it."""
    x = jax.random.normal(jax.random.key(20), (24, 6), jnp.float32)
    w = jax.random.normal(jax.random.key(21), (4, 6), jnp.float32)

    def conv(chunk, rows):  # one head on the leading axis
        return delta_rule.short_conv(chunk[None], rows[None], w[None])[0]

    got = conv(x[:8], jnp.zeros((16, 6), jnp.float32))
    silu = jax.nn.silu
    assert gap(got[0], silu(w[3] * x[0])) < 1e-6
    assert gap(got[1], silu(w[3] * x[1] + w[2] * x[0])) < 1e-6
    assert gap(got[2], silu(w[3] * x[2] + w[2] * x[1] + w[1] * x[0])) < 1e-6
    assert gap(got[5], silu(w[3] * x[5] + w[2] * x[4] + w[1] * x[3] + w[0] * x[2])) < 1e-6
    assert gap(got, R.short_conv(x[:8], w)) < 1e-6
    later = conv(x[16:], x[:16])  # positions 16..23 after rows 0..15
    assert gap(later, R.short_conv(x, w)[16:]) < 1e-6
    assert gap(later[0], silu(w[3] * x[16] + w[2] * x[15] + w[1] * x[14] + w[0] * x[13])) < 1e-6
    assert conv(x[:8].astype(jnp.bfloat16), x[:16]).dtype == jnp.float32


# ------------------------------------------------------------------ the router's group limit
def brute_force_choice(c, n_group, topk_group, top_k):
    """Per token, in plain Python: groups ranked by the sum of their two largest ``c``."""
    chosen = []
    for row in np.asarray(c, np.float64):
        groups = row.reshape(n_group, -1)
        best = np.argsort(-np.sort(groups, axis=1)[:, -2:].sum(axis=1), kind="stable")[:topk_group]
        allowed = [e for e in range(row.size) if e // groups.shape[1] in best]
        chosen.append(sorted(sorted(allowed, key=lambda e: -row[e])[:top_k]))
    return np.asarray(chosen)


def test_group_limit_against_brute_force():
    """64 experts in 8 groups of 8, 4 groups stay, top-8. The router is the identity, so a
    token's scores are set by hand: token 0's eight best experts lie in five groups, and
    the limit must take it off the weakest of them."""
    e, n_group, topk_group, top_k = 64, 8, 4, 8
    m = ht.nn.MoE(e, 32, e, top_k, 0, 2.5, None, 16, jnp.float32, n_group, topk_group)
    scores = np.array(jax.random.uniform(jax.random.key(30), (40, e), jnp.float32, 0.05, 0.6))
    # groups 0, 1, 2 hold two of the eight best each, groups 3 and 4 one each; group 4's one
    # is the weakest pair with its neighbour, so group 4 goes and experts 32's place is taken
    scores[0] = 0.1
    for expert, s in {0: 0.95, 1: 0.94, 8: 0.93, 9: 0.92, 16: 0.91, 17: 0.90, 24: 0.89,
                      25: 0.30, 32: 0.88, 33: 0.12}.items():
        scores[0, expert] = s
    u = jnp.asarray(np.log(scores / (1.0 - scores)), jnp.float32)
    params = {"router": jnp.eye(e, dtype=jnp.float32),
              "router_bias": 0.05 * jax.random.normal(jax.random.key(31), (e,), jnp.float32)}
    chosen, w = m.route(params, u)
    c = jax.nn.sigmoid(u) + params["router_bias"]
    want = brute_force_choice(c, n_group, topk_group, top_k)
    assert np.array_equal(np.sort(np.asarray(chosen), axis=1), want)
    unlimited = np.sort(np.argsort(-np.asarray(c[0]))[:top_k])
    assert len({int(x) // 8 for x in unlimited}) == 5  # the eight best lie in five groups
    assert {int(x) // 8 for x in np.asarray(chosen[0])} == {0, 1, 2, 3}
    assert 32 in unlimited and 32 not in np.asarray(chosen[0]) and 25 in np.asarray(chosen[0])
    assert np.all(np.asarray([len({int(x) // 8 for x in row}) for row in np.asarray(chosen)]) <= 4)
    # the weights are the chosen scores over their own sum, times the scaling factor
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(u)), np.asarray(chosen), axis=1)
    assert gap(w, s / s.sum(axis=1, keepdims=True) * 2.5) < 1e-6
    ref_chosen, ref_w = R.route(params, u, dict(CFG, n_group=n_group, topk_group=topk_group,
                                                num_experts_per_tok=top_k))
    assert np.array_equal(np.asarray(ref_chosen), np.asarray(chosen)) and gap(w, ref_w) < 1e-6
    with pytest.raises(ValueError, match="do not hold a token's 8"):
        ht.nn.MoE(e, 32, e, top_k, 0, 2.5, None, 16, jnp.float32, 16, 1)


@pytest.mark.parametrize("first", [0, 8, 16, 24])
def test_expert_share_is_its_part_of_the_layer(first):
    """One Ling expert layer scaled down, 32 experts in 8 groups of which 4 stay, top-8:
    ``experts_held=(first, 8)`` (two groups, as the cell's 128 of 512) gives the
    reference's part for the same share, and the four shares with the shared expert
    counted once add up to the uncut reference layer."""
    cfg = dict(CFG, num_experts=32, num_experts_per_tok=8, n_group=8, topk_group=4)

    def layer(held):
        return ht.nn.MoE(D, cfg["moe_intermediate_size"], 32, 8, 1, 2.5, held, 16, jnp.float32,
                         8, 4)

    full = layer(None)
    p, u = full.init(jax.random.key(14)), tokens_in(jnp.float32, 15)
    uncut, _ = R.moe(p, u, cfg)
    shared = R.gated_mlp(p["shared"], u)

    def share(f):
        held = dict(p, experts={k: v[f:f + 8] for k, v in p["experts"].items()})
        y, aux = layer((f, 8)).apply(held, u)
        return y, aux, held

    y, aux, held = share(first)
    want, chosen = R.moe(held, u, cfg, (first, 8))
    assert gap(y, want) < 1e-5
    assert np.array_equal(np.asarray(aux["chosen"]), np.asarray(chosen))  # over all 32
    assert aux["load"].shape == (8,)
    mine = (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + 8)
    assert int(aux["load"].sum()) == int(mine.sum()) < T * 8  # the pairs held here, not all
    total = shared + sum(share(f)[0] - shared for f in (0, 8, 16, 24))
    assert gap(total, uncut) < 1e-5
    assert gap(full.apply(p, u)[0], uncut) < 1e-5


# ------------------------------------------------------------------ the model
def model_of(dtype, **kw):
    model = ht.nn.Ling(CFG, continuation=CONT, dtype=dtype, block_rows=16, **kw)
    model.params = model.init(jax.random.key(10))
    return model


def test_model_scores_and_routes_match_reference():
    model = model_of(jnp.float32)
    tokens = jax.random.randint(jax.random.key(11), (T,), 0, CFG["vocab_size"], jnp.int32)
    out = model(tokens)
    ref = R.forward(model.params, tokens, CFG, CONT)
    assert out.logits.shape == (CONT, CFG["vocab_size"])
    assert gap(out.logits, ref["logits"]) < 1e-5
    (loglik,) = model.readback(out)
    assert abs(loglik - float(ref["loglik"])) < 1e-4 * abs(loglik)
    assert out.chosen.shape == (5, T, 4) and out.load.shape == (5, 16)
    for got, want in zip(out.chosen, ref["routes"]):
        assert np.array_equal(np.sort(np.asarray(got), 1), np.sort(np.asarray(want), 1))
        assert max(len({int(e) // 4 for e in row}) for row in np.asarray(got)) <= 2
    assert [int(load.sum()) for load in out.load] == [T * 4] * 5  # no token dropped
    kinds = [type(layer.attn).__name__ for layer in model.layers]
    assert kinds == ["KimiDeltaAttention"] * 5 + ["MultiheadLatentAttention"]
    assert [type(layer.ffn).__name__ for layer in model.layers] == ["GatedMLP"] + ["MoE"] * 5
    # the decay is in the model: the same weights with nothing forgotten give other logits
    forgetful = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf - 100.0 if path[-1].key == "dt_bias" else leaf, model.params)
    model.params = forgetful
    assert gap(model(tokens).logits, ref["logits"]) > 1e-2


def test_model_with_a_share_of_the_experts_and_in_bfloat16():
    """The cell's cut, scaled down: 8 of 16 experts held (two groups of four), the
    reference given the same share; then the deployment's type against its control."""
    tokens = jax.random.randint(jax.random.key(12), (T,), 0, CFG["vocab_size"], jnp.int32)
    model = model_of(jnp.float32, experts_held=(8, 8))
    out = model(tokens)
    ref = R.forward(model.params, tokens, CFG, CONT, experts_held=(8, 8))
    assert gap(out.logits, ref["logits"]) < 1e-5 and out.load.shape == (5, 8)
    assert all(0 < int(load.sum()) < T * 4 for load in out.load)
    whole = R.forward(model_of(jnp.float32).params, tokens, CFG, CONT)
    assert gap(ref["logits"], whole["logits"]) > 1e-2  # the absent experts are left out
    half = model_of(jnp.bfloat16, experts_held=(8, 8))
    want = R.forward(half.params, tokens, CFG, CONT, experts_held=(8, 8))
    routes = np.concatenate([np.sort(np.asarray(r), 1) for r in want["routes"]])

    def mismatch(chosen):
        got = np.concatenate([np.sort(np.asarray(r), 1) for r in chosen])
        return float((got != routes).any(axis=1).mean())

    control = R.forward(half.params, tokens, CFG, CONT, "float8", (8, 8))
    # the program reads 0.07 of the routes off; the float8 control 0.35
    assert mismatch(half(tokens).chosen) < 0.2 < mismatch(control["routes"])


@pytest.mark.parametrize("key,value", [
    ("use_nGPT", True), ("scale_router_input", True), ("value_norm", True),
    ("up_proj_norm", True), ("use_mla_nope", True), ("mtp_use_kda", True),
    ("use_kda_lora", True), ("no_kda_lora", False), ("kda_safe_gate", False),
    ("linear_silu", False), ("num_kv_heads_for_linear_attn", 4), ("q_lora_rank", 24),
    ("score_function", "softmax"), ("moe_router_enable_expert_bias", False),
    ("norm_topk_prob", False), ("gated_attention_proj_granularity_type", "elementwise"),
    ("group_norm_size", 4), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("tie_word_embeddings", True), ("num_key_value_heads", 2), ("rotary_dim", 16),
    ("expert_swiglu_limit_list", [0, 0, 4, 0, 0, 0]),
    ("share_expert_swiglu_limit_list", [0, 0, 0, 0, 0, 7]), ("num_hidden_layers", 5),
    ("first_k_dense_replace", 6), ("moe_shared_expert_intermediate_size", 48)])
def test_config_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match="Ling"):
        ht.nn.LingConfig.from_dict(dict(CFG, **{key: value}))
    config = ht.nn.LingConfig.from_dict(CFG)
    assert [config.is_latent(i) for i in range(6)] == [False] * 5 + [True]
    # a clamp on a layer beyond the cut, as the published lists have it, is no refusal
    deep = dict(CFG, expert_swiglu_limit_list=[0] * 6 + [4], share_expert_swiglu_limit_list=[0] * 7)
    assert ht.nn.LingConfig.from_dict(deep) == config


def test_one_trace_for_repeated_calls():
    model = model_of(jnp.float32)
    a = jax.random.randint(jax.random.key(17), (T,), 0, CFG["vocab_size"], jnp.int32)
    b = jax.random.randint(jax.random.key(18), (T,), 0, CFG["vocab_size"], jnp.int32)
    diagnostics.enable()
    diagnostics.reset()
    try:
        model.readback(model(a))
        model.readback(model(b))
        counters = diagnostics.report()["counters"]
        assert counters["nn.ling.traces"] == 1
        assert "nn.xing4.traces" not in counters and "nn.trinity.traces" not in counters
        assert counters["span_n.nn.forward"] == 2
        assert counters["nn.moe.tokens"] == 2 * 5 * T * 4
        assert counters["nn.moe.load_max"] >= counters["nn.moe.tokens"] / 16
        # the CPU takes the plain paths and says so: five KDA layers, one latent layer
        assert counters["fallback.nn.kda"] == 5 and counters["fallback.nn.mla"] == 1
        assert "kernels.kda.fwd" not in counters
        events = diagnostics.report()["fallback_events"]
        assert any(e["site"] == "nn.kda" and "backend cpu" in e["reason"] for e in events)
        diagnostics.reset()
        mixed("kernel", *mix_inputs(64, 2, 16, "seeded"), 2)
        assert diagnostics.report()["counters"]["kernels.kda.fwd"] == 1  # a trace of the kernel
    finally:
        diagnostics.disable()
        diagnostics.reset()
    with pytest.raises(ValueError, match="Ling scores one document"):
        model(a[None])


@pytest.mark.parametrize("model", ["xing4", "trinity"])
def test_the_other_models_are_unchanged_by_what_ling_shares_with_them(model):
    """``MultiheadLatentAttention`` gained the direct query and the head gate, ``MoE.route``
    the group limit: the lowered text of the ``Xing4`` and the ``Trinity`` program at their
    tests' sizes was, byte for byte, what the commit before those changes (PR 32) lowered. PR
    34 replaced both digests on purpose: it changed ``nn/moe.py``'s index work (``route``'s
    weight read and ``_layout``, by counting and comparison in place of a sort and element
    gathers and scatters, the same values) and nothing else. A PR that changes either on
    purpose replaces the digest here."""
    if model == "xing4":
        from test_xing4 import CFG as cfg, CONT as cont, T as t

        program = ht.nn.Xing4(cfg, continuation=cont, dtype=jnp.bfloat16, block_rows=16)
        want = "4ece729834c56bd4d5e658e702844eb50cc61911ce3adb1b798b9e7ff90a57c1"
    else:
        from test_trinity import CFG as cfg, CONT as cont, T as t

        program = ht.nn.Trinity(cfg, continuation=cont, dtype=jnp.bfloat16, block_rows=16)
        want = "090af39f97f818bf963364307578f24498b0728bf31c3a9a6ddd39aeb6a7f201"
    params = jax.eval_shape(program.init, jax.random.key(0))
    text = program._program.lower(params, jax.ShapeDtypeStruct((t,), jnp.int32)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == want
