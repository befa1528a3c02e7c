"""``ht.nn``'s latent attention, token-routed experts, hyper-connections and the Xing4
scoring forward against the plain reference (``reference_xing4.py``) at a tiny size on
the CPU: hidden 64, 8 experts, 2 dense + 2 expert layers, 256 tokens.

Every sub-block is compared twice. In float32 the program must agree with the reference
to 1e-5 (rms of the difference over the reference's rms). In bfloat16 (the deployment's
type: bfloat16 weights and activations, float32 accumulation) the tolerance is set
between what the program reads and what the reference itself reads when its contractions
are rounded to float8, the next precision down: the program passes it, that control fails
it.
"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import diagnostics
from heat_tpu.nn.hyper_connections import HyperConnection, sinkhorn

import reference_xing4 as R

CFG = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "first_k_dense_replace": 2, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.0, "vocab_size": 512,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 32,
                     "type": "yarn"},
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
}
T, D, N, CONT = 256, 64, 4, 16
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# bfloat16 tolerances, (limit, the program's reading, the float8 control's reading) as
# measured on the CPU with the seeds below; the limit lies between the two readings
BF16 = {
    "mla": (2e-2, 4.7e-3, 9.8e-2),
    "dense": (1.5e-2, 2.4e-3, 6.8e-2),
    "experts": (1.5e-2, 2.9e-3, 6.5e-2),
    "streams": (1.2e-2, 3.1e-3, 4.2e-2),
    "layer": (3e-2, 6.5e-3, 2.2e-1),
    "model": (4e-2, 8.5e-3, 2.4e-1),
}


def gap(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def tokens_in(dtype, seed=1):
    return jax.random.normal(jax.random.key(seed), (T, D), jnp.float32).astype(dtype)


def streams_in(dtype, seed=2):
    return jax.random.normal(jax.random.key(seed), (N, T, D), jnp.float32).astype(dtype)


def block_of(dense: bool, dtype):
    return ht.nn.Xing4Block(ht.nn.Xing4Config.from_dict(CFG), dense, dtype=dtype,
                            block_rows=16)


def mla_case(dtype):
    m = block_of(True, dtype).attn
    p, u = m.init(jax.random.key(3)), tokens_in(dtype)
    return m.apply(p, u), lambda precision: R.mla(p, u, CFG, precision)


def dense_case(dtype):
    m = ht.nn.GatedMLP(D, CFG["intermediate_size"], dtype)
    p, u = m.init(jax.random.key(4)), tokens_in(dtype)
    return m.apply(p, u), lambda precision: R.gated_mlp(p, u, precision)


def experts_case(dtype):
    m = block_of(False, dtype).ffn
    p, u = m.init(jax.random.key(5)), tokens_in(dtype)
    return m.apply(p, u)[0], lambda precision: R.moe(p, u, CFG, None, precision)[0]


def streams_case(dtype):
    """One hyper-connected sub-block round a dense feed-forward."""
    blk = block_of(True, dtype)
    p, x = blk.init(jax.random.key(6)), streams_in(dtype)

    def program(u):
        return blk.ffn.apply(p["ffn"], blk.ffn_norm.apply(p["ffn_norm"], u)), None

    def reference(precision):
        out, _ = R.sub_block(p["ffn_hc"], p["ffn_norm"],
                             lambda u: (R.gated_mlp(p["ffn"], u, precision), None),
                             jnp.moveaxis(x, 0, 1).astype(jnp.float32), CFG, token_block=64)
        return jnp.moveaxis(out, 1, 0)

    return blk.ffn_hc.apply(p["ffn_hc"], (x, program))[0], reference


def layer_case(dtype):
    blk = block_of(False, dtype)
    p, x = blk.init(jax.random.key(7)), streams_in(dtype)

    def reference(precision):
        out, _ = R.layer(p, jnp.moveaxis(x, 0, 1).astype(jnp.float32), CFG, None, precision)
        return jnp.moveaxis(out, 1, 0)

    return blk.apply(p, x)[0], reference


def model_case(dtype):
    """The whole scoring forward: the main head's and the MTP head's logits together."""
    model = ht.nn.Xing4(CFG, continuation=CONT, dtype=dtype, block_rows=16)
    model.params = model.init(jax.random.key(8))
    tokens = jax.random.randint(jax.random.key(9), (T,), 0, CFG["vocab_size"], jnp.int32)
    out = model(tokens)

    def reference(precision):
        ref = R.forward(model.params, tokens, CFG, CONT, precision)
        return jnp.stack([ref["logits"], ref["mtp_logits"]])

    return jnp.stack([out.logits, out.mtp_logits]), reference


CASES = {"mla": mla_case, "dense": dense_case, "experts": experts_case,
         "streams": streams_case, "layer": layer_case, "model": model_case}


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("sub_block", list(CASES))
def test_sub_block_against_reference(sub_block, dtype):
    got, reference = CASES[sub_block](DTYPES[dtype])
    want = reference("float32")
    assert got.dtype == (jnp.float32 if sub_block == "model" else DTYPES[dtype])
    if dtype == "float32":
        assert gap(got, want) < 1e-5
        return
    limit = BF16[sub_block][0]
    assert gap(got, want) < limit, "the program in bfloat16"
    assert gap(reference("float8"), want) > limit, "the float8 control must fail"


def test_model_scores_and_routes_match_reference():
    model = ht.nn.Xing4(CFG, continuation=CONT, dtype=jnp.float32, block_rows=16)
    model.params = model.init(jax.random.key(10))
    tokens = jax.random.randint(jax.random.key(11), (T,), 0, CFG["vocab_size"], jnp.int32)
    out = model(tokens)
    ref = R.forward(model.params, tokens, CFG, CONT)
    assert gap(out.logits, ref["logits"]) < 1e-5
    assert gap(out.mtp_logits, ref["mtp_logits"]) < 1e-5
    loglik, mtp_loglik = model.readback(out)
    assert abs(loglik - float(ref["loglik"])) < 1e-4 * abs(loglik)
    assert abs(mtp_loglik - float(ref["mtp_loglik"])) < 1e-4 * abs(mtp_loglik)
    assert out.chosen.shape == (3, T, 2) and out.load.shape == (3, 8)
    for got, want in zip(out.chosen, ref["routes"]):
        rows = want.shape[0]  # the MTP module routes T-1 positions; the program pads one
        assert np.array_equal(np.sort(np.asarray(got)[:rows], 1), np.sort(np.asarray(want), 1))
    assert int(out.load[0].sum()) == T * 2  # no token dropped


@pytest.mark.parametrize("what", ["doubly_stochastic", "ranges", "identity_limit"])
def test_hyper_connection_mappings(what):
    hc = HyperConnection(D, N)
    p, x = hc.init(jax.random.key(12)), streams_in(jnp.float32, 13)
    pre, post, res = hc.mappings(p, x)
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    if what == "doubly_stochastic":
        assert res.shape == (T, N, N) and float(res.min()) >= 0.0
        np.testing.assert_allclose(np.asarray(res.sum(-1)), 1.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(res.sum(-2)), 1.0, atol=2e-2)  # 20 steps
    elif what == "ranges":
        assert 0.0 < float(pre.min()) and float(pre.max()) < 1.0
        assert 0.0 < float(post.min()) and float(post.max()) < 2.0
    else:
        # a clamped, strongly diagonal input converges to the identity and stays finite
        big = sinkhorn(jnp.clip(100.0 * jnp.eye(N), -30.0, 30.0), 20, 1e-6)
        np.testing.assert_allclose(np.asarray(big), np.eye(N), atol=1e-6)


@pytest.mark.parametrize("first", [0, 2, 4, 6])
def test_expert_share_is_its_part_of_the_layer(first):
    """``experts_held=(first, 2)``: the share's routed part equals the reference's for the
    same share, and the four shares with the shared expert counted once add up to the
    uncut layer."""
    full = block_of(False, jnp.float32).ffn
    p, u = full.init(jax.random.key(14)), tokens_in(jnp.float32, 15)
    uncut, _ = R.moe(p, u, CFG)
    shared = R.gated_mlp(p["shared"], u)

    def share(f):
        m = ht.nn.MoE(D, CFG["moe_intermediate_size"], 8, 2, 1, 2.0, (f, 2), 16)
        held = dict(p, experts={k: v[f:f + 2] for k, v in p["experts"].items()})
        y, aux = m.apply(held, u)
        return y, aux, held

    y, aux, held = share(first)
    want, chosen = R.moe(held, u, CFG, (first, 2))
    assert gap(y, want) < 1e-5
    assert np.array_equal(np.asarray(aux["chosen"]), np.asarray(chosen))  # over all 8
    assert aux["load"].shape == (2,)
    total = shared + sum(share(f)[0] - shared for f in (0, 2, 4, 6))
    assert gap(total, uncut) < 1e-5


def test_one_trace_for_repeated_calls():
    model = ht.nn.Xing4(CFG, continuation=CONT, dtype=jnp.float32, block_rows=16)
    model.params = model.init(jax.random.key(16))
    a = jax.random.randint(jax.random.key(17), (T,), 0, CFG["vocab_size"], jnp.int32)
    b = jax.random.randint(jax.random.key(18), (T,), 0, CFG["vocab_size"], jnp.int32)
    diagnostics.enable()
    diagnostics.reset()
    try:
        first = model(a)
        model.readback(first)
        model.readback(model(b))
        counters = diagnostics.report()["counters"]
        assert counters["nn.xing4.traces"] == 1
        assert counters["span_n.nn.forward"] == 2
        assert counters["nn.moe.tokens"] == 2 * 3 * T * 2
        assert counters["nn.moe.load_max"] >= counters["nn.moe.tokens"] / 8
        assert counters["fallback.nn.mla"] >= 1  # the CPU takes the XLA path and says so
    finally:
        diagnostics.disable()
        diagnostics.reset()
    with pytest.raises(ValueError):
        model(a[None])


def test_config_refuses_what_it_does_not_compute():
    with pytest.raises(ValueError):
        ht.nn.Xing4Config.from_dict(dict(CFG, scoring_func="softmax"))
