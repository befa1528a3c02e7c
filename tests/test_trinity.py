"""``ht.nn``'s grouped-query attention (window and full), the flash kernel's band schedule
and grouped key/value heads, and the Trinity scoring forward against the plain reference
(``reference_trinity.py``) at a tiny size on the CPU: hidden 64, 4 / 2 heads of 16, window
8, the published pattern of 1 dense + 4 expert layers (three window layers a full one), 8
experts top-2, 64 tokens.

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
from heat_tpu.core.kernels import flash_attention as fa

import reference_trinity as R

KINDS = ["sliding_attention", "sliding_attention", "sliding_attention", "sliding_attention",
         "full_attention"]
CFG = {
    "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 32,
    "num_hidden_layers": 5, "num_dense_layers": 1, "layer_types": KINDS,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "num_experts": 8, "num_shared_experts": 1, "num_experts_per_tok": 2,
    "route_scale": 2.826, "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "mup_enabled": True, "score_func": "sigmoid", "route_norm": True, "rope_scaling": None,
    "n_group": 1, "topk_group": 1, "tie_word_embeddings": False,
}
T, D, CONT = 64, 64, 16
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# bfloat16 tolerances, (limit, the program's reading, the float8 control's reading) as
# measured on the CPU with the seeds below; the limit lies between the two readings
BF16 = {
    "window": (2e-2, 4.7e-3, 8.0e-2),
    "full": (2e-2, 4.4e-3, 8.1e-2),
    "layer": (2.5e-2, 5.9e-3, 1.1e-1),
    "model": (6e-2, 1.5e-2, 4.1e-1),
}


def gap(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def tokens_in(dtype, seed=1, t=T):
    return jax.random.normal(jax.random.key(seed), (t, D), jnp.float32).astype(dtype)


def attention_of(kind: str, dtype, window=None):
    cfg = ht.nn.TrinityConfig.from_dict(dict(CFG, sliding_window=window or CFG["sliding_window"]))
    return ht.nn.TrinityBlock(cfg, kind, True, dtype=dtype).attn


def attention_case(kind):
    def case(dtype):
        m = attention_of(kind, dtype)
        p, u = m.init(jax.random.key(3)), tokens_in(dtype)
        return m.apply(p, u), lambda precision: R.attention(p, u, CFG, kind, precision)
    return case


def layer_case(dtype):
    """An expert layer with a window: four norms, the gate, the routed and shared experts."""
    blk = ht.nn.TrinityBlock(ht.nn.TrinityConfig.from_dict(CFG), KINDS[1], False, dtype=dtype,
                             block_rows=16)
    p, x = blk.init(jax.random.key(7)), tokens_in(dtype, 2)
    return blk.apply(p, x)[0], lambda precision: R.layer(
        p, x.astype(jnp.float32), CFG, KINDS[1], None, precision)[0]


def model_case(dtype):
    model = ht.nn.Trinity(CFG, continuation=CONT, dtype=dtype, block_rows=16)
    model.params = model.init(jax.random.key(8))
    tokens = jax.random.randint(jax.random.key(9), (T,), 0, CFG["vocab_size"], jnp.int32)
    return model(tokens).logits, lambda precision: R.forward(
        model.params, tokens, CFG, CONT, precision)["logits"]


CASES = {"window": attention_case("sliding_attention"), "full": attention_case("full_attention"),
         "layer": layer_case, "model": model_case}


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


@pytest.mark.parametrize("window", [1, 8, 63, 64, 1000])
def test_window_layer_at_every_width_of_the_band(window):
    """Window 1 (a row sees itself alone: the output is v through the gate), the window
    at, one under and far over the document's length (the band is the causal triangle)."""
    m = attention_of("sliding_attention", jnp.float32, window)
    p, u = m.init(jax.random.key(20)), tokens_in(jnp.float32, 21)
    cfg = dict(CFG, sliding_window=window)
    got = m.apply(p, u)
    assert gap(got, R.attention(p, u, cfg, "sliding_attention")) < 1e-5
    if window >= T:  # nothing of the band is cut: the same layer without a window
        causal = ht.nn.GroupedQueryAttention(D, 4, 2, 16, None, CFG["rope_theta"], 1e-5)
        assert gap(got, causal.apply(p, u)) < 1e-6
    else:
        wider = R.attention(p, u, dict(CFG, sliding_window=window + 1), "sliding_attention")
        assert gap(got, wider) > 1e-3


def test_a_full_layer_has_no_positions():
    """The last row sees every token: on a full layer the order of the earlier ones does
    not reach it (no rotary positions), on a window layer it does."""
    u = tokens_in(jnp.float32, 22)
    reordered = jnp.concatenate([u[:-1][::-1], u[-1:]])
    for kind, order_matters in (("full_attention", False), ("sliding_attention", True)):
        m = attention_of(kind, jnp.float32, window=T)  # nothing of the band is cut
        p = m.init(jax.random.key(23))
        moved = gap(m.apply(p, reordered)[-1], m.apply(p, u)[-1])
        assert moved > 1e-3 if order_matters else moved < 1e-5


def _brute_schedule(nq, nk, bq, bk, window):
    """Per query block: the key blocks with any (row, col) in the band, and for each
    whether any of its (row, col) lies above the diagonal / at or below the band's edge."""
    rows = np.arange(nq * bq)[:, None]
    cols = np.arange(nk * bk)[None, :]
    seen = cols <= rows
    if window is not None:
        seen &= rows - cols < window
    out = []
    for i in range(nq):
        for j in range(nk):
            blk = (slice(i * bq, (i + 1) * bq), slice(j * bk, (j + 1) * bk))
            if seen[blk].any():
                above = np.broadcast_to(cols > rows, seen.shape)[blk].any()
                below = window is not None and \
                    np.broadcast_to(rows - cols >= window, seen.shape)[blk].any()
                out.append((i, j, bool(above), bool(below)))
    return out


@pytest.mark.parametrize("t,bq,bk,window", [
    (64, 8, 8, 8), (64, 8, 8, 16), (64, 8, 8, 1), (64, 8, 8, 13), (64, 16, 8, 8),
    (64, 8, 16, 8), (64, 16, 8, 20), (64, 8, 16, 21), (64, 16, 16, 32), (64, 16, 16, 33),
    (64, 32, 8, 5), (64, 8, 32, 40), (64, 16, 16, 64), (64, 16, 16, 1000), (96, 32, 16, 48),
    (64, 16, 16, None), (96, 32, 16, None)], ids=str)
def test_band_schedule_against_brute_force(t, bq, bk, window):
    """The visit list is exactly the blocks that meet the band, row sweep by row sweep,
    and the flag bits say which edge a visited block straddles."""
    im, jm, flags = fa._pair_schedule(t // bq, t // bk, bq, bk, True, window)
    want = _brute_schedule(t // bq, t // bk, bq, bk, window)
    assert list(zip(im.tolist(), jm.tolist())) == [(i, j) for i, j, _, _ in want]
    assert [bool(f & 4) for f in flags] == [above for _, _, above, _ in want]
    assert [bool(f & 8) for f in flags] == [below for _, _, _, below in want]
    for i in range(t // bq):  # each row sweep starts once and ends once
        sweep = flags[im == i]
        assert sweep[0] & 1 and sweep[-1] & 2 and (sweep[1:] & 1).sum() == 0 \
            and (sweep[:-1] & 2).sum() == 0
    if window is not None and window <= t // 2:
        assert len(im) < len(fa._pair_schedule(t // bq, t // bk, bq, bk, True)[0])


def _masked_dense(q, k, v, scale, window):
    rep = q.shape[-3] // k.shape[-3]
    k, v = jnp.repeat(k, rep, axis=-3), jnp.repeat(v, rep, axis=-3)
    s = jnp.einsum("...qd,...kd->...qk", q, k, precision="highest") * scale
    gap_ = jnp.arange(q.shape[-2])[:, None] - jnp.arange(k.shape[-2])[None, :]
    keep = gap_ >= 0 if window is None else (gap_ >= 0) & (gap_ < window)
    return jnp.einsum("...qk,...kd->...qd", jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), v,
                      precision="highest")


@pytest.mark.parametrize("hq,hkv,t,bq,bk,sub,window", [
    (4, 2, 512, 128, 128, (64, 64), 100), (4, 1, 512, 256, 128, (64, 128), 300),
    (2, 2, 512, 128, 256, None, 1), (8, 2, 256, 64, 64, (32, 32), 1000),
    (4, 2, 512, 128, 128, (64, 64), 128), (8, 1, 512, 128, 128, (64, 64), None)], ids=str)
def test_interpreted_kernel_with_window_and_grouped_heads(hq, hkv, t, bq, bk, sub, window):
    rng = np.random.default_rng(hq * t + (window or 0))
    q = jnp.asarray(rng.standard_normal((2, hq, t, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, hkv, t, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, hkv, t, 32)), jnp.float32)
    got = fa.flash_forward(q, k, v, True, 0.2, (bq, bk), interpret=True, window=window) \
        if sub is None else fa._flash_pallas(q, k, v, True, 0.2, bq, bk, interpret=True,
                                             sub=sub, window=window)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(_masked_dense(q, k, v, 0.2, window)),
                               rtol=2e-5, atol=2e-5)
    unseen = t - bq - (window or t) + 1  # keys that no row of the last query block sees
    if unseen > 0:
        poisoned = k.at[:, :, :unseen].set(jnp.nan)
        again = fa._flash_pallas(q, poisoned, v, True, 0.2, bq, bk, interpret=True,
                                 sub=sub, window=window)[0]
        np.testing.assert_array_equal(np.asarray(again[:, :, t - bq:]),
                                      np.asarray(got[:, :, t - bq:]))


@pytest.mark.parametrize("what", ["window", "grouped", "window_not_causal", "heads_not_grouped"])
def test_what_the_kernels_refuse_they_refuse_in_words(what):
    q = jnp.ones((1, 4, 512, 32), jnp.float32)
    kv = jnp.ones((1, 2, 512, 32), jnp.float32)
    if what == "window":  # the backward would give the unwindowed gradient
        with pytest.raises(NotImplementedError, match="do not take a window"):
            fa._bwd(True, None, 64, (q, q, q, q, q[..., 0], None), q)
    elif what == "grouped":
        with pytest.raises(NotImplementedError, match="as many key/value heads as query heads"):
            fa._bwd(True, None, None, (q, kv, kv, q, q[..., 0], None), q)
    elif what == "window_not_causal":
        with pytest.raises(ValueError, match="causal band"):
            fa._pair_schedule(4, 4, 128, 128, False, 64)
    else:
        with pytest.raises(ValueError, match="do not group"):
            fa._flash_pallas(q, jnp.ones((1, 3, 512, 32)), jnp.ones((1, 3, 512, 32)), True, 0.2,
                             128, 128, interpret=True)


def test_model_scores_and_routes_match_reference():
    model = ht.nn.Trinity(CFG, continuation=CONT, dtype=jnp.float32, block_rows=16)
    model.params = model.init(jax.random.key(10))
    tokens = jax.random.randint(jax.random.key(11), (T,), 0, CFG["vocab_size"], jnp.int32)
    out = model(tokens)
    ref = R.forward(model.params, tokens, CFG, CONT)
    assert out.logits.shape == (CONT, CFG["vocab_size"])
    assert gap(out.logits, ref["logits"]) < 1e-5
    (loglik,) = model.readback(out)
    assert abs(loglik - float(ref["loglik"])) < 1e-4 * abs(loglik)
    assert out.chosen.shape == (4, T, 2) and out.load.shape == (4, 8)
    for got, want in zip(out.chosen, ref["routes"]):
        assert np.array_equal(np.sort(np.asarray(got), 1), np.sort(np.asarray(want), 1))
    assert [int(load.sum()) for load in out.load] == [T * 2] * 4  # no token dropped
    # the window is in the model: the same weights without it give other logits
    no_window = ht.nn.Trinity(dict(CFG, sliding_window=T), continuation=CONT, dtype=jnp.float32,
                              block_rows=16)
    no_window.params = model.params
    assert gap(no_window(tokens).logits, ref["logits"]) > 1e-2


@pytest.mark.parametrize("first", [0, 32, 64, 96])
def test_expert_share_is_its_part_of_the_layer(first):
    """One Trinity expert layer at 128 experts, top-8: ``experts_held=(first, 32)`` gives
    the reference's part for the same share, and the four shares with the shared expert
    counted once add up to the uncut reference layer."""
    cfg = dict(CFG, num_experts=128, num_experts_per_tok=8)
    full = ht.nn.MoE(D, cfg["moe_intermediate_size"], 128, 8, 1, cfg["route_scale"], None, 16)
    p, u = full.init(jax.random.key(14)), tokens_in(jnp.float32, 15)
    uncut, _ = R.moe(p, u, cfg)
    shared = R.gated_mlp(p["shared"], u)

    def share(f):
        m = ht.nn.MoE(D, cfg["moe_intermediate_size"], 128, 8, 1, cfg["route_scale"], (f, 32), 16)
        held = dict(p, experts={k: v[f:f + 32] for k, v in p["experts"].items()})
        y, aux = m.apply(held, u)
        return y, aux, held

    y, aux, held = share(first)
    want, chosen = R.moe(held, u, cfg, (first, 32))
    assert gap(y, want) < 1e-5
    assert np.array_equal(np.asarray(aux["chosen"]), np.asarray(chosen))  # over all 128
    assert aux["load"].shape == (32,)
    total = shared + sum(share(f)[0] - shared for f in (0, 32, 64, 96))
    assert gap(total, uncut) < 1e-5
    assert gap(full.apply(p, u)[0], uncut) < 1e-5


@pytest.mark.parametrize("key,value", [
    ("score_func", "softmax"), ("n_group", 2), ("topk_group", 2), ("route_norm", False),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("layer_types", KINDS[:4]),
    ("layer_types", KINDS[:4] + ["chunked_attention"]), ("num_dense_layers", 5)])
def test_config_refuses_what_it_does_not_compute(key, value):
    with pytest.raises(ValueError, match="Trinity"):
        ht.nn.TrinityConfig.from_dict(dict(CFG, **{key: value}))
    assert ht.nn.TrinityConfig.from_dict(CFG).layer_types == tuple(KINDS)


def test_one_trace_for_repeated_calls():
    model = ht.nn.Trinity(CFG, continuation=CONT, dtype=jnp.float32, block_rows=16)
    model.params = model.init(jax.random.key(16))
    a = jax.random.randint(jax.random.key(17), (T,), 0, CFG["vocab_size"], jnp.int32)
    b = jax.random.randint(jax.random.key(18), (T,), 0, CFG["vocab_size"], jnp.int32)
    diagnostics.enable()
    diagnostics.reset()
    try:
        model.readback(model(a))
        model.readback(model(b))
        counters = diagnostics.report()["counters"]
        assert counters["nn.trinity.traces"] == 1 and "nn.xing4.traces" not in counters
        assert counters["span_n.nn.forward"] == 2
        assert counters["nn.moe.tokens"] == 2 * 4 * T * 2
        assert counters["nn.moe.load_max"] >= counters["nn.moe.tokens"] / 8
        assert counters["fallback.nn.gqa"] >= 1  # the CPU takes the XLA path and says so
        diagnostics.reset()
        q = jnp.ones((4, 512, 32), jnp.float32)
        fa._flash_pallas(q, q[:2], q[:2], True, 0.2, 128, 128, interpret=True, window=128)
        counters = diagnostics.report()["counters"]
        assert counters["kernels.flash.fwd.pairs_visited"] == 7  # 1 + 2 + 2 + 2 of 16
        assert counters["kernels.flash.fwd.pairs_dense"] == 16
    finally:
        diagnostics.disable()
        diagnostics.reset()
    with pytest.raises(ValueError, match="Trinity scores one document"):
        model(a[None])


@pytest.mark.parametrize("what", ["program", "kernel"])
def test_xing4_is_unchanged_by_what_trinity_shares_with_it(what):
    """``nn/scoring.py`` took ``Xing4``'s scoring tail (the head over the continuation's
    positions, ``readback``, the trace counter) so that ``Trinity`` shares it, and the flash
    forward's one body gained the window and the grouped heads. ``program``: the lowered
    text of the ``Xing4`` program at ``test_xing4.py``'s size was, byte for byte, what the
    commit before those changes (PR 30) lowered; PR 32 replaced the digest on purpose
    (``nn/moe.py``: padding rows read token 0 and no longer a zero fill, a pair held elsewhere
    is selected to 0, the weighted sum takes its pairs rank-major), and PR 34 again (``nn/moe.py``'s
    index work, ``route``'s weight read and ``_layout``, by counting and comparison in place of
    a sort and element gathers and scatters: the same values, and nothing else changed).
    ``kernel``: the jaxpr of the causal
    forward at ``xing4-score-32k``'s shape, the Pallas body included, is still PR 30's. A PR
    that changes either on purpose replaces the digest here."""
    if what == "program":
        from test_xing4 import CFG as XING4, CONT as X_CONT, T as X_T

        model = ht.nn.Xing4(XING4, continuation=X_CONT, dtype=jnp.bfloat16, block_rows=16)
        params = jax.eval_shape(model.init, jax.random.key(0))
        text = model._program.lower(params, jax.ShapeDtypeStruct((X_T,), jnp.int32)).as_text()
        want = "4ece729834c56bd4d5e658e702844eb50cc61911ce3adb1b798b9e7ff90a57c1"
        assert ht.nn.Xing4.traces == "nn.xing4.traces"
        assert ht.nn.Xing4.logliks == ("loglik", "mtp_loglik")
    else:
        q = jax.ShapeDtypeStruct((32, 32768, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((32, 32768, 128), jnp.bfloat16)
        text = str(jax.make_jaxpr(lambda q, k, v: fa.flash_forward(
            q, k, v, True, 0.07, (1024, 1024), name="mla_flash_fwd"))(q, q, v))
        want = "21340080dd9d0121f4545ce9cd14f293881aaffa6fb6f64ba5764322dd5a09d0"
    assert hashlib.sha256(text.encode()).hexdigest() == want
