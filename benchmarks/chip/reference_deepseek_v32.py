"""Plain reference of the DeepSeek-V3.2-Exp scoring forward: float32 ``jax.numpy`` under
``Precision.HIGHEST``, no kernel, no cache, nothing of ``heat_tpu`` imported.

It follows the layer equations of ``doc/source/deepseek_v32.rst``: pre-norm blocks of latent
attention (a query latent, YaRN frequencies, interleaved rotary pairs) in which a lightning
indexer (64 heads that share one LayerNorm-ed key a token, rotary positions on the first 64
of 128 dimensions in the two-halves layout, a ReLU, a weighted sum over the heads) scores
every earlier token for a query, ``lax.top_k`` keeps the best ``index_topk`` and every head
attends under the mask scattered from those indices; a gated feed-forward, dense in the
leading layers and token-routed experts after, with a sigmoid router, a selection bias and a
limit on the groups a token may choose from. As far as memory asks for it the work goes
through in blocks: heads and queries of the attention and of the indexer, tokens of the
dense feed-forward, sorted expert rows, the vocabulary; a layer's selection is one boolean
(T, T). ``cfg`` is the model's configuration dictionary (the published keys;
``n_routed_experts`` is the router's width), ``params`` the model's parameter pytree, read by
name and never written, ``experts_held = (first, count)`` the share of every expert layer
whose weights ``params`` holds. ``precision`` is ``"float32"`` for the reference itself;
``"bfloat16"`` and ``"float8"`` round the operands of every contraction that the deployment
states in bfloat16, the indexer's among them (router, norms, softmax, the indexer's ReLU and
weighted sum stay float32, as it states them) and give the control: the same mathematics one
precision down.

``benchmarks/chip/reference_deepseek_v32.py`` is a byte-equal copy of
``tests/reference_deepseek_v32.py`` (``tests/test_deepseek_v32.py`` holds the two together).
"""

import json
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
I32 = jnp.int32
INDEX_NORM_EPS = 1e-6


def _q(x, precision: str):
    """``x`` as float32 after rounding to ``precision`` (float8: e4m3 with a per-tensor
    absmax scale, as an fp8 path would carry)."""
    x = x.astype(F32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    if precision == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _mm(x, w, precision: str = "float32"):
    return jnp.matmul(_q(x, precision), _q(w, precision), precision=HI)


def _block(total: int, want: int) -> int:
    """The largest block of at most ``want`` rows that divides ``total``."""
    b = min(want, total)
    while total % b:
        b -= 1
    return b


def rms_norm(x, weight, eps: float):
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight.astype(F32)


def layer_norm(x, weight, bias, eps: float):
    x = x.astype(F32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * weight.astype(F32) + bias.astype(F32)


def silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------------ rotary positions
def yarn_inv_freq(cfg) -> np.ndarray:
    """YaRN's blended inverse frequencies of the rope part (float64, then float32)."""
    dim, base, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low if high != low else 1e-3), 0.0, 1.0)
    return (extra / factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def softmax_scale(cfg) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _cos_sin(pos, cfg):
    rs = cfg["rope_scaling"]
    scale = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    angle = pos.astype(F32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))[None, :]
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def rope_pairs(x, pos, cfg):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of ``x`` (T, rope_dim) at positions ``pos``
    (T,) by ``pos * inv_freq[i]``: the main attention's layout."""
    cos, sin = _cos_sin(pos, cfg)
    x = x.astype(F32)
    even, odd = x[:, 0::2], x[:, 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def rope_halves(x, pos, cfg):
    """Rotate the pairs ``(x[i], x[i + rope_dim / 2])``: the indexer's layout."""
    cos, sin = _cos_sin(pos, cfg)
    x = x.astype(F32)
    half = x.shape[1] // 2
    a, b = x[:, :half], x[:, half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=1)


# ------------------------------------------------------------------ indexer and attention
def index_mask(p, u, c_q, cfg, precision: str = "float32", query_block: int = 1024):
    """The boolean (T, T) selection of one layer: ``I[t, s] = sum_j w[t, j] relu(q_j[t] .
    k[s])`` for ``s <= t``, and row ``t``'s ``min(index_topk, t + 1)`` largest by
    ``lax.top_k`` (ties to the lower position), a block of queries and a head at a time."""
    t = u.shape[0]
    heads, hd, dr = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    pos = jnp.arange(t, dtype=I32)
    wq = p["wq_b"].reshape(-1, heads, hd)
    k = layer_norm(_mm(u, p["wk"], precision), p["k_norm"]["weight"], p["k_norm"]["bias"],
                   INDEX_NORM_EPS)
    k = _q(jnp.concatenate([rope_halves(k[:, :dr], pos, cfg), k[:, dr:]], axis=1), precision)
    w = _mm(u, p["weights_proj"], precision) * (heads ** -0.5 * hd ** -0.5)  # (T, heads)
    qb = _block(t, query_block)
    keep = min(cfg["index_topk"], t)

    def block(i):
        rows = i * qb + jnp.arange(qb, dtype=I32)
        c_i = lax.dynamic_slice_in_dim(c_q, i * qb, qb, 0)
        w_i = lax.dynamic_slice_in_dim(w, i * qb, qb, 0)

        def head(j, scores):
            q = _mm(c_i, lax.dynamic_index_in_dim(wq, j, 1, keepdims=False), precision)
            q = jnp.concatenate([rope_halves(q[:, :dr], rows, cfg), q[:, dr:]], axis=1)
            s = jnp.matmul(_q(q, precision), k.T, precision=HI)
            return scores + lax.dynamic_index_in_dim(w_i, j, 1, keepdims=True) * jnp.maximum(s, 0.0)

        scores = lax.fori_loop(0, heads, head, jnp.zeros((qb, t), F32))
        causal = rows[:, None] >= pos[None, :]
        _, best = lax.top_k(jnp.where(causal, scores, -jnp.inf), keep)
        picked = jnp.zeros((qb, t), jnp.bool_).at[jnp.arange(qb, dtype=I32)[:, None], best].set(True)
        return picked & causal  # a row with fewer than ``keep`` earlier tokens drew past its own

    return lax.map(block, jnp.arange(t // qb, dtype=I32)).reshape(t, t)


def mla(p, u, cfg, precision: str = "float32", query_block: int = 1024):
    """Latent attention over the (T, d) input under the indexer's selection, one head and
    one block of queries at a time; no cache, no absorbed products. Returns (y, mask)."""
    t = u.shape[0]
    heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r_kv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(t, dtype=I32)
    c_q = rms_norm(_mm(u, p["wq_a"], precision), p["q_norm"]["weight"], eps)
    mask = index_mask(p["indexer"], u, c_q, cfg, precision, query_block)
    kv = _mm(u, p["wkv_a"], precision)
    c_kv = rms_norm(kv[:, :r_kv], p["kv_norm"]["weight"], eps)
    k_rope = rope_pairs(kv[:, r_kv:], pos, cfg)  # one vector for all heads
    wq_b = p["wq_b"].reshape(-1, heads, dn + dr)
    wkv_b = p["wkv_b"].reshape(-1, heads, dn + dv)
    wo = p["wo"].reshape(heads, dv, -1)
    qb = _block(t, query_block)
    scale = softmax_scale(cfg)

    def head(h, out):
        q = _mm(c_q, lax.dynamic_index_in_dim(wq_b, h, 1, keepdims=False), precision)
        q = jnp.concatenate([q[:, :dn], rope_pairs(q[:, dn:], pos, cfg)], axis=1)
        kv_h = _mm(c_kv, lax.dynamic_index_in_dim(wkv_b, h, 1, keepdims=False), precision)
        k = _q(jnp.concatenate([kv_h[:, :dn], k_rope], axis=1), precision)
        v = _q(kv_h[:, dn:], precision)

        def block(i):
            qi = _q(lax.dynamic_slice_in_dim(q, i * qb, qb, 0), precision)
            s = jnp.matmul(qi, k.T, precision=HI) * scale
            s = jnp.where(lax.dynamic_slice_in_dim(mask, i * qb, qb, 0), s, -jnp.inf)
            return jnp.matmul(_q(jax.nn.softmax(s, axis=-1), precision), v, precision=HI)

        o = lax.map(block, jnp.arange(t // qb, dtype=I32)).reshape(t, dv)
        return out + _mm(o, lax.dynamic_index_in_dim(wo, h, 0, keepdims=False), precision)

    return lax.fori_loop(0, heads, head, jnp.zeros((t, wo.shape[-1]), F32)), mask


# ------------------------------------------------------------------ feed-forward, experts
def gated_mlp(p, u, precision: str = "float32", token_block: int = 4096):
    """``W_down (silu(W_gate u) * W_up u)``, a block of tokens at a time."""
    def piece(x):
        return _mm(silu(_mm(x, p["w_gate"], precision)) * _mm(x, p["w_up"], precision),
                   p["w_down"], precision)

    tb = _block(u.shape[0], token_block)
    return lax.map(piece, u.reshape(-1, tb, u.shape[1])).reshape(u.shape[0], -1)


def route(p, u, cfg):
    """Sigmoid scores ``s`` in float32 over all experts and ``c = s + bias``; a group's
    score is the sum of its two largest ``c``; the ``topk_group`` best of the ``n_group``
    groups stay; the top k of ``c`` among their experts are chosen; weights are the chosen
    ``s`` over their own sum, times ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(_mm(u, p["router"]))
    choice = scores + p["router_bias"].astype(F32)
    t, e = choice.shape
    groups = cfg["n_group"]
    by_group = choice.reshape(t, groups, e // groups)
    group_score = jnp.sum(lax.top_k(by_group, 2)[0], axis=-1)
    _, kept = lax.top_k(group_score, cfg["topk_group"])
    stays = jnp.any(kept[:, :, None] == jnp.arange(groups, dtype=I32)[None, None, :], axis=1)
    choice = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(t, e)
    _, chosen = lax.top_k(choice, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / jnp.sum(w, axis=1, keepdims=True) * cfg["routed_scaling_factor"]
    return chosen.astype(I32), w


def moe(p, u, cfg, experts_held=None, precision: str = "float32", with_shared: bool = True,
        row_block: int = 1024):
    """The routed experts ``experts_held = (first, count)`` hold (all by default) plus
    the shared expert on every token. ``p["experts"]`` holds the held experts' weights
    only. Returns (y, chosen). Rows sorted by expert go through in blocks; a block
    multiplies with every expert that has a row in it."""
    t, d = u.shape
    first, count = experts_held or (0, cfg["n_routed_experts"])
    chosen, w = route(p, u, cfg)
    k = chosen.shape[1]
    local = chosen.reshape(-1) - first
    held = (local >= 0) & (local < count)
    local = jnp.where(held, local, count)  # the experts of other chips sort to the end
    order = jnp.argsort(local, stable=True)
    rows_e = local[order]
    rb = _block(t * k, row_block)
    ex = p["experts"]
    w_rows = w.reshape(-1)[order]

    def block(i, y):
        e_blk = lax.dynamic_slice_in_dim(rows_e, i * rb, rb, 0)
        tok = lax.dynamic_slice_in_dim(order, i * rb, rb, 0) // k
        xb = u[tok]

        def one(e, acc):
            w_e = {name: lax.dynamic_index_in_dim(ex[name], e, 0, keepdims=False)
                   for name in ("w_gate", "w_up", "w_down")}
            return acc + jnp.where((e_blk == e)[:, None], gated_mlp(w_e, xb, precision), 0.0)

        # rows of experts held elsewhere (e == count) are multiplied with nothing
        yb = lax.fori_loop(e_blk[0], jnp.minimum(e_blk[-1], count - 1) + 1, one,
                           jnp.zeros((rb, d), F32))
        return y.at[tok].add(yb * lax.dynamic_slice_in_dim(w_rows, i * rb, rb, 0)[:, None])

    # blocks past the last held pair hold rows of other chips' experts only
    n_held = jnp.sum(held.astype(I32))
    y = lax.fori_loop(0, (n_held + rb - 1) // rb, block, jnp.zeros((t, d), F32))
    if with_shared:
        y = y + gated_mlp(p["shared"], u, precision)
    return y, chosen


# ------------------------------------------------------------------ the model
def layer(p, x, cfg, experts_held=None, precision: str = "float32"):
    """``x <- x + Attn(norm(x))``, then ``x <- x + FFN(norm(x))`` on (T, d) float32. Returns
    (x, chosen experts or None, the selection (T, T))."""
    eps = cfg["rms_norm_eps"]
    a, mask = mla(p["attn"], rms_norm(x, p["attn_norm"]["weight"], eps), cfg, precision)
    x = x + a
    m = rms_norm(x, p["ffn_norm"]["weight"], eps)
    if "router" in p["ffn"]:
        f, chosen = moe(p["ffn"], m, cfg, experts_held, precision)
    else:
        f, chosen = gated_mlp(p["ffn"], m, precision), None
    return x + f, chosen, mask


@partial(jax.jit, static_argnames=("cfg_json", "precision", "experts_held"), donate_argnums=(1,))
def _layer_jit(p, x, sample, cfg_json: str, precision: str, experts_held):
    """One layer as one program: its weights are cast up inside and the stream is donated;
    of the selection the rows ``sample`` leave."""
    x, chosen, mask = layer(p, x, json.loads(cfg_json), experts_held, precision)
    return x, chosen, mask[sample]


def head_logits(norm, head, h, cfg, precision: str = "float32", vocab_block: int = 16384):
    """``RMSNorm(h) W_head`` in blocks of the vocabulary; ``h`` is (m, d)."""
    hn = _q(rms_norm(h, norm["weight"], cfg["rms_norm_eps"]), precision)
    w = head["weight"]
    vb = _block(w.shape[1], vocab_block)
    cols = [jnp.matmul(hn, _q(w[:, j:j + vb], precision), precision=HI)
            for j in range(0, w.shape[1], vb)]
    return jnp.concatenate(cols, axis=1)


def loglik(logits, targets):
    """Sum of the targets' log-probabilities under the rows of ``logits``."""
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    return jnp.sum(jnp.take_along_axis(logp, targets[:, None].astype(I32), axis=1))


def forward(params, tokens, cfg, continuation: int, precision: str = "float32",
            experts_held=None, sample=None) -> dict:
    """The scoring forward of one document ``tokens`` (T,): the logits that score its last
    ``continuation`` tokens (positions T-1-c .. T-2), their log-likelihood, every expert
    layer's chosen experts, and every layer's selection as boolean rows (len(sample), T) at
    the queries ``sample`` (all by default)."""
    key = json.dumps(cfg, sort_keys=True)
    t, c = tokens.shape[0], continuation
    sample = jnp.arange(t, dtype=I32) if sample is None else jnp.asarray(sample, I32)
    x = params["embed"]["weight"][tokens].astype(F32)
    routes, selections = [], []
    for p in params["layers"]:
        x, chosen, selection = _layer_jit(p, x, sample, key, precision,
                                          None if experts_held is None else tuple(experts_held))
        selections.append(selection)
        if chosen is not None:
            routes.append(chosen)
    logits = head_logits(params["norm"], params["head"], x[t - 1 - c:t - 1], cfg, precision)
    return {"logits": logits, "loglik": loglik(logits, tokens[t - c:]), "routes": routes,
            "selections": selections}
