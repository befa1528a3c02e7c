"""Plain reference of the Xing4.0 scoring forward: float32 ``jax.numpy`` under
``Precision.HIGHEST``, no kernel, nothing of ``heat_tpu`` imported.

It follows the layer equations of ``doc/source/xing4.rst`` (latent attention with YaRN,
token-routed experts with a sigmoid router and a selection bias, manifold-constrained
hyper-connections, one multi-token-prediction module) as straightforwardly as memory
allows: tokens, heads and sorted expert rows go through in blocks, and one layer's
weights are cast up at a time, so that a 32,768-token document fits beside the
program's own bfloat16 weights. ``cfg`` is the configuration file's dictionary (the
published keys), ``params`` the model's parameter pytree, read by name and never
written. ``precision`` is ``"float32"`` for the reference itself; ``"bfloat16"`` and
``"float8"`` round the operands of every contraction that the deployment states in
bfloat16 (router, mappings, norms, softmax and Sinkhorn stay float32, as it states
them) and give the control: the same mathematics one precision down.

``benchmarks/chip/reference_xing4.py`` is a byte-equal copy of ``tests/reference_xing4.py``
(``tests/test_xing4.py`` holds the two together).
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


def silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------------ hyper-connections
def sinkhorn(logits, iters: int, eps: float):
    """``exp``, then ``iters`` times: each column over its sum + eps, then each row."""
    m = jnp.exp(logits.astype(F32))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hc_mappings(p, x, cfg):
    """``x``: (t, n, d) streams. Returns H_pre (t, n), H_post (t, n), H_res (t, n, n)."""
    n = cfg["hc_mult"]
    flat = rms_norm(x.reshape(x.shape[0], -1), p["norm"]["weight"], cfg["rms_norm_eps"])
    raw = _mm(flat, p["phi"])  # float32: the deployment states the mappings so
    alpha, bias = p["alpha"].astype(F32), p["bias"].astype(F32)
    pre = alpha[0] * raw[:, :n] + bias[:n]
    post = alpha[1] * raw[:, n:2 * n] + bias[n:2 * n]
    res = alpha[2] * raw[:, 2 * n:].reshape(-1, n, n) + bias[2 * n:].reshape(n, n)
    res = jnp.clip(res, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(res, cfg["hc_sinkhorn_iters"], cfg["hc_eps"]))


def sub_block(hc, norm, f, x, cfg, token_block: int = 2048):
    """One hyper-connected sub-block: ``X <- H_res X + H_post^T F(RMSNorm(H_pre X))``.
    ``x`` is (T, n, d) float32. The mappings are read in token blocks, and the streams
    are rewritten block by block in place, so one copy of them is alive."""
    t, n, d = x.shape
    tb = _block(t, token_block)

    def read(i):
        xi = lax.dynamic_slice_in_dim(x, i * tb, tb, 0)
        pre, post, res = hc_mappings(hc, xi, cfg)
        u = jnp.einsum("tn,tnd->td", pre, xi, precision=HI)
        return rms_norm(u, norm["weight"], cfg["rms_norm_eps"]), post, res

    u, post, res = lax.map(read, jnp.arange(t // tb, dtype=I32))
    y, aux = f(u.reshape(t, d))
    y = y.reshape(t // tb, tb, d)

    def write(i, x):
        xi = lax.dynamic_slice_in_dim(x, i * tb, tb, 0)
        new = jnp.einsum("tij,tjd->tid", res[i], xi, precision=HI) \
            + post[i][:, :, None] * y[i][:, None, :]
        return lax.dynamic_update_slice_in_dim(x, new, i * tb, 0)

    return lax.fori_loop(0, t // tb, write, x), aux


# ------------------------------------------------------------------ latent attention
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


def rope(x, cfg):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by ``pos * inv_freq[i]``;
    ``x`` is (T, ..., rope_dim) and the position is the index on the first axis."""
    rs = cfg["rope_scaling"]
    scale = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    pos = jnp.arange(x.shape[0], dtype=I32).astype(F32)
    angle = pos[:, None] * jnp.asarray(yarn_inv_freq(cfg))[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (angle.shape[1],)
    cos, sin = (jnp.cos(angle) * scale).reshape(shape), (jnp.sin(angle) * scale).reshape(shape)
    x = x.astype(F32)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def mla(p, u, cfg, precision: str = "float32", query_block: int = 1024):
    """Causal latent attention over the (T, d) input, one head and one block of queries
    at a time; no cache, no absorbed products."""
    t = u.shape[0]
    heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r_kv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = rms_norm(_mm(u, p["wq_a"], precision), p["q_norm"]["weight"], eps)
    kv = _mm(u, p["wkv_a"], precision)
    c_kv = rms_norm(kv[:, :r_kv], p["kv_norm"]["weight"], eps)
    k_rope = rope(kv[:, r_kv:], cfg)  # one vector for all heads
    wq_b = p["wq_b"].reshape(-1, heads, dn + dr)
    wkv_b = p["wkv_b"].reshape(-1, heads, dn + dv)
    wo = p["wo"].reshape(heads, dv, -1)
    qb = _block(t, query_block)
    scale = softmax_scale(cfg)
    key_pos = jnp.arange(t, dtype=I32)

    def head(h, out):
        q = _mm(c_q, lax.dynamic_index_in_dim(wq_b, h, 1, keepdims=False), precision)
        q = jnp.concatenate([q[:, :dn], rope(q[:, dn:], cfg)], axis=1)
        kv_h = _mm(c_kv, lax.dynamic_index_in_dim(wkv_b, h, 1, keepdims=False), precision)
        k = _q(jnp.concatenate([kv_h[:, :dn], k_rope], axis=1), precision)
        v = _q(kv_h[:, dn:], precision)

        def block(i):
            qi = _q(lax.dynamic_slice_in_dim(q, i * qb, qb, 0), precision)
            s = jnp.matmul(qi, k.T, precision=HI) * scale
            s = jnp.where((i * qb + jnp.arange(qb, dtype=I32))[:, None] >= key_pos[None, :],
                          s, -jnp.inf)
            return jnp.matmul(_q(jax.nn.softmax(s, axis=-1), precision), v, precision=HI)

        o = lax.map(block, jnp.arange(t // qb, dtype=I32)).reshape(t, dv)
        return out + _mm(o, lax.dynamic_index_in_dim(wo, h, 0, keepdims=False), precision)

    return lax.fori_loop(0, heads, head, jnp.zeros((t, wo.shape[-1]), F32))


# ------------------------------------------------------------------ feed-forward, experts
def gated_mlp(p, u, precision: str = "float32"):
    return _mm(silu(_mm(u, p["w_gate"], precision)) * _mm(u, p["w_up"], precision),
               p["w_down"], precision)


def route(p, u, cfg):
    """Sigmoid scores in float32, the top k of score + selection bias, and the chosen
    scores over their own sum times the scaling factor. No group limit (n_group 1)."""
    scores = jax.nn.sigmoid(_mm(u, p["router"]))
    _, chosen = lax.top_k(scores + p["router_bias"].astype(F32), cfg["num_experts_per_tok"])
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

    y = lax.fori_loop(0, t * k // rb, block, jnp.zeros((t, d), F32))
    if with_shared:
        y = y + gated_mlp(p["shared"], u, precision)
    return y, chosen


# ------------------------------------------------------------------ the model
def layer(p, x, cfg, experts_held=None, precision: str = "float32"):
    """Attention sub-block, then feed-forward sub-block, on (T, n, d) float32 streams.
    Returns (streams, chosen experts or None)."""
    x, _ = sub_block(p["attn_hc"], p["attn_norm"],
                     lambda u: (mla(p["attn"], u, cfg, precision), None), x, cfg)
    if "router" in p["ffn"]:
        return sub_block(p["ffn_hc"], p["ffn_norm"],
                         lambda u: moe(p["ffn"], u, cfg, experts_held, precision), x, cfg)
    return sub_block(p["ffn_hc"], p["ffn_norm"],
                     lambda u: (gated_mlp(p["ffn"], u, precision), None), x, cfg)


@partial(jax.jit, static_argnames=("cfg_json", "precision", "experts_held"), donate_argnums=(1,))
def _layer_jit(p, x, cfg_json: str, precision: str, experts_held):
    """One layer as one program: its weights are cast up inside and the streams are
    donated, so a layer costs its own float32 weights and one copy of the streams."""
    return layer(p, x, json.loads(cfg_json), experts_held, precision)


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
            experts_held=None) -> dict:
    """The scoring forward of one document ``tokens`` (T,): the logits that score its
    last ``continuation`` tokens under the main head (positions T-1-c .. T-2) and under
    the multi-token-prediction head (positions T-2-c .. T-3, which predict two ahead),
    both log-likelihoods, and every expert layer's chosen experts (the module's last)."""
    key = json.dumps(cfg, sort_keys=True)
    t, c, n = tokens.shape[0], continuation, cfg["hc_mult"]
    emb = params["embed"]["weight"]
    x = jnp.repeat(emb[tokens].astype(F32)[:, None, :], n, axis=1)
    routes = []
    for p in params["layers"]:
        x, chosen = _layer_jit(p, x, key, precision, experts_held)
        if chosen is not None:
            routes.append(chosen)
    h = jnp.sum(x, axis=1)
    del x
    targets = tokens[t - c:]
    logits = head_logits(params["norm"], params["head"], h[t - 1 - c:t - 1], cfg, precision)

    m = params["mtp"]
    eps = cfg["rms_norm_eps"]
    joined = jnp.concatenate([rms_norm(emb[tokens[1:]], m["enorm"]["weight"], eps),
                              rms_norm(h[:t - 1], m["hnorm"]["weight"], eps)], axis=1)
    hm = _mm(joined, m["proj"], precision)  # T-1 positions: i <= T-2
    del joined, h
    xm = jnp.repeat(hm[:, None, :], n, axis=1)
    xm, chosen = _layer_jit(m["block"], xm, key, precision, experts_held)
    routes.append(chosen)
    hm = jnp.sum(xm, axis=1)
    mtp_logits = head_logits(m["norm"], params["head"], hm[t - 2 - c:t - 2], cfg, precision)
    return {"logits": logits, "mtp_logits": mtp_logits,
            "loglik": loglik(logits, targets), "mtp_loglik": loglik(mtp_logits, targets),
            "routes": routes}
