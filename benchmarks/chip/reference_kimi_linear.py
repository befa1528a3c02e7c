"""Plain reference of Kimi-Linear's scoring forward: float32 ``jax.numpy`` under
``Precision.HIGHEST``, no kernel, no cache, nothing of ``heat_tpu`` imported.

It follows the layer equations of ``doc/source/kimi_linear.rst``: pre-norm blocks whose token
mixing is Kimi Delta Attention (KDA) on the layers ``linear_attn_config.kda_layers`` names and
latent attention (MLA: a direct query, no positions, no gate) on those ``full_attn_layers``
names, both lists counting from 1; a gated feed-forward, dense in the leading layers and
token-routed experts after (sigmoid router, selection bias, no group limit, renormalised
weights times ``routed_scaling_factor``, one shared expert). KDA's log-decay is fla's
``-exp(A_log) * softplus(u W_fa W_fb + dt_bias)``, **with no bound and no floor**, and its
output gate is one sigmoid a channel through the low-rank pair ``W_ga W_gb``. The delta-rule
recurrence runs **token by token** (``lax.scan`` over the positions, a float32 state a head,
no chunks and no WY form) and the short convolution is four shifted multiply-adds. As far as
memory asks for it the work goes through in blocks: heads and queries of the latent layer,
sorted expert rows, the vocabulary; and one layer's weights are cast up at a time, so that a
32,768-token document fits beside the program's own bfloat16 weights. ``cfg`` is the model's
configuration dictionary (the published keys; ``num_experts`` is the router's width),
``params`` the model's parameter pytree, read by name and never written, ``experts_held =
(first, count)`` the share of every expert layer whose weights ``params`` holds.
``precision`` is ``"float32"`` for the reference itself; ``"bfloat16"`` and ``"float8"``
round the operands of every contraction that the deployment states in bfloat16, and the
recurrence's q, k and v (router, norms, softmax, gates, decay, beta and the state stay
float32, as it states them) and give the control: the same mathematics one precision down.

``benchmarks/chip/reference_kimi_linear.py`` is a byte-equal copy of
``tests/reference_kimi_linear.py`` (``tests/test_kimi_linear.py`` holds the two together).
"""

import json
from functools import partial

import jax
import jax.numpy as jnp
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


def is_latent(cfg, index: int) -> bool:
    """Layer ``index`` (from 0) mixes by latent attention: ``full_attn_layers`` counts from 1."""
    return index + 1 in cfg["linear_attn_config"]["full_attn_layers"]


# ------------------------------------------------------------------ Kimi Delta Attention
def short_conv(x, w):
    """Causal depthwise convolution: ``y_t = sum_j w[j] * x_{t-(width-1)+j}`` on ``x`` (T,
    channels) with ``w`` (width, channels), zeros left of the document; then SiLU."""
    t, width = x.shape[0], w.shape[0]
    w = w.astype(F32)
    y = jnp.zeros_like(x)
    for j in range(width):
        back = width - 1 - j
        y = y + w[j] * jnp.concatenate([jnp.zeros((back, x.shape[1]), F32), x[:t - back]])
    return silu(y)


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def log_decay(p, u, cfg, precision: str = "float32"):
    """``g = -exp(A_log_h) * softplus(u W_fa W_fb + dt_bias)`` (T, H, d) float32: fla's gate,
    unbounded below."""
    lin = cfg["linear_attn_config"]
    heads, hd = lin["num_heads"], lin["head_dim"]
    pre = _mm(_mm(u, p["wf_a"], precision), p["wf_b"], precision) + p["dt_bias"].astype(F32)
    rate = jnp.exp(p["a_log"].astype(F32))[None, :, None]
    return -rate * jax.nn.softplus(pre.reshape(u.shape[0], heads, hd))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, one position after another. ``q, k, g`` (T, H, d_k), ``v``
    (T, H, d_v), ``beta`` (T, H), all float32; the state (H, d_k, d_v) starts at 0.
    ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t =
    S_t^T q_t``. Returns ``o`` (T, H, d_v)."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]
        seen = jnp.sum(k_t[:, :, None] * s, axis=1)  # k_t^T S, (H, d_v)
        s = s + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return s, jnp.sum(q_t[:, :, None] * s, axis=1)

    s0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return lax.scan(step, s0, (q, k, v, g, beta))[1]


def kda(p, u, cfg, precision: str = "float32"):
    """One KDA layer's token mixing over the (T, d) input; no positions."""
    t = u.shape[0]
    lin = cfg["linear_attn_config"]
    heads, hd = lin["num_heads"], lin["head_dim"]

    def branch(name):
        x = short_conv(_mm(u, p["w" + name], precision), p["conv_" + name])
        return x.reshape(t, heads, hd)

    q = l2_norm(branch("q")) * hd ** -0.5
    k = l2_norm(branch("k"))
    v = branch("v")
    g = log_decay(p, u, cfg, precision)
    beta = jax.nn.sigmoid(_mm(u, p["wb"], precision))
    o = delta_rule(_q(q, precision), _q(k, precision), _q(v, precision), g, beta)
    o = rms_norm(o, p["o_norm"]["weight"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_mm(_mm(u, p["wg_a"], precision), p["wg_b"], precision))
    o = o * gate.reshape(t, heads, hd)  # one gate a channel
    return _mm(o.reshape(t, heads * hd), p["wo"], precision)


# ------------------------------------------------------------------ latent attention
def mla(p, u, cfg, precision: str = "float32", query_block: int = 1024):
    """Causal latent attention over the (T, d) input with a direct query projection and **no
    positions**: the 64 "rope" dimensions of the query and of the one shared key go in as
    projected. One head and one block of queries at a time; no cache, no absorbed products."""
    t = u.shape[0]
    heads, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    r_kv, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    kv = _mm(u, p["wkv_a"], precision)
    c_kv = rms_norm(kv[:, :r_kv], p["kv_norm"]["weight"], eps)
    k_shared = kv[:, r_kv:]  # one vector for all heads, unrotated
    wq = p["wq"].reshape(-1, heads, dn + dr)
    wkv_b = p["wkv_b"].reshape(-1, heads, dn + dv)
    wo = p["wo"].reshape(heads, dv, -1)
    qb = _block(t, query_block)
    scale = (dn + dr) ** -0.5
    key_pos = jnp.arange(t, dtype=I32)

    def head(h, out):
        q = _mm(u, lax.dynamic_index_in_dim(wq, h, 1, keepdims=False), precision)
        kv_h = _mm(c_kv, lax.dynamic_index_in_dim(wkv_b, h, 1, keepdims=False), precision)
        k = _q(jnp.concatenate([kv_h[:, :dn], k_shared], axis=1), precision)
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
    """Sigmoid scores ``s`` in float32 over all experts; the top k of ``s + bias`` are chosen
    (one group: no limit); weights are the chosen ``s`` over their own sum, times
    ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(_mm(u, p["router"]))
    _, chosen = lax.top_k(scores + p["router_bias"].astype(F32), cfg["num_experts_per_token"])
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
    first, count = experts_held or (0, cfg["num_experts"])
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
def layer(p, x, cfg, index: int, experts_held=None, precision: str = "float32"):
    """``x <- x + Mix(norm(x))``, then ``x <- x + FFN(norm(x))`` on (T, d) float32; layer
    ``index`` mixes by latent attention or by KDA. Returns (x, chosen experts or None)."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, p["attn_norm"]["weight"], eps)
    mix = mla if is_latent(cfg, index) else kda
    x = x + mix(p["attn"], u, cfg, precision)
    m = rms_norm(x, p["ffn_norm"]["weight"], eps)
    if "router" in p["ffn"]:
        f, chosen = moe(p["ffn"], m, cfg, experts_held, precision)
    else:
        f, chosen = gated_mlp(p["ffn"], m, precision), None
    return x + f, chosen


@partial(jax.jit, static_argnames=("cfg_json", "index", "precision", "experts_held"),
         donate_argnums=(1,))
def _layer_jit(p, x, cfg_json: str, index: int, precision: str, experts_held):
    """One layer as one program: its weights are cast up inside and the stream is
    donated, so a layer costs its own float32 weights and one copy of the stream."""
    return layer(p, x, json.loads(cfg_json), index, experts_held, precision)


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
    last ``continuation`` tokens (positions T-1-c .. T-2), their log-likelihood, and every
    expert layer's chosen experts."""
    key = json.dumps(cfg, sort_keys=True)
    t, c = tokens.shape[0], continuation
    x = params["embed"]["weight"][tokens].astype(F32)
    routes = []
    for index, p in enumerate(params["layers"]):
        x, chosen = _layer_jit(p, x, key, index, precision,
                               None if experts_held is None else tuple(experts_held))
        if chosen is not None:
            routes.append(chosen)
    logits = head_logits(params["norm"], params["head"], x[t - 1 - c:t - 1], cfg, precision)
    return {"logits": logits, "loglik": loglik(logits, tokens[t - c:]), "routes": routes}
