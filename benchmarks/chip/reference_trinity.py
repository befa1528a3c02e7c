"""Plain reference of the Trinity (``afmoe``) scoring forward: float32 ``jax.numpy`` under
``Precision.HIGHEST``, no kernel, no cache, nothing of ``heat_tpu`` imported.

It follows the layer equations of ``doc/source/trinity.rst`` (grouped-query attention
with a normed q and k, rotary positions and a window on the ``sliding_attention`` layers
and neither on the ``full_attention`` ones, a sigmoid output gate, four norms a layer,
token-routed experts with a sigmoid router and a selection bias, an embedding scaled by
``sqrt(hidden_size)``) as straightforwardly as memory allows: key/value heads are
repeated, the band is a ``where`` over the whole score row, heads and queries go through
in blocks so that no ``(T, T)`` score matrix of all heads is held, sorted expert rows go
through in blocks, and one layer's weights are cast up at a time, so that a 32,768-token
document fits beside the program's own bfloat16 weights. ``cfg`` is the configuration
file's dictionary (the published keys), ``params`` the model's parameter pytree, read by
name and never written. ``precision`` is ``"float32"`` for the reference itself;
``"bfloat16"`` and ``"float8"`` round the operands of every contraction that the
deployment states in bfloat16 (router, norms, softmax and the gate's sigmoid stay
float32, as it states them) and give the control: the same mathematics one precision down.

``benchmarks/chip/reference_trinity.py`` is a byte-equal copy of
``tests/reference_trinity.py`` (``tests/test_trinity.py`` holds the two together).
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


# ------------------------------------------------------------------ attention
def rope(x, cfg):
    """Rotary positions on ``x`` (T, heads, head_dim): the pair ``(x[i], x[i + d/2])`` of
    a head turns by ``pos * theta^(-2i/d)``; the position is the index on the first axis."""
    d = x.shape[-1]
    inv_freq = cfg["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(x.shape[0], dtype=I32).astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(p, u, cfg, kind: str, precision: str = "float32", query_block: int = 1024):
    """One layer's attention over the (T, d) input: causal, inside the window on a
    ``sliding_attention`` layer, one head and one block of queries at a time."""
    t = u.shape[0]
    heads, groups, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                         cfg["head_dim"])
    eps, windowed = cfg["rms_norm_eps"], kind == "sliding_attention"
    q = rms_norm(_mm(u, p["wq"], precision).reshape(t, heads, hd), p["q_norm"]["weight"], eps)
    k = rms_norm(_mm(u, p["wk"], precision).reshape(t, groups, hd), p["k_norm"]["weight"], eps)
    v = _mm(u, p["wv"], precision).reshape(t, groups, hd)
    if windowed:  # a full layer has no positions at all
        q, k = rope(q, cfg), rope(k, cfg)
    # query head h reads key/value head h // (heads // groups)
    k = _q(jnp.repeat(k, heads // groups, axis=1), precision)
    v = _q(jnp.repeat(v, heads // groups, axis=1), precision)
    qb = _block(t, query_block)
    key_pos = jnp.arange(t, dtype=I32)[None, :]

    def head(h):
        qh, kh, vh = (lax.dynamic_index_in_dim(x, h, 1, keepdims=False) for x in (q, k, v))

        def block(i):
            qi = _q(lax.dynamic_slice_in_dim(qh, i * qb, qb, 0), precision)
            s = jnp.matmul(qi, kh.T, precision=HI) * hd ** -0.5
            row = (i * qb + jnp.arange(qb, dtype=I32))[:, None]
            keep = key_pos <= row
            if windowed:
                keep &= row - key_pos < cfg["sliding_window"]
            p_ = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
            return jnp.matmul(_q(p_, precision), vh, precision=HI)

        return lax.map(block, jnp.arange(t // qb, dtype=I32)).reshape(t, hd)

    a = jnp.moveaxis(lax.map(head, jnp.arange(heads, dtype=I32)), 0, 1).reshape(t, heads * hd)
    a = a * jax.nn.sigmoid(_mm(u, p["wg"], precision))  # the gate's sigmoid in float32
    return _mm(a, p["wo"], precision)


# ------------------------------------------------------------------ feed-forward, experts
def gated_mlp(p, u, precision: str = "float32"):
    return _mm(silu(_mm(u, p["w_gate"], precision)) * _mm(u, p["w_up"], precision),
               p["w_down"], precision)


def route(p, u, cfg):
    """Sigmoid scores in float32, the top k of score + selection bias, and the chosen
    scores over their own sum (+ 1e-20, ``route_norm``) times ``route_scale``. No group
    limit (``n_group`` = ``topk_group`` = 1)."""
    scores = jax.nn.sigmoid(_mm(u, p["router"]))
    _, chosen = lax.top_k(scores + p["router_bias"].astype(F32), cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=1)
    w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20) * cfg["route_scale"]
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

    y = lax.fori_loop(0, t * k // rb, block, jnp.zeros((t, d), F32))
    if with_shared:
        y = y + gated_mlp(p["shared"], u, precision)
    return y, chosen


# ------------------------------------------------------------------ the model
def layer(p, x, cfg, kind: str, experts_held=None, precision: str = "float32"):
    """``x <- x + norm(attention(norm(x)))``, then ``x <- x + norm(feed-forward(norm(x)))``
    on (T, d) float32. Returns (x, chosen experts or None)."""
    eps = cfg["rms_norm_eps"]
    a = attention(p["attn"], rms_norm(x, p["input_norm"]["weight"], eps), cfg, kind, precision)
    x = x + rms_norm(a, p["post_attn_norm"]["weight"], eps)
    m = rms_norm(x, p["pre_mlp_norm"]["weight"], eps)
    if "router" in p["ffn"]:
        f, chosen = moe(p["ffn"], m, cfg, experts_held, precision)
    else:
        f, chosen = gated_mlp(p["ffn"], m, precision), None
    return x + rms_norm(f, p["post_mlp_norm"]["weight"], eps), chosen


@partial(jax.jit, static_argnames=("cfg_json", "kind", "precision", "experts_held"),
         donate_argnums=(1,))
def _layer_jit(p, x, cfg_json: str, kind: str, precision: str, experts_held):
    """One layer as one program: its weights are cast up inside and the stream is
    donated, so a layer costs its own float32 weights and one copy of the stream."""
    return layer(p, x, json.loads(cfg_json), kind, experts_held, precision)


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
    if cfg.get("mup_enabled", True):
        x = x * cfg["hidden_size"] ** 0.5
    routes = []
    for p, kind in zip(params["layers"], cfg["layer_types"]):
        x, chosen = _layer_jit(p, x, key, kind, precision, experts_held)
        if chosen is not None:
            routes.append(chosen)
    logits = head_logits(params["norm"], params["head"], x[t - 1 - c:t - 1], cfg, precision)
    return {"logits": logits, "loglik": loglik(logits, tokens[t - c:]), "routes": routes}
