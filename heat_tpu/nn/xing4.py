"""The Xing4.0 architecture as a long-document scoring forward.

Layers of latent attention (:class:`~.attention.MultiheadLatentAttention`) and a gated
feed-forward (the leading ones dense, the rest token-routed experts, :class:`~.moe.MoE`),
every sub-block on ``hc_mult`` residual streams mixed by manifold-constrained
hyper-connections (:class:`~.hyper_connections.HyperConnection`), an untied head, and one
multi-token-prediction module that predicts two tokens ahead from the main model's last
hidden state. ``doc/source/xing4.rst`` writes the equations out and lists what is
``assumed`` where the published configuration leaves a choice open.

The request is *scoring* (:mod:`.scoring`, which this model shares with the other four):
``model(tokens)`` runs through :meth:`Module.__call__` like every module, the whole forward
is **one compiled program a call** (``nn.xing4.traces`` counts its traces), and only the
positions that score the continuation go through the head.

No reference counterpart.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import MultiheadLatentAttention
from .hyper_connections import HyperConnection
from .modules import GatedMLP, Module, RMSNorm, contract, normal_weight
from .moe import MoE
from .scoring import NORM_INIT_STD, ScoringForward, _routing, score

__all__ = ["Xing4", "Xing4Block", "Xing4Config", "Xing4Scores"]

# rows a block of an expert layer's sorted buffer holds
BLOCK_ROWS = 512


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """The published keys of the model's ``config.json`` that shape the forward."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    vocab_size: int
    hc_mult: int
    hc_sinkhorn_iters: int
    hc_eps: float
    mhc_h_res_clamp_min: float
    mhc_h_res_clamp_max: float
    rms_norm_eps: float
    rope_theta: float
    rope_scaling: Optional[dict] = None
    num_nextn_predict_layers: int = 1

    @classmethod
    def from_dict(cls, config: dict) -> "Xing4Config":
        """From a ``config.json`` dictionary; keys that do not shape the forward are
        passed over, and a variant this module does not compute is refused."""
        refused = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
                   "topk_group": 1, "norm_topk_prob": True, "hidden_act": "silu",
                   "attention_bias": False, "tie_word_embeddings": False,
                   "moe_layer_freq": 1, "num_nextn_predict_layers": 1}
        for key, only in refused.items():
            if config.get(key, only) != only:
                raise ValueError(f"Xing4 computes {key}={only!r} only; got {config[key]!r}")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in config.items() if k in names})


class Xing4Scores(NamedTuple):
    """What one scoring forward returns, all on the device. ``logits`` (c, vocab): the
    main head at positions ``T-1-c .. T-2``, which score the last ``c`` tokens;
    ``mtp_logits`` (c, vocab): the multi-token-prediction head at ``T-2-c .. T-3``, which
    scores the same tokens from two back; the two log-likelihoods (float32 scalars);
    ``chosen`` (expert layers + 1, T, k): every expert layer's routing, the module's last;
    ``load`` (expert layers + 1, experts held): rows each held expert multiplied."""

    logits: jax.Array
    mtp_logits: jax.Array
    loglik: jax.Array
    mtp_loglik: jax.Array
    chosen: jax.Array
    load: jax.Array


class Xing4Block(Module):
    """One layer on streams ``(n, T, d)``: ``X <- HC(X, attention . norm)``, then
    ``X <- HC(X, feed-forward . norm)``. ``apply`` returns ``(X, aux)``, ``aux`` the
    expert layer's ``{"chosen", "load"}`` or None for a dense layer."""

    def __init__(self, config: Xing4Config, dense: bool,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: int = BLOCK_ROWS):
        c = config
        hc = dict(dim=c.hidden_size, streams=c.hc_mult, sinkhorn_iters=c.hc_sinkhorn_iters,
                  eps=c.hc_eps, res_clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
                  norm_eps=c.rms_norm_eps, norm_init_std=NORM_INIT_STD)
        self.attn_hc = HyperConnection(**hc)
        self.attn_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        self.attn = MultiheadLatentAttention(
            c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank,
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.rope_theta,
            c.rope_scaling, c.rms_norm_eps, dtype, NORM_INIT_STD)
        self.ffn_hc = HyperConnection(**hc)
        self.ffn_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        if dense:
            self.ffn = GatedMLP(c.hidden_size, c.intermediate_size, dtype)
        else:
            self.ffn = MoE(c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                           c.num_experts_per_tok, c.n_shared_experts,
                           c.routed_scaling_factor, experts_held, block_rows, dtype)

    def apply(self, params, x, *, key=None, train=False):
        def attention(u):
            u = self.attn_norm.apply(params["attn_norm"], u)
            return self.attn.apply(params["attn"], u), None

        def feed_forward(u):
            out = self.ffn.apply(params["ffn"], self.ffn_norm.apply(params["ffn_norm"], u))
            return out if isinstance(out, tuple) else (out, None)  # experts give (y, aux)

        x, _ = self.attn_hc.apply(params["attn_hc"], (x, attention))
        return self.ffn_hc.apply(params["ffn_hc"], (x, feed_forward))


class Xing4(ScoringForward):
    """``Xing4(config)(tokens)``: the scoring forward of one document ``tokens`` (T,)
    int32, returning :class:`Xing4Scores`; the arguments are
    :class:`~.scoring.ScoringForward`'s (``config`` an :class:`Xing4Config`, ``block_rows``
    :data:`BLOCK_ROWS` by default; the hyper-connection mappings are float32)."""

    config_type = Xing4Config
    traces = "nn.xing4.traces"
    block_rows = BLOCK_ROWS
    logliks = ("loglik", "mtp_loglik")
    ahead = 2  # the multi-token-prediction head scores from two back

    def __init__(self, config, continuation: int = 128,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: Optional[int] = None):
        super().__init__(config, continuation, experts_held, dtype, block_rows)
        c = self.config
        # the multi-token-prediction module: two norms, a projection of the joined
        # (2d) vector, one expert layer, its own final norm; embedding and head are shared
        self.mtp_enorm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        self.mtp_hnorm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        self.mtp_block = Xing4Block(c, False, experts_held, dtype, self.block_rows)
        self.mtp_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)

    def _layer(self, config, index, experts_held, dtype, block_rows):
        return Xing4Block(config, index < config.first_k_dense_replace, experts_held, dtype,
                          block_rows)

    def init(self, key):
        c, dt = self.config, self.dtype
        d = c.hidden_size
        k_embed, k_head, k_proj, k_mtp, k_norm, k_en, k_hn, k_mn, *k_layers = jax.random.split(
            key, 8 + len(self.layers))
        return {
            "embed": {"weight": normal_weight(k_embed, (c.vocab_size, d), dt, 1.0)},
            "layers": [layer.init(k) for layer, k in zip(self.layers, k_layers)],
            "norm": self.norm.init(k_norm),
            "head": {"weight": normal_weight(k_head, (d, c.vocab_size), dt, d ** -0.5)},
            "mtp": {
                "enorm": self.mtp_enorm.init(k_en),
                "hnorm": self.mtp_hnorm.init(k_hn),
                "proj": normal_weight(k_proj, (2 * d, d), dt, (2 * d) ** -0.5),
                "block": self.mtp_block.init(k_mtp),
                "norm": self.mtp_norm.init(k_mn),
            },
        }

    @staticmethod
    def _sum_streams(x):
        return sum(x[j].astype(jnp.float32) for j in range(x.shape[0])).astype(x.dtype)

    def _embed(self, params, tokens):
        x = super()._embed(params, tokens)
        return jnp.broadcast_to(x[None], (self.config.hc_mult, *x.shape))

    def _document(self, params, tokens):
        targets = tokens[tokens.shape[0] - self.continuation:]
        x, routed = self._walk(params, self._embed(params, tokens))
        h = self._sum_streams(x)
        logits, loglik = score(self.norm, params["norm"], params["head"], h, targets)
        with jax.named_scope("ht.nn.mtp"):
            m, embed = params["mtp"], params["embed"]["weight"]
            # position i joins the embedding of token i+1; the last position has none and
            # takes token 0's: it is causal-last, so nothing reads it, and it is dropped
            joined = jnp.concatenate(
                [self.mtp_enorm.apply(m["enorm"], embed[jnp.roll(tokens, -1)]),
                 self.mtp_hnorm.apply(m["hnorm"], h)], axis=-1)
            hm = contract("te,ed->td", joined, m["proj"]).astype(h.dtype)
            xm = jnp.broadcast_to(hm[None], x.shape)
            xm, aux = self.mtp_block.apply(m["block"], xm)
            routed.append(aux)
            hm = self._sum_streams(xm)
            mtp_logits, mtp_loglik = score(self.mtp_norm, m["norm"], params["head"], hm,
                                           targets, ahead=2)
        return Xing4Scores(logits, mtp_logits, loglik, mtp_loglik, *_routing(routed))
