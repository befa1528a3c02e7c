"""Kimi-Linear as a long-document scoring forward: Kimi Delta Attention with fla's unbounded
softplus decay, a low-rank decay projection and a gate a channel, latent attention without
positions, and token-routed experts with no group limit.

Pre-norm layers (:class:`~.ling.LingBlock`'s form) whose token mixing is of two kinds, named
by the published lists ``linear_attn_config.kda_layers`` and ``full_attn_layers`` (1-indexed):
Kimi Delta Attention (:class:`~.kda.KimiDeltaAttention`, the softplus kind, ``W_f`` and the
channel gate as rank-``head_dim`` pairs) or latent attention with a direct query and no
positions (:class:`~.attention.MultiheadLatentAttention` with ``rope_theta=None``, no head
gate); a gated feed-forward, dense in the leading layers and token-routed experts after
(:class:`~.moe.MoE`: sigmoid scores, a selection bias, no group limit, renormalised weights
times ``routed_scaling_factor``, one shared expert); a final norm and an untied head.
``doc/source/kimi_linear.rst`` writes the equations out and lists what is ``assumed`` where
the published configuration leaves a choice open, and what is left out (the KDA state as a
cache, decode).

The request is *scoring* (:mod:`.scoring`, shared with the other four models):
``model(tokens)`` runs through :meth:`Module.__call__`, the whole forward is **one compiled
program a call** (``nn.kimi_linear.traces`` counts its traces), and only the positions that
score the continuation go through the head. A sliced vocabulary is a smaller vocabulary.

No reference counterpart.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import MultiheadLatentAttention
from .kda import KimiDeltaAttention
from .ling import NORM_INIT_STD, LingBlock
from .modules import GatedMLP, RMSNorm, normal_weight
from .moe import MoE
from .scoring import ScoringForward, score

__all__ = ["KimiLinear", "KimiLinearBlock", "KimiLinearConfig", "KimiLinearScores"]

# 1,024 tokens a held expert on average (32,768 x 8 / 256): a group is padded by half a block
# on average, so the block is a quarter of the mean group
BLOCK_ROWS = 256


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """The published keys of the model's ``config.json`` that shape the forward; the four
    of ``linear_attn_config`` come out under ``kda_*`` and the layer lists as tuples."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    num_experts: int
    num_experts_per_token: int
    num_shared_experts: int
    routed_scaling_factor: float
    vocab_size: int
    rms_norm_eps: float
    kda_heads: int
    kda_head_dim: int
    kda_conv: int
    full_attn_layers: Tuple[int, ...]

    @classmethod
    def from_dict(cls, config: dict) -> "KimiLinearConfig":
        """From a ``config.json`` dictionary; keys that do not shape the forward are passed
        over (``head_dim``, 72, among them: no layer has heads of that width), and a variant
        this module does not compute is refused in words."""
        linear = config["linear_attn_config"]
        if "kda_lower_bound" in config or "kda_lower_bound" in linear:
            raise ValueError("KimiLinear computes fla's softplus decay, which has no lower bound; "
                             "a kda_lower_bound is the bounded gate of another model (Ling's "
                             "kda_safe_gate)")
        if config.get("q_lora_rank") is not None:
            raise ValueError(f"KimiLinear's latent layers take their query directly "
                             f"(q_lora_rank null); a query latent of rank "
                             f"{config['q_lora_rank']} is refused")
        if config.get("num_expert_group", 1) != 1 or config.get("topk_group", 1) != 1:
            raise ValueError(f"KimiLinear's router has no group limit (num_expert_group 1); got "
                             f"{config.get('topk_group')} of {config.get('num_expert_group')} "
                             f"groups")
        refused = {"model_type": "kimi_linear", "hidden_act": "silu", "mla_use_nope": True,
                   "moe_router_activation_func": "sigmoid", "moe_renormalize": True,
                   "moe_layer_freq": 1, "num_nextn_predict_layers": 0,
                   "tie_word_embeddings": False,
                   "num_key_value_heads": config["num_attention_heads"]}
        for key, only in refused.items():
            if config.get(key, only) != only:
                raise ValueError(f"KimiLinear computes {key}={only!r} only; got {config[key]!r}")
        layers = config["num_hidden_layers"]
        kda = {i for i in linear["kda_layers"] if i <= layers}
        full = {i for i in linear["full_attn_layers"] if i <= layers}
        if kda & full or kda | full != set(range(1, layers + 1)):
            raise ValueError(f"KimiLinear's kda_layers and full_attn_layers name each of the "
                             f"layers 1..{layers} once; got {sorted(kda)} and {sorted(full)}")
        if not 0 <= config["first_k_dense_replace"] <= layers:
            raise ValueError("first_k_dense_replace lies outside the layers")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in config.items() if k in names},
                   kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
                   kda_conv=linear["short_conv_kernel_size"], full_attn_layers=tuple(sorted(full)))

    def is_latent(self, index: int) -> bool:
        """Layer ``index`` (0-indexed) mixes by latent attention: the published lists count
        from 1."""
        return index + 1 in self.full_attn_layers


class KimiLinearScores(NamedTuple):
    """What one scoring forward returns, all on the device. ``logits`` (c, vocab): the head
    at positions ``T-1-c .. T-2``, which score the last ``c`` tokens; ``loglik``: their
    log-likelihood (a float32 scalar); ``chosen`` (expert layers, T, k) and ``load`` (expert
    layers, experts held): every expert layer's routing and the rows each held expert
    multiplied."""

    logits: jax.Array
    loglik: jax.Array
    chosen: jax.Array
    load: jax.Array


class KimiLinearBlock(LingBlock):
    """One layer on tokens ``(T, d)``, :class:`~.ling.LingBlock`'s two steps: KDA or latent
    attention, then a dense or a routed feed-forward. ``apply`` returns ``(x, aux)``; ``aux``
    holds an expert layer's ``"chosen"`` and ``"load"``, or is None."""

    def __init__(self, config: KimiLinearConfig, latent: bool, dense: bool,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: int = BLOCK_ROWS):
        c = config
        self.attn_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        if latent:
            self.attn = MultiheadLatentAttention(
                c.hidden_size, c.num_attention_heads, None, c.kv_lora_rank, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, None, None, c.rms_norm_eps, dtype,
                NORM_INIT_STD)
        else:
            self.attn = KimiDeltaAttention(
                c.hidden_size, c.kda_heads, c.kda_head_dim, c.kda_conv, None, c.rms_norm_eps,
                dtype, NORM_INIT_STD, decay_rank=c.kda_head_dim, gate_rank=c.kda_head_dim)
        self.ffn_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        if dense:
            self.ffn = GatedMLP(c.hidden_size, c.intermediate_size, dtype)
        else:
            self.ffn = MoE(c.hidden_size, c.moe_intermediate_size, c.num_experts,
                           c.num_experts_per_token, c.num_shared_experts,
                           c.routed_scaling_factor, experts_held, block_rows, dtype)


class KimiLinear(ScoringForward):
    """``KimiLinear(config)(tokens)``: the scoring forward of one document ``tokens`` (T,)
    int32, returning :class:`KimiLinearScores`.

    ``config`` is a :class:`KimiLinearConfig` or the ``config.json`` dictionary;
    ``continuation`` is the number of trailing tokens that are scored; ``experts_held =
    (first, count)`` is the share of every expert layer that lives here (all by default, see
    :class:`~.moe.MoE`); ``block_rows`` is the block every held expert's group of rows is
    padded to; parameters are stored in ``dtype`` (norms, router, ``A_log`` and ``dt_bias``
    float32) and activations follow it.
    """

    traces = "nn.kimi_linear.traces"

    def __init__(self, config, continuation: int = 128,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: int = BLOCK_ROWS):
        if not isinstance(config, KimiLinearConfig):
            config = KimiLinearConfig.from_dict(config)
        self.config = c = config
        self.continuation = continuation
        self.dtype = jnp.dtype(dtype)
        self.layers = [
            KimiLinearBlock(c, c.is_latent(i), i < c.first_k_dense_replace, experts_held, dtype,
                            block_rows)
            for i in range(c.num_hidden_layers)
        ]
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)

    def init(self, key):
        c, dt = self.config, self.dtype
        d = c.hidden_size
        k_embed, k_head, k_norm, *k_layers = jax.random.split(key, 3 + len(self.layers))
        return {
            "embed": {"weight": normal_weight(k_embed, (c.vocab_size, d), dt, 1.0)},
            "layers": [layer.init(k) for layer, k in zip(self.layers, k_layers)],
            "norm": self.norm.init(k_norm),
            "head": {"weight": normal_weight(k_head, (d, c.vocab_size), dt, d ** -0.5)},
        }

    def _document(self, params, tokens):
        targets = tokens[tokens.shape[0] - self.continuation:]
        x = params["embed"]["weight"][tokens]
        routed = []
        for block, p in zip(self.layers, params["layers"]):
            x, aux = block.apply(p, x)
            if aux:
                routed.append(aux)
        logits, loglik = score(self.norm, params["norm"], params["head"], x, targets)
        return KimiLinearScores(logits, loglik, jnp.stack([a["chosen"] for a in routed]),
                                jnp.stack([a["load"] for a in routed]))
