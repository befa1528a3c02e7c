"""Kimi-Linear as a long-document scoring forward: Kimi Delta Attention with fla's unbounded
softplus decay, a low-rank decay projection and a gate a channel, latent attention without
positions, and token-routed experts with no group limit.

Pre-norm layers (:class:`~.scoring.PreNormBlock`) whose token mixing is of two kinds, named
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
from typing import Optional, Tuple

import jax.numpy as jnp

from .attention import MultiheadLatentAttention
from .kda import KimiDeltaAttention
from .modules import GatedMLP
from .moe import MoE
from .scoring import NORM_INIT_STD, PreNormBlock, ScoringForward

__all__ = ["KimiLinear", "KimiLinearBlock", "KimiLinearConfig"]

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


class KimiLinearBlock(PreNormBlock):
    """One layer on tokens ``(T, d)``, :class:`~.scoring.PreNormBlock`'s two steps: KDA or
    latent attention, then a dense or a routed feed-forward."""

    def __init__(self, config: KimiLinearConfig, latent: bool, dense: bool,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: int = BLOCK_ROWS):
        c = config
        if latent:
            attn = MultiheadLatentAttention(
                c.hidden_size, c.num_attention_heads, None, c.kv_lora_rank, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, None, None, c.rms_norm_eps, dtype,
                NORM_INIT_STD)
        else:
            attn = KimiDeltaAttention(
                c.hidden_size, c.kda_heads, c.kda_head_dim, c.kda_conv, None, c.rms_norm_eps,
                dtype, NORM_INIT_STD, decay_rank=c.kda_head_dim, gate_rank=c.kda_head_dim)
        if dense:
            ffn = GatedMLP(c.hidden_size, c.intermediate_size, dtype)
        else:
            ffn = MoE(c.hidden_size, c.moe_intermediate_size, c.num_experts,
                      c.num_experts_per_token, c.num_shared_experts, c.routed_scaling_factor,
                      experts_held, block_rows, dtype)
        super().__init__(c, attn, ffn)


class KimiLinear(ScoringForward):
    """``KimiLinear(config)(tokens)``: the scoring forward of one document ``tokens`` (T,)
    int32, returning :class:`~.scoring.Scores`; the arguments are
    :class:`~.scoring.ScoringForward`'s (``config`` a :class:`KimiLinearConfig`,
    ``block_rows`` :data:`BLOCK_ROWS` by default; ``A_log`` and ``dt_bias`` are float32)."""

    config_type = KimiLinearConfig
    traces = "nn.kimi_linear.traces"
    block_rows = BLOCK_ROWS

    def _layer(self, config, index, experts_held, dtype, block_rows):
        return KimiLinearBlock(config, config.is_latent(index),
                               index < config.first_k_dense_replace, experts_held, dtype,
                               block_rows)
