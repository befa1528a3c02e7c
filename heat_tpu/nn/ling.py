"""The Ling-3.0 language model (the text part of Ling-3.0-flash-VL) as a long-document
scoring forward.

Pre-norm layers whose token mixing is of two kinds, chosen by the layer's index: the last
of every ``layer_group_size`` layers mixes by latent attention with a direct query and a
head-wise output gate (:class:`~.attention.MultiheadLatentAttention`), the others by Kimi
Delta Attention, a linear attention with a matrix state a head
(:class:`~.kda.KimiDeltaAttention`); a gated feed-forward, dense in the leading layers and
token-routed experts after (:class:`~.moe.MoE`, whose router limits a token to
``topk_group`` of ``n_group`` groups of experts); a final norm and an untied head.
``doc/source/ling.rst`` writes the equations out and lists what is ``assumed`` where the
published configuration leaves a choice open, and what is left out (the vision tower, the
multi-token-prediction modules, the clamped activation of the deepest layers).

The request is *scoring* (:mod:`.scoring`, shared with the other four models):
``model(tokens)`` runs through :meth:`Module.__call__`, the whole forward is **one compiled
program a call** (``nn.ling.traces`` counts its traces), and only the positions that score
the continuation go through the head. A sliced vocabulary is a smaller vocabulary:
``vocab_size`` is what is held, ids are the slice's own and the logits are over the slice.

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

__all__ = ["Ling", "LingBlock", "LingConfig"]

# 512 tokens an expert on average (32,768 x 8 / 512): a group is padded by half a block on
# average, so the block is a quarter of the mean group and not the whole of it
BLOCK_ROWS = 128


@dataclasses.dataclass(frozen=True)
class LingConfig:
    """The published keys of the model's ``config.json`` that shape the forward."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    layer_group_size: int
    num_attention_heads: int
    head_dim: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    short_conv_kernel_size: int
    kda_lower_bound: float
    num_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float

    @classmethod
    def from_dict(cls, config: dict) -> "LingConfig":
        """From a ``config.json`` dictionary; keys that do not shape the forward are
        passed over, and a variant this module does not compute is refused."""
        refused = {"use_nGPT": False, "scale_router_input": False, "value_norm": False,
                   "up_proj_norm": False, "use_mla_nope": False, "mtp_use_kda": False,
                   "use_kda_lora": False, "no_kda_lora": True, "kda_safe_gate": True,
                   "linear_silu": True, "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1,
                   "q_lora_rank": None, "score_function": "sigmoid",
                   "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
                   "gated_attention_proj_granularity_type": "head_wise", "rope_scaling": None,
                   "tie_word_embeddings": False, "hidden_act": "silu",
                   "num_key_value_heads": config["num_attention_heads"],
                   "rotary_dim": config["qk_rope_head_dim"]}
        for key, only in refused.items():
            if config.get(key, only) != only:
                raise ValueError(f"Ling computes {key}={only!r} only; got {config[key]!r}")
        layers = config["num_hidden_layers"]
        for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
            if any(config.get(key, ())[:layers]):
                raise ValueError(f"Ling computes the unclamped gated SiLU only; {key} clamps a "
                                 f"layer among the first {layers}: {config[key][:layers]}")
        group, dense = config["layer_group_size"], config["first_k_dense_replace"]
        if layers % group or not 0 <= dense < layers:
            raise ValueError(f"Ling has whole groups of {group} layers and at least one expert "
                             f"layer after its {dense} dense ones; got {layers}")
        if config["moe_shared_expert_intermediate_size"] % config["moe_intermediate_size"]:
            raise ValueError("Ling's shared expert is a whole number of experts wide")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in config.items() if k in names})

    def is_latent(self, index: int) -> bool:
        """Layer ``index`` mixes by latent attention: the last of every group."""
        return (index + 1) % self.layer_group_size == 0


class LingBlock(PreNormBlock):
    """One layer on tokens ``(T, d)``, :class:`~.scoring.PreNormBlock`'s two steps; ``latent``
    chooses the mixing, ``dense`` the feed-forward."""

    def __init__(self, config: LingConfig, latent: bool, dense: bool,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: int = BLOCK_ROWS):
        c = config
        if latent:
            attn = MultiheadLatentAttention(
                c.hidden_size, c.num_attention_heads, None, c.kv_lora_rank, c.qk_nope_head_dim,
                c.qk_rope_head_dim, c.v_head_dim, c.rope_theta, None, c.rms_norm_eps, dtype,
                NORM_INIT_STD, head_gate=True)
        else:
            attn = KimiDeltaAttention(
                c.hidden_size, c.num_attention_heads, c.head_dim, c.short_conv_kernel_size,
                c.kda_lower_bound, c.rms_norm_eps, dtype, NORM_INIT_STD)
        if dense:
            ffn = GatedMLP(c.hidden_size, c.intermediate_size, dtype)
        else:
            ffn = MoE(c.hidden_size, c.moe_intermediate_size, c.num_experts,
                      c.num_experts_per_tok,
                      c.moe_shared_expert_intermediate_size // c.moe_intermediate_size,
                      c.routed_scaling_factor, experts_held, block_rows, dtype, c.n_group,
                      c.topk_group)
        super().__init__(c, attn, ffn)


class Ling(ScoringForward):
    """``Ling(config)(tokens)``: the scoring forward of one document ``tokens`` (T,) int32,
    returning :class:`~.scoring.Scores`; the arguments are :class:`~.scoring.ScoringForward`'s
    (``config`` a :class:`LingConfig`, ``block_rows`` :data:`BLOCK_ROWS` by default; ``A_log``
    and ``dt_bias`` are float32)."""

    config_type = LingConfig
    traces = "nn.ling.traces"
    block_rows = BLOCK_ROWS

    def _layer(self, config, index, experts_held, dtype, block_rows):
        return LingBlock(config, config.is_latent(index), index < config.first_k_dense_replace,
                         experts_held, dtype, block_rows)
