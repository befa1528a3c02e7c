"""The Trinity (``afmoe``) architecture as a long-document scoring forward.

Layers whose kind varies by depth (``layer_types``): *window* layers (rotary positions,
each row sees its last ``sliding_window`` keys) and *full* layers (causal over the whole
document, no positions at all), all of them grouped-query attention with a normed q and
k and a sigmoid output gate (:class:`~.attention.GroupedQueryAttention`); a gated
feed-forward, dense in the leading layers and token-routed experts after
(:class:`~.moe.MoE`); four norms round the two sub-blocks of a layer (before and after
each); an embedding scaled by ``sqrt(hidden_size)`` (muP) and an untied head.
``doc/source/trinity.rst`` writes the equations out and lists what is ``assumed`` where
the published configuration leaves a choice open.

The request is *scoring* (:mod:`.scoring`, shared with the other four models):
``model(tokens)`` runs through :meth:`Module.__call__`, the whole forward is **one
compiled program a call** (``nn.trinity.traces`` counts its traces), and only the
positions that score the continuation go through the head.

No reference counterpart.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from .attention import GroupedQueryAttention
from .modules import GatedMLP, Module, RMSNorm
from .moe import MoE
from .scoring import NORM_INIT_STD, ScoringForward

__all__ = ["Trinity", "TrinityBlock", "TrinityConfig"]

LAYER_KINDS = ("sliding_attention", "full_attention")
# rows a block of an expert layer's sorted buffer holds
BLOCK_ROWS = 512


@dataclasses.dataclass(frozen=True)
class TrinityConfig:
    """The published keys of the model's ``config.json`` that shape the forward."""

    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    num_experts: int
    num_shared_experts: int
    num_experts_per_tok: int
    route_scale: float
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    mup_enabled: bool = True

    @classmethod
    def from_dict(cls, config: dict) -> "TrinityConfig":
        """From a ``config.json`` dictionary; keys that do not shape the forward are
        passed over, and a variant this module does not compute is refused."""
        refused = {"score_func": "sigmoid", "n_group": 1, "topk_group": 1,
                   "num_expert_groups": 1, "num_limited_groups": 1, "route_norm": True,
                   "rope_scaling": None, "tie_word_embeddings": False, "hidden_act": "silu"}
        for key, only in refused.items():
            if config.get(key, only) != only:
                raise ValueError(f"Trinity computes {key}={only!r} only; got {config[key]!r}")
        kinds = tuple(config["layer_types"])
        if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(LAYER_KINDS):
            raise ValueError(f"Trinity's layer_types names one of {LAYER_KINDS} for each of the "
                             f"{config['num_hidden_layers']} layers; got {kinds}")
        if not 0 <= config["num_dense_layers"] < len(kinds):
            raise ValueError("Trinity has at least one expert layer after its "
                             f"{config['num_dense_layers']} dense ones; got {len(kinds)} layers")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**dict({k: v for k, v in config.items() if k in names}, layer_types=kinds))


class TrinityBlock(Module):
    """One layer on tokens ``(T, d)``: ``x <- x + norm(attention(norm(x)))``, then ``x <-
    x + norm(feed-forward(norm(x)))``. ``kind`` is the layer's entry of ``layer_types``.
    ``apply`` returns ``(x, aux)``, ``aux`` the expert layer's ``{"chosen", "load"}`` or
    None for a dense layer."""

    def __init__(self, config: TrinityConfig, kind: str, dense: bool,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: int = BLOCK_ROWS):
        c = config
        windowed = kind == "sliding_attention"

        def norm():
            return RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)

        self.input_norm = norm()
        self.attn = GroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            window=c.sliding_window if windowed else None,
            rope_theta=c.rope_theta if windowed else None,  # a full layer has no positions
            eps=c.rms_norm_eps, dtype=dtype, norm_init_std=NORM_INIT_STD)
        self.post_attn_norm = norm()
        self.pre_mlp_norm = norm()
        if dense:
            self.ffn = GatedMLP(c.hidden_size, c.intermediate_size, dtype)
        else:
            self.ffn = MoE(c.hidden_size, c.moe_intermediate_size, c.num_experts,
                           c.num_experts_per_tok, c.num_shared_experts, c.route_scale,
                           experts_held, block_rows, dtype)
        self.post_mlp_norm = norm()

    def apply(self, params, x, *, key=None, train=False):
        a = self.attn.apply(params["attn"], self.input_norm.apply(params["input_norm"], x))
        x = x + self.post_attn_norm.apply(params["post_attn_norm"], a)
        out = self.ffn.apply(params["ffn"], self.pre_mlp_norm.apply(params["pre_mlp_norm"], x))
        f, aux = out if isinstance(out, tuple) else (out, None)  # experts give (y, aux)
        return x + self.post_mlp_norm.apply(params["post_mlp_norm"], f), aux


class Trinity(ScoringForward):
    """``Trinity(config)(tokens)``: the scoring forward of one document ``tokens`` (T,)
    int32, returning :class:`~.scoring.Scores`; the arguments are
    :class:`~.scoring.ScoringForward`'s (``config`` a :class:`TrinityConfig`, ``block_rows``
    :data:`BLOCK_ROWS` by default)."""

    config_type = TrinityConfig
    traces = "nn.trinity.traces"
    block_rows = BLOCK_ROWS
    # muP scales the embedding by sqrt(d) in the forward: drawn at 1 / sqrt(d), the
    # residual stream starts at unit size, as a trained model's does
    embed_exponent = -0.5

    def _layer(self, config, index, experts_held, dtype, block_rows):
        return TrinityBlock(config, config.layer_types[index], index < config.num_dense_layers,
                            experts_held, dtype, block_rows)

    def _embed(self, params, tokens):
        x, c = super()._embed(params, tokens), self.config
        if c.mup_enabled:
            x = (x.astype(jnp.float32) * jnp.float32(c.hidden_size ** 0.5)).astype(x.dtype)
        return x
