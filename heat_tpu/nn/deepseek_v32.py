"""DeepSeek-V3.2-Exp as a long-document scoring forward: latent attention in which a
learned index picks the keys each query attends to (DeepSeek sparse attention), and
token-routed experts in groups.

Pre-norm layers (:class:`~.scoring.PreNormBlock`): latent attention with a query latent
and YaRN frequencies (:class:`~.attention.MultiheadLatentAttention`) whose
:class:`~.attention.LightningIndexer` scores every earlier token for a query and keeps the
``index_topk`` best, one set for all heads; a gated feed-forward, dense in the leading
layers and token-routed experts after (:class:`~.moe.MoE`: sigmoid scores, a selection bias,
``topk_group`` of ``n_group`` groups); a final norm and an untied head.
``doc/source/deepseek_v32.rst`` writes the equations out and lists what is ``assumed`` and
what is left out (the multi-token-prediction module, the indexer's 8-bit arithmetic and its
Hadamard rotation, caches, decode).

The request is *scoring* (:mod:`.scoring`, shared with the other four models):
``model(tokens)`` runs through :meth:`Module.__call__`, the whole forward is **one compiled
program a call** (``nn.dsv32.traces`` counts its traces), and only the positions that score
the continuation go through the head. A sliced vocabulary is a smaller vocabulary.

**What a width of 7,168 with 128 heads asks of one chip.** The attention runs its heads in
``head_groups`` groups, so that q, k, v and o of 32,768 tokens exist for one group at a time,
and the feed-forward walks the tokens in ``ffn_pieces`` pieces, so that neither the dense
layer's hidden activation nor the experts' sorted buffer (sized for every pair of its tokens,
whatever share is held) is ever whole.

No reference counterpart.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core import diagnostics
from .attention import MultiheadLatentAttention
from .modules import GatedMLP, Module
from .moe import MoE
from .scoring import NORM_INIT_STD, PreNormBlock, ScoringForward

__all__ = ["DeepseekV32", "DeepseekV32Block", "DeepseekV32Config", "DeepseekV32Scores"]

# every layer's selection is returned for every 64th query and for the positions that score:
# what a comparison with a reference reads, 2.6 MB a layer at 32,768 tokens and not 134
SELECTION_STRIDE = 64
# rows a block of an expert layer's sorted buffer holds: an expert of 7,168 x 2,048 is walked in
# slabs of its hidden width, so its 88 MB of weights are read again for every block (PERF.md, PR 38)
BLOCK_ROWS = 256


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config:
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
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    vocab_size: int
    rms_norm_eps: float
    rope_theta: float
    rope_scaling: Optional[dict] = None

    @classmethod
    def from_dict(cls, config: dict) -> "DeepseekV32Config":
        """From a ``config.json`` dictionary; keys that do not shape the forward are
        passed over, and a variant this module does not compute is refused."""
        refused = {"model_type": "deepseek_v32", "scoring_func": "sigmoid",
                   "topk_method": "noaux_tc", "norm_topk_prob": True, "hidden_act": "silu",
                   "attention_bias": False, "tie_word_embeddings": False, "moe_layer_freq": 1,
                   "num_nextn_predict_layers": 0,
                   "num_key_value_heads": config["num_attention_heads"]}
        for key, only in refused.items():
            if config.get(key, only) != only:
                raise ValueError(f"DeepseekV32 computes {key}={only!r} only; got {config[key]!r}")
        if not 0 <= config["first_k_dense_replace"] <= config["num_hidden_layers"]:
            raise ValueError("first_k_dense_replace lies outside the layers")
        if config["index_head_dim"] < config["qk_rope_head_dim"]:
            raise ValueError("the index heads hold the rotary part whole")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in config.items() if k in names})


class DeepseekV32Scores(NamedTuple):
    """What one scoring forward returns, all on the device. ``logits`` (c, vocab): the head
    at positions ``T-1-c .. T-2``, which score the last ``c`` tokens; ``loglik``: their
    log-likelihood (a float32 scalar); ``chosen`` (expert layers, T, k) and ``load`` (expert
    layers, experts held): every expert layer's routing and the rows each held expert
    multiplied; ``selected`` (layers, sampled queries, words): every layer's selection, as
    packed words, at :meth:`DeepseekV32.sampled_queries`; ``kept`` (layers,) int32: the
    (query, key) pairs each layer's selection keeps over the whole document."""

    logits: jax.Array
    loglik: jax.Array
    chosen: jax.Array
    load: jax.Array
    selected: jax.Array
    kept: jax.Array


class _InPieces(Module):
    """``module`` on tokens (T, d) a piece of ``T / pieces`` at a time, one after another
    (``lax.map``); its parameters are the module's own. An expert layer's routing is laid
    end to end and its load summed. A ``T`` that the pieces do not divide goes through whole."""

    def __init__(self, module: Module, pieces: int):
        self.module, self.pieces = module, pieces

    def init(self, key):
        return self.module.init(key)

    def apply(self, params, x, *, key=None, train=False):
        t = x.shape[0]
        if self.pieces == 1 or t % self.pieces:
            return self.module.apply(params, x)
        out = lax.map(lambda piece: self.module.apply(params, piece),
                      x.reshape(self.pieces, t // self.pieces, -1))
        if not isinstance(out, tuple):
            return out.reshape(t, -1)
        y, aux = out
        return y.reshape(t, -1), {"chosen": aux["chosen"].reshape(t, -1),
                                  "load": jnp.sum(aux["load"], axis=0, dtype=jnp.int32)}


class DeepseekV32Block(PreNormBlock):
    """One layer on tokens ``(T, d)``, :class:`~.scoring.PreNormBlock`'s two steps: latent
    attention over the indexer's selection, then a dense or a routed feed-forward. ``apply``
    returns ``(x, aux)``; ``aux`` holds the layer's ``"selection"`` and, of an expert layer,
    ``"chosen"`` and ``"load"``."""

    def __init__(self, config: DeepseekV32Config, dense: bool,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: int = BLOCK_ROWS, head_groups: int = 1, ffn_pieces: int = 1):
        c = config
        attn = MultiheadLatentAttention(
            c.hidden_size, c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank,
            c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, c.rope_theta, c.rope_scaling,
            c.rms_norm_eps, dtype, NORM_INIT_STD,
            index=(c.index_n_heads, c.index_head_dim, c.index_topk), head_groups=head_groups)
        if dense:
            ffn = GatedMLP(c.hidden_size, c.intermediate_size, dtype)
        else:
            ffn = MoE(c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                      c.num_experts_per_tok, c.n_shared_experts, c.routed_scaling_factor,
                      experts_held, block_rows, dtype, c.n_group, c.topk_group)
        super().__init__(c, attn, _InPieces(ffn, ffn_pieces))


class DeepseekV32(ScoringForward):
    """``DeepseekV32(config)(tokens)``: the scoring forward of one document ``tokens`` (T,)
    int32, returning :class:`DeepseekV32Scores`; the arguments are
    :class:`~.scoring.ScoringForward`'s (``config`` a :class:`DeepseekV32Config`,
    ``block_rows`` :data:`BLOCK_ROWS` by default) and ``head_groups`` and ``ffn_pieces``, which
    cut the attention's heads and the feed-forward's tokens into parts that run one after
    another (the results are the uncut ones; see the module's text)."""

    config_type = DeepseekV32Config
    traces = "nn.dsv32.traces"
    block_rows = BLOCK_ROWS

    def _layer(self, config, index, experts_held, dtype, block_rows, head_groups: int = 1,
               ffn_pieces: int = 1):
        return DeepseekV32Block(config, index < config.first_k_dense_replace, experts_held,
                                dtype, block_rows, head_groups, ffn_pieces)

    def sampled_queries(self, t: int) -> np.ndarray:
        """The positions whose selection a forward of ``t`` tokens returns: every
        :data:`SELECTION_STRIDE`-th and the ``continuation`` that score."""
        return np.union1d(np.arange(0, t, SELECTION_STRIDE),
                          np.arange(t - 1 - self.continuation, t - 1)).astype(np.int32)

    def _document(self, params, tokens):
        t = tokens.shape[0]
        targets = tokens[t - self.continuation:]
        sample = jnp.asarray(self.sampled_queries(t))
        selected, kept = [], []

        def seen(aux):  # every layer's selection, sampled and counted as the layer leaves it
            selected.append(aux["selection"][sample])
            kept.append(jnp.sum(lax.population_count(aux["selection"]), dtype=jnp.int32))

        x, routed = self._walk(params, self._embed(params, tokens), seen)
        return DeepseekV32Scores(*self._scores(params, targets, x, routed), jnp.stack(selected),
                                 jnp.stack(kept))

    def readback(self, scores) -> Tuple[float, ...]:
        """As :meth:`ScoringForward.readback`; with diagnostics on, the selection is counted
        too: ``nn.dsa.selected`` (pairs the layers' selections keep) and ``nn.dsa.causal``
        (pairs they chose among: every layer's ``T (T + 1) / 2``)."""
        logliks = super().readback(scores)
        if diagnostics._enabled:
            t = scores.chosen.shape[1]
            diagnostics.counter("nn.dsa.selected", float(np.asarray(scores.kept, np.int64).sum()))
            diagnostics.counter("nn.dsa.causal", float(len(self.layers) * t * (t + 1) // 2))
        return logliks
