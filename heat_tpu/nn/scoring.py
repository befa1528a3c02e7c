"""The scoring forward that the long-document models share.

The request is *scoring*: the log-likelihood of a document's last ``continuation`` tokens
given everything before them, as evaluation harnesses, rerankers and perplexity filters
ask it. That is one whole causal forward with no key/value cache and no decode loop, and
only the positions that score the continuation go through the head.

:class:`ScoringForward` is everything round the layers that :class:`~.xing4.Xing4`,
:class:`~.trinity.Trinity`, :class:`~.ling.Ling`, :class:`~.deepseek_v32.DeepseekV32` and
:class:`~.kimi_linear.KimiLinear` have in common: the configuration read from its
``config.json`` dictionary, the layer stack and the final norm, the seeded weights
(``{embed, layers, norm, head}``), the walk (embed, every layer, the expert layers'
routing collected) and the :class:`Scores` it ends in. ``model(tokens)`` runs through
:meth:`Module.__call__` like every module, the whole forward is **one compiled program a
call** (``nn.<model>.traces`` counts its traces, as ``spatial.cdist.traces`` does for
``cdist``), :func:`score` puts the head on the rows that score the continuation, and
:meth:`ScoringForward.readback` ends a solve and counts the expert layers' load.
:class:`PreNormBlock` is the pre-norm layer three of the models are made of.

A new scoring model writes a config type (``from_dict`` from the published
``config.json``, refusing in words what the module does not compute), a block, and
``_layer``, which builds layer ``index`` from the config, beside the class attributes
:class:`ScoringForward` names. It overrides ``_embed`` only where its embedding is more
than a row lookup, and ``_document`` (on top of ``_walk`` and ``_scores``) only where it
returns more than :class:`Scores`.

No reference counterpart.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core import diagnostics
from .modules import Module, RMSNorm, _to_value, contract, normal_weight

__all__ = ["PreNormBlock", "Scores", "ScoringForward", "score"]

# the models run on seeded weights here: norm weights are drawn round one, so that a
# weight in the wrong place of an equation moves the logits
NORM_INIT_STD = 0.1


def score(norm, norm_params, head, h, targets, ahead: int = 1):
    """Head logits (float32) of the rows of ``h`` (T, d) that score ``targets``, the
    document's last ``c`` tokens, from ``ahead`` positions back (rows ``T-ahead-c ..
    T-ahead-1``), and the targets' log-likelihood under them."""
    t, c = h.shape[0], targets.shape[0]
    logits = contract("td,dv->tv", norm.apply(norm_params, h[t - ahead - c:t - ahead]),
                      head["weight"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return logits, jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=1))


def _routing(routed):
    """The expert layers' ``chosen`` and ``load``, each stacked over the layers."""
    return jnp.stack([a["chosen"] for a in routed]), jnp.stack([a["load"] for a in routed])


class Scores(NamedTuple):
    """What one scoring forward returns, all on the device. ``logits`` (c, vocab): the head
    at positions ``T-1-c .. T-2``, which score the last ``c`` tokens; ``loglik``: their
    log-likelihood (a float32 scalar); ``chosen`` (expert layers, T, k): every expert layer's
    routing over all experts; ``load`` (expert layers, experts held): rows each held expert
    multiplied."""

    logits: jax.Array
    loglik: jax.Array
    chosen: jax.Array
    load: jax.Array


class PreNormBlock(Module):
    """One layer on tokens ``(T, d)``: ``x <- x + attn(norm(x))``, then ``x <- x +
    ffn(norm(x))``, the two norms RMS norms over ``config.hidden_size``. A subclass builds the
    token mixing ``attn`` and the feed-forward ``ffn`` and hands them here. ``apply`` returns
    ``(x, aux)``, ``aux`` the expert layer's ``{"chosen", "load"}`` and what a mixing that
    returns one adds to it, or None for a dense layer that mixes without one."""

    def __init__(self, config, attn: Module, ffn: Module):
        c = config
        self.attn_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        self.attn = attn
        self.ffn_norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)
        self.ffn = ffn

    def apply(self, params, x, *, key=None, train=False):
        def with_aux(out):  # experts, and attention over a selection, give (y, aux)
            return out if isinstance(out, tuple) else (out, {})

        a, seen = with_aux(self.attn.apply(params["attn"],
                                           self.attn_norm.apply(params["attn_norm"], x)))
        x = x + a
        f, routed = with_aux(self.ffn.apply(params["ffn"],
                                            self.ffn_norm.apply(params["ffn_norm"], x)))
        return x + f, ({**seen, **routed} or None)


class ScoringForward(Module):
    """A model whose call scores one document ``tokens`` (T,) int32 in one compiled
    program, returning :class:`Scores` unless the model returns more.

    ``config`` is the model's ``config_type`` or the ``config.json`` dictionary it is read
    from; ``continuation`` is the number of trailing tokens that are scored;
    ``experts_held = (first, count)`` is the share of every expert layer that lives here (all
    by default, see :class:`~.moe.MoE`); ``block_rows`` is the block every held expert's
    group of rows is padded to (the model's own ``block_rows`` by default); parameters are
    stored in ``dtype`` (norms, router and the model's recurrent gates float32) and
    activations follow it. ``layer_options`` go to ``_layer`` as they are.

    A subclass names its ``config_type``, its trace counter (``traces``), its default
    ``block_rows``, the log-likelihood fields of what it returns (``logliks``) and how far
    back its deepest head looks (``ahead``), and writes ``_layer``."""

    config_type: type
    traces: str
    block_rows: int
    logliks: Tuple[str, ...] = ("loglik",)
    ahead: int = 1
    # the embedding is drawn at std hidden_size ** embed_exponent (one by default)
    embed_exponent: float = 0.0

    def __init__(self, config, continuation: int = 128,
                 experts_held: Optional[Tuple[int, int]] = None, dtype=jnp.bfloat16,
                 block_rows: Optional[int] = None, **layer_options):
        if not isinstance(config, self.config_type):
            config = self.config_type.from_dict(config)
        self.config = c = config
        self.continuation = continuation
        self.dtype = jnp.dtype(dtype)
        if block_rows is not None:
            self.block_rows = block_rows
        self.layers = [self._layer(c, i, experts_held, dtype, self.block_rows, **layer_options)
                       for i in range(c.num_hidden_layers)]
        self.norm = RMSNorm(c.hidden_size, c.rms_norm_eps, NORM_INIT_STD)

    def _layer(self, config, index: int, experts_held, dtype, block_rows: int) -> Module:
        """Layer ``index`` of the stack."""
        raise NotImplementedError

    def init(self, key):
        c, dt = self.config, self.dtype
        d = c.hidden_size
        k_embed, k_head, k_norm, *k_layers = jax.random.split(key, 3 + len(self.layers))
        return {
            "embed": {"weight": normal_weight(k_embed, (c.vocab_size, d), dt,
                                              d ** self.embed_exponent)},
            "layers": [layer.init(k) for layer, k in zip(self.layers, k_layers)],
            "norm": self.norm.init(k_norm),
            "head": {"weight": normal_weight(k_head, (d, c.vocab_size), dt, d ** -0.5)},
        }

    def _embed(self, params, tokens):
        """The stream the first layer reads."""
        return params["embed"]["weight"][tokens]

    def _walk(self, params, x, seen=None):
        """``x`` through the layers: the last layer's output and the expert layers' aux in
        order. ``seen(aux)``, where given, reads every layer's aux as the layer returns it."""
        routed = []
        for block, p in zip(self.layers, params["layers"]):
            x, aux = block.apply(p, x)
            if seen is not None:
                seen(aux)
            if aux and "chosen" in aux:
                routed.append(aux)
        return x, routed

    def _scores(self, params, targets, x, routed) -> Scores:
        """The head over the rows of ``x`` that score ``targets``, and the routing."""
        logits, loglik = score(self.norm, params["norm"], params["head"], x, targets)
        return Scores(logits, loglik, *_routing(routed))

    def _document(self, params, tokens):
        targets = tokens[tokens.shape[0] - self.continuation:]
        x, routed = self._walk(params, self._embed(params, tokens))
        return self._scores(params, targets, x, routed)

    @functools.cached_property
    def _program(self):
        return jax.jit(self._forward)

    def _forward(self, params, tokens):
        if diagnostics._enabled:
            diagnostics.counter(self.traces)  # trace time only
        return self._document(params, tokens.astype(jnp.int32))

    def apply(self, params, x, *, key=None, train=False):
        least = self.continuation + self.ahead + 1
        if x.ndim != 1 or x.shape[0] < least:
            raise ValueError(
                f"{type(self).__name__} scores one document of shape (T,), T >= "
                f"continuation + {self.ahead + 1} = {least}; got {x.shape}")
        return self._program(params, x)

    def __call__(self, tokens, **kwargs):
        return super().__call__(_to_value(tokens), **kwargs)

    def readback(self, scores) -> Tuple[float, ...]:
        """Wait for the program and bring the log-likelihoods to the host. With
        diagnostics on, the expert layers' load is then counted from the auxiliary
        output: ``nn.moe.tokens`` (rows the held experts multiplied, summed over the
        expert layers) and ``nn.moe.load_max`` (the fullest expert's rows, likewise)."""
        logliks = tuple(float(getattr(scores, name)) for name in self.logliks)
        if diagnostics._enabled:
            load = np.asarray(scores.load)
            diagnostics.counter("nn.moe.tokens", float(load.sum()))
            diagnostics.counter("nn.moe.load_max", float(load.max(axis=1).sum()))
        return logliks
