"""The scoring tail that the long-document models share.

The request is *scoring*: the log-likelihood of a document's last ``continuation`` tokens
given everything before them, as evaluation harnesses, rerankers and perplexity filters
ask it. That is one whole causal forward with no key/value cache and no decode loop, and
only the positions that score the continuation go through the head.

:class:`ScoringForward` is what :class:`~.xing4.Xing4` and :class:`~.trinity.Trinity`
have in common round their layers: ``model(tokens)`` runs through
:meth:`Module.__call__` like every module, the whole forward is **one compiled program a
call** (``nn.<model>.traces`` counts its traces, as ``spatial.cdist.traces`` does for
``cdist``), :func:`score` puts the head on the rows that score the continuation, and
:meth:`ScoringForward.readback` ends a solve and counts the expert layers' load.

No reference counterpart.
"""

from __future__ import annotations

import functools
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core import diagnostics
from .modules import Module, _to_value, contract

__all__ = ["ScoringForward", "score"]


def score(norm, norm_params, head, h, targets, ahead: int = 1):
    """Head logits (float32) of the rows of ``h`` (T, d) that score ``targets``, the
    document's last ``c`` tokens, from ``ahead`` positions back (rows ``T-ahead-c ..
    T-ahead-1``), and the targets' log-likelihood under them."""
    t, c = h.shape[0], targets.shape[0]
    logits = contract("td,dv->tv", norm.apply(norm_params, h[t - ahead - c:t - ahead]),
                      head["weight"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return logits, jnp.sum(jnp.take_along_axis(logp, targets[:, None], axis=1))


class ScoringForward(Module):
    """A model whose call scores one document ``tokens`` (T,) int32 in one compiled
    program. A subclass names its trace counter (``traces``), the log-likelihood fields of
    what it returns (``logliks``), how far back its deepest head looks (``ahead``), sets
    ``continuation`` and writes ``_document(params, tokens)``, the traced forward; its
    result has a ``load`` (expert layers, experts held)."""

    traces: str
    logliks: Tuple[str, ...] = ("loglik",)
    ahead: int = 1
    continuation: int

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
