"""Manifold-constrained hyper-connections: ``n`` residual streams in place of one.

A layer's residual path is widened to ``X`` in R^{n x d}. Round every sub-block ``F``
(attention, feed-forward) three mappings are computed from the streams themselves:
``H_pre`` (n) reads the sub-block's input out of the streams, ``H_post`` (n) writes its
output back into them, and ``H_res`` (n x n) mixes the streams on the residual path::

    x'     = RMSNorm(flatten X)                         over n*d
    H_pre  = sigmoid(a_pre  * (x' Phi_pre)  + b_pre)
    H_post = 2 sigmoid(a_post * (x' Phi_post) + b_post)
    H_res  = sinkhorn(clip(a_res * reshape(x' Phi_res, n x n) + b_res, lo, hi))
    X     <- H_res X + H_post^T F(norm(H_pre X))

``sinkhorn`` exponentiates and then alternates column and row normalisation, which
keeps ``H_res`` (nearly) doubly stochastic: the residual path neither amplifies nor
drops a stream, whatever the depth (hyper-connections, arXiv 2409.19606; the manifold
constraint, arXiv 2512.24880). The mappings and Sinkhorn are float32 whatever the
streams' type; reading and writing the streams is bandwidth-bound work next to the
MXU-bound sub-blocks.

No reference counterpart (the reference has no residual networks of its own).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .modules import Module, RMSNorm, contract

__all__ = ["HyperConnection", "sinkhorn"]


def sinkhorn(logits: jax.Array, iters: int, eps: float) -> jax.Array:
    """``exp(logits)`` over the last two axes, then ``iters`` times: every column over
    its sum + ``eps``, then every row over its sum + ``eps``. float32."""
    m = jnp.exp(logits.astype(jnp.float32))
    eps = jnp.float32(eps)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


class HyperConnection(Module):
    """The three mappings of one sub-block over streams ``X`` of shape (n, ..., d): the
    stream axis leads, so that a stream is one contiguous (..., d) slab on the device
    (the mappings keep it last: ``H_pre`` (..., n), ``H_res`` (..., n, n)). Flattened, a
    token's streams are ordered stream by stream, which is the row order of ``Phi``.

    ``mappings(params, X)`` gives ``(H_pre, H_post, H_res)``; ``read`` and ``write``
    apply them; ``apply(params, (X, F))`` is the whole residual step for a callable
    ``F`` that maps (..., d) to ``(y, aux)``, ``y`` (..., d), and returns ``(X, aux)``.
    """

    def __init__(self, dim: int, streams: int, sinkhorn_iters: int = 20, eps: float = 1e-6,
                 res_clamp=(-30.0, 30.0), norm_eps: float = 1e-6, norm_init_std: float = 0.0):
        self.dim = dim
        self.streams = streams
        self.sinkhorn_iters = sinkhorn_iters
        self.eps = eps
        self.res_clamp = tuple(res_clamp)
        self.norm = RMSNorm(streams * dim, norm_eps, norm_init_std)

    def init(self, key):
        n, width = self.streams, self.streams * self.dim
        k_phi, k_alpha, k_bias, k_norm = jax.random.split(key, 4)
        # every mapping moves with its input (alpha of order 1) and the residual mixing
        # prefers a stream's own past (b_res near 2 I) without leaving the others out
        bias = 0.5 * jax.random.normal(k_bias, (2 * n + n * n,), jnp.float32)
        bias = bias.at[2 * n:].add(2.0 * jnp.eye(n, dtype=jnp.float32).reshape(-1))
        return {
            "norm": self.norm.init(k_norm),
            "phi": jax.random.normal(k_phi, (width, 2 * n + n * n), jnp.float32)
            * jnp.float32(width ** -0.5),
            "alpha": jax.random.uniform(k_alpha, (3,), jnp.float32, 0.5, 1.0),
            "bias": bias,
        }

    def mappings(self, params, x):
        n, d = self.streams, self.dim
        # RMSNorm(flat X) Phi = (sum_j X[j] (w[j] * Phi[j])) * rsqrt(mean X^2 + eps): the
        # norm's weight is folded into Phi and the sum runs stream by stream, so neither
        # the flattened nor the normed copy of the streams is ever written
        phi = (params["norm"]["weight"][:, None] * params["phi"]).reshape(n, d, -1)
        square, raw = 0.0, 0.0
        for j in range(n):
            xj = x[j].astype(jnp.float32)
            square = square + jnp.sum(xj * xj, axis=-1, keepdims=True)
            raw = raw + contract("...d,dm->...m", xj, phi[j])
        raw = raw * jax.lax.rsqrt(square / jnp.float32(n * d) + jnp.float32(self.norm.eps))
        alpha, bias = params["alpha"], params["bias"]
        pre = alpha[0] * raw[..., :n] + bias[:n]
        post = alpha[1] * raw[..., n:2 * n] + bias[n:2 * n]
        res = alpha[2] * raw[..., 2 * n:].reshape(*raw.shape[:-1], n, n) \
            + bias[2 * n:].reshape(n, n)
        res = jnp.clip(res, jnp.float32(self.res_clamp[0]), jnp.float32(self.res_clamp[1]))
        return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
                sinkhorn(res, self.sinkhorn_iters, self.eps))

    @staticmethod
    def read(h_pre, x):
        """``H_pre X``: (..., n) and (n, ..., d) to (..., d), in the streams' type; one
        elementwise pass, unrolled over the streams."""
        u = sum(h_pre[..., j, None] * x[j].astype(jnp.float32) for j in range(x.shape[0]))
        return u.astype(x.dtype)

    @staticmethod
    def write(h_res, h_post, x, y):
        """``H_res X + H_post^T y`` in float32, rounded to the streams' type; one
        elementwise pass, unrolled over the streams."""
        y32 = y.astype(jnp.float32)
        rows = []
        for i in range(x.shape[0]):
            row = h_post[..., i, None] * y32
            for j in range(x.shape[0]):
                row = row + h_res[..., i, j, None] * x[j].astype(jnp.float32)
            rows.append(row.astype(x.dtype))
        return jnp.stack(rows, axis=0)

    def apply(self, params, x, *, key=None, train=False):
        streams, f = x
        with jax.named_scope("ht.nn.mhc"):
            h_pre, h_post, h_res = self.mappings(params, streams)
            u = self.read(h_pre, streams)
        y, aux = f(u)
        with jax.named_scope("ht.nn.mhc"):
            return self.write(h_res, h_post, streams, y), aux
