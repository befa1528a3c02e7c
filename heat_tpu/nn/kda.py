"""Kimi Delta Attention (KDA): linear attention by a gated delta rule.

A head keeps a matrix state ``S`` (d_k, d_v) in place of keys and values: every position
decays it channel by channel, takes out what the state already answers for its key and
writes its value in (``core/kernels/delta_rule.py`` has the recurrence). On the input
``u`` (T, dim), with H heads of ``head_dim`` = d_k = d_v::

    q~, k~, v~ = u W_q, u W_k, u W_v
    q^_t       = SiLU(sum_j c_j * q~_{t-3+j})        causal depthwise convolution of width
                                                     ``conv_width``, zeros left of the
                                                     document; likewise k^, v^
    q_t, k_t   = q^ / |q^|_2 * d_k^-1/2, k^ / |k^|_2  per head;   v_t = v^
    f_t        = u W_f + dt_bias                                       a channel, float32
    g_t        = bound * sigmoid(exp(A_log_h) * f_t)                   bounded kind
               = -exp(A_log_h) * softplus(f_t)                         softplus kind
    beta_t     = sigmoid(u W_beta)                                     one a head
    S_t        = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t        = S_t^T q_t
    y_t        = W_o concat_h(sigmoid(u W_g)_h * RMSNorm(o_{t,h}))     one gate a head
               = W_o concat_h(sigmoid(u W_g)_{h,:} * RMSNorm(o_{t,h}))  one gate a channel

Two kinds of decay: the *bounded* one (Ling's ``kda_safe_gate``, ``bound <= g < 0``) and fla's
original *softplus* one (Kimi-Linear's), which has no bound below. ``W_f`` is one full-rank
matrix or, with ``decay_rank``, the low-rank pair ``W_fa W_fb`` (dim -> rank -> H d); the
gate is one scalar a head (``W_g`` dim x H) or, with ``gate_rank``, one a channel through the
low-rank pair ``W_ga W_gb`` (fla's ``FusedRMSNormGated``). The low-rank pairs' inner
activation is rounded to the input's type, as a stack of two linear layers leaves it.

No positions, no bias. This is the whole-sequence forward (scoring, prefill) from a zero
state: the state is not kept as a cache and nothing decodes through it. Everything between
the projections runs chunk by chunk in ``core/kernels/delta_rule.py``: on a TPU in one
Pallas call (``kda_chunk_fwd`` for a bound of at least -5, ``kda_unbounded_fwd`` otherwise),
which takes the three projections as they are stored and returns the gated, normed heads,
so q, k, v and o never go through HBM on their own; where its gate declines (another
backend, a head width off the lane tiles, a sequence that is no whole number of chunks) the
same chunk step runs in plain ``jnp`` and ``record_fallback("nn.kda", why)`` says why.
Parameters are stored in ``dtype`` (``A_log``, ``dt_bias`` and the norm weight float32);
contractions accumulate in float32; the convolution, the norms, the decay (from a float32
pre-activation), beta and the gate are computed in float32, and q, k, ``beta k`` and ``beta
v`` go to the recurrence in the input's type with ``g`` in float32.

No reference counterpart.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core import diagnostics
from ..core.kernels import delta_rule
from .modules import Module, RMSNorm, contract, normal_weight

__all__ = ["KimiDeltaAttention"]


class KimiDeltaAttention(Module):
    """The KDA token mixing on tokens ``(T, dim)``; see the module's docstring for the
    equations. ``log_decay_bound`` is the published ``kda_lower_bound`` of the bounded kind
    (below 0), or None for the softplus kind; ``decay_rank`` and ``gate_rank`` make the
    decay's projection and the output gate low-rank pairs, the gate then one a channel."""

    def __init__(self, dim: int, num_heads: int, head_dim: int, conv_width: int = 4,
                 log_decay_bound: Optional[float] = delta_rule.LOG_DECAY_BOUND,
                 eps: float = 1e-6, dtype=jnp.float32, norm_init_std: float = 0.0,
                 decay_rank: Optional[int] = None, gate_rank: Optional[int] = None):
        if log_decay_bound is not None and not log_decay_bound < 0:
            raise ValueError(f"a bounded KDA decay has its bound below 0; got {log_decay_bound}")
        self.dim, self.num_heads, self.head_dim = dim, num_heads, head_dim
        self.conv_width = conv_width
        self.bound = None if log_decay_bound is None else float(log_decay_bound)
        self.decay_rank, self.gate_rank = decay_rank, gate_rank
        self.eps = eps
        self.dtype = jnp.dtype(dtype)
        self.o_norm = RMSNorm(head_dim, eps, norm_init_std)

    def init(self, key):
        ks = jax.random.split(key, 13)
        d, h, wide, dt = self.dim, self.num_heads, self.num_heads * self.head_dim, self.dtype

        def projection(k, out):
            return normal_weight(k, (d, out), dt, d ** -0.5)

        def taps(k):
            return normal_weight(k, (self.conv_width, wide), dt, self.conv_width ** -0.5)

        def pair(k, rank, out):  # dim -> rank -> out, each factor of unit gain
            ka, kb = jax.random.split(k)
            return projection(ka, rank), normal_weight(kb, (rank, out), dt, rank ** -0.5)

        params = {
            "wq": projection(ks[0], wide), "wk": projection(ks[1], wide),
            "wv": projection(ks[2], wide), "wb": projection(ks[4], h),
            "conv_q": taps(ks[6]), "conv_k": taps(ks[7]), "conv_v": taps(ks[8]),
            "o_norm": self.o_norm.init(ks[11]),
            "wo": normal_weight(ks[12], (wide, d), dt, wide ** -0.5),
        }
        if self.decay_rank is None:
            params["wf"] = projection(ks[3], wide)
        else:
            params["wf_a"], params["wf_b"] = pair(ks[3], self.decay_rank, wide)
        if self.gate_rank is None:
            params["wg"] = projection(ks[5], h)
        else:
            params["wg_a"], params["wg_b"] = pair(ks[5], self.gate_rank, wide)
        if self.bound is None:
            # fla's rates, A = 1 .. 16, on a pre-activation whose bias lies in -5 .. 0: log-decays
            # from -0.001 to below -17 a step, about a tenth of them steeper than -5
            params["a_log"] = jnp.log(jax.random.uniform(ks[9], (h,), jnp.float32, 1.0, 16.0))
            params["dt_bias"] = jax.random.uniform(ks[10], (wide,), jnp.float32, -5.0, 0.0)
        else:
            # decays from a few positions to several hundred: a rate of 0.5 .. 2 on a
            # pre-activation whose bias lies in -8 .. 0
            params["a_log"] = jnp.log(jax.random.uniform(ks[9], (h,), jnp.float32, 0.5, 2.0))
            params["dt_bias"] = jax.random.uniform(ks[10], (wide,), jnp.float32, -8.0, 0.0)
        return params

    def _through(self, x, params, name, out):
        """``x W_<name>`` (float32) by the full-rank matrix or its low-rank pair."""
        if name in params:
            return contract(f"td,d{out}->t{out}", x, params[name])
        inner = contract("td,dr->tr", x, params[name + "_a"]).astype(x.dtype)
        return contract(f"tr,r{out}->t{out}", inner, params[name + "_b"])

    def apply(self, params, x, *, key=None, train=False):
        if x.ndim != 2:
            raise ValueError(f"KimiDeltaAttention mixes tokens of shape (T, dim); got {x.shape}")
        h, dt = self.num_heads, x.dtype
        with jax.named_scope("ht.nn.kda"):
            xq, xk, xv = (contract("td,de->te", x, params["w" + name]).astype(dt)
                          for name in "qkv")
            taps = tuple(params["conv_" + name] for name in "qkv")
            # one rate a head, laid over its channels; the pre-activation stays float32
            rate = jnp.repeat(jnp.exp(params["a_log"]), self.head_dim)
            pre = self._through(x, params, "wf", "e") + params["dt_bias"]
            beta = jax.nn.sigmoid(contract("td,dh->th", x, params["wb"]))
            gate = self._through(x, params, "wg", "h")
            if self.gate_rank is None:
                gate = jax.nn.sigmoid(gate)  # a channel gate's sigmoid is the kernel's
            why = (delta_rule.decline_reason(xq, taps[0], h) if delta_rule.available()
                   else f"backend {jax.default_backend()}")
            mix = delta_rule.kda_mix
            if why is not None:
                diagnostics.record_fallback("nn.kda", f"{why}: T={x.shape[0]} {dt}")
                mix = delta_rule.kda_mix_reference
            y = mix(xq, xk, xv, taps, pre, rate, beta, gate, params["o_norm"]["weight"], h,
                    self.bound, self.eps)
            return contract("te,ed->td", y, params["wo"]).astype(dt)
