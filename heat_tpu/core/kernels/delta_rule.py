"""Kimi Delta Attention between its projections: the gated delta-rule recurrence, chunked,
with the convolution, norms and gates round it (Pallas, TPU).

For one head, with a state ``S`` (d_k, d_v) in float32 that starts at 0::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` (d_k,) is the log-decay of every channel, ``g_t <= 0``, ``beta_t`` a scalar in (0, 1),
``|k_t| = 1``. :func:`chunk_step` takes ``k`` and the two products ``beta k`` and ``beta v``,
rounded to the operands' type.

**Two kinds of decay.** The *bounded* kind (Ling's ``kda_safe_gate``) is ``g = bound *
sigmoid(rate * pre)``, ``bound <= g <= 0``; the *softplus* kind (fla's original gate, which
Kimi-Linear ships) is ``g = -rate * softplus(pre)``, with no bound below. ``pre`` is the
float32 pre-activation a channel, ``rate`` = ``exp(A_log)`` a head laid over its channels;
either is computed in float32 inside the step.

**The chunked (WY) form.** Over a chunk of ``C`` = :data:`CHUNK` positions that starts at
state ``S_0``, with ``G_r = g_1 + .. + g_r`` (float32, inside the chunk)::

    A[r, i]   = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])        i <  r, else 0
    Aqk[r, i] =        sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])        i <= r, else 0
    T         = (I + A)^-1
    U         = T (beta V)  -  T (beta K * exp(G)) S_0                 the chunk's updates
    O         = (Q * exp(G)) S_0  +  Aqk U
    S_C       = Diag(exp(G_C)) S_0  +  (K * exp(G_C - G))^T U

``exp(-G)`` **is never taken over more than one sub-chunk**: a chunk is cut into sub-chunks
of ``sub`` rows, a row block ``J`` takes its reference ``b_J`` = ``G`` just before the block,
its rows carry ``exp(G_r - b_J + half)`` and the columns ``exp(min(b_J - G_i - half,
half))``, where ``2 half`` is the largest log-decay a sub-chunk can span: that range is
shared between the two factors, so neither leaves float32 (a row factor lies in
``exp(+-half)``; a column factor underflows only where the pair's weight is under
``exp(half - 87)``); right of the block the clamp holds and the triangle's select drops the
entry. Two forms, chosen by the decay (static):

- *narrow*, the bounded kind with ``bound >= ``:data:`LOG_DECAY_BOUND` (-5): sub-chunks of
  :data:`SUB` = 16 rows, ``half`` = 16 * 5 / 2 = 40; the Pallas call ``kda_chunk_fwd``.
- *wide*, the softplus kind or a wider bound: every step's log-decay is first floored at
  :data:`FLOOR` = -17. That is exact to float32: a pair whose positions a floored step lies
  between weighs less than ``exp(-17) < 2^-24`` before and after, and no other pair's weight
  moves, so every weight ``exp(G_r - G_i)`` of at least ``2^-24`` is the unfloored one (the
  state's ``exp(G_r)`` and ``exp(G_C)`` likewise). Sub-chunks of :data:`WIDE_SUB` = 8 rows
  then span at most 136, ``half`` = 68. Entries of the sub-chunk that lie right of the
  diagonal may overflow to ``inf`` in this form; the triangle's select drops them, and no
  product reads them. The Pallas call ``kda_unbounded_fwd``.

``T`` is exact arithmetic on nilpotent float32 matrices: the ``sub`` x ``sub`` diagonal
blocks by ``(I + X)^-1 = (I - X)(I + X^2)(I + X^4)..`` (powers of a sub-chunk's block stay
small: no cancellation), then the ``C / sub`` block structure the same way: ten products in
all for either form (narrow: six for the blocks of 16, one for the blocks below them, two for
their structure of 4, one to finish; wide: four, one, four, one). ``T`` is then rounded to
the operands' type, so its products follow that type
(:func:`_product`): for float32 operands ``Precision.HIGHEST``; for bfloat16 operands each
factor as two bfloat16 pieces and three products, 2^-17 of the result, at a third of the
MXU passes and none of the splits and sums that six passes bring. Every other product takes
its operands in the type they are stored in (gated operands are formed in float32 and
rounded to it; the state is rounded to it for its two products, as published kernels do)
and accumulates in float32; 16-bit operands are one MXU pass, float32 operands multiply at
``Precision.HIGHEST``. The cumulative log-decay is exact in float32: the 0/1 triangle is
exact in bfloat16 and ``g`` goes in as three bfloat16 pieces (24 bits) side by side.

**Two heads to a lane row.** A C x C matrix of one head fills half of each 128-lane vreg.
Where a step's head count is even (:func:`_paired`), heads ``2p`` and ``2p + 1`` lie side by
side, ``(n / 2, C, 2C)``: ``A`` and ``Aqk`` are put together a pair from the sub-chunks'
products, their masks are applied once a pair, and each product of ``T`` takes the pair's
left operand against the block diagonal ``[[X_2p, 0], [0, X_2p+1]]`` (2C x 2C) of its right
one, split into bfloat16 pieces once a pair: one product 128 deep a pair where there were
two 64 deep a head. The terms added are exact zeros, so each head's result is what it was a
head at a time. ``T`` and ``Aqk`` are sliced back into heads for their products with the
d-wide operands (faster on a v5e than block-diagonal right operands there: 12.66 against
12.77 ms a layer of 32 heads over 32,768 positions, narrow form). An odd count keeps the
form a head at a time. Eight heads a step, paired, take a layer 12.5 ms (narrow) and 14.0 ms
(wide) on a v5e, 14.75 and 16.28 a head at a time.

**Round the recurrence** (:func:`head_chunk`) a chunk step also does what Kimi Delta
Attention puts before and after it, so that q, k, v, g and o never go through HBM on their
own: from the three projections as stored, the causal depthwise convolution (its taps on
the :data:`BEFORE` rows before the chunk too), SiLU, the L2 norm of q and k a head and
``beta``, and the log-decay from its float32 pre-activation; after it the head's RMS norm
and its sigmoid gate. **Two widths of gate**: one a head (Ling's ``head_wise``), which rides
beside ``beta``, or one a channel (fla's ``FusedRMSNormGated``, Kimi-Linear's), whose
float32 pre-activation comes in as a ``(T, H d)`` operand beside ``pre``, chunk by chunk.

**The call.** ``xq, xk, xv``, the decay's pre-activation and a channel gate's (T, H d) lie as
the projections leave them, heads side by side on the lanes, so nothing is transposed on the
way in or out.
The grid is (head groups, chunks): a step takes one chunk of :data:`HEADS` heads, which
ride a leading axis through :func:`chunk_step` (as four pairs for its C x C work) so that
every product is issued for all of them at once: a head's inverse alone is a chain of ten
dependent products, each a full MXU latency, and heads looped one after another did not
overlap (36.8 ms a layer of 32 heads over 32,768 positions on a v5e for four heads a step,
41.9 for one; on a leading axis 18.7 for four, 15.2 for eight, 14.4 for sixteen, before the
heads were paired). Each head's state
(kept as ``S^T``, so a channel's decay is a lane's) stays in VMEM over the chunk axis, which
is sequential. The rows before a chunk are a second, 16-row view of the same three arrays.

:func:`kda_mix_reference` is the same chunk step on all heads at once under ``lax.scan``
over the chunks, in plain ``jnp``: the fallback of ``nn/kda.py`` on other backends and
shapes (it pads a sequence that does not tile), and what the tests hold the interpreted
kernel to, beside the token-by-token recurrences of the benchmark's
``reference_ling.py`` and ``reference_kimi_linear.py``.

No reference counterpart.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import diagnostics

__all__ = ["CHUNK", "SUB", "WIDE_SUB", "BEFORE", "LOG_DECAY_BOUND", "FLOOR", "chunk_step",
           "short_conv", "head_chunk", "kda_mix", "kda_mix_reference", "available",
           "decline_reason"]

CHUNK = 64
SUB = 16
BEFORE = 16  # rows before a chunk that a step sees (a bfloat16 tile): the convolution's reach
LOG_DECAY_BOUND = -5.0  # SUB * 5 = 80 < 88: exp stays inside float32 over a sub-chunk
HEADS = 8  # heads a grid step takes together
_LANES = 128
_HALF = -LOG_DECAY_BOUND * SUB / 2  # 40: half of a sub-chunk's range of log-decay
FLOOR = -17.0  # the wide form's floor on a step's log-decay: exp(-17) < 2^-24
WIDE_SUB = 8
_WIDE_HALF = -FLOOR * WIDE_SUB / 2  # 68: half of a wide sub-chunk's range of log-decay
_L2_EPS = 1e-6
_F32 = jnp.float32


def _dot(a, b, dims):
    """``dot_general`` of (n, ., .) operands over ``dims``, one product a head, accumulated
    in float32: 16-bit operands one MXU pass (whatever the process-wide default says),
    float32 operands exact."""
    exact = a.dtype.itemsize >= 4 or b.dtype.itemsize >= 4
    return lax.dot_general(a, b, (dims, ((0,), (0,))), preferred_element_type=_F32,
                           precision=lax.Precision.HIGHEST if exact else lax.Precision.DEFAULT)


_NN = ((2,), (1,))  # a @ b
_NT = ((2,), (2,))  # a @ b^T
_TN = ((1,), (1,))  # a^T @ b


def _pieces(x, n: int):
    """``x`` (float32) as ``n`` bfloat16 pieces that add up to it: 8, 16, 24 bits."""
    parts, rest = [], x
    for _ in range(n):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(_F32)
    return parts


def _paired(n: int) -> bool:
    """Whether a step of ``n`` heads lays its C x C matrices out two heads to a lane row."""
    return n % 2 == 0


def _side_by_side(y):
    """(n, r, w) a head each as (n / 2, r, 2w): heads 2p and 2p + 1 side by side."""
    n, r, w = y.shape
    y = y.reshape(n // 2, 2, r, w)
    return jnp.concatenate([y[:, 0], y[:, 1]], axis=2)


def _by_heads(y):
    """(n / 2, r, 2w), two heads side by side, as (n, r, w) a head each."""
    h, r, w = y.shape
    return jnp.stack([y[:, :, :w // 2], y[:, :, w // 2:]], axis=1).reshape(2 * h, r, w // 2)


def _block_diagonal(y):
    """A pair's two C x C matrices side by side, (n, C, 2C), as the (n, 2C, 2C)
    ``[[y_2p, 0], [0, y_2p+1]]``; a head's own (n, C, C) as it is."""
    c, w = y.shape[1:]
    if w == c:
        return y
    zero = jnp.zeros((y.shape[0], c, c), y.dtype)
    return jnp.concatenate([jnp.concatenate([y[:, :, :c], zero], axis=2),
                            jnp.concatenate([zero, y[:, :, c:]], axis=2)], axis=1)


def _product(a, b, exact: bool):
    """``a @ b`` of float32 squares, a head each, or of pairs side by side (n, C, 2C), where
    ``a`` multiplies :func:`_block_diagonal` of ``b``: one product 2C deep a pair where there
    were two C deep, and exact zeros added. ``exact``: at ``Precision.HIGHEST`` (six MXU
    passes with their splits and sums). Otherwise each operand as two bfloat16 pieces, split
    once a pair, and the three products that matter, ``(a_hi + a_lo) b_hi + a_hi b_lo``:
    2^-17 of the result, for a result that is rounded to bfloat16's 2^-9 when it is used."""
    if exact:
        return _dot(a, _block_diagonal(b), _NN)
    n = a.shape[1]
    (a_hi, a_lo), (b_hi, b_lo) = _pieces(a, 2), [_block_diagonal(p) for p in _pieces(b, 2)]
    both = _dot(jnp.concatenate([a_hi, a_lo], axis=1), b_hi, _NN)
    return both[:, :n] + both[:, n:] + _dot(a_hi, b_lo, _NN)


def _inverse_of_one_plus(x, nilpotency: int, exact: bool):
    """``(I + x)^-1`` of float32 squares ``x`` (n, c, c), or of pairs side by side (n, c, 2c),
    with ``x^nilpotency = 0``: ``(I - x)(I + x^2)(I + x^4)..``, each factor one
    :func:`_product`."""
    c, w = x.shape[1:]
    eye = (lax.broadcasted_iota(jnp.int32, (c, w), 0)
           == lax.rem(lax.broadcasted_iota(jnp.int32, (c, w), 1), jnp.int32(c))).astype(_F32)
    inv, power, reach = eye - x, x, 2
    while reach < nilpotency:
        power = _product(power, power, exact)
        inv = inv + _product(inv, power, exact)
        reach *= 2
    return inv


def chunk_step(q, k, kb, vb, g, st, sub: int = SUB, half: float = _HALF):
    """One chunk of ``n`` heads, every line a head's own: the heads ride a leading axis so
    that each product is issued for all of them before the next one that waits for it
    (their chains are independent, a chain's products are not); where ``n`` is even the C x C
    matrices take heads 2p and 2p + 1 side by side on the lanes (:func:`_paired`). ``q, k,
    kb`` (n, C, d_k) and ``vb`` (n, C, d_v) in the operands' type, ``g`` (n, C, d_k) float32,
    ``st`` the states transposed, (n, d_v, d_k) float32. Returns ``(o (n, C, d_v) float32,
    st)``. ``C`` is a whole number of sub-chunks of ``sub`` rows, over which ``g`` sums to no
    less than ``-2 half``: the narrow form's defaults, or :data:`WIDE_SUB` and ``_WIDE_HALF``
    on log-decays floored at :data:`FLOOR`."""
    n, c, d_k = k.shape
    op = q.dtype
    row = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    same = (row // sub) == (col // sub)
    # the log-decay summed inside a row's sub-chunk (inclusive) and before the sub-chunk:
    # a 0/1 triangle, exact in bfloat16, times g as three bfloat16 pieces side by side
    triangle = jnp.concatenate([same & (col <= row), (col // sub) < (row // sub)])
    sums = _dot(jnp.broadcast_to(triangle.astype(jnp.bfloat16), (n, 2 * c, c)),
                jnp.concatenate(_pieces(g, 3), axis=2), _NN)
    sums = sums[:, :, :d_k] + sums[:, :, d_k:2 * d_k] + sums[:, :, 2 * d_k:]
    local, before = sums[:, :c], sums[:, c:]
    total = before + local
    kf = k.astype(_F32)
    # a row's and a column's factor share the sub-chunk's range of exp(+-2 half) between them
    lift = jnp.exp(local + half)
    rows_k, rows_q = kb.astype(_F32) * lift, q.astype(_F32) * lift
    # from here on a C x C matrix is (n, C, C), or (n / 2, C, 2C) with a pair on the lanes:
    # its masks, pieces and products then fill the vregs
    paired = _paired(n)
    if paired:
        row = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
        col = lax.rem(lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1), jnp.int32(c))
        same = (row // sub) == (col // sub)
    a_parts, qk_parts = [], []
    for j in range(c // sub):
        lo = j * sub
        columns = (kf * jnp.exp(jnp.minimum(before[:, lo:lo + 1] - total - half, half))
                   ).astype(op)
        rows = jnp.concatenate([rows_k[:, lo:lo + sub], rows_q[:, lo:lo + sub]], axis=1)
        s = _dot(rows.astype(op), columns, _NT)
        if paired:
            s = _side_by_side(s)
        a_parts.append(s[:, :sub])
        qk_parts.append(s[:, sub:])
    a = jnp.where(col < row, jnp.concatenate(a_parts, axis=1), 0.0)
    a_qk = jnp.where(col <= row, jnp.concatenate(qk_parts, axis=1), 0.0)
    # T = (I + A)^-1: the diagonal blocks, then the blocks below them
    exact = op.itemsize >= 4  # T is rounded to the operands' type: float32 wants it exact
    diagonal = _inverse_of_one_plus(jnp.where(same, a, 0.0), sub, exact)
    below = _product(diagonal, jnp.where(same, 0.0, a), exact)
    t = _product(_inverse_of_one_plus(below, c // sub, exact), diagonal, exact).astype(op)
    if paired:  # a head's own T and Aqk for the products with d-wide operands
        t, a_qk = _by_heads(t), _by_heads(a_qk)

    decay = jnp.exp(total)
    wu = _dot(t, jnp.concatenate([(kb.astype(_F32) * decay).astype(op), vb], axis=2), _NN)
    st_op = st.astype(op)
    u = (wu[:, :, d_k:] - _dot(wu[:, :, :d_k].astype(op), st_op, _NT)).astype(op)
    o = _dot((q.astype(_F32) * decay).astype(op), st_op, _NT) + _dot(a_qk.astype(op), u, _NN)
    whole = jnp.sum(g, axis=1, keepdims=True)  # the chunk's log-decay, (n, 1, d_k)
    st = st * jnp.exp(whole) + _dot(u, (kf * jnp.exp(whole - total)).astype(op), _TN)
    return o, st


def short_conv(x, rows, w):
    """``SiLU(sum_j w[j] * x_{t-(width-1)+j})`` over a chunk ``x`` (n, C, d) whose ``rows``
    (n, B, d) come before it, with ``w`` (n, width, d) float32: a causal depthwise
    convolution, float32."""
    c, width = x.shape[1], w.shape[1]
    ext = jnp.concatenate([rows.astype(_F32), x.astype(_F32)], axis=1)
    first = rows.shape[1] - (width - 1)
    y = w[:, 0:1] * ext[:, first:first + c]
    for j in range(1, width):
        y = y + w[:, j:j + 1] * ext[:, first + j:first + j + c]
    return y * jax.nn.sigmoid(y)


def _wide(bound: Optional[float]) -> bool:
    """Whether a decay of this ``bound`` (None: the softplus kind) takes the wide form."""
    return bound is None or bound < LOG_DECAY_BOUND


def _softplus(x):
    """``log(1 + exp(x))`` in float32, as Mosaic lowers it: no overflow for large ``x``."""
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def head_chunk(xq, xk, xv, before, taps, pre, rate, side, norm_w, st, bound: Optional[float],
               eps: float, gate=None):
    """One chunk of ``n`` heads from the projections to the gated, normed output. ``xq, xk,
    xv`` (n, C, d) as stored; ``before``: the three arrays' :data:`BEFORE` rows before the
    chunk (zeros at the document's start); ``taps``: three (n, width, d) float32; ``pre`` (n,
    C, d) and ``rate`` (n, 1, d) float32: the log-decay is ``bound * sigmoid(rate * pre)``,
    or with ``bound`` None ``-rate * softplus(pre)``; ``side`` (n, C, 2) float32: beta and the
    head gate's sigmoid, or (n, C, 1), beta alone, where ``gate`` (n, C, d) float32 is the
    pre-activation of a gate a channel; ``norm_w`` (1, d) float32; ``st`` (n, d, d) float32.
    Returns ``(y (n, C, d) float32, st)``."""
    d, op = xq.shape[2], xq.dtype
    if bound is None:
        g = -rate * _softplus(pre)
    else:
        g = bound * jax.nn.sigmoid(rate * pre)
    form = (SUB, _HALF)
    if _wide(bound):  # a floor where nothing of float32 is lost: see the module's text
        g, form = jnp.maximum(g, FLOOR), (WIDE_SUB, _WIDE_HALF)

    def unit(y):
        return y * lax.rsqrt(jnp.sum(y * y, axis=2, keepdims=True) + _L2_EPS)

    q, k, v = (short_conv(x, rows, w) for x, rows, w in zip((xq, xk, xv), before, taps))
    q, k = unit(q) * d ** -0.5, unit(k)
    beta, gate = side[:, :, 0:1], side[:, :, 1:2] if gate is None else jax.nn.sigmoid(gate)
    o, st = chunk_step(q.astype(op), k.astype(op), (beta * k).astype(op), (beta * v).astype(op),
                       g, st, *form)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=2, keepdims=True) + eps) * norm_w
    return o * gate, st


def _taps32(taps):
    return tuple(w.astype(_F32) for w in taps)


def kda_mix_reference(xq, xk, xv, taps, pre, rate, beta, gate, norm_w, heads: int,
                      bound: Optional[float], eps: float):
    """The chunked form in plain ``jnp``: operands as :func:`kda_mix` takes them. A sequence
    that is no whole number of chunks is padded with positions after its end, which nothing
    before them reads, and cut again."""
    t, d = xq.shape[0], xq.shape[1] // heads
    n = -(-t // CHUNK)

    def by_chunk(x, width):
        x = jnp.pad(x, ((0, n * CHUNK - t), (0, 0)))
        return jnp.moveaxis(x.reshape(n, CHUNK, heads, width), 2, 1)  # (n, heads, C, width)

    xs = [by_chunk(x, d) for x in (xq, xk, xv)]
    # the rows before chunk j are the last of chunk j - 1; zeros before the first
    rows = [jnp.concatenate([jnp.zeros_like(x[:1, :, :BEFORE]), x[:-1, :, CHUNK - BEFORE:]])
            for x in xs]
    if gate.shape[1] == heads:
        side, channel = by_chunk(jnp.stack([beta, gate], axis=-1).astype(_F32)
                                 .reshape(t, 2 * heads), 2), ()
    else:  # one gate a channel: beta alone beside the decay, the gate chunk by chunk too
        side, channel = by_chunk(beta.astype(_F32), 1), (by_chunk(gate.astype(_F32), d),)
    taps = tuple(jnp.moveaxis(w.reshape(-1, heads, d), 1, 0) for w in _taps32(taps))
    rate, norm_w = rate.astype(_F32).reshape(heads, 1, d), norm_w.astype(_F32).reshape(1, d)

    def one(st, chunk):
        y, st = head_chunk(*chunk[:3], chunk[3:6], taps, chunk[6], rate, chunk[7], norm_w, st,
                           bound, eps, *chunk[8:])
        return st, y

    st0 = jnp.zeros((heads, d, d), _F32)
    _, y = lax.scan(one, st0, (*xs, *rows, by_chunk(pre.astype(_F32), d), side, *channel))
    return jnp.moveaxis(y, 1, 2).reshape(n * CHUNK, heads * d)[:t].astype(xq.dtype)


def available(interpret: bool = False) -> bool:
    """Whether the kernel can run here: on a TPU backend, or interpreted anywhere."""
    return interpret or jax.default_backend() == "tpu"


def decline_reason(x, taps, heads: int) -> Optional[str]:
    """Why the kernel is not compiled for projections ``x`` (T, heads * d) and convolutions
    of ``taps`` (width, heads * d), or ``None`` where it is."""
    if x.dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"operands {x.dtype}: the kernel takes bfloat16 or float32"
    t, d = x.shape[0], x.shape[1] // heads
    if d % _LANES:
        return f"tiles: a head's width d={d} must be whole lane tiles of {_LANES}"
    if t % CHUNK:
        return f"tiles: T={t} is no whole number of chunks of {CHUNK}"
    if taps.shape[0] - 1 > BEFORE:
        return f"a convolution of width {taps.shape[0]} reaches past the {BEFORE} rows a step sees"
    return None


def _heads_a_step(heads: int) -> int:
    hb = min(HEADS, heads)
    while heads % hb:
        hb -= 1
    return hb


def _kernel(*refs, hb: int, d: int, bound: Optional[float], eps: float):
    import jax.experimental.pallas as pl

    (xq_ref, xk_ref, xv_ref, rq_ref, rk_ref, rv_ref, wq_ref, wk_ref, wv_ref, pre_ref, rate_ref,
     side_ref, norm_ref) = refs[:13]
    channel, (y_ref, st_ref) = refs[13:-2], refs[-2:]  # a channel gate's ref, if any

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _start():  # a head group's first chunk: S_0 = 0
        st_ref[...] = jnp.zeros_like(st_ref)

    inside = (j > 0).astype(_F32)  # the rows before the first chunk are zeros, not rows 0..15

    def heads_of(ref, width=d):  # (rows, hb * width) on the lanes -> (hb, rows, width)
        return jnp.stack([ref[:, h * width:(h + 1) * width] for h in range(hb)])

    before = tuple(heads_of(r).astype(_F32) * inside for r in (rq_ref, rk_ref, rv_ref))
    taps = tuple(heads_of(w) for w in (wq_ref, wk_ref, wv_ref))
    if channel:  # beta alone on the side, the gate's pre-activation a chunk of its own
        side, gate = jnp.stack([side_ref[:, h:h + 1] for h in range(hb)]), heads_of(channel[0])
    else:
        side = jnp.stack([jnp.concatenate([side_ref[:, h:h + 1], side_ref[:, hb + h:hb + h + 1]],
                                          axis=1) for h in range(hb)])
        gate = None
    y, st = head_chunk(heads_of(xq_ref), heads_of(xk_ref), heads_of(xv_ref), before, taps,
                       heads_of(pre_ref), heads_of(rate_ref), side, norm_ref[...], st_ref[...],
                       bound, eps, gate)
    for h in range(hb):
        y_ref[:, h * d:(h + 1) * d] = y[h].astype(y_ref.dtype)
    st_ref[...] = st


@functools.partial(jax.jit, static_argnames=("heads", "bound", "eps", "interpret"))
def _kda_pallas(xq, xk, xv, taps, pre, rate, beta, gate, norm_w, heads: int,
                bound: Optional[float], eps: float, interpret: bool = False):
    import jax.experimental.pallas as pl  # deferred so CPU-only processes never pay it
    from jax.experimental.pallas import tpu as pltpu

    t, d = xq.shape[0], xq.shape[1] // heads
    if t % CHUNK:
        raise ValueError(f"the chunked delta rule takes whole chunks of {CHUNK} positions; "
                         f"got T={t}")
    hb = _heads_a_step(heads)
    wide = _wide(bound)
    # the framework enables x64 globally; Mosaic only legalizes i32 scalars
    with jax.enable_x64(False):
        if diagnostics._enabled:  # trace time only: a trace of the path that took the kernel
            diagnostics.counter("kernels.kda.fwd.unbounded" if wide else "kernels.kda.fwd")
            if _paired(hb):
                diagnostics.counter("kernels.kda.fwd.paired")
        channel = ()
        if gate.shape[1] != heads:  # a gate a channel: beta alone, (head groups, T, hb)
            side = jnp.moveaxis(beta.astype(_F32).reshape(t, heads // hb, hb), 1, 0)
            channel = (gate.astype(_F32),)
        else:
            # beta and the gate of a step's heads side by side: (head groups, T, 2 hb)
            side = jnp.stack([beta, gate], axis=1).astype(_F32).reshape(t, 2, heads // hb, hb)
            side = jnp.moveaxis(side, 2, 0).reshape(heads // hb, t, 2 * hb)
        rows = CHUNK // BEFORE
        chunk = pl.BlockSpec((CHUNK, hb * d), lambda i, j: (j, i))
        before = pl.BlockSpec((BEFORE, hb * d), lambda i, j: (jnp.maximum(j * rows - 1, 0), i))
        width = taps[0].shape[0]
        tap = pl.BlockSpec((width, hb * d), lambda i, j: (0, i))
        return pl.pallas_call(
            functools.partial(_kernel, hb=hb, d=d, bound=bound, eps=eps),
            grid=(heads // hb, t // CHUNK),
            in_specs=[chunk, chunk, chunk, before, before, before, tap, tap, tap, chunk,
                      pl.BlockSpec((1, hb * d), lambda i, j: (0, i)),
                      pl.BlockSpec((None, CHUNK, side.shape[2]), lambda i, j: (i, j, 0)),
                      pl.BlockSpec((1, d), lambda i, j: (0, 0))] + [chunk] * len(channel),
            out_specs=chunk,
            out_shape=jax.ShapeDtypeStruct((t, heads * d), xq.dtype),
            scratch_shapes=[pltpu.VMEM((hb, d, d), _F32)],
            interpret=interpret,
            # a head group's chunks follow one another: the state is carried in VMEM
            compiler_params=None if interpret else pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name="kda_unbounded_fwd" if wide else "kda_chunk_fwd",
        )(xq, xk, xv, xq, xk, xv, *_taps32(taps), pre.astype(_F32),
          rate.astype(_F32).reshape(1, heads * d), side, norm_w.astype(_F32).reshape(1, d),
          *channel)


def kda_mix(xq, xk, xv, taps, pre, rate, beta, gate, norm_w, heads: int,
            bound: Optional[float], eps: float, interpret: bool = False):
    """Kimi Delta Attention between its projections, over the whole sequence from a zero
    state: ``xq, xk, xv`` (T, heads * d) the three projections as stored, ``taps`` their
    three convolutions (width, heads * d), ``pre`` (T, heads * d) float32 and ``rate``
    (heads * d,) the decay's pre-activation and its rate (the log-decay is ``bound *
    sigmoid(rate * pre)`` for a ``bound < 0``, or ``-rate * softplus(pre)`` for ``bound``
    None), ``beta`` (T, heads) float32 after its sigmoid, ``gate`` either (T, heads) after
    its sigmoid (a gate a head) or (T, heads * d) float32 before it (a gate a channel),
    ``norm_w`` (d,) the head norm's weight. Returns the gated, normed heads (T, heads * d) in
    ``xq``'s type, ready for the output projection. The narrow form runs as
    ``kda_chunk_fwd``, the wide one (the softplus kind, or a bound below
    :data:`LOG_DECAY_BOUND`) as ``kda_unbounded_fwd``. ``T`` is a whole number of chunks
    (``ValueError`` otherwise). Callers ask :func:`decline_reason` first. No gradient is
    defined on this entry."""
    return _kda_pallas(xq, xk, xv, tuple(taps), pre, rate, beta, gate, norm_w, heads=heads,
                       bound=None if bound is None else float(bound), eps=eps,
                       interpret=interpret)
