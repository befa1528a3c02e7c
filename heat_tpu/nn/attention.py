"""Sequence-parallel attention: the long-context machinery of the framework.

The reference has no attention at all (SURVEY §2.4: "no ring attention … no attention
anywhere"); its long-axis machinery is halo exchange, resplit pencils and the ring
rotation of ``spatial/distance.py:209``. On TPU the same ring schedule, applied to
attention, is *ring attention* (blockwise online-softmax attention with k/v chunks
rotating over the ICI torus via ``ppermute``) — so the TPU build promotes attention to
a first-class op with three execution strategies:

- **dense** — one device or replicated inputs: plain blockwise attention, XLA-fused.
- **ring** (``ring_attention``) — q/k/v sharded on the *sequence* axis. P steps; at
  each step every device attends its local queries against the currently-held k/v
  chunk with a running (m, l, o) online-softmax accumulator, then rotates k/v one
  neighbour around the ring. Peak memory per device is O(T/P) and the k/v transfer
  overlaps the matmuls — the standard TPU context-parallel schedule.
- **Ulysses** (``ulysses_attention``) — q/k/v sharded on sequence; two ``all_to_all``
  reshards flip the sharding to the *head* axis, attention runs dense per head-shard,
  and a final ``all_to_all`` flips back. Cheaper than the ring when heads ≥ devices
  and the full sequence fits per device.

``scaled_dot_product_attention`` is the torch-parity entry point
(torch.nn.functional.scaled_dot_product_attention semantics); on a DNDarray whose
sequence axis is split it dispatches to the ring automatically.

All accumulation is float32 regardless of input dtype (bf16 inputs stay bf16 on the
MXU, ``preferred_element_type`` lifts the products).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import diagnostics
from ..core.kernels import sparse_index
from ..core.kernels.flash_attention import (
    flash_attention,
    flash_forward,
    flash_latent,
    forward_blocks,
    latent_blocks,
    use_flash,
)

from ..core.dndarray import DNDarray

__all__ = [
    "scaled_dot_product_attention",
    "ring_attention",
    "ring_attention_zigzag",
    "zigzag_order",
    "zigzag_inverse",
    "ulysses_attention",
    "MultiheadAttention",
    "MultiheadLatentAttention",
    "LightningIndexer",
    "GroupedQueryAttention",
    "yarn_inv_freq",
    "rotate_halves",
    "even_then_odd",
    "TransformerEncoderLayer",
    "TransformerEncoder",
    "TransformerDecoderLayer",
    "TransformerDecoder",
    "Transformer",
]

_NEG_INF = float(np.finfo(np.float32).min)


def _attention_weights(q, k, mask, is_causal, scale):
    """Normalized (row-stochastic, fully-masked rows → 0) attention weights in
    f32 — the shared score/causal/mask/stabilized-softmax pipeline of the XLA
    paths (with and without dropout)."""
    d = q.shape[-1]
    s = (1.0 / math.sqrt(d)) if scale is None else scale
    scores = jnp.einsum(
        "...qd,...kd->...qk", q, k, preferred_element_type=jnp.float32
    ) * jnp.float32(s)
    if is_causal:
        causal = jnp.arange(q.shape[-2])[:, None] >= jnp.arange(k.shape[-2])[None, :]
        scores = jnp.where(causal, scores, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, _NEG_INF)
        else:
            scores = scores + mask.astype(jnp.float32)
    # rows where everything is masked: keep them finite; their weights are 0
    m = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), _NEG_INF / 2)
    p = jnp.exp(scores - m)
    return p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)


def _dense_attention(q, k, v, mask=None, is_causal=False, scale=None):
    """Single-device exact attention on local arrays, f32 accumulation.

    q: (..., Tq, D), k/v: (..., Tk, D). Causal masking is top-left aligned
    (position i attends keys ≤ i), matching torch sdpa. On TPU, unmasked
    block-even shapes run the flash Pallas kernel (streaming VMEM, no (T,T)
    score matrix in HBM); everything else takes the XLA path below.
    """

    if use_flash(q, k, v, mask, scale):
        return flash_attention(q, k, v, is_causal, scale, mask)
    pw = _attention_weights(q, k, mask, is_causal, scale)
    return jnp.einsum(
        "...qk,...kd->...qd", pw, v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p: float = 0.0,
                                 is_causal: bool = False,
                                 scale: Optional[float] = None,
                                 enable_gqa: bool = False,
                                 dropout_key=None):
    """torch.nn.functional.scaled_dot_product_attention semantics (full signature:
    ``attn_mask, dropout_p, is_causal, scale, enable_gqa``).

    Inputs are (..., T, D) — typically (B, H, T, D). On plain arrays this is one
    fused XLA program. On DNDarrays split along the sequence axis (dim -2) it runs
    :func:`ring_attention` under ``shard_map`` — context parallelism without the
    caller changing a line.

    ``enable_gqa`` broadcasts grouped k/v heads (Hkv dividing Hq) like torch.
    ``dropout_p`` applies torch's train-time inverted attention dropout (drop
    probabilities after softmax, rescale kept ones by 1/(1-p)) and needs an
    explicit ``dropout_key`` (jax has no ambient RNG state); it forces the XLA
    path.
    """
    if not 0.0 <= dropout_p <= 1.0:
        raise ValueError(f"dropout_p must be in [0, 1], got {dropout_p}")
    if dropout_p:
        if dropout_key is None:
            raise ValueError(
                "dropout_p > 0 needs an explicit dropout_key PRNG key (jax has no "
                "ambient RNG state like torch)"
            )
    if enable_gqa:
        hq = query.shape[-3]
        hkv = key.shape[-3]
        if value.shape[-3] != hkv:
            raise ValueError(
                f"enable_gqa needs key and value to share a head count, got "
                f"{hkv} and {value.shape[-3]}"
            )
        if hq != hkv:
            if hq % hkv:
                raise ValueError(f"enable_gqa needs Hkv | Hq, got {hkv}, {hq}")
            rep = hq // hkv
            key = _repeat_kv_heads(key, rep)
            value = _repeat_kv_heads(value, rep)
    if dropout_p:
        if isinstance(query, DNDarray) and query.split == query.ndim - 2:
            import warnings

            warnings.warn(
                "scaled_dot_product_attention dropout forfeits the ring-attention "
                "path on sequence-split inputs: the (T, T) weight matrix is "
                "materialized densely. Use dropout_p=0 for long-context runs.",
                stacklevel=2,
            )
        q_ = query.larray if isinstance(query, DNDarray) else query
        k_ = key.larray if isinstance(key, DNDarray) else key
        v_ = value.larray if isinstance(value, DNDarray) else value
        m_ = attn_mask.larray if isinstance(attn_mask, DNDarray) else attn_mask
        out = _dense_attention_dropout(q_, k_, v_, m_, is_causal, scale,
                                       dropout_p, dropout_key)
        if isinstance(query, DNDarray):
            from ..core._operations import wrap_result

            return wrap_result(out, query, query.split)
        return out
    if isinstance(query, DNDarray):
        from ..core._operations import wrap_result

        seq_axis = query.ndim - 2
        if (
            query.split == seq_axis
            and isinstance(key, DNDarray) and key.split == seq_axis
            and isinstance(value, DNDarray) and value.split == seq_axis
            and attn_mask is None
            and query.comm.is_distributed()
            and isinstance(query.comm.axis_name, str)
            and query.shape[seq_axis] % query.comm.size == 0
            and key.shape[seq_axis] % query.comm.size == 0
        ):
            out = _ring_sharded(
                query.larray, key.larray, value.larray, query.comm,
                is_causal=is_causal, scale=scale,
            )
            return wrap_result(out, query, query.split)
        q = query.larray
        k = key.larray if isinstance(key, DNDarray) else key
        v = value.larray if isinstance(value, DNDarray) else value
        m = attn_mask.larray if isinstance(attn_mask, DNDarray) else attn_mask
        if (
            query.split is not None
            and query.split < seq_axis
            and isinstance(key, DNDarray) and key.split == query.split
            and isinstance(value, DNDarray) and value.split == query.split
            and (m is None or m.ndim == 2)
            and query.comm.is_distributed()
            and isinstance(query.comm.axis_name, str)
            and query.shape[query.split] % query.comm.size == 0
            and key.shape[query.split] == query.shape[query.split]
        ):
            out = _batch_sharded(q, k, v, m, query.comm, query.split,
                                 is_causal=is_causal, scale=scale)
        else:
            out = _dense_attention(q, k, v, m, is_causal, scale)
        return wrap_result(out, query, query.split)
    k = key.larray if isinstance(key, DNDarray) else key
    v = value.larray if isinstance(value, DNDarray) else value
    m = attn_mask.larray if isinstance(attn_mask, DNDarray) else attn_mask
    return _dense_attention(query, k, v, m, is_causal, scale)


def _online_attend(q_blk, q_pos, o, m, l, k_blk, v_blk, k_pos, s, masked: bool):
    """One online-softmax block merge shared by the ring variants: returns the
    updated (o, m, l) accumulator after q_blk attends k_blk/v_blk, optionally
    causal-masked by the global positions."""
    scores = jnp.einsum(
        "...qd,...kd->...qk", q_blk, k_blk, preferred_element_type=jnp.float32
    ) * jnp.float32(s)
    if masked:
        scores = jnp.where(q_pos[:, None] >= k_pos[None, :], scores, _NEG_INF)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    m_safe = jnp.maximum(m_new, _NEG_INF / 2)
    corr = jnp.exp(m - m_safe)
    pij = jnp.exp(scores - m_safe[..., None])
    l_new = l * corr + jnp.sum(pij, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "...qk,...kd->...qd", pij, v_blk, preferred_element_type=jnp.float32
    )
    return o_new, m_new, l_new


def _repeat_kv_heads(x, rep: int):
    """GQA: tile k/v heads to match the query head count (torch enable_gqa)."""
    if isinstance(x, DNDarray):
        from ..core._operations import wrap_result

        v = jnp.repeat(x.larray, rep, axis=-3)
        split = x.split  # the head axis is -3; seq/batch splits survive the repeat
        return wrap_result(v, x, split)
    return jnp.repeat(x, rep, axis=-3)


def _dense_attention_dropout(q, k, v, mask, is_causal, scale, dropout_p, key):
    """Dense attention with torch's train-time inverted attention dropout: drop
    probabilities after softmax, rescale kept ones by 1/(1-p)."""
    if dropout_p == 1.0:  # torch: every weight dropped, output all-zero
        return jnp.zeros(q.shape[:-1] + (v.shape[-1],), q.dtype)
    pw = _attention_weights(q, k, mask, is_causal, scale)
    keep = jax.random.bernoulli(key, 1.0 - dropout_p, pw.shape)
    pw = jnp.where(keep, pw / (1.0 - dropout_p), 0.0)
    return jnp.einsum(
        "...qk,...kd->...qd", pw, v, preferred_element_type=jnp.float32
    ).astype(q.dtype)


def ring_attention(q, k, v, axis_name: str, is_causal: bool = False,
                   scale: Optional[float] = None):
    """Ring attention over sequence-sharded chunks — call inside ``shard_map``.

    q/k/v: local chunks (..., T_local, D) of a global (..., T, D); the sequence axis
    is sharded over ``axis_name``. P steps of blockwise attention with an online
    softmax; k/v rotate one neighbour per step (ppermute), so no device ever holds
    more than 1/P of the keys. Equivalent to dense softmax(qkᵀ)v up to fp error.
    """
    p = lax.psum(1, axis_name)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
    my = lax.axis_index(axis_name)
    tq = q.shape[-2]
    tk = k.shape[-2]
    d = q.shape[-1]
    s = (1.0 / math.sqrt(d)) if scale is None else scale
    q_pos = my * tq + jnp.arange(tq)

    # derive the accumulators from q so they carry q's device-varying type under
    # shard_map's representation checks (a fresh jnp.zeros would be "replicated")
    zero_q = jnp.sum(q.astype(jnp.float32) * 0, axis=-1)  # (..., Tq) of zeros
    o0 = jnp.zeros_like(q, jnp.float32)
    m0 = zero_q + _NEG_INF
    l0 = zero_q
    perm = [(i, (i - 1) % p) for i in range(p)]  # after s steps, device i holds chunk (i+s) % p

    def attend(o, m, l, k_c, v_c, src):
        k_pos = src * tk + jnp.arange(tk)
        return _online_attend(q, q_pos, o, m, l, k_c, v_c, k_pos, s, is_causal)

    def step(carry, step_idx):
        k_c, v_c, o, m, l = carry
        o, m, l = attend(o, m, l, k_c, v_c, (my + step_idx) % p)
        k_next = lax.ppermute(k_c, axis_name, perm)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
        v_next = lax.ppermute(v_c, axis_name, perm)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
        return (k_next, v_next, o, m, l), None

    # scan only the p-1 steps that are followed by a rotation; the last block is
    # consumed outside the scan so its k/v are never ppermuted onward (that final
    # rotation would be dead inter-chip traffic XLA cannot eliminate from the carry)
    o, m, l = o0, m0, l0
    if p > 1:
        (k, v, o, m, l), _ = lax.scan(
            step, (k, v, o, m, l), jnp.arange(p - 1)
        )
    o, m, l = attend(o, m, l, k, v, (my + p - 1) % p)
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


def _ring_sharded(q, k, v, comm, is_causal=False, scale=None):
    """Launch :func:`ring_attention` under shard_map on ``comm``'s mesh.

    q/k/v are global (B, H, T, D)-like jax.Arrays sequence-sharded on dim -2.
    """
    from jax import shard_map

    mesh = comm.mesh
    axis = comm.axis_name
    ndim = q.ndim
    spec = P(*([None] * (ndim - 2) + [axis, None]))

    fn = shard_map(
        partial(ring_attention, axis_name=axis, is_causal=is_causal, scale=scale),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def _batch_sharded(q, k, v, mask, comm, split, is_causal=False, scale=None):
    """Dense attention on q/k/v sharded along a batch or head dim: every device
    attends its own slice under shard_map, no communication. The partitioner
    cannot do this itself: it refuses a Mosaic call on sharded operands
    ("cannot be automatically partitioned")."""
    from jax import shard_map

    spec = P(*[comm.axis_name if i == split else None for i in range(q.ndim)])

    def body(ql, kl, vl, *ml):
        return _dense_attention(ql, kl, vl, ml[0] if ml else None, is_causal, scale)

    masks = () if mask is None else (mask,)
    # check_vma off: a pallas_call's out_shape carries no varying-axes annotation
    fn = shard_map(
        body, mesh=comm.mesh,
        in_specs=(spec, spec, spec) + (P(),) * len(masks), out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v, *masks)


def zigzag_order(t: int, p: int) -> np.ndarray:
    """Sequence permutation for the zigzag causal layout: the sequence is cut into
    ``2p`` chunks and device ``i`` holds chunks ``(i, 2p-1-i)``. Apply with
    ``x[..., zigzag_order(T, p), :]`` before :func:`ring_attention_zigzag`; invert
    with :func:`zigzag_inverse`."""
    if t % (2 * p):
        raise ValueError(
            f"zigzag layout needs the sequence length divisible by 2*p, got t={t}, p={p}"
        )
    c = t // (2 * p)
    order = []
    for i in range(p):
        order.extend(range(i * c, (i + 1) * c))
        order.extend(range((2 * p - 1 - i) * c, (2 * p - i) * c))
    return np.asarray(order, dtype=np.int32)


def zigzag_inverse(t: int, p: int) -> np.ndarray:
    """Inverse permutation of :func:`zigzag_order`."""
    order = zigzag_order(t, p)
    inv = np.empty_like(order)
    inv[order] = np.arange(t, dtype=np.int32)
    return inv


def ring_attention_zigzag(q, k, v, axis_name: str, scale: Optional[float] = None):
    """Load-balanced CAUSAL ring attention — call inside ``shard_map`` with inputs
    in the zigzag layout (:func:`zigzag_order`).

    The plain causal ring wastes half its FLOPs: in SPMD lockstep every device
    executes every step, but device ``i`` only *needs* the k/v chunks ``≤ i`` —
    the rest are fully masked compute. With the zigzag assignment (device ``i``
    holds sequence chunks ``i`` and ``2p-1-i``) every step has exactly one
    always-needed half-product (high queries × low keys) and one
    predicate-selected half-product, so per-device work is ``T²/2p²`` per step —
    half the plain ring — and uniform across devices. This is the standard
    long-context balance trick (e.g. llama3-style context parallelism).

    q/k/v: local (..., 2c, D) chunks where the first ``c`` rows are the device's
    LOW chunk and the last ``c`` its HIGH chunk. Output is in the same layout.
    """
    p = lax.psum(1, axis_name)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
    my = lax.axis_index(axis_name)
    two_c = q.shape[-2]
    c = two_c // 2
    d = q.shape[-1]
    s = (1.0 / math.sqrt(d)) if scale is None else scale

    q_lo, q_hi = q[..., :c, :], q[..., c:, :]
    # global chunk ids: lo = my, hi = 2p-1-my; positions inside a chunk are local
    lo_pos = my * c + jnp.arange(c)

    def hi_pos_of(dev):
        return (2 * p - 1 - dev) * c + jnp.arange(c)

    def attend_block(q_blk, q_positions, o, m, l, k_blk, v_blk, k_positions,
                     masked: bool):
        return _online_attend(
            q_blk, q_positions, o, m, l, k_blk, v_blk, k_positions, s, masked
        )

    zero = jnp.sum(q_lo.astype(jnp.float32) * 0, axis=-1)
    acc_lo = (jnp.zeros_like(q_lo, jnp.float32), zero + _NEG_INF, zero)
    acc_hi = (jnp.zeros_like(q_hi, jnp.float32), zero + _NEG_INF, zero)
    perm = [(i, (i - 1) % p) for i in range(p)]

    # step 0 (self): lo×lo and hi×hi are diagonal blocks (masked); hi×lo is full
    k_lo, k_hi = k[..., :c, :], k[..., c:, :]
    v_lo, v_hi = v[..., :c, :], v[..., c:, :]
    acc_lo = attend_block(q_lo, lo_pos, *acc_lo, k_lo, v_lo, lo_pos, True)
    acc_hi = attend_block(q_hi, hi_pos_of(my), *acc_hi, k_hi, v_hi, hi_pos_of(my), True)
    acc_hi = attend_block(q_hi, hi_pos_of(my), *acc_hi, k_lo, v_lo, lo_pos, False)

    def attend_pair(kc, vc, src, acc_lo, acc_hi):
        k_lo, k_hi = kc[..., :c, :], kc[..., c:, :]
        v_lo, v_hi = vc[..., :c, :], vc[..., c:, :]
        # hi queries × src's LOW keys: always needed (2p-1-my > src for src != my)
        acc_hi = attend_block(q_hi, hi_pos_of(my), *acc_hi, k_lo, v_lo,
                              src * c + jnp.arange(c), False)
        # the predicate-selected half: LOW q × src's low k (src < my), else
        # HIGH q × src's high k (src > my) — both full blocks, same shapes
        pred = src < my
        q_sel = jnp.where(pred, q_lo, q_hi)
        k_sel = jnp.where(pred, k_lo, k_hi)
        v_sel = jnp.where(pred, v_lo, v_hi)
        o_sel, m_sel, l_sel = (
            jnp.where(pred, acc_lo[0], acc_hi[0]),
            jnp.where(pred, acc_lo[1], acc_hi[1]),
            jnp.where(pred, acc_lo[2], acc_hi[2]),
        )
        upd = attend_block(
            q_sel, jnp.zeros(c, jnp.int32), o_sel, m_sel, l_sel,
            k_sel, v_sel, jnp.zeros(c, jnp.int32), False,
        )
        acc_lo = tuple(jnp.where(pred, u, a) for u, a in zip(upd, acc_lo))
        acc_hi = tuple(jnp.where(pred, a, u) for a, u in zip(acc_hi, upd))
        return acc_lo, acc_hi

    def step(carry, step_idx):
        kc, vc, acc_lo, acc_hi = carry
        # rotate the HELD pair onward while attending it — both only read kc/vc,
        # so the ICI transfer overlaps the matmuls (same structure as the plain
        # ring); the final pair is consumed outside the scan with no dead hop
        k_next = lax.ppermute(kc, axis_name, perm)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
        v_next = lax.ppermute(vc, axis_name, perm)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
        acc_lo, acc_hi = attend_pair(kc, vc, (my + step_idx) % p, acc_lo, acc_hi)
        return (k_next, v_next, acc_lo, acc_hi), None

    if p > 1:
        kc = lax.ppermute(k, axis_name, perm)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
        vc = lax.ppermute(v, axis_name, perm)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
        if p > 2:
            (kc, vc, acc_lo, acc_hi), _ = lax.scan(
                step, (kc, vc, acc_lo, acc_hi), jnp.arange(1, p - 1)
            )
        acc_lo, acc_hi = attend_pair(kc, vc, (my + p - 1) % p, acc_lo, acc_hi)
    o_lo = acc_lo[0] / jnp.maximum(acc_lo[2], 1e-30)[..., None]
    o_hi = acc_hi[0] / jnp.maximum(acc_hi[2], 1e-30)[..., None]
    return jnp.concatenate([o_lo, o_hi], axis=-2).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, is_causal: bool = False,
                      scale: Optional[float] = None):
    """Ulysses / all-to-all sequence parallelism — call inside ``shard_map``.

    q/k/v: (B, H, T_local, D) sequence-sharded chunks with H divisible by the mesh
    size. Two all_to_alls flip the sharding sequence→heads, attention runs dense on
    the full sequence for H/P heads, one all_to_all flips back.
    """
    # (B, H, T/P, D) -> (B, H/P, T, D): split heads axis (1), concat seq axis (2)
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm
    o = _dense_attention(qh, kh, vh, is_causal=is_causal, scale=scale)
    return lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1, tiled=True)  # ht: ignore[collective-uncontracted] -- axis-name shard_map-body kernel API: no communicator in scope by design; callers (attention()/_ring_sharded) own the comm


from .modules import Module, RMSNorm, contract, normal_weight


class MultiheadAttention(Module):
    """torch.nn.MultiheadAttention semantics (batch_first, self- or cross-attention).

    Packed in-projection weight (3E, E) + out-projection (E, E), both with torch's
    xavier_uniform_ / zero-bias init, so state_dicts map 1:1 (with ``kdim``/``vdim``
    differing from ``embed_dim``, separate ``q/k/v_proj_weight`` under torch's
    names, like torch's ``_qkv_same_embed_dim=False`` path). ``apply(params, x)``
    is self-attention; ``apply(params, (q, k, v))`` is cross-attention. On
    sequence-split DNDarray inputs the underlying sdpa runs the ring schedule.

    ``dropout`` is torch's attention-weight dropout: active only under
    ``apply(..., train=True, key=...)`` (explicit PRNG key — jax has no ambient
    RNG state); the eval-style ``mha(q, k, v)`` call never drops, like torch
    modules in ``.eval()``.
    """

    def __init__(self, embed_dim: int, num_heads: int, dropout: float = 0.0,
                 bias: bool = True, batch_first: bool = True,
                 kdim: Optional[int] = None, vdim: Optional[int] = None):
        if embed_dim % num_heads:
            raise ValueError("embed_dim must be divisible by num_heads")
        if not 0.0 <= dropout <= 1.0:
            raise ValueError(f"dropout must be in [0, 1], got {dropout}")
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.dropout = dropout
        self.bias = bias
        self.batch_first = batch_first
        self.kdim = embed_dim if kdim is None else kdim
        self.vdim = embed_dim if vdim is None else vdim
        # torch: packed (3E, E) in-projection only when q/k/v share the embed dim;
        # otherwise separate q/k/v weights under torch's exact param names
        self._qkv_same_embed_dim = self.kdim == embed_dim and self.vdim == embed_dim

    def init(self, key):
        e = self.embed_dim
        # torch's _reset_parameters: xavier_uniform_ on every projection weight,
        # zeros on both biases
        xavier = lambda k, shape: jax.random.uniform(
            k, shape, jnp.float32,
            -math.sqrt(6.0 / sum(shape)), math.sqrt(6.0 / sum(shape)),
        )
        if self._qkv_same_embed_dim:
            k1, k2 = jax.random.split(key)
            params = {
                "in_proj_weight": xavier(k1, (3 * e, e)),
                "out_proj_weight": xavier(k2, (e, e)),
            }
        else:
            kq, kk, kv, k2 = jax.random.split(key, 4)
            params = {
                "q_proj_weight": xavier(kq, (e, e)),
                "k_proj_weight": xavier(kk, (e, self.kdim)),
                "v_proj_weight": xavier(kv, (e, self.vdim)),
                "out_proj_weight": xavier(k2, (e, e)),
            }
        if self.bias:
            params["in_proj_bias"] = jnp.zeros((3 * e,), jnp.float32)
            params["out_proj_bias"] = jnp.zeros((e,), jnp.float32)
        return params

    def apply(self, params, x, *, key=None, train=False, attn_mask=None,
              is_causal: bool = False, key_padding_mask=None):
        if isinstance(x, tuple):
            q_in, k_in, v_in = x
        else:
            q_in = k_in = v_in = x
        unwrap = lambda t: t.larray if isinstance(t, DNDarray) else t
        attn_mask = unwrap(attn_mask) if attn_mask is not None else None
        if attn_mask is not None and attn_mask.dtype == jnp.bool_:
            # torch.nn.MultiheadAttention convention: True = NOT allowed to attend
            # — the INVERSE of torch sdpa's (and our sdpa path's) True = attend.
            # Float masks are additive in both conventions.
            attn_mask = ~attn_mask
        if key_padding_mask is not None:
            # (B, S): bool True = ignore that key for every query; floats are an
            # additive bias (both torch conventions); merged additively so it
            # broadcasts over heads and queries
            from ..core.kernels.flash_attention import _as_bias

            kpm = unwrap(key_padding_mask)
            pad = (
                jnp.where(kpm, jnp.float32(_NEG_INF), jnp.float32(0))
                if kpm.dtype == jnp.bool_
                else kpm.astype(jnp.float32)
            )[:, None, None, :]  # (B, 1, 1, S)
            attn_mask = pad if attn_mask is None else _as_bias(attn_mask) + pad
        proto = q_in if isinstance(q_in, DNDarray) else None
        seq_axis_in = 1 if self.batch_first else 0
        seq_split = (
            proto is not None
            and proto.split == seq_axis_in
            and isinstance(k_in, DNDarray) and k_in.split == seq_axis_in
            and isinstance(v_in, DNDarray) and v_in.split == seq_axis_in
        )
        q_in, k_in, v_in = unwrap(q_in), unwrap(k_in), unwrap(v_in)
        if not self.batch_first:
            q_in, k_in, v_in = (jnp.swapaxes(t, 0, 1) for t in (q_in, k_in, v_in))

        e = self.embed_dim
        b = params.get("in_proj_bias")
        bias_of = lambda i: b[i * e:(i + 1) * e] if b is not None else 0.0
        if self._qkv_same_embed_dim:
            w = params["in_proj_weight"]
            proj = lambda t, i: t @ w[i * e:(i + 1) * e].T + bias_of(i)
            q, k, v = proj(q_in, 0), proj(k_in, 1), proj(v_in, 2)
        else:
            q = q_in @ params["q_proj_weight"].T + bias_of(0)
            k = k_in @ params["k_proj_weight"].T + bias_of(1)
            v = v_in @ params["v_proj_weight"].T + bias_of(2)

        def split_heads(t):  # (B, T, E) -> (B, H, T, hd)
            bsz, tlen, _ = t.shape
            return t.reshape(bsz, tlen, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)

        qh, kh, vh = split_heads(q), split_heads(k), split_heads(v)
        comm = proto.comm if proto is not None else None
        if train and self.dropout > 0.0:
            # torch: attention-weight dropout only in train mode; needs an
            # explicit PRNG key (jax has no ambient RNG state)
            if key is None:
                raise ValueError(
                    "MultiheadAttention with dropout > 0 needs apply(..., key=...) "
                    "in train mode (jax has no ambient RNG state like torch)"
                )
            if seq_split:
                import warnings

                warnings.warn(
                    "MultiheadAttention dropout forfeits the ring-attention path: "
                    "the (T, T) weight matrix is materialized densely. For "
                    "long-context training use dropout=0 (or drop residual "
                    "streams instead).",
                    stacklevel=2,
                )
            o = _dense_attention_dropout(
                qh, kh, vh, attn_mask, is_causal, None, self.dropout, key
            )
        elif (
            seq_split
            and attn_mask is None
            and comm is not None
            and comm.is_distributed()
            and isinstance(comm.axis_name, str)
            and qh.shape[2] % comm.size == 0
            and kh.shape[2] % comm.size == 0
        ):
            # the documented long-context path: sequence-split input → ring schedule
            o = _ring_sharded(qh, kh, vh, comm, is_causal=is_causal)
        else:
            o = _dense_attention(qh, kh, vh, mask=attn_mask, is_causal=is_causal)
        bsz, _, tlen, _ = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(bsz, tlen, e)
        o = o @ params["out_proj_weight"].T
        if self.bias:
            o = o + params["out_proj_bias"]
        if not self.batch_first:
            o = jnp.swapaxes(o, 0, 1)
        if proto is not None:
            from ..core._operations import wrap_result

            # output has the query's (B, T, E) / (T, B, E) shape: batch and sequence
            # splits both survive in either layout (only the embed axis is mixed by
            # the projections)
            keep = proto.split if proto.split in (0, 1) else None
            return wrap_result(o, proto, keep)
        return o

    def __call__(self, query, key=None, value=None, key_padding_mask=None,
                 need_weights: bool = False, attn_mask=None,
                 average_attn_weights: bool = True, is_causal: bool = False):
        """torch call convention: ``mha(q, k, v)`` returns ``(output, None)`` when
        ``need_weights=False`` (weights are never materialized — blockwise kernels
        don't form the T×T matrix). ``key_padding_mask`` is (B, S) with True =
        ignore that key, like torch."""
        if need_weights:
            raise NotImplementedError(
                "need_weights=True would materialize the T×T attention matrix; "
                "blockwise/ring execution never forms it"
            )
        if key is None:
            key = query
        if value is None:
            value = key
        x = query if (key is query and value is query) else (query, key, value)
        # honor the bound train/key context like base Module.__call__ (the
        # ``key`` name here is the attention key tensor, so the RNG key can only
        # arrive via _bind from a parent apply(..., train=True, key=...) or via
        # .train() mode)
        rng_key, train = self._resolve_ctx()
        out = self.apply(
            self.params, x, key=rng_key, train=train, attn_mask=attn_mask,
            is_causal=is_causal, key_padding_mask=key_padding_mask,
        )
        return out, None


def yarn_inv_freq(rope_dim: int, theta: float, scaling: Optional[dict]) -> np.ndarray:
    """Inverse frequencies of a rotary part of width ``rope_dim``. With YaRN
    (``scaling``: ``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    ``beta_slow``) the pairs that turn more than ``beta_fast`` times inside the original
    context keep their frequency, those that turn less than ``beta_slow`` times are
    slowed by ``factor``, and a linear ramp blends the pairs between."""
    freq = theta ** (-np.arange(0, rope_dim, 2, dtype=np.float64) / rope_dim)
    if not scaling:
        return freq.astype(np.float32)
    factor, orig = scaling["factor"], scaling["original_max_position_embeddings"]

    def pair_turning(turns):  # index of the pair that makes ``turns`` turns in ``orig``
        return rope_dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(scaling["beta_slow"])), rope_dim - 1)
    span = (high - low) or 1e-3
    slowed = np.clip((np.arange(rope_dim // 2, dtype=np.float64) - low) / span, 0.0, 1.0)
    return (freq / factor * slowed + freq * (1.0 - slowed)).astype(np.float32)


def _yarn_mscale(scaling: Optional[dict], key: str) -> float:
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * scaling[key] * math.log(scaling["factor"]) + 1.0


def rope_turns(t: int, inv_freq, magnitude: float = 1.0):
    """``(cos, sin)``, each ``(t, len(inv_freq))`` float32: position ``p``'s pair ``i`` turns
    by ``p * inv_freq[i]``, both scaled by ``magnitude``. What :func:`rotate_halves` applies,
    and what the latent flash kernel takes to apply the same turns itself."""
    angle = jnp.arange(t, dtype=jnp.int32).astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    return jnp.cos(angle) * jnp.float32(magnitude), jnp.sin(angle) * jnp.float32(magnitude)


def rotate_halves(x, inv_freq, magnitude: float = 1.0):
    """Rotary positions on ``x`` (..., T, rope_dim) laid out as two halves ``[a | b]``:
    the pair ``(a[i], b[i])`` at position ``t`` (the index on axis -2) turns by
    ``t * inv_freq[i]``. Angles, cosines and sines are float32; the result has ``x``'s
    type. A layout of interleaved pairs ``(x[2i], x[2i+1])`` becomes this one by taking
    the even columns first (:func:`even_then_odd`); on the TPU the halves are two lane
    slices, where interleaved pairs would put a dimension of 2 on the lanes."""
    half = x.shape[-1] // 2
    cos, sin = rope_turns(x.shape[-2], inv_freq, magnitude)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1).astype(x.dtype)


def even_then_odd(w, start: int):
    """The columns of ``w`` (last axis) from ``start`` on, reordered even ones first:
    a projection whose rope columns are published as interleaved pairs then yields the
    two-halves layout of :func:`rotate_halves`. Applied to q's and k's projection alike
    it leaves ``q . k`` as it was."""
    width = w.shape[-1] - start
    order = np.concatenate([np.arange(start), start + np.arange(0, width, 2),
                            start + np.arange(1, width, 2)])
    return jnp.take(w, jnp.asarray(order, jnp.int32), axis=-1)


class LightningIndexer(Module):
    """The learned index of a sparse attention: which ``topk`` earlier tokens each query
    attends to (DeepSeek sparse attention's lightning indexer).

    A small attention of ``n_heads`` heads that share one key a token, read from the layer's
    input ``u`` (T, dim) and its query latent ``c_q`` (T, q_lora_rank)::

        q_j[t] = (c_q[t] W_qb)_j                      n_heads vectors of head_dim
        k[s]   = LayerNorm(u[s] W_k)                  one vector of head_dim, weight and bias
        w_j[t] = (u[t] W_w)_j n_heads^-1/2 head_dim^-1/2          float32
        I[t,s] = sum_j w_j[t] relu(q_j[t] . k[s])     float32, s <= t

    with rotary positions on the first ``rope_dim`` dimensions of ``q_j`` and ``k`` (the
    two-halves layout, ``inv_freq`` as the layer's own). Query ``t`` keeps the ``min(topk,
    t + 1)`` positions of largest ``I[t, .]``, ties to the lower position. ``apply`` takes
    ``(u, c_q)`` and returns the selection as packed words ``(T, mask_words(T))`` int32
    (``core/kernels/sparse_index.py``: bit ``s`` of row ``t``).

    On a TPU scores and selection are one Pallas call (``dsa_index_fwd`` in a device trace)
    that holds neither a (T, T) score nor a sort; where it does not apply the ``jnp`` form
    runs (dense scores, ``lax.top_k``) and ``record_fallback("nn.dsa", why)`` says why.
    Operands are in the input's type with float32 accumulation; the head weights, the ReLU
    and the sum over the heads are float32.
    """

    def __init__(self, dim: int, q_lora_rank: int, n_heads: int, head_dim: int, rope_dim: int,
                 topk: int, inv_freq, rope_magnitude: float = 1.0, eps: float = 1e-6,
                 dtype=jnp.float32, norm_init_std: float = 0.0):
        self.dim, self.q_lora_rank = dim, q_lora_rank
        self.n_heads, self.head_dim, self.rope_dim, self.topk = n_heads, head_dim, rope_dim, topk
        self.inv_freq, self.rope_magnitude = inv_freq, rope_magnitude
        self.eps = eps
        self.dtype = jnp.dtype(dtype)
        self.norm_init_std = norm_init_std

    def init(self, key):
        kq, kk, kw, kn, kb = jax.random.split(key, 5)
        r, h, d, dt = self.q_lora_rank, self.n_heads, self.head_dim, self.dtype
        std = jnp.float32(self.norm_init_std)
        return {
            "wq_b": normal_weight(kq, (r, h * d), dt, r ** -0.5),
            "wk": normal_weight(kk, (self.dim, d), dt, self.dim ** -0.5),
            "k_norm": {"weight": 1.0 + std * jax.random.normal(kn, (d,), jnp.float32),
                       "bias": std * jax.random.normal(kb, (d,), jnp.float32)},
            "weights_proj": normal_weight(kw, (self.dim, h), dt, self.dim ** -0.5),
        }

    def _rotate(self, x):
        r = self.rope_dim
        return jnp.concatenate(
            [rotate_halves(x[..., :r], self.inv_freq, self.rope_magnitude), x[..., r:]], axis=-1)

    def apply(self, params, x, *, key=None, train=False):
        u, c_q = x
        if u.ndim != 2:
            raise ValueError(f"LightningIndexer selects over one document (T, dim); got {u.shape}")
        h, d, dt = self.n_heads, self.head_dim, u.dtype
        with jax.named_scope("ht.nn.dsa"):
            q = contract("tr,rhd->htd", c_q, params["wq_b"].reshape(-1, h, d)).astype(dt)
            k = contract("td,de->te", u, params["wk"]).astype(dt).astype(jnp.float32)
            k = k - jnp.mean(k, axis=-1, keepdims=True)
            k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True) + jnp.float32(self.eps))
            k = (k * params["k_norm"]["weight"] + params["k_norm"]["bias"]).astype(dt)
            q, k = self._rotate(q), self._rotate(k)
            w = contract("td,dh->ht", u, params["weights_proj"]) * jnp.float32((h * d) ** -0.5)
            why = (sparse_index.decline_reason(q, k, w) if sparse_index.available()
                   else f"backend {jax.default_backend()}")
            if why is None:
                return sparse_index.dsa_index(q, k, w, self.topk)
            diagnostics.record_fallback("nn.dsa", f"{why}: T={u.shape[0]} {dt}")
            return sparse_index.dsa_index_plain(q, k, w, self.topk)


class MultiheadLatentAttention(Module):
    """Causal self-attention through low-rank latents (multi-head latent attention).

    ``c_q = RMSNorm(x W_qa)``; per head ``[q_nope; q_rope] = c_q W_qb``;
    ``[c_kv; k_rope] = x W_kva`` with ``c_kv <- RMSNorm(c_kv)``; per head
    ``[k_nope; v] = c_kv W_kvb``. ``k_rope`` is one vector for all heads; rotary
    positions (YaRN frequencies) go on the rope parts only; with ``rope_theta=None`` the layer
    has no positions at all (a published ``mla_use_nope``) and the rope parts are one more
    stretch of the query and of the shared key, unrotated. Scores are
    ``q . k * (nope + rope)^-1/2 * m^2`` with YaRN's ``m = 0.1 mscale_all_dim ln(factor) + 1``,
    the heads' outputs (``v_head_dim`` wide, not the query's width) are concatenated into
    ``W_o``. No bias anywhere. Input ``(..., T, dim)``, positions ``0..T-1`` on axis -2.

    Three variants that published models of this kind take. ``q_lora_rank=None``: no query
    latent, ``[q_nope; q_rope] = x W_q`` per head directly (no ``W_qa``, no query norm).
    ``head_gate=True``: every head's output is multiplied by ``sigmoid(x W_g)``, one
    scalar a head (float32), before ``W_o``. ``index=(heads, head_dim, topk)``: a
    :class:`LightningIndexer` reads ``x`` and ``c_q`` and every head of query ``t`` attends
    to the ``topk`` earlier tokens it selects, one set for all heads (one document
    ``(T, dim)`` then, and a query latent); ``apply`` returns ``(y, {"selection": packed
    words (T, mask_words(T))})``.

    ``head_groups`` > 1 runs the heads a group at a time (a ``lax.scan``): q, k, v and o exist
    for one group only and ``W_o``'s products are summed in float32. At 128 heads of 192 and
    32,768 tokens q alone is 1.6 GB; in four groups the core is the shape of 32 heads.

    This is the whole-sequence forward (scoring, prefill): no key/value cache and no
    absorbed products. On TPU the core runs in the flash Pallas kernel at
    ``d_qk != d_v``, named ``mla_flash_fwd`` in device traces, or ``dsa_flash_fwd`` under a
    selection, whose packed words it reads. Where the nope and value widths are whole lane
    tiles it takes the operands as the projections leave them (``flash_latent``): q with
    its rope lanes unturned (the kernel turns them), ``[k_nope | v]`` read in place and the
    one rope key for all heads; elsewhere it takes a concatenated q and k and a sliced v.
    Where neither applies (another backend, a sequence that does not tile) the XLA path runs
    on the concatenated operands and ``record_fallback("nn.mla", ...)`` says why. Parameters
    are stored in ``dtype`` (norm weights float32); contractions accumulate in float32.
    """

    def __init__(self, dim: int, num_heads: int, q_lora_rank: Optional[int], kv_lora_rank: int,
                 qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
                 rope_theta: Optional[float] = 10000.0, rope_scaling: Optional[dict] = None,
                 eps: float = 1e-6, dtype=jnp.float32, norm_init_std: float = 0.0,
                 head_gate: bool = False, index: Optional[Tuple[int, int, int]] = None,
                 head_groups: int = 1):
        if num_heads % head_groups:
            raise ValueError(f"{head_groups} groups do not divide {num_heads} heads")
        if index is not None and q_lora_rank is None:
            raise ValueError("the indexer reads the query latent: q_lora_rank is None")
        if index is not None and rope_theta is None:
            raise ValueError("the indexer's keys carry positions: rope_theta is None")
        self.dim = dim
        self.num_heads, self.head_groups = num_heads, head_groups
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.nope, self.rope, self.v_dim = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
        self.inv_freq = (None if rope_theta is None
                         else yarn_inv_freq(qk_rope_head_dim, rope_theta, rope_scaling))
        # cos and sin carry mscale / mscale_all_dim; the softmax scale carries m^2
        self.rope_magnitude = (_yarn_mscale(rope_scaling, "mscale")
                               / _yarn_mscale(rope_scaling, "mscale_all_dim"))
        self.scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5 \
            * _yarn_mscale(rope_scaling, "mscale_all_dim") ** 2
        self.dtype = jnp.dtype(dtype)
        self.head_gate = head_gate
        self.q_norm = None if q_lora_rank is None else RMSNorm(q_lora_rank, eps, norm_init_std)
        self.kv_norm = RMSNorm(kv_lora_rank, eps, norm_init_std)
        self.indexer = None if index is None else LightningIndexer(
            dim, q_lora_rank, index[0], index[1], qk_rope_head_dim, index[2], self.inv_freq,
            self.rope_magnitude, eps, dtype, norm_init_std)

    def init(self, key):
        kqa, kqb, kva, kvb, ko, kqn, kkn = jax.random.split(key, 7)
        h, dt = self.num_heads, self.dtype
        if self.q_norm is None:
            query = {"wq": normal_weight(kqb, (self.dim, h * (self.nope + self.rope)), dt,
                                         self.dim ** -0.5)}
        else:
            query = {
                "wq_a": normal_weight(kqa, (self.dim, self.q_lora_rank), dt, self.dim ** -0.5),
                "q_norm": self.q_norm.init(kqn),
                "wq_b": normal_weight(kqb, (self.q_lora_rank, h * (self.nope + self.rope)), dt,
                                      self.q_lora_rank ** -0.5),
            }
        params = {
            **query,
            "wkv_a": normal_weight(kva, (self.dim, self.kv_lora_rank + self.rope), dt,
                                   self.dim ** -0.5),
            "kv_norm": self.kv_norm.init(kkn),
            "wkv_b": normal_weight(kvb, (self.kv_lora_rank, h * (self.nope + self.v_dim)), dt,
                                   self.kv_lora_rank ** -0.5),
            "wo": normal_weight(ko, (h * self.v_dim, self.dim), dt, (h * self.v_dim) ** -0.5),
        }
        if self.head_gate:
            params["wg"] = normal_weight(jax.random.fold_in(key, 7), (self.dim, h), dt,
                                         self.dim ** -0.5)
        if self.indexer is not None:
            params["indexer"] = self.indexer.init(jax.random.fold_in(key, 8))
        return params

    def _core(self, q, k, v, selection=None):
        """Causal softmax(q k^T scale) v on (..., H, T, .) operands; under a ``selection``
        (packed words) a row sees the keys of its set bits only."""
        blocks, why = None, f"backend {jax.default_backend()}"
        if jax.default_backend() == "tpu":
            blocks = forward_blocks(q, k, v, selection is not None)
            why = "no block pair tiles and fits"
        if blocks is not None:
            name = "mla_flash_fwd" if selection is None else "dsa_flash_fwd"
            return flash_forward(q, k, v, True, self.scale, blocks, name=name, mask=selection)
        if diagnostics._enabled:  # trace time only
            diagnostics.record_fallback("nn.mla", f"{why}: T={q.shape[-2]} {q.dtype}")
        t = q.shape[-2]
        s = contract("...qd,...kd->...qk", q, k) * jnp.float32(self.scale)
        if selection is None:
            rows = jnp.arange(t, dtype=jnp.int32)
            keep = rows[:, None] >= rows[None, :]
        else:
            keep = sparse_index.unpack_mask(selection, t)
        s = jnp.where(keep, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return contract("...qk,...kd->...qd", p, v).astype(q.dtype)

    def _latent_blocks(self, q, selection):
        """The blocks of the latent operand form for the projected ``q``, or None where it
        does not apply: another backend, or what ``latent_blocks`` declines."""
        if jax.default_backend() != "tpu":
            return None
        t = q.shape[-2]
        kv = jax.ShapeDtypeStruct(q.shape[:-1] + (self.nope + self.v_dim,), q.dtype)
        k_rope = jax.ShapeDtypeStruct(q.shape[:-3] + (t, self.rope), q.dtype)
        return latent_blocks(q, kv, k_rope, selection is not None, self.inv_freq is not None)

    def _latent(self, params, x):
        """``(c_kv, k_rope)``: the normed key/value latent and the one rotated rope key."""
        if self.inv_freq is None:  # no positions: the shared key part as projected
            kv = contract("...td,dr->...tr", x, params["wkv_a"]).astype(x.dtype)
            return (self.kv_norm.apply(params["kv_norm"], kv[..., :self.kv_lora_rank]),
                    kv[..., self.kv_lora_rank:])
        kv = contract("...td,dr->...tr", x,
                      even_then_odd(params["wkv_a"], self.kv_lora_rank)).astype(x.dtype)
        c_kv = self.kv_norm.apply(params["kv_norm"], kv[..., :self.kv_lora_rank])
        return c_kv, rotate_halves(kv[..., self.kv_lora_rank:], self.inv_freq,
                                   self.rope_magnitude)

    def _heads(self, x, c_q, latent, selection, wq, wkv_b, wo, wg=None):
        """The heads whose columns of ``wq`` and ``wkv_b``, rows of ``wo`` and columns of
        ``wg`` these are, through the core and ``W_o``: (..., T, dim) float32. ``latent()``
        gives :meth:`_latent`'s pair: called after the queries are made, so that the heads
        in one group trace the operations in the order they always had."""
        dn, dr, dv, dt = self.nope, self.rope, self.v_dim, x.dtype
        h = wo.shape[0] // dv
        if self.inv_freq is None:
            q = contract("...tr,rhe->...hte", c_q, wq.reshape(wq.shape[0], h, dn + dr)).astype(dt)
        else:
            # the rope columns of both projections, published as interleaved pairs, are
            # taken even ones first: rotate_halves then turns the published pairs
            wq_b = even_then_odd(wq.reshape(wq.shape[0], h, dn + dr), dn)
            q = contract("...tr,rhe->...hte", c_q, wq_b).astype(dt)
        blocks = self._latent_blocks(q, selection)
        if blocks is None and self.inv_freq is not None:
            q = jnp.concatenate(
                [q[..., :dn], rotate_halves(q[..., dn:], self.inv_freq, self.rope_magnitude)],
                axis=-1)
        c_kv, k_rope = latent()
        kv_h = contract("...tr,rhe->...hte", c_kv,
                        wkv_b.reshape(self.kv_lora_rank, h, dn + dv)).astype(dt)
        if blocks is not None:
            turns = (None if self.inv_freq is None
                     else rope_turns(q.shape[-2], self.inv_freq, self.rope_magnitude))
            o = flash_latent(q, kv_h, k_rope, self.scale, blocks, turns,
                             name="mla_flash_fwd" if selection is None else "dsa_flash_fwd",
                             mask=selection)
        else:
            k_rope = jnp.broadcast_to(k_rope[..., None, :, :], kv_h.shape[:-1] + (dr,))
            k = jnp.concatenate([kv_h[..., :dn], k_rope], axis=-1)
            o = self._core(q, k, kv_h[..., dn:], selection)
        if wg is not None:
            gate = jax.nn.sigmoid(contract("...td,dh->...ht", x, wg))
            o = (o.astype(jnp.float32) * gate[..., None]).astype(dt)
        return contract("...htv,hvd->...td", o, wo.reshape(h, dv, self.dim))

    def apply(self, params, x, *, key=None, train=False):
        x = x.larray if isinstance(x, DNDarray) else x
        dt = x.dtype
        with jax.named_scope("ht.nn.mla"):
            if self.q_norm is None:
                c_q, wq = x, params["wq"]
            else:
                c_q = self.q_norm.apply(
                    params["q_norm"], contract("...td,dr->...tr", x, params["wq_a"]).astype(dt))
                wq = params["wq_b"]
            selection = None
            if self.indexer is not None:
                selection = self.indexer.apply(params["indexer"], (x, c_q))
            weights = [wq, params["wkv_b"], params["wo"]]
            if self.head_gate:
                weights.append(params["wg"])
            if self.head_groups == 1:
                y = self._heads(x, c_q, lambda: self._latent(params, x), selection, *weights)
            else:
                # a group's heads are neighbours: columns of W_qb, W_kvb and W_g, rows of W_o
                latent, g = self._latent(params, x), self.head_groups
                groups = tuple(
                    jnp.moveaxis(w.reshape(w.shape[:axis] + (g, -1) + w.shape[axis + 1:]), axis, 0)
                    for w, axis in zip(weights, (1, 1, 0, 1)))
                y = lax.scan(
                    lambda acc, ws: (acc + self._heads(x, c_q, lambda: latent, selection, *ws), None),
                    jnp.zeros(x.shape[:-1] + (self.dim,), jnp.float32), groups)[0]
            y = y.astype(dt)
            return y if selection is None else (y, {"selection": selection})


class GroupedQueryAttention(Module):
    """Causal self-attention over grouped heads, with the per-head norm, the window and
    the output gate that current open models put round them.

    ``q = x W_q`` (``num_heads`` heads of ``head_dim``), ``k = x W_k`` and ``v = x W_v``
    (``num_kv_heads`` heads), no bias. q and k are RMS-normed over the head's width, one
    weight vector for all heads. With ``rope_theta`` rotary positions
    turn all of a head's dimensions, halves convention (``x[:d/2]`` with ``x[d/2:]``);
    without, the layer has no positions at all. Query head ``h`` reads key/value head
    ``h // (num_heads // num_kv_heads)``. Row ``i`` sees keys ``j <= i`` and, with
    ``window``, ``i - j < window``. The concatenated heads are multiplied by
    ``sigmoid(x W_g)`` elementwise before ``W_o``. Input ``(..., T, dim)``, positions
    ``0..T-1`` on axis -2.

    This is the whole-sequence forward (scoring, prefill): no key/value cache. On TPU the
    core runs in the flash Pallas kernel, which reads each key/value head where it lies
    (nothing is repeated in HBM) and, under a window, visits only the key blocks that
    meet the band; the call is named ``swa_flash_fwd`` (window) or ``gqa_flash_fwd``
    (full) in device traces. Where the kernel does not apply (another backend, a sequence
    that does not tile) the XLA path runs, its band mask built from two ``iota``
    comparisons, and ``record_fallback("nn.gqa", ...)`` says why. Parameters are stored in
    ``dtype`` (norm weights float32); contractions accumulate in float32, and the norms,
    the softmax and the gate's sigmoid are float32.
    """

    def __init__(self, dim: int, num_heads: int, num_kv_heads: int, head_dim: int,
                 window: Optional[int] = None, rope_theta: Optional[float] = None,
                 eps: float = 1e-6, dtype=jnp.float32, norm_init_std: float = 0.0):
        if num_heads % num_kv_heads:
            raise ValueError(f"{num_kv_heads} key/value heads do not group {num_heads} heads")
        if window is not None and window < 1:
            raise ValueError(f"a window holds at least the row's own key; got {window}")
        self.dim, self.head_dim = dim, head_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.window = window
        self.inv_freq = None if rope_theta is None else yarn_inv_freq(head_dim, rope_theta, None)
        self.scale = head_dim ** -0.5
        self.dtype = jnp.dtype(dtype)
        self.q_norm = RMSNorm(head_dim, eps, norm_init_std)
        self.k_norm = RMSNorm(head_dim, eps, norm_init_std)

    def init(self, key):
        kq, kk, kv, kg, ko, kqn, kkn = jax.random.split(key, 7)
        d, hd, dt = self.dim, self.head_dim, self.dtype
        wide, narrow = self.num_heads * hd, self.num_kv_heads * hd
        return {
            "wq": normal_weight(kq, (d, wide), dt, d ** -0.5),
            "wk": normal_weight(kk, (d, narrow), dt, d ** -0.5),
            "wv": normal_weight(kv, (d, narrow), dt, d ** -0.5),
            "wg": normal_weight(kg, (d, wide), dt, d ** -0.5),
            "wo": normal_weight(ko, (wide, d), dt, wide ** -0.5),
            "q_norm": self.q_norm.init(kqn),
            "k_norm": self.k_norm.init(kkn),
        }

    def _core(self, q, k, v):
        """Causal (and windowed) softmax(q k^T scale) v on q (..., H, T, d) and k, v
        (..., Hkv, T, d)."""
        blocks, why = None, f"backend {jax.default_backend()}"
        if jax.default_backend() == "tpu":
            blocks, why = forward_blocks(q, k, v), "no block pair tiles and fits"
        if blocks is not None:
            name = "gqa_flash_fwd" if self.window is None else "swa_flash_fwd"
            return flash_forward(q, k, v, True, self.scale, blocks, name=name,
                                 window=self.window)
        if diagnostics._enabled:  # trace time only
            diagnostics.record_fallback("nn.gqa", f"{why}: T={q.shape[-2]} {q.dtype}")
        t, g = q.shape[-2], self.num_kv_heads
        qg = q.reshape(q.shape[:-3] + (g, self.num_heads // g) + q.shape[-2:])
        s = contract("...grqd,...gkd->...grqk", qg, k) * jnp.float32(self.scale)
        gap = lax.broadcasted_iota(jnp.int32, (t, t), 0) - lax.broadcasted_iota(jnp.int32, (t, t), 1)
        keep = gap >= 0 if self.window is None else (gap >= 0) & (gap < self.window)
        p = jax.nn.softmax(jnp.where(keep, s, _NEG_INF), axis=-1).astype(v.dtype)
        return contract("...grqk,...gkd->...grqd", p, v).astype(q.dtype).reshape(q.shape)

    def apply(self, params, x, *, key=None, train=False):
        x = x.larray if isinstance(x, DNDarray) else x
        h, g, hd, dt = self.num_heads, self.num_kv_heads, self.head_dim, x.dtype
        with jax.named_scope("ht.nn.gqa"):
            q = contract("...td,dhe->...hte", x, params["wq"].reshape(self.dim, h, hd)).astype(dt)
            k = contract("...td,dhe->...hte", x, params["wk"].reshape(self.dim, g, hd)).astype(dt)
            v = contract("...td,dhe->...hte", x, params["wv"].reshape(self.dim, g, hd)).astype(dt)
            q = self.q_norm.apply(params["q_norm"], q)
            k = self.k_norm.apply(params["k_norm"], k)
            if self.inv_freq is not None:
                q, k = rotate_halves(q, self.inv_freq), rotate_halves(k, self.inv_freq)
            gate = jax.nn.sigmoid(contract("...td,dhe->...hte", x,
                                           params["wg"].reshape(self.dim, h, hd)))
            o = (self._core(q, k, v).astype(jnp.float32) * gate).astype(dt)
            return contract("...hte,hed->...td", o,
                            params["wo"].reshape(h, hd, self.dim)).astype(dt)


def _keyed_dropout(x, p: float, key, train: bool):
    """Inverted dropout on a jax.Array or DNDarray (explicit key; inert in eval)
    — delegates to :func:`heat_tpu.nn.functional.dropout`, which preserves any
    split (elementwise op)."""
    from . import functional as F

    return F.dropout(x, p, training=train, key=key)


def _resolve_activation(activation):
    """'relu' / 'gelu' / any callable — the torch TransformerXLayer contract."""
    if callable(activation):
        return activation
    if activation in ("relu", "gelu"):
        from . import functional as F

        return getattr(F, activation)
    raise ValueError(
        f"activation must be 'relu', 'gelu' or a callable, got {activation!r}"
    )


class _FeedForwardMixin:
    """The linear1 → activation → dropout → linear2 → dropout block shared by the
    encoder and decoder layers (expects self.linear1/linear2/activation/dropout_p)."""

    def _ff_block(self, params, x, key, train):
        k1, k2 = jax.random.split(key) if key is not None else (None, None)
        h = self.activation(self.linear1.apply(params["linear1"], x))
        h = _keyed_dropout(h, self.dropout_p, k1, train)
        h = self.linear2.apply(params["linear2"], h)
        return _keyed_dropout(h, self.dropout_p, k2, train)


class _LayerStack(Module):
    """N fresh-parameter deep copies of a layer plus an optional final norm —
    the shared container shape of TransformerEncoder and TransformerDecoder."""

    def __init__(self, layer, num_layers: int, norm=None):
        import copy

        self.layers = [copy.deepcopy(layer) for _ in range(num_layers)]
        self.num_layers = num_layers
        self.norm = norm

    def named_submodules(self):
        subs = [(str(i), m) for i, m in enumerate(self.layers)]
        if self.norm is not None:
            subs.append(("norm", self.norm))
        return subs

    def init(self, key):
        ks = jax.random.split(key, self.num_layers + 1)
        params = {str(i): m.init(k) for (i, m), k in
                  zip(enumerate(self.layers), ks)}
        if self.norm is not None:
            params["norm"] = self.norm.init(ks[-1])
        return params

    def _run_stack(self, params, x, key, call):
        """Thread x through the layers (per-layer key split), then the final norm.
        ``call(layer, layer_params, x, k)`` runs one layer."""
        ks = (
            jax.random.split(key, self.num_layers)
            if key is not None
            else [None] * self.num_layers
        )
        for i, (layer, k) in enumerate(zip(self.layers, ks)):
            x = call(layer, params[str(i)], x, k)
        if self.norm is not None:
            x = self.norm.apply(params["norm"], x)
        return x


class TransformerEncoderLayer(_FeedForwardMixin, Module):
    """torch.nn.TransformerEncoderLayer semantics (self-attention + feedforward,
    post-norm by default, ``norm_first`` pre-norm variant).

    The reference exposes this via its torch fall-through (``nn/__init__.py:18-31``);
    here it composes the native :class:`MultiheadAttention` (ring dispatch on
    sequence-split DNDarrays), :class:`~heat_tpu.nn.modules.Linear` and LayerNorm,
    so the whole layer jits to one XLA program. ``batch_first`` defaults True (the
    TPU-natural layout, unlike torch's False default — see the deviations page);
    dropout needs ``apply(..., train=True, key=...)``.
    """

    def __init__(self, d_model: int, nhead: int, dim_feedforward: int = 2048,
                 dropout: float = 0.1, activation="relu",
                 layer_norm_eps: float = 1e-5, batch_first: bool = True,
                 norm_first: bool = False, bias: bool = True):
        from .modules import LayerNorm, Linear

        self.self_attn = MultiheadAttention(
            d_model, nhead, dropout=dropout, bias=bias, batch_first=batch_first
        )
        self.linear1 = Linear(d_model, dim_feedforward, bias=bias)
        self.linear2 = Linear(dim_feedforward, d_model, bias=bias)
        self.norm1 = LayerNorm(d_model, eps=layer_norm_eps)
        self.norm2 = LayerNorm(d_model, eps=layer_norm_eps)
        self.dropout_p = dropout
        self.norm_first = norm_first
        self.activation = _resolve_activation(activation)

    def init(self, key):
        ks = jax.random.split(key, 5)
        return {
            "self_attn": self.self_attn.init(ks[0]),
            "linear1": self.linear1.init(ks[1]),
            "linear2": self.linear2.init(ks[2]),
            "norm1": self.norm1.init(ks[3]),
            "norm2": self.norm2.init(ks[4]),
        }

    def _sa_block(self, params, x, key, train, src_mask, src_key_padding_mask,
                  is_causal):
        k_attn, k_drop = (
            jax.random.split(key) if key is not None else (None, None)
        )
        out = self.self_attn.apply(
            params["self_attn"], x, key=k_attn, train=train, attn_mask=src_mask,
            key_padding_mask=src_key_padding_mask, is_causal=is_causal,
        )
        return _keyed_dropout(out, self.dropout_p, k_drop, train)

    def apply(self, params, src, *, key=None, train=False, src_mask=None,
              src_key_padding_mask=None, is_causal: bool = False):
        k_sa, k_ff = jax.random.split(key) if key is not None else (None, None)
        norm1 = lambda v: self.norm1.apply(params["norm1"], v)
        norm2 = lambda v: self.norm2.apply(params["norm2"], v)
        x = src
        if self.norm_first:
            x = x + self._sa_block(params, norm1(x), k_sa, train, src_mask,
                                   src_key_padding_mask, is_causal)
            x = x + self._ff_block(params, norm2(x), k_ff, train)
        else:
            x = norm1(x + self._sa_block(params, x, k_sa, train, src_mask,
                                         src_key_padding_mask, is_causal))
            x = norm2(x + self._ff_block(params, x, k_ff, train))
        return x

    def __call__(self, src, src_mask=None, src_key_padding_mask=None,
                 is_causal: bool = False, *, key=None, train=None):
        key, train = self._resolve_ctx(key, train)
        return self.apply(
            self.params, src, key=key, train=train, src_mask=src_mask,
            src_key_padding_mask=src_key_padding_mask, is_causal=is_causal,
        )


class TransformerEncoder(_LayerStack):
    """torch.nn.TransformerEncoder: N independently-parameterised copies of an
    encoder layer (same hyperparameters, fresh params per layer), plus an
    optional final norm."""

    def __init__(self, encoder_layer: TransformerEncoderLayer, num_layers: int,
                 norm=None):
        super().__init__(encoder_layer, num_layers, norm)

    def apply(self, params, src, *, key=None, train=False, src_mask=None,
              src_key_padding_mask=None, is_causal: bool = False):
        return self._run_stack(
            params, src, key,
            lambda layer, p, x, k: layer.apply(
                p, x, key=k, train=train, src_mask=src_mask,
                src_key_padding_mask=src_key_padding_mask, is_causal=is_causal,
            ),
        )

    def __call__(self, src, src_mask=None, src_key_padding_mask=None,
                 is_causal: bool = False, *, key=None, train=None):
        key, train = self._resolve_ctx(key, train)
        return self.apply(
            self.params, src, key=key, train=train, src_mask=src_mask,
            src_key_padding_mask=src_key_padding_mask, is_causal=is_causal,
        )


class TransformerDecoderLayer(_FeedForwardMixin, Module):
    """torch.nn.TransformerDecoderLayer semantics: masked self-attention over the
    target, cross-attention into the encoder memory, then feedforward — each with
    residual + LayerNorm (post-norm default, ``norm_first`` pre-norm).

    Same composition story as :class:`TransformerEncoderLayer`; the reference
    reaches this through its torch fall-through (``nn/__init__.py:18-31``).
    """

    def __init__(self, d_model: int, nhead: int, dim_feedforward: int = 2048,
                 dropout: float = 0.1, activation="relu",
                 layer_norm_eps: float = 1e-5, batch_first: bool = True,
                 norm_first: bool = False, bias: bool = True):
        from .modules import LayerNorm, Linear

        self.self_attn = MultiheadAttention(
            d_model, nhead, dropout=dropout, bias=bias, batch_first=batch_first
        )
        self.multihead_attn = MultiheadAttention(
            d_model, nhead, dropout=dropout, bias=bias, batch_first=batch_first
        )
        self.linear1 = Linear(d_model, dim_feedforward, bias=bias)
        self.linear2 = Linear(dim_feedforward, d_model, bias=bias)
        self.norm1 = LayerNorm(d_model, eps=layer_norm_eps)
        self.norm2 = LayerNorm(d_model, eps=layer_norm_eps)
        self.norm3 = LayerNorm(d_model, eps=layer_norm_eps)
        self.dropout_p = dropout
        self.norm_first = norm_first
        self.activation = _resolve_activation(activation)

    def init(self, key):
        ks = jax.random.split(key, 7)
        return {
            "self_attn": self.self_attn.init(ks[0]),
            "multihead_attn": self.multihead_attn.init(ks[1]),
            "linear1": self.linear1.init(ks[2]),
            "linear2": self.linear2.init(ks[3]),
            "norm1": self.norm1.init(ks[4]),
            "norm2": self.norm2.init(ks[5]),
            "norm3": self.norm3.init(ks[6]),
        }

    def _attn_block(self, attn, params, q, kv, key, train, mask, padding_mask,
                    is_causal):
        k_attn, k_drop = (
            jax.random.split(key) if key is not None else (None, None)
        )
        x = q if kv is None else (q, kv, kv)
        out = attn.apply(
            params, x, key=k_attn, train=train, attn_mask=mask,
            key_padding_mask=padding_mask, is_causal=is_causal,
        )
        return _keyed_dropout(out, self.dropout_p, k_drop, train)

    def apply(self, params, tgt, memory=None, *, key=None, train=False,
              tgt_mask=None, memory_mask=None, tgt_key_padding_mask=None,
              memory_key_padding_mask=None, tgt_is_causal: bool = False,
              memory_is_causal: bool = False):
        if memory is None:
            raise ValueError("TransformerDecoderLayer needs the encoder memory")
        k_sa, k_ca, k_ff = (
            jax.random.split(key, 3) if key is not None else (None, None, None)
        )
        norm = lambda i, v: getattr(self, f"norm{i}").apply(params[f"norm{i}"], v)
        sa = lambda v, k: self._attn_block(
            self.self_attn, params["self_attn"], v, None, k, train, tgt_mask,
            tgt_key_padding_mask, tgt_is_causal,
        )
        ca = lambda v, k: self._attn_block(
            self.multihead_attn, params["multihead_attn"], v, memory, k, train,
            memory_mask, memory_key_padding_mask, memory_is_causal,
        )
        x = tgt
        if self.norm_first:
            x = x + sa(norm(1, x), k_sa)
            x = x + ca(norm(2, x), k_ca)
            x = x + self._ff_block(params, norm(3, x), k_ff, train)
        else:
            x = norm(1, x + sa(x, k_sa))
            x = norm(2, x + ca(x, k_ca))
            x = norm(3, x + self._ff_block(params, x, k_ff, train))
        return x

    def __call__(self, tgt, memory, tgt_mask=None, memory_mask=None,
                 tgt_key_padding_mask=None, memory_key_padding_mask=None,
                 tgt_is_causal: bool = False, memory_is_causal: bool = False,
                 *, key=None, train=None):
        key, train = self._resolve_ctx(key, train)
        return self.apply(
            self.params, tgt, memory, key=key, train=train, tgt_mask=tgt_mask,
            memory_mask=memory_mask, tgt_key_padding_mask=tgt_key_padding_mask,
            memory_key_padding_mask=memory_key_padding_mask,
            tgt_is_causal=tgt_is_causal, memory_is_causal=memory_is_causal,
        )


class TransformerDecoder(_LayerStack):
    """torch.nn.TransformerDecoder: N fresh-parameter copies of a decoder layer
    plus an optional final norm."""

    def __init__(self, decoder_layer: TransformerDecoderLayer, num_layers: int,
                 norm=None):
        super().__init__(decoder_layer, num_layers, norm)

    def apply(self, params, tgt, memory=None, *, key=None, train=False,
              **mask_kwargs):
        return self._run_stack(
            params, tgt, key,
            lambda layer, p, x, k: layer.apply(
                p, x, memory, key=k, train=train, **mask_kwargs
            ),
        )

    def __call__(self, tgt, memory, *, key=None, train=None, **mask_kwargs):
        key, train = self._resolve_ctx(key, train)
        return self.apply(self.params, tgt, memory, key=key, train=train,
                          **mask_kwargs)


class Transformer(Module):
    """torch.nn.Transformer semantics: an encoder-decoder pair sharing one set of
    hyperparameters, plus the ``generate_square_subsequent_mask`` helper.

    ``forward(src, tgt)`` runs ``decoder(tgt, encoder(src))``; all the usual mask
    and padding arguments pass through. ``batch_first`` defaults True (the
    TPU-natural layout — see the deviations page)."""

    def __init__(self, d_model: int = 512, nhead: int = 8,
                 num_encoder_layers: int = 6, num_decoder_layers: int = 6,
                 dim_feedforward: int = 2048, dropout: float = 0.1,
                 activation="relu", layer_norm_eps: float = 1e-5,
                 batch_first: bool = True, norm_first: bool = False,
                 bias: bool = True):
        from .modules import LayerNorm

        self.d_model = d_model
        self.nhead = nhead
        self.batch_first = batch_first
        self.encoder = TransformerEncoder(
            TransformerEncoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                layer_norm_eps, batch_first, norm_first, bias,
            ),
            num_encoder_layers,
            norm=LayerNorm(d_model, eps=layer_norm_eps),
        )
        self.decoder = TransformerDecoder(
            TransformerDecoderLayer(
                d_model, nhead, dim_feedforward, dropout, activation,
                layer_norm_eps, batch_first, norm_first, bias,
            ),
            num_decoder_layers,
            norm=LayerNorm(d_model, eps=layer_norm_eps),
        )

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"encoder": self.encoder.init(k1), "decoder": self.decoder.init(k2)}

    def apply(self, params, src, tgt=None, *, key=None, train=False,
              src_mask=None, tgt_mask=None, memory_mask=None,
              src_key_padding_mask=None, tgt_key_padding_mask=None,
              memory_key_padding_mask=None, src_is_causal: bool = False,
              tgt_is_causal: bool = False, memory_is_causal: bool = False):
        if tgt is None:
            raise ValueError("Transformer needs both src and tgt")
        k1, k2 = jax.random.split(key) if key is not None else (None, None)
        memory = self.encoder.apply(
            params["encoder"], src, key=k1, train=train, src_mask=src_mask,
            src_key_padding_mask=src_key_padding_mask, is_causal=src_is_causal,
        )
        return self.decoder.apply(
            params["decoder"], tgt, memory, key=k2, train=train,
            tgt_mask=tgt_mask, memory_mask=memory_mask,
            tgt_key_padding_mask=tgt_key_padding_mask,
            memory_key_padding_mask=memory_key_padding_mask,
            tgt_is_causal=tgt_is_causal, memory_is_causal=memory_is_causal,
        )

    def __call__(self, src, tgt, *, key=None, train=None, **mask_kwargs):
        key, train = self._resolve_ctx(key, train)
        return self.apply(self.params, src, tgt, key=key, train=train,
                          **mask_kwargs)

    @staticmethod
    def generate_square_subsequent_mask(sz: int):
        """(sz, sz) additive f32 mask: 0 on/below the diagonal, -inf above —
        torch's causal-mask helper, usable as ``attn_mask``/``tgt_mask``."""
        return jnp.where(
            jnp.arange(sz)[:, None] >= jnp.arange(sz)[None, :],
            jnp.float32(0), jnp.float32(-jnp.inf),
        )
