"""Fused KMeans assignment + centroid-accumulate Pallas kernel, rows on the lanes.

The jnp Lloyd body materialises the (n, k) distance matrix in HBM, reads it back for
the argmin, then reads x again for the segment-sum update. This kernel streams x
through VMEM once per iteration and keeps everything else on the chip.

**Geometry.** XLA keeps ``f32[n, d]`` with the long axis minor, so the kernel takes
``x.T``, shape ``(d, n)``, which is a bitcast of the operand where it lies: no copy, no
padded lane. A grid step sees a ``(d, BN)`` block; the clusters sit on the sublanes and
the rows on the lanes, so the ``(k, BN)`` score tile is lane-dense (k = 8 is one sublane
tile), the argmin reduces over sublanes and the labels come out as a ``(1, BN)`` lane
vector. ``|x|^2`` is the same for every cluster and does not enter the argmin; it is
computed only where the ``sse`` is asked for. The loop form (``with_labels=False``)
writes ``(sums, counts)`` and nothing of n elements.

**Precision.** ``c . x`` is a ``dot_general`` at ``Precision.HIGHEST``. The update
``onehot . x^T`` contracts the lanes of both operands; the one-hot is exact in bfloat16,
so x's three bfloat16 parts (hi + mid + lo carry all 24 mantissa bits), accumulated in
float32, give the float32 sums in three MXU passes where ``HIGHEST`` spends six.

**What bounds a step** (TPU v5e, 2^24 x 64 rows, k = 8; my chip runs, PR 28): HBM. A
Lloyd iteration takes 5.93 ms inside the fit, 2.9 us per 8,192-row step (2,048 steps),
against 5.24 ms for one pass over the 4.29 GB operand at the published 819 GB/s:
``fit_hbm_roofline_share`` 85.1% where the kernel this replaced read 8.9% at 55.7 ms an
iteration (ledger, PR 27). Mosaic's static schedule of the step is 3,639 bundles, 2.4 us
at 1.5 GHz, mostly vector loads and stores round the MXU pushes, so the arithmetic hides
behind the block's DMA. Tried and dropped (same runs): ``HIGHEST`` for the update too,
8.0 ms a pass; exact float32 on the VPU, 8.9-12.1 ms; BN = 4,096, 6.5 ms; BN = 32,768
under a raised ``vmem_limit_bytes``, 6.0 against 6.2 ms, not worth a second scope.

Reference workload: KMeans 10M×64 (north-star #3, reference heat/cluster/kmeans.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["fused_assign_update", "fused_assign_update_reference"]

# Mosaic's default scoped-VMEM limit is 16 MiB. The gate keeps the kernel's count of
# what a step holds (``_vmem_bytes``) under this budget, and the x block at the size
# beyond which a step gained under 1% on the chip (8,192 rows at d = 64; PERF.md, PR 28)
_VMEM_BUDGET = 14 * 2**20
_X_BLOCK_BYTES = 2 * 2**20
_MAX_BLOCK_N = 32768


def fused_assign_update_reference(
    xv: jax.Array, centers: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Pure-jnp reference: (labels, sums, counts, sse) of nearest-centroid assignment."""
    xx = jnp.sum(xv * xv, axis=1, keepdims=True)
    cc = jnp.sum(centers * centers, axis=1)[None, :]
    d2 = xx + cc - 2.0 * jnp.matmul(xv, centers.T, precision=jax.lax.Precision.HIGHEST)
    d2 = jnp.maximum(d2, 0.0)
    labels = jnp.argmin(d2, axis=1).astype(jnp.int32)
    k = centers.shape[0]
    sums = jnp.zeros_like(centers).at[labels].add(xv)
    counts = jnp.zeros((k,), xv.dtype).at[labels].add(1.0)
    sse = jnp.sum(jnp.min(d2, axis=1))
    return labels, sums, counts, sse


def _kernel(x_ref, c_ref, *out_refs, n: int, k: int, with_labels: bool):
    import jax.experimental.pallas as pl  # deferred so CPU-only processes never pay it

    if with_labels:
        labels_ref, sums_ref, counts_ref, sse_ref = out_refs
    else:
        sums_ref, counts_ref = out_refs
    i = pl.program_id(0)
    bn = x_ref.shape[1]
    kp = c_ref.shape[0]  # k padded to the sublane tile

    @pl.when(i == 0)
    def _():
        sums_ref[:] = jnp.zeros_like(sums_ref)
        counts_ref[:] = jnp.zeros_like(counts_ref)
        if with_labels:
            sse_ref[0, 0] = jnp.float32(0.0)

    x = x_ref[:]  # (d, BN)
    valid = None
    if n % bn:
        # the last block reaches past n and what lies there is garbage: x itself is
        # masked before any product (0 x NaN), not only the one-hot
        valid = i * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1) < n
        x = jnp.where(valid, x, 0.0)
    c = c_ref[:]  # (kp, d)
    cc = jnp.sum(c * c, axis=1, keepdims=True)  # (kp, 1)
    if kp != k:
        cc = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (kp, 1), 0) < k, cc, jnp.inf)
    # (kp, BN) score tile on the MXU. The quadratic expansion cancels
    # catastrophically for near points, so the cross term needs full input
    # precision (same rationale as spatial._pairwise).
    xc = jax.lax.dot_general(
        c,
        x,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    score = cc - 2.0 * xc  # d2 less |x|^2, which no argmin over the clusters sees
    # explicit int32 argmin (Mosaic's reduce-index only lowers int32; the framework
    # runs with x64 enabled): first index attaining the minimum, numpy tie rule
    row = jax.lax.broadcasted_iota(jnp.int32, (kp, bn), 0)
    best = jnp.min(score, axis=0, keepdims=True)  # (1, BN)
    labels = jnp.min(jnp.where(score == best, row, jnp.int32(kp)), axis=0, keepdims=True)
    if valid is not None:
        labels = jnp.where(valid, labels, jnp.int32(kp))  # no cluster: counted nowhere
    onehot = (row == labels).astype(jnp.bfloat16)  # (kp, BN), exact

    # per-cluster partial sums, (kp, BN) . (d, BN)^T over the lanes: x as three
    # bfloat16 parts against the exact one-hot, float32 accumulation, smallest first
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)

    def over_lanes(part):
        # one bfloat16 pass each, stated: a process-wide jax_default_matmul_precision
        # of "highest" must not reach these operands (Mosaic refuses it for bfloat16)
        return jax.lax.dot_general(
            onehot, part, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT,
        )

    sums_ref[:] += (over_lanes(lo) + over_lanes(mid)) + over_lanes(hi)
    counts_ref[:] += jnp.sum(onehot.astype(jnp.float32), axis=1, keepdims=True)

    if with_labels:
        labels_ref[:] = labels
        xx = jnp.sum(x * x, axis=0, keepdims=True)  # (1, BN)
        d2 = jnp.maximum(xx + best, 0.0)
        if valid is not None:
            d2 = jnp.where(valid, d2, 0.0)
        sse_ref[0, 0] += jnp.sum(d2)


def _round_up(v: int, m: int) -> int:
    return -(-v // m) * m


def _vmem_bytes(d: int, kp: int, block_n: int) -> int:
    """What Mosaic keeps in VMEM for one grid step, counted from above: the (d, BN) x
    block in both pipeline buffers plus its float32 and bfloat16 working copies (4.25 x
    blocks in all; Mosaic's own stack at d = 64 reads 4.1), 3 bytes an element of the
    (kp, BN) score / index / one-hot tiles, the (1, BN) labels block in both buffers,
    and the (kp, d) centers and sums with their buffers and lane padding. Held against
    Mosaic's refusals over d x k (7 .. 4,096 x 3 .. 1,024) when PR 28 set it."""
    x_blocks = int(4.25 * 4 * _round_up(d, 8) * block_n)
    return x_blocks + block_n * (3 * kp + 16) + 6 * 4 * kp * max(d, 128)


def _block_n(d: int, k: int) -> Optional[int]:
    """Rows a grid step for these shapes: the largest power-of-two multiple of 128 whose
    x block stays within ``_X_BLOCK_BYTES`` and whose resident bytes fit the budget;
    ``None`` where not even 128 rows fit (the gate then declines)."""
    kp = _round_up(k, 8)
    bn = _MAX_BLOCK_N
    while bn >= 128:
        if 4 * d * bn <= _X_BLOCK_BYTES and _vmem_bytes(d, kp, bn) <= _VMEM_BUDGET:
            return bn
        bn //= 2
    return None


@functools.partial(jax.jit, static_argnames=("with_labels", "block_n", "interpret"))
def _fused_pallas(xv, centers, with_labels: bool = True, block_n: Optional[int] = None,
                  interpret: bool = False):
    import jax.experimental.pallas as pl  # ht: ignore[trace-lazy-import] -- pallas imports deferred so CPU-only processes never pay them; runs once per compile, imports nothing of heat_tpu
    from jax.experimental.pallas import tpu as pltpu  # ht: ignore[trace-lazy-import] -- pallas imports deferred so CPU-only processes never pay them; runs once per compile, imports nothing of heat_tpu

    # the framework enables x64 globally; Mosaic only legalizes i32 scalars, so the
    # kernel (all-i32/f32 by construction) is traced with x64 off
    with jax.enable_x64(False):
        return _fused_pallas_body(xv, centers, pl, pltpu, with_labels, block_n, interpret)


def _fused_pallas_body(xv, centers, pl, pltpu, with_labels: bool, block_n, interpret: bool):
    n, d = xv.shape
    k = centers.shape[0]
    kp = _round_up(k, 8)
    # ``block_n`` is a test hook; the launcher takes the block from the shapes
    bn = min(block_n or _block_n(d, k) or 128, _round_up(n, 128))
    resident = lambda shape, space: pl.BlockSpec(shape, lambda i: (0,) * len(shape), memory_space=space)

    out_specs = [resident((kp, d), pltpu.VMEM), resident((kp, 1), pltpu.VMEM)]
    out_shape = [
        jax.ShapeDtypeStruct((kp, d), jnp.float32),
        jax.ShapeDtypeStruct((kp, 1), jnp.float32),
    ]
    if with_labels:
        out_specs = [pl.BlockSpec((1, bn), lambda i: (0, i), memory_space=pltpu.VMEM),
                     *out_specs, resident((1, 1), pltpu.SMEM)]
        out_shape = [jax.ShapeDtypeStruct((1, n), jnp.int32), *out_shape,
                     jax.ShapeDtypeStruct((1, 1), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, k=k, with_labels=with_labels),
        grid=(pl.cdiv(n, bn),),  # ragged n: the tail is masked in the kernel, nothing is padded
        in_specs=[
            # x where it lies: the transpose of f32[n, d]{0,1} is a bitcast
            pl.BlockSpec((d, bn), lambda i: (0, i), memory_space=pltpu.VMEM),
            resident((kp, d), pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kmeans_assign_update",
    )(xv.astype(jnp.float32).T, jnp.pad(centers.astype(jnp.float32), ((0, kp - k), (0, 0))))
    if not with_labels:
        sums, counts = out
        return sums[:k], counts[:k, 0]
    labels, sums, counts, sse = out
    return labels[0], sums[:k], counts[:k, 0], sse[0, 0]


def available(interpret: bool = False) -> bool:
    """Whether the kernel can run here: on a TPU backend, or interpreted anywhere."""
    return interpret or jax.default_backend() == "tpu"


def decline_reason(d: int, k: int) -> Optional[str]:
    """Why the kernel is not compiled for ``d`` features and ``k`` clusters, or ``None``
    where it is: the gate says no before Mosaic does."""
    if _block_n(d, k) is None:
        return f"VMEM: d={d}, k={k} leave no 128-row block within {_VMEM_BUDGET >> 20} MiB"
    return None


def fused_assign_update(xv: jax.Array, centers: jax.Array, with_labels: bool = True,
                        interpret: bool = False):
    """``(labels, sums, counts, sse)`` in one streaming pass over ``xv``; with
    ``with_labels=False`` (the form a Lloyd iteration needs) ``(sums, counts)`` only, and
    nothing of n elements is written.

    Uses the Pallas TPU kernel on TPU backends (or ``interpret=True`` anywhere, where no
    VMEM limit applies); falls back to the jnp reference otherwise, and where
    :func:`decline_reason` says why.
    """
    if not available(interpret) or (
        not interpret and decline_reason(xv.shape[1], centers.shape[0]) is not None
    ):
        labels, sums, counts, sse = fused_assign_update_reference(xv, centers)
        return (labels, sums, counts, sse) if with_labels else (sums, counts)
    return _fused_pallas(xv, centers, with_labels=with_labels, interpret=interpret)
