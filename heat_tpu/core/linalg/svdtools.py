"""Hierarchical SVD (reference heat/core/linalg/svdtools.py, 531 LoC).

The reference's hSVD is the framework's north-star workload: per-rank truncated SVDs of
the local column blocks, then a tree reduction where "loser" ranks ``Send`` their
``U·diag(sigma)`` to "winner" ranks that concatenate and re-truncate
(``svdtools.py:260-470``), with a merge-budget scheduler (``:357-382``) deciding the tree
arity under a memory cap.

The TPU build keeps the identical mathematical tree — local truncation, pairwise/k-way
merge, error accumulation ``err² = Σ err_i² + err_merge²`` — but the "ranks" are column
blocks of one global sharded array: each level is a few jnp ops (batched where shapes
agree) and the Sends are XLA data movement. The merge scheduling survives as plain host
logic between device steps, exactly as SURVEY.md prescribes for data-dependent comm
schedules.
"""

from __future__ import annotations

from typing import List, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from .. import factories, types
from ..dndarray import DNDarray
from .basics import PARITY_PRECISION, matmul, vector_norm

__all__ = ["hsvd", "hsvd_rank", "hsvd_rtol"]


def guarded_svd(x, full_matrices: bool = False, compute_uv: bool = True):
    """``jnp.linalg.svd`` with the TPU x64 guard, shared by hsvd and the full
    :func:`heat_tpu.linalg.svd`: the float32 SVD lowering SIGABRTs the TPU
    compiler when global x64 mode is on (a CHECK in shape.h; still so on TPU v5e
    with jax 0.9.0 / libtpu 0.0.34, where float64 SVD and float32 QR/eigh/Cholesky
    compile unguarded), so the op is traced in x32 scope there."""
    if jax.default_backend() != "cpu" and x.dtype == jnp.float32:
        with jax.enable_x64(False):
            return jnp.linalg.svd(x, full_matrices=full_matrices, compute_uv=compute_uv)
    return jnp.linalg.svd(x, full_matrices=full_matrices, compute_uv=compute_uv)


def hsvd_rank(
    A: DNDarray,
    maxrank: int,
    compute_sv: bool = False,
    maxmergedim: Optional[int] = None,
    safetyshift: int = 5,
    silent: bool = True,
):
    """Hierarchical SVD truncated to ``maxrank`` (reference ``svdtools.py:32``)."""
    if A.ndim != 2:
        raise RuntimeError(f"hsvd_rank requires a 2-D array, got {A.ndim}-D")
    A_local_size = max(int(np.ceil(s / max(A.comm.size, 1))) for s in A.gshape)
    if maxmergedim is None:
        maxmergedim = max(A_local_size + 1, 2 * (maxrank + safetyshift) + 1)
    return hsvd(
        A,
        maxrank=maxrank,
        maxmergedim=maxmergedim,
        safetyshift=safetyshift,
        compute_sv=compute_sv,
        silent=silent,
        warnings_off=True,
    )


def hsvd_rtol(
    A: DNDarray,
    rtol: float,
    compute_sv: bool = False,
    maxrank: Optional[int] = None,
    maxmergedim: Optional[int] = None,
    safetyshift: int = 5,
    no_of_merges: Optional[int] = None,
    silent: bool = True,
):
    """Hierarchical SVD truncated to a relative reconstruction-error bound
    (reference ``svdtools.py:125``)."""
    if A.ndim != 2:
        raise RuntimeError(f"hsvd_rtol requires a 2-D array, got {A.ndim}-D")
    return hsvd(
        A,
        rtol=rtol,
        maxrank=maxrank,
        maxmergedim=maxmergedim,
        safetyshift=safetyshift,
        no_of_merges=no_of_merges or 2,
        compute_sv=compute_sv,
        silent=silent,
        warnings_off=True,
    )


def hsvd(
    A: DNDarray,
    maxrank: Optional[int] = None,
    maxmergedim: Optional[int] = None,
    rtol: Optional[float] = None,
    safetyshift: int = 0,
    no_of_merges: Optional[int] = 2,
    compute_sv: bool = False,
    silent: bool = True,
    warnings_off: bool = False,
):
    """Low-level hierarchical SVD (reference ``svdtools.py:260``).

    Returns ``(U, sigma, V, rel_error_estimate)`` if ``compute_sv`` else
    ``(U, rel_error_estimate)``.
    """
    if A.ndim != 2:
        raise RuntimeError(f"hsvd requires a 2-D array, got {A.ndim}-D")
    if A.dtype not in (types.float32, types.float64):
        raise TypeError(f"hsvd requires float32/float64, got {A.dtype}")
    if maxrank is None and rtol is None:
        raise ValueError("at least one of maxrank and rtol must be given")

    # split=0 → run on A.T so the distributed axis is the column axis
    # (reference svdtools.py:316-319)
    transposeflag = A.split == 0
    work = A.T if transposeflag else A

    Anorm = float(vector_norm(work).item())
    x = work.larray
    m, n = x.shape
    nblocks = work.comm.size if work.split == 1 and work.is_distributed() else 1
    if maxrank is None:
        maxrank = min(m, n)

    # per-level absolute tolerance (reference: rtol * ||A|| / sqrt(2*nblocks-1))
    loc_atol = None if rtol is None else rtol * Anorm / np.sqrt(2 * nblocks - 1)

    # level 0: truncated SVD of each shard's column block (whole array if replicated).
    # All blocks of a level go through ONE batched SVD (zero-padded to a common
    # width — zero columns add exact-zero singular values, removed by truncation)
    # and ONE host readback of the singular values for the truncation decisions;
    # the reference runs P sequential device round-trips here (svdtools.py:341).
    # The stack is built by a sharding-preserving reshape (device i already holds
    # exactly column block i under the canonical ceil-division chunking), so each
    # device only ever materialises its own (m, n/P) block — matching the strictly
    # local property of the reference's per-rank SVD (svdtools.py:478) — and the
    # batched SVD runs embarrassingly parallel over the mesh.
    level = 0
    if nblocks == 1:
        outs = _batched_truncated_svd(level, [x], maxrank, loc_atol, safetyshift, silent)
    else:
        stacked = _stack_column_blocks(x, nblocks, work.comm)
        outs = _truncate_stacked(level, stacked, maxrank, loc_atol, safetyshift, silent)
    nodes = [u * s for u, s, _ in outs]  # carry U·diag(sigma) into the merges
    err_squared = [e for _, _, e in outs]
    sigmas = [s for _, s, _ in outs]

    arity = no_of_merges or 2
    while len(nodes) > 1:
        level += 1
        # merge-budget scheduling (reference svdtools.py:357-382) needs only the node
        # *widths*, which are static shapes — pure host logic, no device sync
        groups, keep = [], []
        i = 0
        while i < len(nodes):
            group_idx = [i]
            width = nodes[i].shape[1]
            j = i + 1
            while (
                j < len(nodes)
                and len(group_idx) < arity
                and (maxmergedim is None or width + nodes[j].shape[1] <= maxmergedim)
            ):
                group_idx.append(j)
                width += nodes[j].shape[1]
                j += 1
            (groups if len(group_idx) > 1 else keep).append(group_idx)
            i = j
        cats = [jnp.concatenate([nodes[k] for k in g], axis=1) for g in groups]
        outs = (
            _batched_truncated_svd(level, cats, maxrank, loc_atol, safetyshift, silent)
            if cats
            else []
        )
        merged = {}
        for g, (u, s, e) in zip(groups, outs):
            merged[g[0]] = (u * s, sum(err_squared[k] for k in g) + e, s)
        for g in keep:
            k = g[0]
            merged[k] = (nodes[k], err_squared[k], sigmas[k])
        order = sorted(merged)
        nodes = [merged[k][0] for k in order]
        err_squared = [merged[k][1] for k in order]
        sigmas = [merged[k][2] for k in order]

    # final truncation removes the safetyshift (reference svdtools.py:419-421)
    final_u, final_sigma, final_err = _local_truncated_svd(
        level + 1, 0, nodes[0], maxrank, loc_atol, 0, silent
    )
    total_err_squared = sum(err_squared) + final_err
    rel_err = float(np.sqrt(total_err_squared)) / Anorm if Anorm > 0 else 0.0

    U = factories.array(final_u, split=None, device=A.device, comm=A.comm)
    rel_error_estimate = factories.array(
        np.asarray(rel_err, dtype=np.dtype(final_u.dtype)), device=A.device, comm=A.comm
    )

    # postprocessing (reference svdtools.py:457-470)
    if transposeflag or compute_sv:
        work_dnd = A.T if transposeflag else A
        V = matmul(work_dnd.T, U, precision=PARITY_PRECISION)
        sigma = vector_norm(V, axis=0)
        if float(vector_norm(sigma).item()) > 0:
            from ..manipulations import diag

            V = matmul(V, diag(1.0 / sigma), precision=PARITY_PRECISION)
        if transposeflag:
            if compute_sv:
                return V, sigma, U, rel_error_estimate
            return V, rel_error_estimate
        return U, sigma, V, rel_error_estimate
    return U, rel_error_estimate


# jit cache for the level-0 block stacker, keyed by mesh/shape/dtype (compiles once
# per hsvd configuration; on the real chip a fresh trace costs tens of seconds).
_stack_cache: dict = {}


def _stack_column_blocks(x: jax.Array, nblocks: int, comm) -> jax.Array:
    """Restack the column-sharded ``(m, n)`` array as ``(nblocks, m, w)`` column
    blocks, block ``i`` = ``x[:, i*w:(i+1)*w]`` with ``w = ceil(n / nblocks)`` (the
    canonical ceil-division chunk, :meth:`MeshCommunication.chunk`), zero-padding the
    last block.

    The leading block axis carries the mesh axis (``P('d', None, None)``): device ``i``
    already owns exactly column block ``i`` of a split-1 array, so the pad + reshape +
    transpose is pure local relabeling — the compiled program contains no collectives
    (verified: no all-to-all/all-gather/collective-permute in the HLO) and each device
    holds only its own ``m × w`` block, unlike a ``jnp.stack`` of global slices which
    replicates every block everywhere."""
    m, n = x.shape
    w = -(-n // nblocks)
    pad = w * nblocks - n
    target = comm.sharding(3, 0)
    key = (target, nblocks, m, n, str(x.dtype))  # NamedSharding hashes mesh + devices
    fn = _stack_cache.get(key)
    if fn is None:

        def f(v):
            vp = jnp.pad(v, ((0, 0), (0, pad)))
            st = vp.reshape(m, nblocks, w).transpose(1, 0, 2)
            return jax.lax.with_sharding_constraint(st, target)

        fn = jax.jit(f)
        _stack_cache[key] = fn
    return fn(x)


def _batched_truncated_svd(
    level: int,
    blocks: List[jax.Array],
    maxrank: int,
    loc_atol: Optional[float],
    safetyshift: int,
    silent: bool = True,
) -> List[Tuple[jax.Array, jax.Array, float]]:
    """Truncated SVDs of a list of node blocks: zero-pad to a common width, stack,
    and delegate to :func:`_truncate_stacked`. Used for the merge levels (node widths
    are small, ≤ ``maxrank + safetyshift`` columns each) and the final root
    truncation; level 0 builds its stack sharding-preservingly via
    :func:`_stack_column_blocks` instead."""
    wmax = max(b.shape[1] for b in blocks)
    stacked = jnp.stack(
        [
            jnp.pad(b, ((0, 0), (0, wmax - b.shape[1]))) if b.shape[1] < wmax else b
            for b in blocks
        ]
    )
    return _truncate_stacked(level, stacked, maxrank, loc_atol, safetyshift, silent)


def _truncate_stacked(
    level: int,
    stacked: jax.Array,
    maxrank: int,
    loc_atol: Optional[float],
    safetyshift: int,
    silent: bool = True,
) -> List[Tuple[jax.Array, jax.Array, float]]:
    """Truncated SVDs of one whole tree level from a pre-stacked ``(B, m, w)`` operand
    (reference runs ``compute_local_truncated_svd`` ``svdtools.py:478`` per node, each
    with its own host sync): ONE batched ``jnp.linalg.svd`` — shard-local when the
    stack's block axis is sharded — and the singular values cross to host in ONE
    transfer for the noise-floor / rank / atol truncation decisions. Per node, returns
    ``(U_trunc, sigma_trunc, err²_dropped)``."""
    u, s, _ = guarded_svd(stacked)
    noiselevel = 1e-14 if stacked.dtype == jnp.float64 else 1e-7
    # the level's single host sync; under multiple controllers the blocks live on
    # other hosts too, so the (tiny) singular-value matrix is allgathered so every
    # controller makes identical truncation decisions (reference allgathers the
    # local rank dims the same way, svdtools.py:349)
    if isinstance(s, jax.Array) and not s.is_fully_addressable:
        from jax.experimental import multihost_utils

        s_all = np.asarray(multihost_utils.process_allgather(s, tiled=True))
    else:
        s_all = np.asarray(s)

    results: List[Tuple[jax.Array, jax.Array, float]] = []
    for node_id in range(stacked.shape[0]):
        s_np = s_all[node_id]
        above = np.nonzero(s_np >= noiselevel)[0]
        if len(above) == 0:
            err = float(np.linalg.norm(s_np) ** 2)
            results.append(
                (
                    jnp.zeros((stacked.shape[1], 1), stacked.dtype),
                    jnp.zeros((1,), stacked.dtype),
                    err,
                )
            )
            continue
        cut_noise_rank = int(above.max()) + 1
        if loc_atol is None:
            trunc = min(maxrank, cut_noise_rank)
        else:
            tails = np.array(
                [np.linalg.norm(s_np[k:]) ** 2 for k in range(len(s_np) + 1)]
            )
            ideal = int(np.nonzero(tails < loc_atol**2)[0].min())
            trunc = min(maxrank, ideal, cut_noise_rank)
            if trunc != ideal and not silent:
                print(
                    f"in hSVD (level {level}, node {node_id}): atol requires rank "
                    f"{ideal}, but maxrank={maxrank}. Loss of desired precision likely!"
                )
        trunc = min(len(s_np), trunc + safetyshift)
        # squared energy actually discarded at this node. The reference charges the
        # kept safety-shift columns too (``sigma_loc[loc_trunc_rank - safetyshift:]``,
        # svdtools.py:525), double-counting them against the final truncation; counting
        # only the dropped tail keeps the estimate an upper bound and makes it tight.
        err = float(np.linalg.norm(s_np[trunc:]) ** 2)
        results.append((u[node_id, :, :trunc], s[node_id, :trunc], err))
    return results


def _local_truncated_svd(
    level: int,
    node_id: int,
    x: jax.Array,
    maxrank: int,
    loc_atol: Optional[float],
    safetyshift: int,
    silent: bool = True,
) -> Tuple[jax.Array, jax.Array, float]:
    """Single-node wrapper over :func:`_batched_truncated_svd` (kept for the final
    root truncation and for direct testing)."""
    return _batched_truncated_svd(level, [x], maxrank, loc_atol, safetyshift, silent)[0]
