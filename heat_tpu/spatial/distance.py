"""Pairwise distances (reference heat/spatial/distance.py, 479 LoC).

The reference's ``_dist`` (``distance.py:209``) is a ring algorithm: each rank holds an
X-chunk, Y-chunks rotate around the ranks with Send/Recv, one local torch.cdist per
step. Here both formulations exist: when X and Y are row-split and divide the mesh,
:func:`_ring_pairwise` runs that exact schedule explicitly (``ppermute`` hops around
the ICI ring, O(n_y/P) resident Y per device); every other split combination — feature
splits, unsplit operands, ragged sizes — is the SPMD-global formulation where XLA
inserts the gathers. Output split: row-split X → split 0; else row-split Y → split 1;
else replicated.

One call is one program: the promotion cast, either formulation, :func:`rbf`'s kernel
and the result's layout are traced once and run as a single cached ``jax.jit``
(:func:`_program`), so a call costs one dispatch and one write of the matrix.
``spatial.cdist.traces`` (``ht.diagnostics``) counts the traces.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ..core import diagnostics, types
from ..core._operations import wrap_result
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = ["cdist", "manhattan", "rbf"]


def _pairwise(x: jax.Array, y: jax.Array, metric: str, p: float = 2.0) -> jax.Array:
    if metric == "euclidean":
        # |x-y|² = |x|² + |y|² - 2xy, the quadratic expansion the reference uses in
        # _euclidian_fast (distance.py:32) — one big MXU matmul instead of O(n²d) substracts
        xx = jnp.sum(x * x, axis=1)[:, None]
        yy = jnp.sum(y * y, axis=1)[None, :]
        # the expansion cancels catastrophically for near points — the cross term
        # needs full input precision, not the MXU's bf16-input default
        sq = xx + yy - 2.0 * jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
        return jnp.sqrt(jnp.maximum(sq, 0.0))
    if metric == "manhattan":
        return jnp.sum(jnp.abs(x[:, None, :] - y[None, :, :]), axis=-1)
    raise ValueError(f"unknown metric {metric}")


def _ring_pairwise(comm, xv: jax.Array, yv: jax.Array, metric: str) -> jax.Array:
    """Distance matrix via a ring rotation of Y shards under ``shard_map`` — the
    explicit TPU form of the reference's ring algorithm (``_dist`` ``distance.py:209``:
    X-chunks stay put, Y-chunks travel rank-to-rank with Send/Recv).

    Each device holds its X shard and, per step, one visiting Y shard; ``ppermute``
    moves the Y shards one hop around the ICI ring. Peak memory per device is
    O(n_y/P) for Y instead of the all-gathered O(n_y) the SPMD-global formulation
    materialises — the reason the reference uses a ring, preserved here.
    """
    axis = comm.axis_name
    nproc = comm.size
    ny_chunk = yv.shape[0] // nproc

    def ring(xl, yl):
        idx = jax.lax.axis_index(axis)
        # mark the accumulator device-varying so the loop carry type is stable
        out0 = jax.lax.pcast(
            jnp.zeros((xl.shape[0], yv.shape[0]), xl.dtype), (axis,), to="varying"
        )

        def fill(i, yblk, out):
            src = (idx - i) % nproc  # whose Y block this device holds at step i
            d = _pairwise(xl, yblk, metric)
            return jax.lax.dynamic_update_slice(
                out, d, (jnp.int32(0), (src * ny_chunk).astype(jnp.int32))
            )

        def step(i, carry):
            yblk, out = carry
            out = fill(i, yblk, out)
            return comm.ring_shift(yblk, 1, axis_name=axis), out

        # nproc-1 rotations; the last block is consumed without a wasted final hop
        yblk, out = jax.lax.fori_loop(0, nproc - 1, step, (yl, out0))
        return fill(nproc - 1, yblk, out)

    return jax.shard_map(
        ring,
        mesh=comm.mesh,
        in_specs=(PartitionSpec(axis, None), PartitionSpec(axis, None)),
        out_specs=PartitionSpec(axis, None),
    )(xv, yv)


# one jitted program per (metric, promoted dtype, ring mesh + axis, output sharding): the
# trace depends on nothing else, and shapes and input shardings are jax.jit's own key
_PROGRAMS: dict = {}


def _program(metric: str, dtype, ring_comm, out_sharding) -> Callable:
    """The compiled form of one :func:`_dist` call: the promotion cast, the distance
    matrix (``ring_comm`` set: by :func:`_ring_pairwise` on that communicator) and, for
    :func:`rbf`, the kernel, laid out as ``out_sharding`` (None: wherever XLA leaves it)."""
    key = (
        metric,
        dtype,
        None if ring_comm is None else (ring_comm.mesh, ring_comm.axis_name),
        out_sharding,
    )
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn

    def dist(x, y, gamma):
        if diagnostics._enabled:
            diagnostics.counter("spatial.cdist.traces")  # trace time only
        x = x.astype(dtype)
        y = x if y is None else y.astype(dtype)
        if ring_comm is not None:
            d = _ring_pairwise(ring_comm, x, y, metric)
        else:
            d = _pairwise(x, y, metric)
        return d if gamma is None else jnp.exp(-(d**2) / gamma)

    return _PROGRAMS.setdefault(key, jax.jit(dist, out_shardings=out_sharding))


def _dist(
    X: DNDarray, Y: Optional[DNDarray], metric: str, gamma: Optional[float] = None
) -> DNDarray:
    """Shared driver (reference ``_dist`` ``distance.py:209``).

    Any (X.split, Y.split) combination is accepted: split feature axes are a
    contraction XLA resolves, a row-split X yields a row-split result, and the
    both-row-split case runs the explicit :func:`_ring_pairwise` schedule when the
    shapes divide the mesh evenly (falling back to the SPMD-global formulation
    otherwise). ``gamma`` set: the result is ``exp(-d²/gamma)`` (:func:`rbf`)."""
    with diagnostics.span("spatial.cdist", X) if diagnostics._enabled else diagnostics.NO_SPAN:
        sanitize_in(X)
        if X.ndim != 2:
            raise NotImplementedError(f"X should be 2D, but is {X.ndim}D")
        promoted = types.promote_types(X.dtype, types.float32)
        xv = X.larray
        if Y is None:
            y_split = X.split
            yv = None
            ny = xv.shape[0]
        else:
            sanitize_in(Y)
            if Y.ndim != 2:
                raise NotImplementedError(f"Y should be 2D, but is {Y.ndim}D")
            p2 = types.promote_types(Y.dtype, types.float32)
            if p2 is not promoted:
                promoted = types.promote_types(promoted, p2)
            y_split = Y.split
            yv = Y.larray
            ny = yv.shape[0]
        comm = X.comm
        use_ring = (
            X.split == 0
            and y_split == 0
            and X.is_distributed()
            and not getattr(comm, "is_hierarchical", False)
            and xv.shape[0] % comm.size == 0
            and ny % comm.size == 0
        )
        out_split = 0 if X.split == 0 else (1 if y_split == 0 else None)
        # a ragged extent is padded by wrap_result's comm.shard, after the program
        ragged = out_split is not None and (xv.shape[0], ny)[out_split] % comm.size != 0
        program = _program(
            metric,
            promoted.jax_type(),
            comm if use_ring else None,
            None if ragged else comm.sharding(2, out_split),
        )
        return wrap_result(program(xv, yv, gamma), X, out_split)


def cdist(X: DNDarray, Y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Euclidean distance matrix (reference ``distance.py:136``). The quadratic
    expansion is always used — on the MXU it is both the fast and the natural form."""
    return _dist(X, Y, "euclidean")


def manhattan(X: DNDarray, Y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """City-block distance matrix (reference ``distance.py:186``)."""
    return _dist(X, Y, "manhattan")


def rbf(
    X: DNDarray,
    Y: Optional[DNDarray] = None,
    sigma: float = 1.0,
    quadratic_expansion: bool = False,
) -> DNDarray:
    """Gaussian RBF kernel matrix exp(-d²/(2σ²)) (reference ``distance.py:159``)."""
    return _dist(X, Y, "euclidean", gamma=2.0 * sigma * sigma)
