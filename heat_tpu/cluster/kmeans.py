"""K-Means clustering (reference heat/cluster/kmeans.py, 157 LoC)."""

from __future__ import annotations

from typing import Optional, Union

import jax.numpy as jnp

import heat_tpu as ht
from ..core.dndarray import DNDarray
from ._kcluster import _KCluster

__all__ = ["KMeans"]


class KMeans(_KCluster):
    """Lloyd's algorithm over a row-split point set (reference ``kmeans.py:14``).

    North-star workload #3: the per-iteration communication is one all-reduce of the
    (k, d) sums/counts, emitted by XLA from the segment-sum centroid update.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        if isinstance(init, str) and init == "kmeans++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: ht.spatial.cdist(x, y, quadratic_expansion=True),
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def _update_centroids_local(self, xv, labels, old):
        """Masked mean per cluster (reference ``kmeans.py:76-103``): a segment-sum the
        compiler turns into one psum across shards; pure jnp so the whole Lloyd loop
        jits as one program."""
        k = self.n_clusters
        sums = jnp.zeros((k, xv.shape[1]), xv.dtype).at[labels].add(xv)
        counts = jnp.zeros((k,), xv.dtype).at[labels].add(1.0)
        new = sums / jnp.maximum(counts[:, None], 1.0)
        # keep old center for empty clusters
        return jnp.where(counts[:, None] > 0, new, old)

    def _fused_step(self, x):
        """Pallas streaming assignment+update on TPU (core/kernels/kmeans.py): one HBM
        pass over x per Lloyd iteration, read where it lies as ``(d, n)`` with the rows
        on the lanes and the clusters on the sublanes; the iteration's form writes
        ``(sums, counts)`` only, the call after the loop the labels and the inertia too.
        HBM bounds a step: 5.93 ms an iteration over 2^24 x 64 float32 rows on a v5e
        against 5.24 ms at the published bandwidth, ``fit_hbm_roofline_share`` 85.1% (my
        chip runs, PR 28; 55.7 ms and 8.9% before: ledger, PR 27). Sharded point sets run
        the kernel per shard under ``shard_map`` with a psum of the (k, d) partials —
        the same single collective the jnp path's segment-sum emits. A float32 fit on a
        TPU that a gate here sends to the jnp body returns the reason (``_lloyd_fn``
        records it as ``fallback.cluster.kmeans`` at trace time)."""
        import jax

        from ..core.kernels import kmeans as kernel

        if not kernel.available():
            return None
        # the kernel computes in f32; float64 fits must keep the generic path to
        # preserve x64 numerics
        if ht.promote_types(x.dtype, ht.float32) is not ht.float32:
            return None

        def one_device(xv, centers, with_labels):
            # looked up at call time: tests swap in the interpreted kernel
            return kernel.fused_assign_update(xv, centers, with_labels)

        comm = x.comm
        if comm.size == 1 or x.split is None:
            return one_device

        axis = comm.axis_name
        if not isinstance(axis, str):
            return "hierarchical mesh axis"
        if x.gshape[0] % comm.size != 0:
            return f"ragged shards: {x.gshape[0]} rows over {comm.size} devices"

        from jax.sharding import PartitionSpec as P

        def sharded(xv, centers, with_labels):
            def body(xl, c):
                out = one_device(xl, c, with_labels)
                # the labels stay with their shard; the rest are partials to sum.
                # comm-routed (not raw jax.lax.psum): records the collective
                # family in ht.diagnostics and rides the resilience guard
                local, partials = (out[:1], out[1:]) if with_labels else ((), out)
                return (*local, *(comm.psum(v, axis_name=axis) for v in partials))

            # check_vma off: a pallas_call's out_shape carries no varying-axes
            # annotation, which the check demands inside shard_map
            return jax.shard_map(
                body,
                mesh=comm.mesh,
                in_specs=(P(axis, None), P()),
                out_specs=(P(axis), P(), P(), P()) if with_labels else (P(), P()),
                check_vma=False,
            )(xv, centers)

        return sharded
