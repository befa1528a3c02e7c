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
        """Pallas streaming assignment+update on TPU (core/kernels/kmeans.py): one
        HBM pass over x per Lloyd iteration instead of three. Sharded point sets run
        the kernel per shard under ``shard_map`` with a psum of the (k, d) partials —
        the same single collective the jnp path's segment-sum emits."""
        import jax

        if jax.default_backend() != "tpu":
            return None
        # the kernel computes in f32; float64 fits must keep the generic path to
        # preserve x64 numerics
        if ht.promote_types(x.dtype, ht.float32) is not ht.float32:
            return None
        from ..core.kernels import fused_assign_update

        comm = x.comm
        if comm.size == 1 or x.split is None:
            return fused_assign_update

        axis = comm.axis_name
        if not isinstance(axis, str):  # hierarchical meshes: keep the generic path
            return None
        if x.gshape[0] % comm.size != 0:
            return None  # ragged shards: generic path

        from jax.sharding import PartitionSpec as P

        def sharded(xv, centers):
            def body(xl, c):
                labels, sums, counts, sse = fused_assign_update(xl, c)
                # comm-routed (not raw jax.lax.psum): records the collective
                # family in ht.diagnostics and rides the resilience guard
                return (
                    labels,
                    comm.psum(sums, axis_name=axis),
                    comm.psum(counts, axis_name=axis),
                    comm.psum(sse, axis_name=axis),
                )

            # check_vma off: a pallas_call's out_shape carries no varying-axes
            # annotation, which the check demands inside shard_map
            return jax.shard_map(
                body,
                mesh=comm.mesh,
                in_specs=(P(axis, None), P()),
                out_specs=(P(axis), P(), P(), P()),
                check_vma=False,
            )(xv, centers)

        return sharded

