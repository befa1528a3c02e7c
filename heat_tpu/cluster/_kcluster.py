"""Base class for k-statistics clustering (reference heat/cluster/_kcluster.py, 333 LoC).

The reference's fit loop per iteration: ``cdist`` (possibly a ring), ``argmin`` (custom
MPI op), masked-mean centroid update (one Allreduce per cluster). On TPU the whole
iteration is a few jnp ops over the sharded point set — XLA fuses the distance matrix
into the assignment and emits a single cross-shard reduction for the centroid update.
"""

from __future__ import annotations

from typing import Callable, Optional, Union

import numpy as np

import jax.numpy as jnp

import heat_tpu as ht
from ..core import diagnostics
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray

__all__ = ["_KCluster"]

# jitted Lloyd programs keyed by (class, k, max_iter, tol, metric); the traced
# closures depend on nothing else, so instances share compiled code
_LLOYD_CACHE: dict = {}


class _KCluster(ClusteringMixin, BaseEstimator):
    """Shared machinery for KMeans/KMedians/KMedoids (reference ``_kcluster.py:10``)."""

    def __init__(
        self,
        metric: Callable,
        n_clusters: int,
        init: Union[str, DNDarray],
        max_iter: int,
        tol: float,
        random_state: Optional[int] = None,
    ):
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state

        self._metric = metric
        self._metric_kind = "euclidean"  # local-metric name for the jitted Lloyd loop
        self._seed_p = 2  # metric exponent for ++ seeding (1 = manhattan)
        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None

    @property
    def cluster_centers_(self) -> DNDarray:
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    @property
    def inertia_(self) -> float:
        return self._inertia

    @property
    def n_iter_(self) -> int:
        return self._n_iter

    def _initialize_cluster_centers(self, x: DNDarray, oversampling: float = None, iter_multiplier: float = None):
        """Pick initial centroids (reference ``_kcluster.py:97``)."""
        if self.random_state is not None:
            ht.random.seed(self.random_state)
        k = self.n_clusters
        if isinstance(self.init, DNDarray):
            if self.init.gshape != (k, x.gshape[1]):
                raise ValueError(
                    f"passed centroids must have shape ({k}, {x.gshape[1]}), got {self.init.gshape}"
                )
            self._cluster_centers = self.init.resplit(None)
            return
        if not isinstance(self.init, str):
            raise ValueError(f"unsupported initialization method {self.init!r}")
        if self.init == "random":
            idx = ht.random.randperm(x.gshape[0])[:k]
            centers = jnp.take(x.larray, idx.larray, axis=0)
            self._cluster_centers = ht.array(centers, comm=x.comm)
            return
        if self.init in ("probability_based", "kmeans++"):
            # greedy k-means++ seeding (reference :97-174 uses plain D² sampling; the
            # greedy variant draws 2+log k candidates per step and keeps the one that
            # minimizes the potential — strictly better seeds, all fused device ops)
            import jax as _jax

            from .batchparallelclustering import _plus_plus

            xv = x.larray.astype(jnp.float32)
            key = _jax.random.key(int(ht.random.randint(0, 2**31 - 1, (1,)).item()))
            centers = _plus_plus(xv, k, self._seed_p, key)
            self._cluster_centers = ht.array(centers.astype(x.larray.dtype), comm=x.comm)
            return
        if self.init == "batchparallel":
            from .batchparallelclustering import BatchParallelKMeans

            bpk = BatchParallelKMeans(n_clusters=k, init="k-means++", max_iter=25)
            bpk.fit(x)
            self._cluster_centers = bpk.cluster_centers_
            return
        raise ValueError(f"unsupported initialization method {self.init!r}")

    def _assign_to_cluster(self, x: DNDarray, eval_functional_value: bool = False):
        """Nearest-centroid assignment (reference ``_kcluster.py:233``)."""
        distances = self._metric(x, self._cluster_centers)
        labels = ht.argmin(distances, axis=1)
        if eval_functional_value:
            self._inertia = float(ht.sum(ht.min(distances, axis=1) ** 2).item())
        return labels

    def _update_centroids_local(self, xv, labels, old):
        """Pure-jnp centroid update, jittable; subclasses implement (the reference's
        per-estimator ``_update_centroids``, as a pure function of local values)."""
        raise NotImplementedError()

    def _fused_step(self, x: DNDarray):
        """Optional fused assignment+update (Pallas) for the Lloyd body.

        Returns ``fn(xv, centers, with_labels)`` giving ``(labels, sums, counts, sse)``
        or, with ``with_labels=False``, ``(sums, counts)``; a ``str`` saying why a fit
        the kernel is meant for falls to the generic jnp body; or ``None`` where no
        kernel applies. Subclasses override where a kernel exists (KMeans)."""
        return None

    def fit(self, x: DNDarray):
        """Shared Lloyd iteration (reference duplicates this across
        kmeans.py:105/kmedians.py:101/kmedoids.py:118): assign, update, converge when
        the squared centroid shift drops to ``tol``.

        The entire loop is ONE jitted ``lax.while_loop`` — assignment, update, and the
        convergence test all stay on device (the reference syncs the host twice per
        iteration for shift and inertia); the only readbacks are the final
        ``n_iter``/``inertia`` scalars after convergence.
        """
        if not isinstance(x, DNDarray):
            raise ValueError(f"input needs to be a DNDarray, but was {type(x)}")
        on = diagnostics._enabled
        with diagnostics.span("cluster.fit", x) if on else diagnostics.NO_SPAN:
            self._initialize_cluster_centers(x)

            promoted = ht.promote_types(x.dtype, ht.float32).jax_type()
            xv = x.larray.astype(promoted)
            centers0 = self._cluster_centers.larray.astype(promoted)
            # the whole-fit program and the readback that waits for it
            with diagnostics.span("cluster.fit.lloyd", x) if on else diagnostics.NO_SPAN:
                n_iter, centers, labels, inertia = self._lloyd_fn(x)(xv, centers0)
                self._n_iter = int(n_iter)
            self._cluster_centers = ht.array(
                centers.astype(centers0.dtype), comm=x.comm
            )
            from ..core._operations import wrap_result

            self._labels = wrap_result(labels.astype(jnp.int64), x, x.split)
            self._inertia = float(inertia)
            return self

    def _lloyd_fn(self, x: DNDarray):
        """The jitted whole-fit Lloyd program, cached per
        (estimator class, k, max_iter, tol, metric, fused?) so repeated fits hit XLA's
        compilation cache instead of re-tracing a fresh closure every call."""
        fused = self._fused_step(x)
        if callable(fused) and x.split not in (None, 0):
            fused = f"split={x.split}"
        declined = fused if isinstance(fused, str) else None
        if declined:
            fused = None
        # the fused closure bakes in the comm's mesh/axis (shard_map variant), so the
        # cache key must carry that configuration, not just "fused or not"; a declined
        # fit's program records its reason, so the reason is its key
        if fused is None:
            fused_kind = declined
        elif x.split is None or x.comm.size == 1:
            fused_kind = "plain"
        else:
            fused_kind = ("sharded", x.comm.mesh, x.comm.axis_name)
        key = (
            type(self),
            self.n_clusters,
            self.max_iter,
            float(self.tol),
            self._metric_kind,
            fused_kind,
        )
        fn = _LLOYD_CACHE.get(key)
        if fn is not None:
            return fn

        import jax
        from jax import lax

        from ..core.kernels import kmeans as kmeans_kernel
        from ..spatial.distance import _pairwise

        metric_kind = self._metric_kind
        update = self._update_centroids_local
        max_iter, tol = self.max_iter, float(self.tol)

        @jax.jit
        def lloyd(xv, centers0):
            # trace time only: how often the program was traced, and why a float32 fit
            # on a TPU did not get the kernel (nothing per call, nothing in the program)
            diagnostics.counter("cluster.fit.traces")
            step, why = fused, declined
            if step is not None:
                why = kmeans_kernel.decline_reason(xv.shape[1], centers0.shape[0])
                step = None if why else step
            if why:
                diagnostics.record_fallback("cluster.kmeans", why)

            def cond(state):
                i, _, shift = state
                return jnp.logical_and(i < max_iter, shift > tol)

            def body(state):
                i, centers, _ = state
                if step is not None:
                    # one streaming pass: distances, argmin, and the segment sums
                    # never leave VMEM, and nothing of n elements is written
                    # (core/kernels/kmeans.py)
                    sums, counts = step(xv, centers, False)
                    new = jnp.where(
                        counts[:, None] > 0,
                        (sums / jnp.maximum(counts[:, None], 1.0)).astype(centers.dtype),
                        centers,
                    )
                else:
                    d = _pairwise(xv, centers, metric_kind)
                    labels = jnp.argmin(d, axis=1)
                    new = update(xv, labels, centers)
                shift = jnp.sum((centers - new) ** 2)
                return i + 1, new, shift

            i, centers, _ = lax.while_loop(
                cond, body, (jnp.int32(0), centers0, jnp.array(jnp.inf, centers0.dtype))
            )
            if step is not None:
                labels, _, _, inertia = step(xv, centers, True)
            else:
                d = _pairwise(xv, centers, metric_kind)
                labels = jnp.argmin(d, axis=1)
                inertia = jnp.sum(jnp.min(d, axis=1) ** 2)
            return i, centers, labels, inertia

        _LLOYD_CACHE[key] = lloyd
        return lloyd

    def predict(self, x: DNDarray) -> DNDarray:
        """Nearest learned centroid for each sample (reference ``_kcluster.py:298``)."""
        if self._cluster_centers is None:
            raise RuntimeError("fit needs to be called before predict")
        with diagnostics.span("cluster.predict", x) if diagnostics._enabled else diagnostics.NO_SPAN:
            return self._assign_to_cluster(x)
