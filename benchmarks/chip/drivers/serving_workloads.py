"""The three request types that run on one chip, copied from
``benchmarks/serving/workloads.py`` (``smoke=False`` shapes come from the configuration
file) with two changes: every input, initial centroid and weight is made on the device
from the run's key, so that the plain reference can be given the same ones, and a
request returns its answer instead of dropping it. ``sparse_matvec`` stays out: its
full-size builder needs 275 GB (ROADMAP R2).

``request(slot)`` runs ONE request end to end through the framework (dispatch, any
collectives, ``block_until_ready``) on staged batch ``slot`` and returns the answer.
``inputs`` holds the raw arrays that the type's reference (``reference.<type>_gap``) needs.
The configuration names each builder as ``"serving_workloads:build_<type>"``.
"""

import itertools
from typing import Any, Callable, Dict, NamedTuple

_GEN_COUNTER = itertools.count(1)  # staged-batch generation ids, never recycled


class Workload(NamedTuple):
    name: str
    request: Callable[[int], Any]
    inputs: Dict[str, Any]


def _batch_pool(ht, jax, jnp, key, shape, split, tag: str, pool: int):
    """``pool`` float32 batches, raw and staged, each staged one registered with the result cache's
    generation table (metadata only while that tier is off)."""
    from heat_tpu.core import _result_cache

    raw = [jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
           for i in range(pool)]
    staged = [ht.array(r, split=split) for r in raw]
    for i, batch in enumerate(staged):
        _result_cache.register_generation(batch.parray, f"wl:{tag}:{i}", next(_GEN_COUNTER))
    return raw, staged


def build_kmeans_assign(ht, jax, jnp, cfg: dict, key, pool: int) -> Workload:
    n, d, k = cfg["fit_rows"], cfg["features"], cfg["n_clusters"]
    sample = jax.random.normal(jax.random.fold_in(key, 100), (n, d), jnp.float32)
    centers0 = sample[:k]  # rows of a seeded normal sample are a random draw already
    km = ht.cluster.KMeans(n_clusters=k, init=ht.array(centers0),
                           max_iter=cfg["fit_iters"], tol=-1.0)
    km.fit(ht.array(sample, split=0))
    raw, staged = _batch_pool(ht, jax, jnp, key, (cfg["batch"], d), 0, "kmeans_assign", pool)

    def request(slot: int):
        labels = km.predict(staged[slot])
        return jax.block_until_ready(labels.parray)

    return Workload("kmeans_assign", request,
                    {"sample": sample, "centers0": centers0, "batches": raw})


def build_cdist_knn(ht, jax, jnp, cfg: dict, key, pool: int) -> Workload:
    d = cfg["features"]
    corpus_raw = jax.random.normal(jax.random.fold_in(key, 100), (cfg["corpus_rows"], d),
                                   jnp.float32)
    corpus = ht.array(corpus_raw, split=0)
    # queries replicated, corpus row-split: a small batch against a large sharded corpus
    raw, staged = _batch_pool(ht, jax, jnp, key, (cfg["batch"], d), None, "cdist_knn", pool)

    def request(slot: int):
        nearest = ht.argmin(ht.spatial.cdist(staged[slot], corpus), axis=1)
        return jax.block_until_ready(nearest.parray)

    return Workload("cdist_knn", request, {"corpus": corpus_raw, "batches": raw})


def build_mlp_infer(ht, jax, jnp, cfg: dict, key, pool: int) -> Workload:
    d, h, classes = cfg["features"], cfg["hidden"], cfg["classes"]
    model = ht.nn.Sequential(ht.nn.Linear(d, h), ht.nn.ReLU(), ht.nn.Linear(h, classes))

    def uniform(i, shape, fan_in):  # ht.nn.Linear's own initial distribution
        bound = fan_in ** -0.5
        return jax.random.uniform(jax.random.fold_in(key, 100 + i), shape, jnp.float32,
                                  -bound, bound)

    weights = {"w1": uniform(0, (d, h), d), "b1": uniform(1, (h,), d),
               "w2": uniform(2, (h, classes), h), "b2": uniform(3, (classes,), h)}
    model.params = [{"weight": weights["w1"], "bias": weights["b1"]}, (),
                    {"weight": weights["w2"], "bias": weights["b2"]}]
    raw, staged = _batch_pool(ht, jax, jnp, key, (cfg["batch"], d), 0, "mlp_infer", pool)

    def request(slot: int):
        logits = model(staged[slot])
        return jax.block_until_ready(logits.parray)

    return Workload("mlp_infer", request, {**weights, "batches": raw})

