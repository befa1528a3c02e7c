"""Plain references: float32 ``jax.numpy`` under ``Precision.HIGHEST``, in row blocks.

Nothing of the program is imported and nothing it made is used: inputs, initial
centroids and weights are the benchmark's own, made from the seed. ``precision``
is ``"float32"`` for the reference itself; ``"bfloat16"`` and ``"float8"`` round the
operands of every contraction first (float32 accumulation, as the MXU does) and give
the control: the same mathematics one precision below what the configuration states.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32


def _q(x, precision: str):
    """``x`` as float32 after rounding to ``precision`` (float8: e4m3 with a per-tensor
    absmax scale, as an fp8 path would carry)."""
    x = x.astype(F32)
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    if precision == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _sq_dists(x, y, precision: str):
    """Squared euclidean distances of the rows of ``x`` to the rows of ``y``."""
    xx = jnp.sum(x * x, axis=1, keepdims=True)
    yy = jnp.sum(y * y, axis=1)[None, :]
    cross = jnp.matmul(_q(x, precision), _q(y, precision).T, precision=HI)
    return jnp.maximum(xx + yy - 2.0 * cross, 0.0)


@partial(jax.jit, static_argnames=("iters", "block", "precision"))
def kmeans_lloyd(x, centers0, iters: int, block: int, precision: str = "float32"):
    """``iters`` Lloyd iterations from ``centers0`` over ``x`` in blocks of ``block`` rows
    (an empty cluster keeps its centre). Returns (centers, labels, inertia)."""
    n, d = x.shape
    k = centers0.shape[0]
    if n % block:
        raise ValueError(f"{n} rows are not whole blocks of {block}")
    centers0 = centers0.astype(F32)

    def block_of(i):
        return lax.dynamic_slice_in_dim(x, i * block, block, axis=0).astype(F32)

    def one_iteration(_, centers):
        def accumulate(i, acc):
            sums, counts = acc
            xb = block_of(i)
            onehot = jax.nn.one_hot(jnp.argmin(_sq_dists(xb, centers, precision), axis=1),
                                    k, dtype=F32)
            sums = sums + jnp.matmul(onehot.T, _q(xb, precision), precision=HI)
            return sums, counts + jnp.sum(onehot, axis=0)

        sums, counts = lax.fori_loop(0, n // block, accumulate,
                                     (jnp.zeros((k, d), F32), jnp.zeros((k,), F32)))
        mean = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where(counts[:, None] > 0, mean, centers)

    centers = lax.fori_loop(0, iters, one_iteration, centers0)

    def label_block(i, acc):
        labels, inertia = acc
        d2 = _sq_dists(block_of(i), centers, precision)
        labels = lax.dynamic_update_slice_in_dim(
            labels, jnp.argmin(d2, axis=1).astype(jnp.int32), i * block, axis=0)
        return labels, inertia + jnp.sum(jnp.min(d2, axis=1))

    labels, inertia = lax.fori_loop(0, n // block, label_block,
                                    (jnp.zeros((n,), jnp.int32), jnp.zeros((), F32)))
    return centers, labels, inertia


@partial(jax.jit, static_argnames=("chain", "precision"))
def matmul_chain_rows(a_rows, b, chain: int, precision: str = "float32"):
    """Rows ``a_rows`` of ``a`` carried through ``chain`` products with ``b``."""
    bq = _q(b, precision)
    x = a_rows.astype(F32)
    for _ in range(chain):
        x = jnp.matmul(_q(x, precision), bq, precision=HI)
    return x


@partial(jax.jit, static_argnames=("precision",))
def nearest(x, y, precision: str = "float32"):
    """Index of the row of ``y`` nearest to each row of ``x``."""
    return jnp.argmin(_sq_dists(x.astype(F32), y.astype(F32), precision), axis=1)


@jax.jit
def nearest_regret(x, y, chosen):
    """How much farther the ``chosen`` row of ``y`` lies from each row of ``x`` than the
    nearest one does, as a share of the nearest distance; the worst row."""
    dist = jnp.sqrt(_sq_dists(x.astype(F32), y.astype(F32), "float32"))
    best = jnp.min(dist, axis=1)
    got = jnp.take_along_axis(dist, chosen.astype(jnp.int32)[:, None], axis=1)[:, 0]
    return jnp.max((got - best) / best)


@partial(jax.jit, static_argnames=("precision",))
def mlp_logits(x, w1, b1, w2, b2, precision: str = "float32"):
    """Linear, ReLU, Linear."""
    h = jnp.maximum(jnp.matmul(_q(x, precision), _q(w1, precision), precision=HI) + b1, 0.0)
    return jnp.matmul(_q(h, precision), _q(w2, precision), precision=HI) + b2


def kmeans_assign_gap(inputs: dict, cfg: dict, slot: int, answer, control=None) -> float:
    """Worst row's regret of the served labels against the centroids that the reference
    fits itself from the same sample and start; ``control`` names the lower precision
    that stands in the program's place (fit and assignment both)."""
    def centers(precision):
        if ("centers", precision) not in inputs:
            inputs["centers", precision] = kmeans_lloyd(
                inputs["sample"], inputs["centers0"], iters=cfg["fit_iters"],
                block=min(cfg["fit_rows"], 1 << 18), precision=precision)[0]
        return inputs["centers", precision]

    batch = inputs["batches"][slot]
    got = answer if control is None else nearest(batch, centers(control), control)
    return float(nearest_regret(batch, centers("float32"), got))


def cdist_knn_gap(inputs: dict, cfg: dict, slot: int, answer, control=None) -> float:
    """Worst query's regret of the served nearest corpus row."""
    batch = inputs["batches"][slot]
    got = answer if control is None else nearest(batch, inputs["corpus"], control)
    return float(nearest_regret(batch, inputs["corpus"], got))


def mlp_infer_gap(inputs: dict, cfg: dict, slot: int, answer, control=None) -> float:
    """Largest logit difference as a share of the reference's largest logit."""
    batch = inputs["batches"][slot]
    w = (inputs["w1"], inputs["b1"], inputs["w2"], inputs["b2"])
    got = answer if control is None else mlp_logits(batch, *w, precision=control)
    return max_gap(got, mlp_logits(batch, *w))


def max_gap(got, ref) -> float:
    """Largest absolute difference as a share of the reference's largest magnitude."""
    got, ref = jnp.asarray(got, F32), jnp.asarray(ref, F32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def rms_gap(got, ref) -> float:
    """Norm of the difference as a share of the reference's norm."""
    got, ref = jnp.asarray(got, F32), jnp.asarray(ref, F32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
