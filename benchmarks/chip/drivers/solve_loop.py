"""Whole solves back to back for the window: ``solve_s`` is the window over the solves.

The configuration names its problem (``"problem": "solve_loop:KMeansFit"``): a class
built from ``(config, seed)`` that makes its data on the device from the seed and has
``solve()`` (one whole solve through the program's entry point, ended by a readback),
``release()`` (drop the program's state, keep the inputs) and ``compare(precision)``
(the last solve's output against the plain reference; see ``reference.py``).
"""

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


@partial(jax.jit, static_argnames=("n", "d", "block"))
def normal_rows(key, n: int, d: int, block: int):
    """Standard-normal float32 rows, written block by block into ONE buffer, so set-up
    never holds two copies of the operand."""
    def fill(i, buf):
        rows = jax.random.normal(jax.random.fold_in(key, i), (block, d), jnp.float32)
        return lax.dynamic_update_slice_in_dim(buf, rows, i * block, axis=0)

    return lax.fori_loop(0, n // block, fill, jnp.zeros((n, d), jnp.float32))


def sharded_normal(key, n: int, axis: int, scale: float, dtype):
    """An ``n x n`` normal matrix split along ``axis`` over all devices, each shard
    made on its own device."""
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("d",))
    local = [n, n]
    local[axis] //= len(devices)

    def shard(k):  # the key is an argument: a key baked into the program compiles per seed
        k = jax.random.fold_in(k, lax.axis_index("d"))
        return (jax.random.normal(k, local, jnp.float32) * scale).astype(dtype)

    spec = P("d", None) if axis == 0 else P(None, "d")
    return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P(), out_specs=spec))(key)


class KMeansFit:
    """``ht.cluster.KMeans.fit`` on float32 rows; the initial centroids are the first
    ``k`` rows of the seeded data, so the reference starts where the program does."""

    def __init__(self, config: dict, seed: int):
        import heat_tpu as ht

        self.cfg = config
        n, d, k = config["rows"], config["features"], config["n_clusters"]
        self.block = min(config["reference_block_rows"], n)
        key = jax.random.key(seed, impl="rbg")
        self.x_raw = normal_rows(key, n, d, self.block)
        # the first k rows, made again rather than sliced: an XLA slice of the operand
        # would copy all of it into lane-padded tiles
        self.centers0 = jax.random.normal(jax.random.fold_in(key, 0), (self.block, d),
                                          jnp.float32)[:k]
        self.x = ht.array(self.x_raw, split=0)
        self.km = ht.cluster.KMeans(n_clusters=k, init=ht.array(self.centers0),
                                    max_iter=config["max_iter"], tol=config["tol"])

    def solve(self):
        self.km.fit(self.x)  # ends with the n_iter and inertia readbacks

    def release(self):
        km = self.km
        self.got = (km.cluster_centers_.larray, km.labels_.larray, km.n_iter_)
        self.km = self.x = None

    def compare(self, precision: str) -> dict:
        import reference

        lloyd = partial(reference.kmeans_lloyd, self.x_raw, self.centers0,
                        iters=self.cfg["max_iter"], block=self.block)
        ref_centers, ref_labels, _ = lloyd(precision="float32")
        if precision == "float32":
            centers, labels, n_iter = self.got
        else:  # the control stands in the program's place
            centers, labels, _ = lloyd(precision=precision)
            n_iter = self.cfg["max_iter"]
        # inertia is not compared: it is flat at the optimum, so the bfloat16 control moves
        # it by 6e-6 .. 2e-5 while float32 summation alone moves it by up to 4e-6 (PERF.md)
        return {
            "iterations_missing": float(self.cfg["max_iter"] - n_iter),
            "centers_gap": reference.max_gap(centers, ref_centers),
            "label_mismatch_share": float(jnp.mean(labels != ref_labels)),
        }


class MatmulChain:
    """``chain`` dependent ``ht.linalg.matmul`` calls of a split-0 by a split-1 operand
    and one scalar readback (``bench._bench_matmul``'s chain)."""

    def __init__(self, config: dict, seed: int):
        import heat_tpu as ht

        self.cfg, self.ht = config, ht
        n = config["n"]
        key = jax.random.key(seed, impl="rbg")
        dtype = jnp.dtype(config["dtype"])
        self.a_raw = sharded_normal(jax.random.fold_in(key, 0), n, 0, 1.0, dtype)
        # scaled so that chained products keep unit variance
        self.b_raw = sharded_normal(jax.random.fold_in(key, 1), n, 1, n ** -0.5, dtype)
        self.a = ht.array(self.a_raw, split=0)
        self.b = ht.array(self.b_raw, split=1)
        self.rows = np.sort(np.random.default_rng(seed).choice(
            n, min(config["reference_rows"], n), replace=False))
        self.c = None

    def solve(self):
        c = self.a
        for _ in range(self.cfg["chain"]):
            c = self.ht.linalg.matmul(c, self.b)
        with jax.profiler.TraceAnnotation("bench.readback"):
            float(c.larray[0, 0])  # a single element read back syncs the queue
        self.c = c

    def release(self):
        self.got = self.c.larray[self.rows].astype(jnp.float32)
        self.c = self.a = self.b = None

    def compare(self, precision: str) -> dict:
        import reference

        chain = partial(reference.matmul_chain_rows, self.a_raw[self.rows], self.b_raw,
                        chain=self.cfg["chain"])
        ref = chain(precision="float32")
        got = self.got if precision == "float32" else chain(precision=precision)
        rms = float(jnp.sqrt(jnp.mean(ref * ref)))
        return {"chain_rms_gap": reference.rms_gap(got, ref),
                "chain_max_gap": float(jnp.max(jnp.abs(got - ref))) / rms}


def setup(config: dict, traffic: dict, seed: int, resolve) -> dict:
    problem = resolve(config["problem"])(config, seed)
    for _ in range(traffic["warmup_solves"]):
        problem.solve()
    return {"problem": problem}


def window(state: dict, traffic: dict, seconds: float, seed: int) -> dict:
    problem = state["problem"]
    solves = 0
    start = now = time.perf_counter()
    while now - start < seconds:  # the last solve that starts inside the window ends it
        with jax.profiler.TraceAnnotation("bench.solve"):
            problem.solve()
        solves += 1
        now = time.perf_counter()
    return {"attempted": solves, "failed": 0, "wall_s": now - start,
            "values": {"solve_s": (now - start) / solves}, "samples": {}}


def release(state: dict) -> None:
    state["problem"].release()


def compare(state: dict, result: dict, precision: str) -> dict:
    return state["problem"].compare(precision)
