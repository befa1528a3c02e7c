"""Headline benchmark: runs on the accelerator and prints ONE JSON line.

Covers four of the five north-star configs (BASELINE.md): distributed matmul
split-0 × split-1 (reference ``benchmarks/cb/linalg.py:44-56``), KMeans fit
(``benchmarks/cb/cluster.py:24-32``, scaled to the 10M×64 north-star; rides the
fused Pallas Lloyd kernel), ``hsvd_rank`` (``benchmarks/cb/linalg.py:29-40``), and
the data-parallel MLP step (``examples/nn/mnist.py``). The reference publishes no absolute
numbers in-tree (BASELINE.json ``published: {}``), so ``vs_baseline`` of the headline
matmul reports achieved fraction of the chip's peak bf16 matmul throughput; the other
metrics ride along in ``extra_metrics`` as wall-clock seconds.

All of them time the *framework* path — ``ht.linalg.matmul`` / ``KMeans.fit`` /
``ht.linalg.hsvd_rank`` on split DNDarrays — not raw jnp calls. Timing is
best-of-3 around a scalar readback; the matmul chain keeps the device queue full so
per-call dispatch latency overlaps with compute.

Every number here is a device number: without a TPU the script fails, an unknown
``device_kind`` is an error, and a phase that raises ends the run with a
non-zero exit. The printed record names the platform, ``device_kind`` and device
count it ran on. The host-side gates (``benchmarks/cb/*.py``,
``benchmarks/serving/*.py``) are their own scripts and CI jobs.
"""

import json
import time


_BF16_PEAK = {
    # per-chip bf16 matmul peak TFLOP/s by device_kind substring
    "v5 lite": 197.0,  # v5e (394 is its int8 figure)
    "v5e": 197.0,
    "v5p": 459.0,
    "v5": 459.0,
    "v4": 275.0,
    "v6": 918.0,
}


def _peak_tflops(device_kind: str) -> float:
    kind = device_kind.lower()
    for sub, peak in _BF16_PEAK.items():
        if sub in kind:
            return peak
    raise ValueError(
        f"no bf16 peak on record for device_kind {device_kind!r}; add it to "
        "_BF16_PEAK with its source"
    )


def _bench_matmul(ht, jax, jnp):
    # 32768 amortizes per-dispatch latency: each call is ~9 ms of MXU work
    n = 32768
    iters = 8
    dtype = ht.bfloat16
    scale = 1.0 / (n**0.5)  # keep chained products at unit variance

    a = ht.array(jax.random.normal(jax.random.key(0), (n, n), dtype.jax_type()), split=0)
    b = ht.array(
        jax.random.normal(jax.random.key(1), (n, n), dtype.jax_type()) * scale, split=1
    )

    def chain():
        c = a
        for _ in range(iters):
            c = ht.linalg.matmul(c, b)
        return float(c.larray[0, 0])  # single-element readback syncs the queue

    chain()  # compile + warmup
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        chain()
        best = min(best, (time.perf_counter() - t0) / iters)
    ndev = len(jax.devices())
    tflops = 2 * n**3 / best / 1e12 / ndev
    return n, dtype.__name__, tflops


def _bench_kmeans(ht, jax, jnp):
    n, d, k = 10_000_000, 64, 8
    x = ht.array(
        jax.random.normal(jax.random.key(2), (n, d), jnp.float32), split=0
    )
    km = ht.cluster.KMeans(n_clusters=k, init="random", max_iter=30, tol=-1.0,
                           random_state=0)
    km.fit(x)  # compile + warmup (tol<0 forces all 30 iterations)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        km.fit(x)
        best = min(best, time.perf_counter() - t0)
    return n, d, k, best


def _bench_hsvd(ht, jax, jnp):
    m, n_per, blocks, rank = 2048, 4096, 8, 10
    n = n_per * blocks
    # rank-`rank` matrix, the reference's benchmark fixture shape
    # (benchmarks/cb/linalg.py:29-40: 1000 x 500*nprocs, rank 10)
    u = jax.random.normal(jax.random.key(3), (m, rank), jnp.float32)
    v = jax.random.normal(jax.random.key(4), (rank, n), jnp.float32)
    a = ht.array(u @ v, split=1)
    ht.linalg.hsvd_rank(a, rank)  # compile + warmup
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        ht.linalg.hsvd_rank(a, rank)
        best = min(best, time.perf_counter() - t0)
    return m, n, rank, best


def _bench_dp_step(ht, jax, jnp):
    """North-star #5: data-parallel MLP training step (reference examples/nn/mnist.py
    wrapped in DataParallel; here one fused XLA program per step)."""
    n, d, h, classes = 8192, 784, 256, 10
    x = ht.array(jax.random.normal(jax.random.key(5), (n, d), jnp.float32), split=0)
    y = ht.array(
        jax.random.randint(jax.random.key(6), (n,), 0, classes, jnp.int32).astype(jnp.int64),
        split=0,
    )
    model = ht.nn.Sequential(ht.nn.Linear(d, h), ht.nn.ReLU(), ht.nn.Linear(h, classes))
    opt = ht.optim.DataParallelOptimizer("sgd", lr=0.05)
    ht.nn.DataParallel(model, optimizer=opt)
    crit = ht.nn.CrossEntropyLoss()

    def loss_fn(params, xb, yb):
        return crit(model.apply(params, xb), yb)

    opt.step(loss_fn, x, y)  # compile + warmup
    iters = 20
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = opt.step(loss_fn, x, y)
        float(loss)  # sync
        best = min(best, (time.perf_counter() - t0) / iters)
    return n, d, h, best


def _bench_attention(ht, jax, jnp):
    """Long-context causal self-attention throughput (bf16 on MXU).

    On TPU this unmasked block-even shape routes through the flash Pallas kernel
    (``heat_tpu/core/kernels/flash_attention.py``); on a mesh the identical math
    runs as ring attention (``heat_tpu/nn/attention.py``). FLOP count: 2 matmuls of
    2*B*H*T^2*D each, halved by causality."""
    b, h, t, d = 8, 16, 4096, 64
    dt = jnp.bfloat16
    from heat_tpu.nn.attention import scaled_dot_product_attention as sdpa

    q = jax.random.normal(jax.random.key(7), (b, h, t, d), dt)
    k = jax.random.normal(jax.random.key(8), (b, h, t, d), dt)
    v = jax.random.normal(jax.random.key(9), (b, h, t, d), dt)

    def best_of_3(fn, iters=10):
        float(jnp.sum(fn(q, k, v).astype(jnp.float32)))  # compile + warmup
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn(q, k, v)
            float(jnp.sum(out.astype(jnp.float32)))  # sync
            best = min(best, (time.perf_counter() - t0) / iters)
        return best

    best = best_of_3(jax.jit(lambda q, k, v: sdpa(q, k, v, is_causal=True)))
    flops = 2 * 2 * b * h * t * t * d / 2  # two matmuls, causal halves the work

    # padding-masked variant: a shared (T, T) bool mask streams through the same
    # flash kernel (previously masks forced the HBM-bound XLA path)
    pad_mask = jnp.broadcast_to(jnp.arange(t)[None, :] < (t - t // 8), (t, t))
    best_m = best_of_3(jax.jit(lambda q, k, v: sdpa(q, k, v, attn_mask=pad_mask)))
    masked_flops = 2 * 2 * b * h * t * (t - t // 8) * d

    # the same forward without the causal schedule: every block pair is a plain step
    best_n = best_of_3(jax.jit(lambda q, k, v: sdpa(q, k, v)))
    return (b, h, t, d, flops / best / 1e12, masked_flops / best_m / 1e12,
            2 * flops / best_n / 1e12)  # `flops` is the causal half


def _bench_sort(ht, jax, jnp):
    """Distributed-sort family headline (reference ``benchmarks/cb`` has no sort
    entry). Sorts a split-0 array along the split axis —
    on a multi-device mesh this rides the merge-split network
    (``heat_tpu/core/dist_sort.py``); on one chip it is the local jnp path."""
    n = 1 << 24
    x = ht.array(
        jax.random.normal(jax.random.key(10), (n,), jnp.float32), split=0
    )
    def run():
        s, _ = ht.sort(x, axis=0)
        return float(s.larray[-1])  # scalar readback syncs the queue
    run()  # compile + warmup
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return n, best


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the accelerator; JAX found platform={dev.platform!r} "
            "(no TPU). Nothing was measured."
        )
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    peak = _peak_tflops(dev.device_kind)

    import heat_tpu as ht

    n, dtype_name, tflops = _bench_matmul(ht, jax, jnp)
    extras = []

    kn, kd, kk, s = _bench_kmeans(ht, jax, jnp)
    extras.append({"metric": f"kmeans_fit_{kn}x{kd}_k{kk}_30iter_split0",
                   "value": round(s, 3), "unit": "s"})
    hm, hn, hrank, s = _bench_hsvd(ht, jax, jnp)
    extras.append({"metric": f"hsvd_rank_{hm}x{hn}_r{hrank}_split1",
                   "value": round(s, 3), "unit": "s"})
    dn, dd, dh, s = _bench_dp_step(ht, jax, jnp)
    extras.append({"metric": f"dp_mlp_step_{dn}x{dd}_h{dh}_split0",
                   "value": round(s * 1e3, 3), "unit": "ms"})
    sn, s = _bench_sort(ht, jax, jnp)
    extras.append({"metric": f"sort_{sn}_f32_split0",
                   "value": round(sn / s / 1e6, 3), "unit": "Melem/s"})
    ab, ah, at, ad, causal, masked, noncausal = _bench_attention(ht, jax, jnp)
    extras.append({"metric": f"attention_causal_b{ab}h{ah}t{at}d{ad}_tflops",
                   "value": round(causal, 3), "unit": "TFLOP/s"})
    extras.append({"metric": f"attention_padmask_b{ab}h{ah}t{at}d{ad}_tflops",
                   "value": round(masked, 3), "unit": "TFLOP/s"})
    extras.append({"metric": f"attention_noncausal_b{ab}h{ah}t{at}d{ad}_tflops",
                   "value": round(noncausal, 3), "unit": "TFLOP/s"})

    print(json.dumps({
        "metric": f"matmul_{n}x{n}_{dtype_name}_split0x1_tflops_per_chip",
        "value": round(tflops, 3),
        "unit": "TFLOP/s",
        # fraction of the chip's bf16 matmul peak
        "vs_baseline": round(tflops / peak, 4),
        "device": device,
        "extra_metrics": extras,
    }))


if __name__ == "__main__":
    main()
