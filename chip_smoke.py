"""Chip smoke: the system's main path, end to end, once, on the accelerator.

One process, the entry points a user calls (``import heat_tpu as ht``, the dispatch
executor, the scheduler), the full north-star widths (BASELINE.md), a few iterations
each. It is the quickest proof that the program still starts on the chip — not a
benchmark: it prints wall times for orientation and claims nothing about speed.

    python chip_smoke.py

- No TPU → non-zero exit before anything else runs. Nothing here sets
  ``JAX_PLATFORMS`` or an interpret mode, and no size depends on the platform.
- Every phase checks what came out by the repo's own means (a numpy / XLA / f32
  reference on a sampled block, shapes, finiteness, placement on TPU devices). A
  phase that fails raises; nothing is caught and continued.
- The books close the run: no eager replay of a failed staged program, no
  quarantined signature, no recorded fallback.
- The last line of stdout is ``{"ok": true, "device": {...}}``.

The phases are importable functions of their sizes; ``tests/test_zz_chip_smoke.py``
drives each at a tiny size on the virtual CPU mesh (``platform="cpu"``, kernels in
interpret mode).
"""

import json
import sys
import time

SERVER_WORKLOADS = ("kmeans_assign", "cdist_knn", "mlp_infer")
# sparse_matvec stays out: its non-smoke builder materialises a dense 262144² f32
# matrix (275 GB) before sparsifying — it fits no chip (ROADMAP follow-up)


class SmokeFailure(AssertionError):
    """A smoke check did not hold."""


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _placed(x, platform: str):
    """``x`` (DNDarray or jax.Array) lives on ``platform`` devices only; a split
    DNDarray is sharded over its whole communicator, exactly 1/P per shard."""
    arr = x.parray if hasattr(x, "parray") else x
    plats = sorted({d.platform for d in arr.devices()})
    _check(plats == [platform], f"result lives on {plats}, expected [{platform!r}]")
    split = getattr(x, "split", None)
    if split is not None:
        size = x.comm.size
        _check(
            len(arr.sharding.device_set) == size,
            f"split={split} array spans {len(arr.sharding.device_set)} of {size} devices",
        )
        shard = arr.sharding.shard_shape(arr.shape)
        _check(
            shard[split] * size == arr.shape[split],
            f"shard extent {shard[split]} is not 1/{size} of {arr.shape[split]}",
        )
    return x


def _close(got, ref, rel: float, what: str) -> float:
    """max|got - ref| <= rel * max|ref| (and everything finite); returns the ratio."""
    import numpy as np

    got = np.asarray(got, dtype=np.float64 if not np.iscomplexobj(got) else np.complex128)
    ref = np.asarray(ref, dtype=got.dtype)
    _check(got.shape == ref.shape, f"{what}: shape {got.shape} vs reference {ref.shape}")
    _check(bool(np.all(np.isfinite(got))), f"{what}: non-finite values")
    scale = float(np.max(np.abs(ref))) or 1.0
    err = float(np.max(np.abs(got - ref))) / scale
    _check(err <= rel, f"{what}: relative error {err:.3e} > {rel:.1e}")
    return err


def _has_mosaic_call(jitted, *args) -> bool:
    """Whether the lowered program carries a Mosaic (Pallas TPU) custom call."""
    return "tpu_custom_call" in jitted.lower(*args).as_text()


def _counter(name: str) -> float:
    import heat_tpu as ht

    return ht.diagnostics.report()["counters"].get(name, 0)


# ---------------------------------------------------------------------- phases
def phase_split_algebra(n: int = 4096, n_int: int = 1 << 20, platform: str = "tpu") -> dict:
    """North-star 1 and the split algebra, default dtypes included: x64 is on
    globally, so int64 reductions and a float64 elementwise+reduce compile here."""
    import numpy as np

    import heat_tpu as ht

    s = ht.arange(10, split=0).sum()
    _check(s.dtype is ht.int64 and int(s.item()) == 45, f"arange(10).sum() = {s}")
    _placed(s, platform)

    a_np = np.arange(n * 8, dtype=np.float32).reshape(n, 8) / n
    a = ht.array(a_np, split=0)
    b = ht.array(2 * a_np, split=1)
    c = _placed(a + b, platform)
    _close(c.numpy(), 3 * a_np, 1e-6, "split-0 + split-1")

    r_np = np.arange((n + 1) * 7, dtype=np.float32).reshape(n + 1, 7)
    r = ht.array(r_np, split=0)
    r.resplit_(1)
    _check(r.split == 1 and np.array_equal(_placed(r, platform).numpy(), r_np),
           "ragged resplit_(1)")
    r.resplit_(0)
    _check(r.split == 0 and np.array_equal(_placed(r, platform).numpy(), r_np),
           "ragged resplit_(0) round trip")

    i = ht.arange(n_int, dtype=ht.int64, split=0)
    total = _placed((i * i).sum(), platform)
    exact = (n_int - 1) * n_int * (2 * n_int - 1) // 6
    _check(total.dtype is ht.int64 and int(total.item()) == exact,
           f"int64 sum of squares {total.item()} != {exact}")

    f = ht.linspace(0.0, 1.0, 100_001, dtype=ht.float64, split=0)
    val = _placed(ht.exp(f).sum(), platform)
    ref = float(np.exp(np.linspace(0.0, 1.0, 100_001)).sum())
    _check(val.dtype is ht.float64 and abs(float(val.item()) - ref) <= 1e-11 * ref,
           f"float64 exp+sum {val.item()!r} vs {ref!r}")
    return {"int64_sum": exact}


def phase_trainer(n: int = 8192, d: int = 784, h: int = 256, classes: int = 10,
                  steps: int = 6, platform: str = "tpu") -> dict:
    """North-star 5: the data-parallel MLP through ``ht.nn.DataParallel`` +
    ``ht.optim.DataParallelOptimizer.step``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import heat_tpu as ht

    xv = jax.random.normal(jax.random.key(5), (n, d), jnp.float32)
    teacher = jax.random.normal(jax.random.key(6), (d, classes), jnp.float32)
    x = _placed(ht.array(xv, split=0), platform)
    y = _placed(ht.array(jnp.argmax(xv @ teacher, axis=1).astype(jnp.int64), split=0),
                platform)
    model = ht.nn.Sequential(ht.nn.Linear(d, h), ht.nn.ReLU(), ht.nn.Linear(h, classes))
    opt = ht.optim.DataParallelOptimizer("sgd", lr=0.1)
    ht.nn.DataParallel(model, optimizer=opt)
    crit = ht.nn.CrossEntropyLoss()

    def loss_fn(params, xb, yb):
        return crit(model.apply(params, xb), yb)

    losses = [float(opt.step(loss_fn, x, y)) for _ in range(steps)]
    _check(bool(np.all(np.isfinite(losses))), f"non-finite loss in {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    for leaf in jax.tree.leaves(model.params):
        _placed(leaf, platform)
    return {"loss_first": round(losses[0], 4), "loss_last": round(losses[-1], 4)}


def phase_matmul(n: int = 32768, block: int = 256, platform: str = "tpu") -> dict:
    """North-star 2 at the bench width: ``ht.linalg.matmul`` split-0 × split-1 in
    bf16, a sampled rows × cols block against an f32 ``Precision.HIGHEST`` product.
    On more than one device both operands are split, so the ring plan must run."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    dt = jnp.bfloat16
    a = _placed(ht.array(jax.random.normal(jax.random.key(0), (n, n), dt), split=0),
                platform)
    b = _placed(
        ht.array(jax.random.normal(jax.random.key(1), (n, n), dt) * (n ** -0.5), split=1),
        platform,
    )
    ring_before = _counter("linalg.plan.ring")
    for _ in range(2):
        c = _placed(ht.linalg.matmul(a, b), platform)
    _check(c.dtype is ht.bfloat16 and c.gshape == (n, n), f"matmul result {c.dtype} {c.gshape}")
    if a.comm.size > 1:
        _check(_counter("linalg.plan.ring") > ring_before,
               "both operands split on a mesh, yet no linalg.plan.ring was recorded")
    r0, c0 = (n // 3) // 8 * 8, (2 * n // 3) // 8 * 8
    ref = jnp.matmul(
        a.larray[r0:r0 + block].astype(jnp.float32),
        b.larray[:, c0:c0 + block].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    err = _close(c.larray[r0:r0 + block, c0:c0 + block].astype(jnp.float32), ref,
                 2e-2, "bf16 matmul block vs f32 HIGHEST")
    return {"rel_err": err}


def phase_kmeans(n: int = 10_000_000, d: int = 64, k: int = 8, iters: int = 5,
                 slab: int = 65536, platform: str = "tpu", interpret: bool = False) -> dict:
    """North-star 3: ``ht.cluster.KMeans.fit``. The Lloyd program must carry the
    fused Pallas step (a gate that declines is counted as ``fallback.cluster.kmeans``,
    but the fit still succeeds), and the kernel must agree with
    ``fused_assign_update_reference`` on a sampled slab. 10M rows are no multiple of
    the kernel's block: the tail is masked in the kernel, nothing is padded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import heat_tpu as ht
    from heat_tpu.core.kernels.kmeans import (
        fused_assign_update, fused_assign_update_reference,
    )

    x = _placed(ht.array(jax.random.normal(jax.random.key(2), (n, d), jnp.float32), split=0),
                platform)
    km = ht.cluster.KMeans(n_clusters=k, init="random", max_iter=iters, tol=-1.0,
                           random_state=0)
    km.fit(x)
    _check(km.n_iter_ == iters, f"n_iter_ {km.n_iter_} != {iters}")
    _check(np.isfinite(km.inertia_), f"inertia {km.inertia_}")
    labels = _placed(km.labels_, platform)
    centers = _placed(km.cluster_centers_, platform).larray
    if not interpret:
        _check(_has_mosaic_call(km._lloyd_fn(x), x.larray, centers),
               "KMeans.fit did not take the fused Pallas step")

    # the kernel on its own takes single-device operands: a Mosaic call cannot be
    # partitioned automatically (KMeans.fit wraps it in shard_map on a mesh)
    s0 = (n // 2) // 8 * 8
    one = jax.devices()[0]
    xs = jax.device_put(x.larray[s0:s0 + slab], one)
    centers = jax.device_put(centers, one)
    got = fused_assign_update(xs, centers, interpret=interpret)
    ref = fused_assign_update_reference(xs, centers)
    agree = float(jnp.mean(got[0] == ref[0]))
    _check(agree >= 1 - 1e-4, f"kernel labels agree with the reference on {agree:.6f}")
    fit_agree = float(np.mean(np.asarray(labels.larray[s0:s0 + slab]) == np.asarray(ref[0])))
    _check(fit_agree >= 1 - 1e-4, f"fit labels agree with the reference on {fit_agree:.6f}")
    _close(got[1], ref[1], 1e-3, "kernel cluster sums")
    _close(got[2], ref[2], 1e-4, "kernel cluster counts")
    _close(got[3], ref[3], 1e-4, "kernel sse")
    return {"inertia": float(km.inertia_), "label_agreement": agree}


def phase_attention(b: int = 8, h: int = 16, t: int = 4096, d: int = 64,
                    dtype: str = "bfloat16", platform: str = "tpu",
                    interpret: bool = False) -> dict:
    """Attention through ``scaled_dot_product_attention`` on batch-split DNDarrays:
    causal forward, ``jax.grad`` through it (both backward kernels), the shared
    bool-mask variant — each compared on a slice with the XLA path."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht
    from heat_tpu.core.kernels.flash_attention import use_flash
    from heat_tpu.nn.attention import _attention_weights
    from heat_tpu.nn.attention import scaled_dot_product_attention as sdpa

    dt = jnp.dtype(dtype)
    rel = 3e-2 if dt == jnp.bfloat16 else 2e-3
    q, k, v = (jax.random.normal(jax.random.key(7 + i), (b, h, t, d), dt) for i in range(3))
    _check(use_flash(q, k, v, None, interpret=interpret), "use_flash rejects this shape")
    sl = (slice(0, 1), slice(0, 2))

    def xla_path(qs, ks, vs, mask, causal):
        pw = _attention_weights(qs, ks, mask, causal, None)
        return jnp.einsum("...qk,...kd->...qd", pw, vs,
                          preferred_element_type=jnp.float32).astype(qs.dtype)

    def split0(x):
        return ht.array(x, split=0)

    qd, kd, vd = (_placed(split0(x), platform) for x in (q, k, v))
    info = {}
    out = _placed(sdpa(qd, kd, vd, is_causal=True), platform)
    if not interpret:
        _check(_has_mosaic_call(jax.jit(lambda q, k, v: sdpa(q, k, v, is_causal=True)), q, k, v),
               "scaled_dot_product_attention did not lower to the flash kernel")
    info["fwd"] = _close(out.larray[sl], xla_path(q[sl], k[sl], v[sl], None, True), rel,
                         "causal forward")

    def loss(qv, kv, vv):
        o = sdpa(split0(qv), split0(kv), split0(vv), is_causal=True)
        return jnp.sum(o.larray.astype(jnp.float32) ** 2)

    def ref_loss(qs, ks, vs):
        return jnp.sum(xla_path(qs, ks, vs, None, True).astype(jnp.float32) ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    ref_grads = jax.grad(ref_loss, argnums=(0, 1, 2))(q[sl], k[sl], v[sl])
    for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        _placed(g, platform)
        info[name] = _close(g[sl], rg, rel, f"causal backward {name}")

    mask = jnp.broadcast_to(jnp.arange(t)[None, :] < (t - t // 8), (t, t))
    _check(use_flash(q, k, v, mask, interpret=interpret), "use_flash rejects the bool mask")
    outm = _placed(sdpa(qd, kd, vd, attn_mask=mask), platform)
    info["masked"] = _close(outm.larray[sl], xla_path(q[sl], k[sl], v[sl], mask, False),
                            rel, "masked forward")
    return info


def phase_hsvd(m: int = 2048, n: int = 32768, rank: int = 10, platform: str = "tpu") -> dict:
    """North-star 4 at the bench width: ``ht.linalg.hsvd_rank`` of a rank-``rank``
    split=1 matrix must reconstruct it."""
    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    u = jax.random.normal(jax.random.key(3), (m, rank), jnp.float32)
    v = jax.random.normal(jax.random.key(4), (rank, n), jnp.float32)
    a = _placed(ht.array(jnp.matmul(u, v, precision=jax.lax.Precision.HIGHEST), split=1),
                platform)
    U, sigma, V, err = ht.linalg.hsvd_rank(a, rank, compute_sv=True)
    for part in (U, sigma, V):
        _placed(part, platform)
    _check(U.gshape == (m, rank) and V.gshape == (n, rank) and sigma.gshape == (rank,),
           f"hsvd shapes {U.gshape} {sigma.gshape} {V.gshape}")
    # full f32 products: at the TPU's default (bf16-pass) precision the fixture and
    # the reconstruction would each carry ~2e-3 of their own
    recon = jnp.matmul(U.larray * sigma.larray[None, :], V.larray.T,
                       precision=jax.lax.Precision.HIGHEST)
    rel = float(jnp.linalg.norm(a.larray - recon) / jnp.linalg.norm(a.larray))
    _check(rel <= 1e-3, f"hsvd reconstruction error {rel:.3e} > 1e-3")
    _check(float(err) <= 1e-2, f"hsvd error estimate {float(err):.3e}")
    return {"reconstruction_rel_err": rel}


def phase_server(smoke: bool = False, requests: int = 8, concurrency: int = 4) -> dict:
    """The server: a few closed- and open-loop requests of each workload through
    the scheduler, in-process (``python harness.py`` itself always re-execs onto a
    CPU mesh). A failed request raises out of the harness."""
    from benchmarks.serving.harness import run

    records, _ = run(smoke=smoke, requests=requests, concurrency=concurrency,
                     which=list(SERVER_WORKLOADS), emit=lambda line: None)
    by_case = {(r["workload"], r["mode"]): r for r in records}
    for name in SERVER_WORKLOADS:
        for mode, want in (("closed", requests), ("open", max(8, 2 * requests // 3))):
            rec = by_case.get((name, mode))
            _check(rec is not None, f"no {mode}-loop record for {name}")
            _check(rec["requests"] == want,
                   f"{name} {mode}: {rec['requests']} of {want} requests answered")
    return {"answered": sum(r["requests"] for r in records)}


def phase_fft_complex(n: int = 65536, d: int = 64, platform: str = "tpu") -> dict:
    """One FFT along each of the split and the unsplit axis and complex64
    arithmetic on a split array: on the device, and equal to numpy. complex128 is
    the one dtype a TPU refuses — with a typed error, not an aborted process."""
    import jax
    import numpy as np

    import heat_tpu as ht

    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((n, d)).astype(np.float32)
    x = ht.array(x_np, split=0)
    f1 = _placed(ht.fft.fft(x, axis=1), platform)
    _check(f1.dtype is ht.complex64 and f1.split == 0, f"fft result {f1.dtype} split={f1.split}")
    e1 = _close(f1.numpy(), np.fft.fft(x_np, axis=1), 1e-5, "fft along the unsplit axis")
    f0 = _placed(ht.fft.fft(x, axis=0), platform)
    e0 = _close(f0.numpy(), np.fft.fft(x_np, axis=0), 1e-4, "fft along the split axis")

    z_np = (x_np[:, 0] + 1j * x_np[:, 1]).astype(np.complex64)
    z = _placed(ht.array(z_np, split=0), platform)
    w = _placed(z * (1 + 2j) + ht.conj(z), platform)
    _check(w.dtype is ht.complex64, f"complex64 arithmetic gave {w.dtype}")
    _close(w.numpy(), z_np * (1 + 2j) + np.conj(z_np), 1e-6, "complex64 arithmetic")
    _close(_placed(ht.abs(w).sum(), platform).numpy(),
           np.abs(z_np * (1 + 2j) + np.conj(z_np)).sum(dtype=np.float64), 1e-5, "abs+sum")

    if jax.default_backend() == "tpu":
        try:
            ht.array([1 + 2j])  # complex128
        except TypeError:
            pass
        else:
            raise SmokeFailure("complex128 on a TPU was not refused with a TypeError")
    return {"fft_rel_err": max(e0, e1)}


def phase_mesh(n: int = 1 << 20, platform: str = "tpu") -> dict:
    """What only a mesh of several devices shows: the explicit-collective programs
    of ``__graft_entry__._dryrun_impl`` (dp×tp step, hierarchical DASO, ring cdist,
    halo convolve, ring-attention LM step) on the real devices, sort along the split
    axis on ``dist_sort``, and the split→split resplit as one all_to_all."""
    import jax
    import numpy as np

    import __graft_entry__
    import heat_tpu as ht
    from heat_tpu.core import dist_sort

    ndev = len(jax.devices())
    __graft_entry__._dryrun_impl(ndev)

    x_np = np.random.default_rng(1).standard_normal(n).astype(np.float32)
    x = _placed(ht.array(x_np, split=0), platform)
    _check(dist_sort.can_distribute_sort(x.comm, x.gshape, x.split, 0, x.parray.dtype),
           "sort along the split axis would not ride dist_sort")
    s, _ = ht.sort(x, axis=0)
    _check(np.array_equal(_placed(s, platform).numpy(), np.sort(x_np)), "distributed sort")

    side = max(ndev * 8, int(n ** 0.5) // (ndev * 8) * (ndev * 8))
    m_np = x_np[: side * side].reshape(side, side)
    before = _counter("linalg.plan.resplit")
    for _ in range(2):
        y = ht.array(m_np, split=0).resplit(1)
    _check(_counter("linalg.plan.resplit") > before,
           "split 0 -> 1 resplit did not ride the all_to_all program")
    _check(np.array_equal(_placed(y, platform).numpy(), m_np), "all_to_all resplit values")
    return {"devices": ndev}


def phase_books() -> dict:
    """Close the books: the executor replays a staged program eagerly when its
    compile or run fails — right for serving, fatal for a bring-up if unnoticed."""
    import heat_tpu as ht

    stats = ht.executor_stats()
    _check(stats["eager_fallbacks"] == 0, f"eager_fallbacks = {stats['eager_fallbacks']}")
    _check(stats["quarantined"] == {}, f"quarantined signatures: {stats['quarantined']}")
    rep = ht.diagnostics.report()
    _check(not rep["fallback_events"], f"recorded fallbacks: {rep['fallback_events'][:5]}")
    return {"programs": stats["programs"], "hits": stats["hits"], "misses": stats["misses"]}


# ------------------------------------------------------------------------ main
def _device_gate() -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU."""
    from importlib import metadata

    import jax
    import jaxlib

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found {device}, not a TPU; nothing ran")
    print(json.dumps({
        "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": metadata.version("libtpu")},
    }), flush=True)
    return device


class _CompileCacheMeter:
    """Counts JAX's persistent-compilation-cache hits and misses of this process."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs


def main() -> None:
    t_start = time.perf_counter()
    device = _device_gate()
    meter = _CompileCacheMeter()

    import jax

    import heat_tpu as ht

    ht.diagnostics.enable()  # so every record_fallback site records
    phases = [
        ("split_algebra", phase_split_algebra),
        ("trainer", phase_trainer),
        ("matmul", phase_matmul),
        ("kmeans", phase_kmeans),
        ("attention", phase_attention),
        ("hsvd", phase_hsvd),
        ("server", phase_server),
        ("fft_complex", phase_fft_complex),
    ]
    if device["count"] > 1:
        phases.append(("mesh", phase_mesh))
    phases.append(("books", phase_books))
    for name, fn in phases:
        print(json.dumps({"phase": name, "state": "start"}), flush=True)
        t0 = time.perf_counter()
        try:
            info = fn()
        except BaseException as exc:
            exc.add_note(f"chip_smoke: phase {name!r} failed")
            raise
        print(json.dumps({"phase": name, "state": "ok",
                          "seconds": round(time.perf_counter() - t0, 1), **info}), flush=True)
    print(json.dumps({
        "wall_s": round(time.perf_counter() - t_start, 1),
        "compile_cache": {"dir": jax.config.jax_compilation_cache_dir,
                          "hits": meter.hits, "misses": meter.misses,
                          "backend_compile_s": round(meter.compile_s, 1)},
    }), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
