"""Pallas kernel tests: the fused KMeans assignment must agree with its jnp reference
(validated in interpreter mode so the same test runs on the CPU mesh), the Lloyd
program must carry the kernel in the form each place needs, and the kernel must compile
for a described v5e at the block its gate picks. The flash forward compiles there too,
at the latent-attention cell's shape and the blocks its gate picks, and so does the expert
layer with its grouped kernel and its combine kernel at the three model cells' shapes (this
file is the one that loads the TPU compiler; the kernels' other cases are in
``test_grouped_matmul.py``)."""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.cluster import _kcluster
from heat_tpu.core.kernels import fused_assign_update, fused_assign_update_reference
from heat_tpu.core.kernels import flash_attention as flash_kernel
from heat_tpu.core.kernels import grouped_matmul
from heat_tpu.core.kernels import kmeans as kmeans_kernel
from heat_tpu.core.kernels.kmeans import _block_n, _fused_pallas
from heat_tpu.testing import TestCase

SHAPES = [(1024, 64, 8), (130, 10, 3), (1500, 7, 5), (1000, 64, 8), (4096 + 37, 64, 8)]


def _data(n, d, k, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((k, d)).astype(np.float32))
    return x, c


def _near_ties(x, c, rel=1e-5):
    """Rows whose two smallest reference distances lie within ``rel`` of each other:
    there the kernel, which leaves |x|^2 out of the argmin, may round the other way."""
    x, c = np.asarray(x, np.float64), np.asarray(c, np.float64)
    d2 = np.sort(((x[:, None, :] - c[None, :, :]) ** 2).sum(-1), axis=1)
    return (d2[:, 1] - d2[:, 0]) <= rel * np.maximum(d2[:, 1], 1e-30)


def _assert_matches_reference(x, c, got):
    l0, s0, n0, e0 = fused_assign_update_reference(x, c)
    l1, s1, n1, e1 = got
    differ = np.asarray(l0) != np.asarray(l1)
    assert not np.any(differ & ~_near_ties(x, c))
    if not differ.any():
        np.testing.assert_array_equal(np.asarray(n0), np.asarray(n1))
    np.testing.assert_allclose(np.asarray(s0), np.asarray(s1), rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(float(e0), float(e1), rtol=1e-4)


@pytest.mark.parametrize("block_n", [128, None], ids=["bn128", "bn_default"])
@pytest.mark.parametrize("n,d,k", SHAPES, ids=lambda v: str(v))
def test_interpreted_kernel_matches_reference(n, d, k, block_n):
    """Aligned, smaller than a block, ragged (not a multiple of the block), with k and d
    off the (8, 128) tile: labels, sums, counts and sse against the jnp reference."""
    x, c = _data(n, d, k)
    _assert_matches_reference(x, c, _fused_pallas(x, c, block_n=block_n, interpret=True))


@pytest.mark.parametrize("n,d,k", SHAPES, ids=lambda v: str(v))
def test_loop_form_equals_full_form(n, d, k):
    """The form a Lloyd iteration calls returns the full form's (sums, counts), bit for bit."""
    x, c = _data(n, d, k, seed=1)
    _, s1, n1, _ = _fused_pallas(x, c, block_n=128, interpret=True)
    s2, n2 = _fused_pallas(x, c, with_labels=False, block_n=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(n1), np.asarray(n2))


def test_nan_beyond_a_ragged_tail_reaches_nothing():
    """The last block of a ragged n reaches past the operand; the interpreter fills that
    part with NaN (asserted here, as the premise), the chip with whatever lies there.
    Sums, counts and sse stay finite and equal to the reference."""
    import jax.experimental.pallas as pl

    def copy(x_ref, o_ref):
        o_ref[:] = x_ref[:]

    block = pl.BlockSpec((8, 128), lambda i: (0, i))
    probe = pl.pallas_call(copy, grid=(2,), in_specs=[block], out_specs=block,
                           out_shape=jax.ShapeDtypeStruct((8, 256), jnp.float32),
                           interpret=True)(jnp.ones((8, 130), jnp.float32))
    assert np.isnan(np.asarray(probe)[:, 130:]).all()

    x, c = _data(1000, 64, 8, seed=2)
    for form in (True, False):
        got = _fused_pallas(x, c, with_labels=form, block_n=512, interpret=True)
        assert all(np.isfinite(np.asarray(v)).all() for v in got)
    _assert_matches_reference(x, c, _fused_pallas(x, c, block_n=512, interpret=True))
    assert float(jnp.sum(_fused_pallas(x, c, with_labels=False, block_n=512, interpret=True)[1])) == 1000


def test_identical_centroids_take_the_first_index():
    """NumPy's tie rule: of two equal centroids the lower index gets every row."""
    x, c = _data(700, 10, 4, seed=3)
    c = c.at[2].set(c[0])
    labels, _, counts, _ = _fused_pallas(x, c, block_n=256, interpret=True)
    assert not np.any(np.asarray(labels) == 2)
    assert float(counts[2]) == 0.0
    np.testing.assert_array_equal(np.asarray(labels),
                                  np.asarray(fused_assign_update_reference(x, c)[0]))


class TestFusedAssignUpdate(TestCase):
    def test_reference_semantics(self):
        """The reference itself matches a plain numpy computation."""
        rng = np.random.default_rng(1)
        x = rng.standard_normal((200, 6)).astype(np.float32)
        c = rng.standard_normal((4, 6)).astype(np.float32)
        d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        labels, sums, counts, sse = fused_assign_update_reference(
            jnp.asarray(x), jnp.asarray(c)
        )
        np.testing.assert_array_equal(np.asarray(labels), d2.argmin(1))
        np.testing.assert_allclose(float(sse), d2.min(1).sum(), rtol=1e-4)
        for j in range(4):
            np.testing.assert_allclose(
                np.asarray(sums)[j], x[d2.argmin(1) == j].sum(0), rtol=1e-4, atol=1e-4
            )

    def test_dispatcher_fallback(self):
        """On non-TPU backends the dispatcher returns the jnp reference results, in
        either form."""
        if jax.default_backend() == "tpu":
            self.skipTest("fallback path is the non-TPU branch")
        x, c = _data(300, 8, 5, seed=2)
        ref = fused_assign_update_reference(x, c)
        for a, b in zip(fused_assign_update(x, c), ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        for a, b in zip(fused_assign_update(x, c, with_labels=False), ref[1:3]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    def test_kmeans_unchanged_on_cpu(self):
        """The Lloyd loop still converges identically through the generic path."""
        rng = np.random.default_rng(3)
        centers = rng.normal(0, 10, (3, 4)).astype(np.float32)
        y = rng.integers(0, 3, 600)
        x = ht.array(centers[y] + rng.normal(0, 0.3, (600, 4)).astype(np.float32), split=0)
        km = ht.cluster.KMeans(n_clusters=3, init="kmeans++", max_iter=50, random_state=0)
        km.fit(x)
        got = np.sort(km.cluster_centers_.numpy(), axis=0)
        np.testing.assert_allclose(got, np.sort(centers, axis=0), atol=0.2)


# ------------------------------------------------- the Lloyd program on the CPU mesh
@pytest.fixture
def fused_interpret(monkeypatch):
    """``KMeans.fit`` takes the fused step here, its kernel interpreted; the Lloyd cache
    starts empty and the counters are on."""
    monkeypatch.setattr(kmeans_kernel, "available", lambda interpret=False: True)
    monkeypatch.setattr(kmeans_kernel, "fused_assign_update",
                        functools.partial(kmeans_kernel.fused_assign_update, interpret=True))
    monkeypatch.setattr(_kcluster, "_LLOYD_CACHE", {})
    was_on = ht.diagnostics.enabled()
    ht.diagnostics.enable()
    ht.diagnostics.reset()
    yield
    ht.diagnostics.reset()
    if not was_on:
        ht.diagnostics.disable()


def _counter(name):
    return ht.diagnostics.report()["counters"].get(name, 0)


def _blobs(n, d=4, k=3, seed=3):
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 10, (k, d)).astype(np.float32)
    return centers[rng.integers(0, k, n)] + rng.normal(0, 0.3, (n, d)).astype(np.float32), centers


def _pallas_calls(jaxpr, in_loop=False):
    """``(equation, inside a while?)`` of every ``pallas_call`` under ``jaxpr``, through
    pjit, shard_map and the loop's own body."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn, in_loop
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub, in_loop or eqn.primitive.name == "while")


@pytest.mark.parametrize("split", [None, 0])
def test_the_loop_writes_no_labels_and_the_last_call_does(fused_interpret, split):
    """In the Lloyd program the kernel inside the ``while`` body has no output of a
    row's size; the call after the loop returns the labels. The fit agrees with the
    generic path's on well separated blobs."""
    n = 128 * ht.get_comm().size * 2
    data, centers = _blobs(n)
    x = ht.array(data, split=split)
    km = ht.cluster.KMeans(n_clusters=3, init="kmeans++", max_iter=50, random_state=0)
    km.fit(x)
    np.testing.assert_allclose(np.sort(km.cluster_centers_.numpy(), axis=0),
                               np.sort(centers, axis=0), atol=0.2)
    assert km.labels_.shape == (n,)

    rows = n if split is None else n // ht.get_comm().size
    jaxpr = jax.make_jaxpr(km._lloyd_fn(x))(x.larray, km.cluster_centers_.larray).jaxpr
    calls = list(_pallas_calls(jaxpr))
    inside = [eqn for eqn, in_loop in calls if in_loop]
    after = [eqn for eqn, in_loop in calls if not in_loop]
    assert len(inside) == 1 and len(after) == 1
    assert all(int(np.prod(v.aval.shape)) < rows for v in inside[0].outvars)
    assert any(v.aval.shape == (1, rows) and v.aval.dtype == jnp.int32 for v in after[0].outvars)


def test_traces_are_counted_once_for_one_shape(fused_interpret):
    data, _ = _blobs(512)
    x = ht.array(data, split=None)
    for _ in range(2):
        ht.cluster.KMeans(n_clusters=3, init="random", max_iter=5, random_state=0).fit(x)
    assert _counter("cluster.fit.traces") == 1
    assert _counter("fallback.cluster.kmeans") == 0
    ht.cluster.KMeans(n_clusters=3, init="random", max_iter=5, random_state=0).fit(
        ht.array(data[:256], split=None))
    assert _counter("cluster.fit.traces") == 2


@pytest.mark.parametrize("case", ["vmem", "ragged_shards", "float64"])
def test_a_declined_float32_fit_says_why(fused_interpret, case):
    """A gate that sends a float32 fit to the jnp body records ``fallback.cluster.kmeans``
    with its reason, at trace time; a float64 fit was never the kernel's and records none."""
    size = ht.get_comm().size
    if case == "vmem":  # no 128-row block of 20,000 features fits the kernel's budget
        x = ht.array(np.random.default_rng(0).normal(size=(32, 20000)).astype(np.float32))
    elif case == "ragged_shards":
        if size == 1:
            pytest.skip("one device has no ragged shard")
        x = ht.array(_blobs(128 * size + 1)[0], split=0)
    else:
        x = ht.array(_blobs(256)[0].astype(np.float64), split=None)
    km = ht.cluster.KMeans(n_clusters=3, init="random", max_iter=2, random_state=0)
    km.fit(x)
    km.fit(x)  # the second fit traces nothing and records nothing
    assert _counter("cluster.fit.traces") == 1
    events = ht.diagnostics.report()["fallback_events"]
    if case == "float64":
        assert _counter("fallback.cluster.kmeans") == 0
    else:
        assert _counter("fallback.cluster.kmeans") == 1
        assert events[-1]["site"] == "cluster.kmeans"
        assert ("VMEM" if case == "vmem" else "ragged shards") in events[-1]["reason"]


# ------------------------- the kernel compiled for a described v5e (no chip needed)
@pytest.fixture(scope="module")
def one_chip():
    """A described v5e device to compile for; the TPU compiler is loaded by the first
    test that asks, never at import (one process at a time may hold libtpu)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an AOT compile for an absent chip is written to the persistent cache but cannot
    # be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("d,k", [(64, 8), (7, 5), (128, 256), (1024, 16), (4096, 8), (16, 1024)],
                         ids=lambda v: str(v))
def test_mosaic_compiles_the_block_the_gate_picks(one_chip, d, k):
    """Both forms, ragged n, at ``_block_n(d, k)``: the gate says no before Mosaic does,
    and the operand enters as a bitcast (no copy of n x d elements in the program)."""
    bn = _block_n(d, k)
    assert bn is not None
    n = 3 * bn + 37
    x = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip)
    for with_labels in (False, True):
        # the serving configuration sets the process-wide default to "highest": the
        # kernel states the precision of each contraction and must compile under it
        with jax.default_matmul_precision("highest" if with_labels else "default"):
            compiled = jax.jit(functools.partial(_fused_pallas, with_labels=with_labels)).lower(x, c).compile()
        text = compiled.as_text()
        assert "tpu_custom_call" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * n


def test_the_gate_declines_what_mosaic_would_refuse(one_chip):
    assert _block_n(64, 8) == 8192  # the benchmark's shape: a 2 MiB block, 2,048 steps a pass
    for d, k in [(20000, 8), (1024, 1024)]:
        assert _block_n(d, k) is None
        assert "VMEM" in kmeans_kernel.decline_reason(d, k)
        x = jax.ShapeDtypeStruct((3 * 128 + 37, d), jnp.float32, sharding=one_chip)
        c = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip)
        with pytest.raises(Exception, match="(?i)vmem|memory"):
            jax.jit(functools.partial(_fused_pallas, block_n=128)).lower(x, c).compile()


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_mosaic_compiles_the_flash_forward_at_the_cell_shape(one_chip, precision):
    """``xing4-score-32k``'s attention core, bf16[32, 32768, 192] against [.., 128], at the
    blocks ``forward_blocks`` picks and Mosaic's default VMEM scope. Also under a
    process-wide "highest" (the serving configuration sets it): the kernel states one
    MXU pass for 16-bit operands, which Mosaic would otherwise refuse."""
    q = jax.ShapeDtypeStruct((32, 32768, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((32, 32768, 128), jnp.bfloat16, sharding=one_chip)
    blocks = flash_kernel.forward_blocks(q, q, v)
    assert blocks == (1024, 1024) and flash_kernel._sub_tiles(*blocks) == (256, 512)
    from jax.experimental.pallas import tpu as pltpu

    assert flash_kernel._compiler_params(pltpu).vmem_limit_bytes is None
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(lambda q, k, v: flash_kernel.flash_forward(
            q, k, v, True, 0.07, blocks, name="mla_flash_fwd")).lower(q, q, v).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "mla_flash_fwd" in text


@pytest.mark.parametrize("window,name", [(2048, "swa_flash_fwd"), (None, "gqa_flash_fwd")])
def test_mosaic_compiles_the_grouped_forward_at_the_trinity_cell_shape(one_chip, window, name):
    """``trinity-score-32k``'s attention cores: bf16 q [32, 32768, 128] over 4 key/value heads
    taken where they lie, under the band of 2,048 keys and causal, at the blocks
    ``forward_blocks`` picks and Mosaic's default VMEM scope; no repeated key/value operand
    (a ``[32, 32768, 128]`` k or v) enters the program."""
    q = jax.ShapeDtypeStruct((32, 32768, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((4, 32768, 128), jnp.bfloat16, sharding=one_chip)
    blocks = flash_kernel.forward_blocks(q, kv, kv)
    assert blocks == (1024, 1024)
    visited = len(flash_kernel._pair_schedule(32768 // blocks[0], 32768 // blocks[1], *blocks,
                                              True, window)[0])
    dense = (32768 // blocks[0]) * (32768 // blocks[1])
    assert visited < dense / 8 if window else visited > dense / 2
    compiled = jax.jit(lambda q, k, v: flash_kernel.flash_forward(
        q, k, v, True, 128 ** -0.5, blocks, name=name, window=window)).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and name in text
    # the program's one temporary is the kernel's second output, the log-sum-exp that this
    # entry drops (f32[32, 32768, 1], the 1 padded to 128 lanes): no copy of k or v
    assert compiled.memory_analysis().temp_size_in_bytes < 32 * 32768 * 128 * 4 + 2**20


def test_the_flash_gates_decline_what_mosaic_would_refuse(one_chip):
    """One footprint model behind both gates: a streamed bias at (1024, 1024) is 15 MiB
    by the model, over the 12 MiB budget, and Mosaic does refuse it under its 16 MiB
    scope; the block pairs the gates pick for the same operands compile."""
    t = 4096
    q = jax.ShapeDtypeStruct((8, 16, t, 64), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((t, t), jnp.float32, sharding=one_chip)
    assert flash_kernel._fwd_footprint(1024, 1024, 64, 64, 2, with_bias=True) > flash_kernel._VMEM_BUDGET
    assert not flash_kernel._fits(q, q, 1024, 1024, with_bias=True)
    with pytest.raises(Exception, match="(?i)vmem|memory"):
        jax.jit(lambda q, k, v, b: flash_kernel._flash_pallas(
            q, k, v, False, 0.125, 1024, 1024, bias=b)[0]).lower(q, q, q, bias).compile()
    for with_bias in (False, True):
        blocks = flash_kernel._fwd_blocks(q.dtype, t, t, with_bias)
        assert flash_kernel._fits(q, q, *blocks, with_bias=with_bias)
        assert flash_kernel._fwd_footprint(*blocks, 64, 64, 2, with_bias) <= flash_kernel._VMEM_BUDGET
        jax.jit(lambda q, k, v, b: flash_kernel._flash_pallas(
            q, k, v, False, 0.125, *blocks, bias=b)[0]).lower(
                q, q, q, bias if with_bias else None).compile()


if __name__ == "__main__":
    import unittest

    unittest.main()


@pytest.mark.parametrize("cell,d,experts,top_k,chunk", [("trinity-score-32k", 2048, 128, 8, 128),
                                                       ("xing4-score-32k", 3584, 64, 4, 128)])
def test_mosaic_compiles_the_expert_layer_at_the_cell_shape(one_chip, monkeypatch, cell, d, experts,
                                                           top_k, chunk):
    """One expert layer of each model cell, 32,768 tokens in bfloat16 through ``MoE.apply``, as
    a TPU would run it: the grouped kernel is in the program under its name, with whole experts
    resident (the raised VMEM limit is one Mosaic accepts), the rows fetched through the
    sorted index, and the row chunk the rule reads off the widths; the loop over the experts is
    gone (no ``while`` at all, since the layout counts and no longer sorts and searches), and
    with it every ``dynamic-update-slice`` into the sorted buffer; the buffer is 32-bit words
    and the combine is the second kernel (PR 36)."""
    monkeypatch.setattr(grouped_matmul, "available", lambda interpret=False: True)
    m = ht.nn.MoE(d, 1024, experts, top_k, 1, 2.5, None, 512, dtype=jnp.bfloat16)
    assert grouped_matmul._row_chunk(512, d, 1024, 2) == chunk

    def placed(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(placed, jax.eval_shape(m.init, jax.random.key(0)))
    x = jax.ShapeDtypeStruct((32768, d), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(lambda p, x: m.apply(p, x)).lower(params, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_grouped_fwd" in text
    rows = 32768 * top_k + experts * 512
    _the_buffer_is_words_and_the_combine_a_kernel(text, rows, d, top_k)
    # no loop is left, and no sort but the router's ``top_k``: the layout counts (PR 34)
    assert not [line for line in text.splitlines() if " while(" in line]
    assert all("/top_k" in line for line in text.splitlines() if " sort(" in line)


def _the_buffer_is_words_and_the_combine_a_kernel(text, rows, d, top_k, tokens=32768):
    """In a compiled expert layer the sorted buffer exists once, as the grouped kernel's
    output in 32-bit words, a row padded to whole (8, 128) tiles: its input side is never made
    (the rows come through the sorted index) and neither is ``bf16[rows, d]``; the combine is
    ``moe_combine_fwd``, so no gather writes a row a pair (``bf16[pairs, d]``), no ``(k, T, d)``
    temporary is left for a weighted sum to read back, and nothing updates the buffer in place."""
    padded = grouped_matmul._token_tiles(d, 2)[1]
    buffer = f"u32[{rows * padded},128]"
    lines = text.splitlines()
    written = [line for line in lines if f" = {buffer}" in line and " parameter(" not in line]
    assert len(written) == 1 and "moe_grouped_fwd" in written[0]
    assert "moe_combine_fwd" in text
    assert [line for line in lines if "moe_combine_fwd" in line and f" = f32[{tokens},{d}]" in line]
    pairs = tokens * top_k
    for gone in (f"bf16[{rows},{d}]", f"bf16[{pairs},{d}]", f"[{top_k},{tokens},{d}]"):
        assert gone not in text, gone
    assert not [line for line in lines if "dynamic-update-slice" in line and buffer in line]


@pytest.mark.parametrize("cell,d,top_k,experts,held,block_rows", [
    ("trinity-score-32k", 2048, 8, 128, None, 512), ("xing4-score-32k", 3584, 4, 64, None, 512),
    ("ling-score-32k", 2560, 8, 512, (0, 128), 128)])
def test_mosaic_compiles_the_combine_at_the_cell_shape(one_chip, cell, d, top_k, experts, held,
                                                      block_rows):
    """``moe_combine_fwd`` alone at each model cell's shape: 32,768 tokens, the buffer of the
    worst case of all pairs held here in 32-bit words, 1,024 pairs a step (128 tokens at top-8,
    256 at top-4). Where every pair is held the row DMAs start without a branch; Ling's
    quarter branches on each slot. The float32 sum is the program's one output: no temporary."""
    rows = 32768 * top_k + (held[1] if held else experts) * block_rows
    padded = grouped_matmul._token_tiles(d, 2)[1]
    assert grouped_matmul._combine_blocks(top_k) == (1024 // top_k, 128 // top_k)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(functools.partial(grouped_matmul.combine, d=d, dtype=jnp.bfloat16,
                                         all_held=held is None)).lower(
        shaped((rows * padded, 128), jnp.uint32), shaped((32768, top_k), jnp.int32),
        shaped((32768, top_k), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_combine_fwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**24


@pytest.mark.parametrize("dtype,precision", [(jnp.bfloat16, "highest"), (jnp.float32, "default")],
                         ids=["bfloat16", "float32"])
def test_the_grouped_kernel_states_its_precision(one_chip, dtype, precision):
    """Under a process-wide "highest" (the serving configuration sets it) the kernel's bfloat16
    contractions still compile: they state one MXU pass, which Mosaic would otherwise refuse.
    Float32 streams compile too (their rows are words as they are, their products ``HIGHEST``)."""
    def shaped(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (shaped((1024, 2048)), shaped((4096,), jnp.int32), shaped((4, 2048, 512)),
            shaped((4, 2048, 512)), shaped((4, 512, 2048)), shaped((8,), jnp.int32),
            shaped((1,), jnp.int32))
    assert grouped_matmul.decline_reason(args[0], 4096, args[2], args[4], 512, 8) is None
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(functools.partial(grouped_matmul.grouped_gated_silu, block_rows=512)
                           ).lower(*args).compile()
    text = compiled.as_text()
    assert "moe_grouped_fwd" in text
    # the buffer comes out as 32-bit words whatever the streams: a bfloat16 row of 2048 is 8
    # sublane rows of 128 words, a float32 row 16
    padded = grouped_matmul._token_tiles(2048, jnp.dtype(dtype).itemsize)[1]
    assert padded == (8 if dtype == jnp.bfloat16 else 16) and f"u32[{4096 * padded},128]" in text
    combined = jax.jit(functools.partial(grouped_matmul.combine, d=2048, dtype=dtype, all_held=True)
                       ).lower(shaped((4096 * padded, 128), jnp.uint32), shaped((1024, 4), jnp.int32),
                               shaped((1024, 4), jnp.float32)).compile()
    assert "moe_combine_fwd" in combined.as_text()


def test_mosaic_compiles_the_delta_rule_at_the_ling_cell_shape(one_chip):
    """The chunked KDA forward at ``ling-score-32k``'s shape, 32 heads of 128 over 32,768
    positions in bfloat16 with the log-decay in float32: the gate takes it and Mosaic compiles
    it under its name (the convolution's shifted rows, transposed products and the float32
    inverse as bfloat16 pieces included); beside operands and output it holds only beta and
    the gate laid out by head group."""
    from heat_tpu.core.kernels import delta_rule

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, g = shaped((32768, 4096), jnp.bfloat16), shaped((32768, 4096), jnp.float32)
    w, side = shaped((4, 4096), jnp.bfloat16), shaped((32768, 32), jnp.float32)
    assert delta_rule.decline_reason(x, w, 32) is None
    compiled = jax.jit(functools.partial(delta_rule.kda_mix, heads=32, bound=-5.0, eps=1e-6)).lower(
        x, x, x, (w, w, w), g, shaped((4096,), jnp.float32), side, side,
        shaped((128,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_chunk_fwd" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**28


def _kda_cell_jaxpr(cell):
    """The jaxpr of ``kda_mix`` at a cell's shape: 32 heads of 128 over 32,768 positions in
    bfloat16, Ling's bounded decay with a gate a head or Kimi-Linear's softplus decay with a
    gate a channel."""
    from heat_tpu.core.kernels import delta_rule

    x, f = jax.ShapeDtypeStruct((32768, 4096), jnp.bfloat16), jax.ShapeDtypeStruct((32768, 4096), jnp.float32)
    w, side = jax.ShapeDtypeStruct((4, 4096), jnp.bfloat16), jax.ShapeDtypeStruct((32768, 32), jnp.float32)
    vec = jax.ShapeDtypeStruct((4096,), jnp.float32), jax.ShapeDtypeStruct((128,), jnp.float32)
    bound, eps, gate = (-5.0, 1e-6, side) if cell == "ling" else (None, 1e-5, f)
    return jax.make_jaxpr(functools.partial(delta_rule.kda_mix, heads=32, bound=bound, eps=eps))(
        x, x, x, (w, w, w), f, vec[0], side, gate, vec[1])


def test_the_bounded_delta_rule_traces_what_it_traced_before_the_softplus_kind():
    """The chunked KDA forward at ``ling-score-32k``'s shape (bounded decay, one gate a head)
    traces, character for character, a fixed jaxpr: the softplus kind and the gate a channel
    left Ling's kernel as it was. The digest was taken again when ``chunk_step`` came to lay
    two heads side by side on the lanes, which changed its layout and nothing else in the
    narrow form."""
    import hashlib

    text = str(_kda_cell_jaxpr("ling"))
    assert "name=kda_chunk_fwd" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "5674420c0a1906b3"


def _dots(jaxpr):
    """Every ``dot_general`` in a jaxpr, those of its sub-jaxprs (a Pallas body) included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _dots(sub)


@pytest.mark.parametrize("cell", ["ling", "kimi"])
def test_the_delta_rule_inverse_multiplies_pairs_of_heads_128_deep(cell):
    """At a cell's shape a grid step takes 8 heads as 4 pairs side by side on the lanes: the
    inverse's ten products are 20 dots against a pair's 128 x 128 block diagonal, and no dot
    is left whose right operand is one head's 64 x 64 square."""
    shapes = [(e.invars[0].aval.shape, e.invars[1].aval.shape) for e in _dots(_kda_cell_jaxpr(cell).jaxpr)]
    assert not [s for s in shapes if s[1][-2:] == (64, 64)], shapes
    assert sum(rhs == (4, 128, 128) for _, rhs in shapes) == 20


@pytest.mark.parametrize("cell,digest", [("xing4", "fb0198571288fe28"), ("ling", "fd2a53a07f906462")])
def test_latent_attention_with_positions_traces_what_it_traced_before_nope(cell, digest, monkeypatch):
    """``MultiheadLatentAttention`` with rotary positions at the Xing4 cell's settings (query
    latent, YaRN) and at the Ling cell's (direct query, head gate), 32,768 tokens in bfloat16 on
    the TPU path (``mla_flash_fwd``): the jaxpr is, character for character, a fixed one. The
    digests were taken on the commit before the switch to no positions was added, and taken
    again when the latent operand form came in (q, ``[k_nope | v]`` and the one rope key into
    the kernel as the projections leave them, the query's rope lanes turned in the kernel, no
    LSE), which changed that and nothing else."""
    import hashlib

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yarn = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096, "type": "yarn"}
    if cell == "xing4":
        m = ht.nn.MultiheadLatentAttention(3584, 32, 768, 512, 128, 64, 128, 10000, yarn, 1e-6,
                                           jnp.bfloat16, 0.1)
    else:
        m = ht.nn.MultiheadLatentAttention(2560, 32, None, 512, 128, 64, 128, 6000000, None, 1e-6,
                                           jnp.bfloat16, 0.1, head_gate=True)
    params = jax.eval_shape(m.init, jax.random.key(0))
    x = jax.ShapeDtypeStruct((32768, m.dim), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda p, x: m.apply(p, x))(params, x))
    assert "name=mla_flash_fwd" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


@pytest.mark.parametrize("t,dtype", [(32768, jnp.bfloat16), (4096, jnp.float32)],
                         ids=["cell", "float32_check"])
def test_mosaic_compiles_the_unbounded_delta_rule_at_the_kimi_cell_shape(one_chip, t, dtype):
    """The chunked KDA forward at ``kimi-score-32k``'s shape, 32 heads of 128 over 32,768
    positions in bfloat16, with the softplus decay's and the channel gate's pre-activations in
    float32 (T, 4096) beside the projections: the gate takes it and Mosaic compiles the wide
    form (sub-chunks of 8, the floor) under its own name, with no temporary beyond beta laid
    out by head group. Likewise in float32 over the 4,096 positions of the cell's check of its
    KDA layers (``kda_rms_gap``)."""
    from heat_tpu.core.kernels import delta_rule

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, f = shaped((t, 4096), dtype), shaped((t, 4096), jnp.float32)
    w, beta = shaped((4, 4096), dtype), shaped((t, 32), jnp.float32)
    assert delta_rule.decline_reason(x, w, 32) is None
    compiled = jax.jit(functools.partial(delta_rule.kda_mix, heads=32, bound=None, eps=1e-5)).lower(
        x, x, x, (w, w, w), f, shaped((4096,), jnp.float32), beta, f,
        shaped((128,), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "kda_unbounded_fwd" in text and "kda_chunk_fwd" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**24


def test_mosaic_compiles_the_expert_layer_at_the_kimi_cell_shape(one_chip, monkeypatch):
    """One expert layer of ``kimi-score-32k``: 128 of 256 experts of 2304 x 1024 held, top-8 with
    no group limit, blocks of 256 rows: the grouped kernel takes a whole expert a step, the
    sorted buffer is 32-bit words and the combine is the second kernel; no loop is left."""
    from heat_tpu.nn.kimi_linear import BLOCK_ROWS

    monkeypatch.setattr(grouped_matmul, "available", lambda interpret=False: True)
    m = ht.nn.MoE(2304, 1024, 256, 8, 1, 2.446, (0, 128), BLOCK_ROWS, jnp.bfloat16)
    assert grouped_matmul._slab(2304, 1024, BLOCK_ROWS, 2, 2) == 1024

    def placed(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(placed, jax.eval_shape(m.init, jax.random.key(0)))
    x = jax.ShapeDtypeStruct((32768, 2304), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda p, x: m.apply(p, x)).lower(params, x).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_fwd" in text
    _the_buffer_is_words_and_the_combine_a_kernel(text, 32768 * 8 + 128 * BLOCK_ROWS, 2304, 8)
    assert not [line for line in text.splitlines() if " while(" in line]


def test_mosaic_compiles_the_expert_layer_at_the_ling_cell_shape(one_chip, monkeypatch):
    """One expert layer of ``ling-score-32k``: 128 of 512 experts of 2560 x 768 held, top-8 in
    8 groups of which 4 stay, blocks of 128 rows: the grouped kernel is in the program, the
    sorted buffer is sized for the worst case of all pairs held here and is 32-bit words, a row
    of 2560 padded to 16 sublane rows (8 KB), and the combine is the second kernel (PR 36)."""
    from heat_tpu.nn.ling import BLOCK_ROWS

    monkeypatch.setattr(grouped_matmul, "available", lambda interpret=False: True)
    m = ht.nn.MoE(2560, 768, 512, 8, 1, 2.5, (0, 128), BLOCK_ROWS, jnp.bfloat16, 8, 4)

    def placed(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(placed, jax.eval_shape(m.init, jax.random.key(0)))
    assert params["experts"]["w_gate"].shape == (128, 2560, 768)
    assert params["router"].shape == (2560, 512)
    x = jax.ShapeDtypeStruct((32768, 2560), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda p, x: m.apply(p, x)).lower(params, x).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_fwd" in text
    rows = 32768 * 8 + 128 * BLOCK_ROWS
    _the_buffer_is_words_and_the_combine_a_kernel(text, rows, 2560, 8)


@pytest.mark.parametrize("d,h,experts,top_k,block_rows,digest", [
    (2048, 1024, 128, 8, 512, "06fc07abadceb9df"), (3584, 1024, 64, 4, 512, "5ba58f62f9329acd"),
    (2560, 768, 128, 8, 128, "407b3c04177124c5")], ids=["trinity", "xing4", "ling"])
def test_the_grouped_kernel_traces_what_it_traced_before_the_slabs(d, h, experts, top_k, block_rows,
                                                                   digest):
    """Where the whole expert fits VMEM a step takes all of its hidden width: the jaxpr of the
    grouped kernel at each accepted cell's shape (32,768 tokens in bfloat16) is, character for
    character, what the commit before PR 38 traced (the digests were taken on that commit)."""
    import hashlib

    rows = 32768 * top_k + experts * block_rows
    shapes = [((32768, d), jnp.bfloat16), ((rows,), jnp.int32), ((experts, d, h), jnp.bfloat16),
              ((experts, d, h), jnp.bfloat16), ((experts, h, d), jnp.bfloat16),
              ((rows // block_rows,), jnp.int32), ((1,), jnp.int32)]
    assert grouped_matmul._slab(d, h, block_rows, 2, 2) == h
    text = str(jax.make_jaxpr(functools.partial(grouped_matmul.grouped_gated_silu,
                                                block_rows=block_rows))(
        *(jax.ShapeDtypeStruct(*shape) for shape in shapes)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_mosaic_compiles_the_expert_layer_at_the_dsv32_cell_shape(one_chip, monkeypatch):
    """One piece of ``dsv32-score-32k``'s expert layer: 8,192 tokens, 16 of 256 experts of 7168 x
    2048 held, top-8 in 8 groups of which 4 stay, at the model's block rows: the grouped kernel
    walks a quarter of the hidden width a step (grid axis 1) under a VMEM limit Mosaic accepts, the
    buffer is 32-bit words (a row of 7168 is 32 sublane rows, 16 KB) and the combine is the second
    kernel: the layer takes both kernels and no loop."""
    from heat_tpu.nn.deepseek_v32 import BLOCK_ROWS

    monkeypatch.setattr(grouped_matmul, "available", lambda interpret=False: True)
    m = ht.nn.MoE(7168, 2048, 256, 8, 1, 2.5, (0, 16), BLOCK_ROWS, jnp.bfloat16, 8, 4)
    assert grouped_matmul._slab(7168, 2048, BLOCK_ROWS, 2, 2) == 512

    def placed(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(placed, jax.eval_shape(m.init, jax.random.key(0)))
    x = jax.ShapeDtypeStruct((8192, 7168), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda p, x: m.apply(p, x)).lower(params, x).compile().as_text()
    assert "tpu_custom_call" in text and "moe_grouped_fwd" in text
    _the_buffer_is_words_and_the_combine_a_kernel(text, 8192 * 8 + 16 * BLOCK_ROWS, 7168, 8, 8192)
    assert not [line for line in text.splitlines() if " while(" in line]


# ------------------------- the index kernel and the flash forward under its packed mask (PR 37)
def _index_inputs(h, t, d, case, dtype, seed=0):
    """``q`` (H, T, D), ``k`` (T, D), ``w`` (H, T). ``ties``: small integers and signed powers
    of two, so that every score is exact in any order of summation and many are equal."""
    kq, kk, kw = jax.random.split(jax.random.key(seed), 3)
    if case == "ties":
        q = jax.random.randint(kq, (h, t, d), -2, 3).astype(dtype)
        k = jax.random.randint(kk, (t, d), -2, 3).astype(dtype)
        w = jnp.exp2(jax.random.randint(kw, (h, t), -2, 2).astype(jnp.float32))
        return q, k, w * jnp.where(jax.random.bernoulli(kw, 0.3, (h, t)), -1.0, 1.0)
    return (jax.random.normal(kq, (h, t, d)).astype(dtype), jax.random.normal(kk, (t, d)).astype(dtype),
            jax.random.normal(kw, (h, t), jnp.float32))


@pytest.mark.parametrize("h,t,topk,case,dtype", [
    (8, 256, 64, "ties", jnp.bfloat16), (8, 384, 64, "ties", jnp.bfloat16),
    (8, 384, 64, "normal", jnp.bfloat16), (16, 512, 100, "normal", jnp.float32),
    (8, 256, 300, "normal", jnp.bfloat16)], ids=str)
def test_interpreted_index_kernel_selects_what_top_k_selects(h, t, topk, case, dtype):
    """``dsa_index_fwd`` against dense scores and ``lax.top_k``: the same sets, bit for bit of
    the packed words, with ties (``ties``: most scores are equal to others) going to the lower
    position; key blocks of 256 and of 128 (T = 384), a ``topk`` above T (everything causal),
    rows with fewer earlier tokens than ``topk``."""
    from heat_tpu.core.kernels import sparse_index

    q, k, w = _index_inputs(h, t, 128, case, dtype)
    assert sparse_index.decline_reason(q, k, w) is None
    got = sparse_index.dsa_index(q, k, w, topk, interpret=True)
    want = sparse_index.select_plain(sparse_index.index_scores(q, k, w), topk)
    assert got.shape == (t, sparse_index.mask_words(t)) and got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(sparse_index.unpack_mask(got, t)), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sparse_index.pack_mask(want)))
    kept = np.asarray(want).sum(axis=1)
    assert kept.tolist() == [min(topk, i + 1) for i in range(t)]
    if case == "ties":  # the test means what it says: the rank's value is shared
        scores = np.asarray(sparse_index.index_scores(q, k, w))
        assert any(len(np.unique(scores[i, :i + 1])) < i + 1 for i in range(topk, t))


def test_the_index_gate_declines_what_does_not_tile():
    from heat_tpu.core.kernels import sparse_index

    q, k, w = _index_inputs(8, 256, 128, "normal", jnp.bfloat16)
    assert "tiles" in sparse_index.decline_reason(q[:, :200], k[:200], w[:, :200])
    assert "tiles" in sparse_index.decline_reason(q[..., :64], k[..., :64], w)
    assert "takes bfloat16 or float32" in sparse_index.decline_reason(q.astype(jnp.float16), k, w)
    big = jax.ShapeDtypeStruct((64, 2 ** 18, 128), jnp.bfloat16)
    assert "VMEM" in sparse_index.decline_reason(big, jax.ShapeDtypeStruct((2 ** 18, 128), jnp.bfloat16), w)


@pytest.mark.parametrize("t,blocks,sub,dtype", [
    (512, (128, 128), None, jnp.float32), (512, (256, 256), (128, 128), jnp.float32),
    (8192, (1024, 1024), None, jnp.float32), (1024, (512, 512), None, jnp.bfloat16)], ids=str)
def test_interpreted_flash_forward_under_a_packed_mask(t, blocks, sub, dtype):
    """The masked schedule of the forward body: every head of a row attends to the keys whose
    bits are set and to no other, whole blocks with no selected key included (their rows keep
    a finite running maximum), key blocks that share a tile of words and ones that start the
    next (T 8,192 = two tiles), sub-tiles of a step reading their own bits."""
    from heat_tpu.core.kernels import sparse_index

    kq, kk, kv, ks = jax.random.split(jax.random.key(1), 4)
    h, d, dv = 2, 64, 32
    q, k = (jax.random.normal(key, (h, t, d)).astype(dtype) for key in (kq, kk))
    v = jax.random.normal(kv, (h, t, dv)).astype(dtype)
    mask = sparse_index.select_plain(jax.random.normal(ks, (t, t)), max(t // 26, 20))
    got, _ = flash_kernel._flash_pallas(q, k, v, True, d ** -0.5, *blocks, interpret=True, sub=sub,
                                        mask=sparse_index.pack_mask(mask), name="dsa_flash_fwd")
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    s = jnp.einsum("hqd,hkd->hqk", f32(q), f32(k), precision="highest") * d ** -0.5
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("hqk,hkd->hqd", p, f32(v), precision="highest")
    assert float(jnp.max(jnp.abs(f32(got) - want))) < (1e-5 if dtype == jnp.float32 else 2e-2)
    with pytest.raises(ValueError, match="packed mask goes with the causal schedule"):
        flash_kernel._flash_pallas(q, k, v, False, 1.0, *blocks, interpret=True,
                                   mask=sparse_index.pack_mask(mask))


@pytest.mark.parametrize("name,shape,digest", [
    ("mla_flash_fwd", (32, 32, 192, 128, None), "21340080dd9d0121"),
    ("gqa_flash_fwd", (32, 4, 128, 128, None), "84e04e2104125b18"),
    ("swa_flash_fwd", (32, 4, 128, 128, 2048), "44a69c677a3cc085")])
def test_the_three_schedules_trace_what_they_traced_before_the_mask(name, shape, digest):
    """One forward body for four names: with no mask the jaxpr of the call at each accepted
    cell's shape is, character for character, what the commit before PR 37 traced (the digests
    were taken on that commit). A PR that changes the body on purpose replaces them."""
    import hashlib

    hq, hkv, d, dv, window = shape
    q = jax.ShapeDtypeStruct((hq, 32768, d), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((hkv, 32768, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((hkv, 32768, dv), jnp.bfloat16)
    blocks = flash_kernel.forward_blocks(q, k, v)
    text = str(jax.make_jaxpr(lambda q, k, v: flash_kernel.flash_forward(
        q, k, v, True, 0.07, blocks, name=name, window=window))(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_mosaic_compiles_the_index_kernel_at_the_dsv32_cell_shape(one_chip):
    """``dsv32-score-32k``'s indexer: 64 heads of 128 over 32,768 tokens in bfloat16, the
    document's keys and a 16 MB score block in VMEM under the limit the call raises; the
    program's one output is the packed words, 134 MB, and it has no temporary."""
    from heat_tpu.core.kernels import sparse_index

    t = 32768
    q = jax.ShapeDtypeStruct((64, t, 128), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((t, 128), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((64, t), jnp.float32, sharding=one_chip)
    assert sparse_index.decline_reason(q, k, w) is None
    assert sparse_index._footprint(64, t, 128, 2) + sparse_index._VMEM_MARGIN < 48 * 2 ** 20
    with jax.default_matmul_precision("highest"):  # the kernel states its products' precision
        compiled = jax.jit(lambda q, k, w: sparse_index.dsa_index(q, k, w, 2048)).lower(q, k, w).compile()
    assert "dsa_index_fwd" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == t * 1024 * 4 and memory.temp_size_in_bytes == 0


def test_mosaic_compiles_the_masked_flash_forward_at_the_dsv32_cell_shape(one_chip):
    """One group of 16 heads of ``dsv32-score-32k``'s attention, bf16 [16, 32768, 192] against
    [.., 128] under the packed words, at the blocks the gate picks with the mask's tile counted
    and Mosaic's default VMEM scope; the words enter as they are (no copy of them)."""
    from heat_tpu.core.kernels import sparse_index

    t = 32768
    q = jax.ShapeDtypeStruct((16, t, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((16, t, 128), jnp.bfloat16, sharding=one_chip)
    words = jax.ShapeDtypeStruct((t, sparse_index.mask_words(t)), jnp.int32, sharding=one_chip)
    blocks = flash_kernel.forward_blocks(q, q, v, True)
    assert blocks == (1024, 1024) and words.shape == (t, 1024)
    assert flash_kernel._fwd_footprint(*blocks, 192, 128, 2, with_mask=True) \
        == flash_kernel._fwd_footprint(*blocks, 192, 128, 2) + 2 * 1024 * 128 * 4
    compiled = jax.jit(lambda q, k, v, m: flash_kernel.flash_forward(
        q, k, v, True, 0.07, blocks, name="dsa_flash_fwd", mask=m)).lower(q, q, v, words).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dsa_flash_fwd" in text
    assert not [line for line in text.splitlines() if " copy(" in line and "s32[32768,1024]" in line]


# ------------------------- the latent operand form of the flash forward
def _latent_operands(h, t, dtype, seed=0):
    """q ``(h, t, 128 + 64)``, ``[k_nope | v]`` ``(h, t, 128 + 128)``, the rope key ``(t, 64)``
    and its YaRN-like turns ``(cos, sin)`` at a magnitude that is not 1."""
    from heat_tpu.nn.attention import rope_turns, yarn_inv_freq

    kq, kkv, kr = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (h, t, 192)).astype(dtype)
    kv = jax.random.normal(kkv, (h, t, 256)).astype(dtype)
    k_rope = jax.random.normal(kr, (t, 64)).astype(dtype)
    inv_freq = yarn_inv_freq(64, 10000.0, {"factor": 40, "original_max_position_embeddings": 256,
                                            "beta_fast": 32, "beta_slow": 1})
    return q, kv, k_rope, inv_freq, rope_turns(t, inv_freq, 0.9)


@pytest.mark.parametrize("h,t,blocks,dtype,turned,masked", [
    (2, 1024, (512, 512), jnp.float32, False, False),
    (2, 1024, (512, 512), jnp.float32, True, False),
    (2, 1024, (256, 512), jnp.bfloat16, True, True),
    (16, 512, (256, 256), jnp.bfloat16, True, False),
    (16, 1024, (512, 512), jnp.bfloat16, False, True),
    (2, 8192, (1024, 1024), jnp.float32, True, True)], ids=str)
def test_interpreted_latent_form_is_the_concatenated_call(h, t, blocks, dtype, turned, masked):
    """``flash_latent`` on the operands as the projections leave them against
    ``flash_forward`` on the concatenated q and k (the query's rope lanes turned by
    ``rotate_halves``, the rope key repeated for every head) and the sliced v: with and
    without rotary positions, with and without a packed mask, 2 and 16 heads, key blocks that
    share a tile of words (T 1,024) and ones that start the next (T 8,192 = two tiles). In
    float32 within 1e-5; in bfloat16 within one rounding at the output's scale (half a
    bfloat16 step at its largest magnitude): a turned query element may round the other way
    where the interpreter contracts a product and a sum, which moves the outputs near 0."""
    from heat_tpu.core.kernels import sparse_index
    from heat_tpu.nn.attention import rotate_halves

    q, kv, k_rope, inv_freq, turns = _latent_operands(h, t, dtype)
    mask = None
    if masked:
        picked = sparse_index.select_plain(jax.random.normal(jax.random.key(3), (t, t)), max(t // 26, 20))
        mask = sparse_index.pack_mask(picked)
    got = flash_kernel.flash_latent(q, kv, k_rope, 0.07, blocks, turns if turned else None,
                                    interpret=True, mask=mask)
    full_q = jnp.concatenate([q[..., :128], rotate_halves(q[..., 128:], inv_freq, 0.9)], -1) if turned else q
    k = jnp.concatenate([kv[..., :128], jnp.broadcast_to(k_rope, (h, t, 64))], -1)
    want = flash_kernel.flash_forward(full_q, k, kv[..., 128:], True, 0.07, blocks,
                                      interpret=True, mask=mask)
    assert got.shape == want.shape == (h, t, 128) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        assert np.max(np.abs(got - want)) <= 1e-5
    else:
        assert np.max(np.abs(got - want)) <= np.max(np.abs(want)) * 2.0 ** -8
    with pytest.raises(ValueError, match="latent form takes"):
        flash_kernel.flash_latent(q[..., :160], kv, k_rope, 0.07, blocks, interpret=True)


def _dsv32_group_heads_jaxpr(monkeypatch):
    """The jaxpr of ``MultiheadLatentAttention._heads`` for one group of 16 heads at
    ``dsv32-score-32k``'s shapes (32,768 tokens, bfloat16, the indexer's packed words) on the
    TPU path, and the shapes of the group's two projections."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yarn = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096, "type": "yarn"}
    m = ht.nn.MultiheadLatentAttention(7168, 128, 1536, 512, 128, 64, 128, 10000, yarn, 1e-6,
                                       jnp.bfloat16, 0.1, index=(64, 128, 2048), head_groups=8)
    t, h = 32768, 16

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    def heads(x, c_q, c_kv, k_rope, words, wq, wkv_b, wo):
        return m._heads(x, c_q, lambda: (c_kv, k_rope), words, wq, wkv_b, wo)

    jaxpr = jax.make_jaxpr(heads)(
        shaped(t, 7168), shaped(t, 1536), shaped(t, 512), shaped(t, 64),
        shaped(t, 1024, dtype=jnp.int32), shaped(1536, h * 192), shaped(512, h * 256),
        shaped(h * 128, 7168))
    return jaxpr, (h, t, 192), (h, t, 256)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of its sub-jaxprs, with the jaxpr it lies in."""
    for eqn in jaxpr.eqns:
        yield eqn, jaxpr
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_latent_attention_takes_the_projections_as_they_leave(monkeypatch):
    """At ``dsv32-score-32k``'s group of 16 heads on the TPU path nothing is put together in
    HBM: no ``broadcast_in_dim`` makes the rope key ``(16, T, 64)``, no ``concatenate`` makes a
    ``(16, T, 192)`` q or k, and the Pallas call (``dsa_flash_fwd``, one output: no LSE) reads the
    ``[k_nope | v]`` projection itself, twice, as two lane blocks."""
    jaxpr, q_shape, kv_shape = _dsv32_group_heads_jaxpr(monkeypatch)
    h, t = q_shape[:2]
    eqns = list(_eqns(jaxpr.jaxpr))
    made = [(e.primitive.name, tuple(v.aval.shape)) for e, _ in eqns for v in e.outvars]
    assert ("broadcast_in_dim", (h, t, 64)) not in made
    assert ("concatenate", q_shape) not in made
    kv_h = [v for e, _ in eqns for v in e.outvars
            if tuple(v.aval.shape) == kv_shape and v.aval.dtype == jnp.bfloat16]
    assert len(kv_h) == 1  # the projection, in the layer's type
    call = [e for e, _ in eqns if e.primitive.name in ("jit", "pjit") and kv_h[0] in e.invars]
    assert len(call) == 1 and call[0].params["name"] == "_flash_pallas"
    inner = call[0].params["jaxpr"].jaxpr
    kv_in = inner.invars[list(call[0].invars).index(kv_h[0])]
    pallas = [e for e in inner.eqns if e.primitive.name == "pallas_call"]
    assert len(pallas) == 1 and list(pallas[0].invars).count(kv_in) == 2
    assert len(pallas[0].outvars) == 1 and "dsa_flash_fwd" in str(pallas[0].params)


@pytest.mark.parametrize("h,turned,masked,name", [
    (32, True, False, "mla_flash_fwd"), (16, True, True, "dsa_flash_fwd"),
    (32, False, False, "mla_flash_fwd")], ids=["xing4_ling", "dsv32", "kimi"])
def test_mosaic_compiles_the_latent_form_at_the_cell_shapes(one_chip, h, turned, masked, name):
    """The latent operand form at the four latent cells' shapes, 32,768 tokens in bfloat16: 32
    heads with the query's rope lanes turned in the kernel (Xing4, Ling), a group of 16 under
    the packed words (DeepSeek-V3.2), 32 without positions (Kimi-Linear), at (1024, 1024)
    blocks, the table and the turned copy inside the footprint's budget, and Mosaic's default
    VMEM scope: the call has one output and no copy of ``[k_nope | v]`` enters the program."""
    from heat_tpu.core.kernels import sparse_index

    t = 32768

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, kv, k_rope = shaped((h, t, 192)), shaped((h, t, 256)), shaped((t, 64))
    words = shaped((t, sparse_index.mask_words(t)), jnp.int32)
    cos = shaped((t, 32), jnp.float32)
    blocks = flash_kernel.latent_blocks(q, kv, k_rope, masked, turned)
    assert blocks == (1024, 1024)
    assert flash_kernel._fwd_footprint(*blocks, 192, 128, 2, with_mask=masked,
                                       turned=64 if turned else 0) <= flash_kernel._VMEM_BUDGET

    def call(q, kv, k_rope, words, cos, sin):
        return flash_kernel.flash_latent(q, kv, k_rope, 0.07, blocks, (cos, sin) if turned else None,
                                         name=name, mask=words if masked else None)

    text = jax.jit(call).lower(q, kv, k_rope, words, cos, cos).compile().as_text()
    assert "tpu_custom_call" in text and name in text
    assert not [line for line in text.splitlines() if " copy(" in line and f"bf16[{h},32768,256]" in line]
    assert not [line for line in text.splitlines() if "custom-call(" in line and f"f32[{h},32768,1]" in line]

