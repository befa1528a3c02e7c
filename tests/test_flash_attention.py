"""Flash-attention Pallas kernel: interpret-mode parity on the CPU mesh (the real
compile path is exercised on TPU by bench.py and the verify drive)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from heat_tpu.core.kernels.flash_attention import (
    _flash_pallas,
    flash_attention_reference,
    flash_forward,
    forward_blocks,
    use_flash,
)


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", [(1, 2, 1024, 64), (2, 1, 512, 128)])
    def test_interpret_parity(self, causal, shape):
        rng = np.random.default_rng(0)
        q, k, v = (jnp.array(rng.standard_normal(shape), jnp.float32) for _ in range(3))
        scale = 1.0 / np.sqrt(shape[-1])
        got, lse = _flash_pallas(q, k, v, causal, float(scale), 512, 512, interpret=True)
        want = flash_attention_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_interpret_parity_cross_lengths(self):
        """Tq != Tk (cross-attention shapes)."""
        rng = np.random.default_rng(1)
        q = jnp.array(rng.standard_normal((1, 1, 512, 64)), jnp.float32)
        k = jnp.array(rng.standard_normal((1, 1, 1536, 64)), jnp.float32)
        v = jnp.array(rng.standard_normal((1, 1, 1536, 64)), jnp.float32)
        got, _ = _flash_pallas(q, k, v, False, float(1 / np.sqrt(64)), 512, 512, interpret=True)
        want = flash_attention_reference(q, k, v, False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    def test_causal_skips_above_diagonal(self):
        """Causal output is independent of keys strictly above the diagonal —
        poisoning the future keys with huge values must not change the result."""
        rng = np.random.default_rng(2)
        q = jnp.array(rng.standard_normal((1, 1, 1024, 64)), jnp.float32)
        k = jnp.array(rng.standard_normal((1, 1, 1024, 64)), jnp.float32)
        v = jnp.array(rng.standard_normal((1, 1, 1024, 64)), jnp.float32)
        # queries in the first block attend only the first block of keys
        k_poison = k.at[:, :, 512:, :].set(1e4)
        a, _ = _flash_pallas(q, k, v, True, 0.125, 512, 512, interpret=True)
        b, _ = _flash_pallas(q, k_poison, v, True, 0.125, 512, 512, interpret=True)
        np.testing.assert_allclose(
            np.asarray(a[:, :, :512]), np.asarray(b[:, :, :512]), rtol=1e-5, atol=1e-5
        )

    def test_use_flash_gating(self):
        q = jnp.zeros((1, 2, 1024, 64), jnp.float32)
        # mask present -> no flash
        assert not use_flash(q, q, q, jnp.zeros((1024, 1024)))
        # non-block-multiple sequence -> no flash
        q_ragged = jnp.zeros((1, 2, 1000, 64), jnp.float32)
        assert not use_flash(q_ragged, q_ragged, q_ragged, None)
        # CPU backend -> no flash (suite runs on the CPU mesh)
        assert not use_flash(q, q, q, None)
        # interpret mode ignores the backend
        assert use_flash(q, q, q, None, interpret=True)

    def test_streaming_accepts_huge_kv(self):
        """Since the kernels stream k/v blocks through the grid, VMEM residency
        is O(block²) — a 128 MB k/v panel is fine (it never sits in VMEM whole)."""
        q = jnp.zeros((1, 1, 1024, 64), jnp.bfloat16)
        k = jnp.zeros((1, 1, 1 << 20, 64), jnp.bfloat16)  # 128 MB of k+v
        assert use_flash(q, k, k, None, interpret=True)


    def test_mask_fwd_parity_interpret(self):
        """(Tq, Tk) bool and additive-float masks stream through the kernel and
        match the dense reference, including fully-masked rows (output 0)."""
        from heat_tpu.core.kernels.flash_attention import _as_bias
        from heat_tpu.nn.attention import _dense_attention

        rng = np.random.default_rng(9)
        shape = (1, 2, 1024, 64)
        q, k, v = (jnp.array(rng.standard_normal(shape), jnp.float32) for _ in range(3))
        bool_mask = jnp.array(rng.random((1024, 1024)) > 0.3)
        bool_mask = bool_mask.at[5].set(False)  # a fully-masked query row
        float_mask = jnp.where(bool_mask, 0.0, -1e9).astype(jnp.float32)
        for mask in (bool_mask, float_mask):
            got = _flash_pallas(
                q, k, v, False, 0.125, 512, 512,
                interpret=True, bias=_as_bias(mask),
            )[0]
            want = _dense_attention(q, k, v, mask=mask, scale=0.125)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4
            )
            if mask.dtype == jnp.bool_:
                # a fully bool-masked row outputs exactly 0 (l = 0); a finite
                # additive mask (-1e9) instead degrades to uniform attention,
                # identically in the dense path
                assert float(jnp.max(jnp.abs(got[:, :, 5]))) == 0.0

    def test_mask_plus_causal_parity_interpret(self):
        """Causal scheduling and a streamed mask compose: blocks above the
        diagonal stay absent from the schedule, the mask applies to the rest."""
        from heat_tpu.core.kernels.flash_attention import _as_bias
        from heat_tpu.nn.attention import _dense_attention

        rng = np.random.default_rng(11)
        shape = (1, 2, 1024, 64)
        q, k, v = (jnp.array(rng.standard_normal(shape), jnp.float32) for _ in range(3))
        mask = jnp.array(rng.random((1024, 1024)) > 0.2)
        got = _flash_pallas(
            q, k, v, True, 0.125, 512, 512, interpret=True, bias=_as_bias(mask)
        )[0]
        want = _dense_attention(q, k, v, mask=mask, is_causal=True, scale=0.125)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


class TestFlashUnequalWidths:
    """d_qk != d_v (latent attention: 192 against 128). Interpret mode under
    ``default_matmul_precision("highest")``, so that the tolerance is float32's on any
    device the suite runs on (ROADMAP D9)."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("widths", [(192, 128), (64, 128), (128, 64)])
    def test_interpret_parity(self, widths, causal):
        d, dv = widths
        rng = np.random.default_rng(21)
        q = jnp.array(rng.standard_normal((2, 1024, d)), jnp.float32)
        k = jnp.array(rng.standard_normal((2, 1024, d)), jnp.float32)
        v = jnp.array(rng.standard_normal((2, 1024, dv)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            got, lse = _flash_pallas(q, k, v, causal, 0.07, 512, 512, interpret=True,
                                     name="mla_flash_fwd")
            want = flash_attention_reference(q, k, v, causal, 0.07)
        assert got.shape == want.shape == (2, 1024, dv) and lse.shape == (2, 1024)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_forward_entry_in_bfloat16(self):
        rng = np.random.default_rng(22)
        q = jnp.array(rng.standard_normal((2, 1024, 192)), jnp.bfloat16)
        k = jnp.array(rng.standard_normal((2, 1024, 192)), jnp.bfloat16)
        v = jnp.array(rng.standard_normal((2, 1024, 128)), jnp.bfloat16)
        blocks = forward_blocks(q, k, v)
        assert blocks == (1024, 1024)
        got = flash_forward(q, k, v, True, 0.07, blocks, name="mla_flash_fwd", interpret=True)
        want = flash_attention_reference(q, k, v, True, 0.07)
        assert got.dtype == jnp.bfloat16 and got.shape == (2, 1024, 128)
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_blocks_at_the_cell_shape(self):
        """32 heads of 32,768 tokens at 192 / 128 in bfloat16: since the step walks its
        block in (256, 512) sub-tiles, (1024, 1024) fits Mosaic's 16 MiB default scope
        (tests/test_kernels.py compiles it for a described v5e); 32 x 32 pairs lie
        inside the SMEM bound. float64 and ragged lengths: none."""
        q = jax.ShapeDtypeStruct((32, 32768, 192), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((32, 32768, 128), jnp.bfloat16)
        assert forward_blocks(q, q, v) == (1024, 1024)
        ragged = jax.ShapeDtypeStruct((32, 32767, 192), jnp.bfloat16)
        assert forward_blocks(ragged, ragged, jax.ShapeDtypeStruct((32, 32767, 128), jnp.bfloat16)) is None
        wide = jax.ShapeDtypeStruct((1, 1024, 192), jnp.float64)
        assert forward_blocks(wide, wide, wide) is None

    def test_gradient_is_refused(self):
        """The backward kernels take one head width: the custom_vjp's backward says so."""
        from heat_tpu.core.kernels.flash_attention import _bwd

        q = jnp.ones((1, 512, 64), jnp.float32)
        v = jnp.ones((1, 512, 128), jnp.float32)
        residuals = (q, q, v, v, jnp.ones((1, 512), jnp.float32), None)
        with pytest.raises(NotImplementedError, match="one head width"):
            _bwd(True, 0.1, None, residuals, v)


class TestSubTiledForward:
    """The forward walks a (bq, bk) block as (bq / br) x (bk / bs) sub-tiles in one
    region (PR 30). Blocks (256, 512) with sub-tiles (128, 128): the straddling block
    of query rows 256..511 has, for its first row chunk, two key sub-tiles wholly
    below the diagonal, one on it and one wholly above."""

    BLOCKS, SUB = (256, 512), (128, 128)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    @pytest.mark.parametrize("widths", [(192, 128), (64, 64), (128, 64)])
    def test_interpret_parity(self, widths, dtype, causal):
        d, dv = widths
        rng = np.random.default_rng(31)
        q = jnp.array(rng.standard_normal((2, 1024, d)), dtype)
        k = jnp.array(rng.standard_normal((2, 1024, d)), dtype)
        v = jnp.array(rng.standard_normal((2, 1024, dv)), dtype)
        with jax.default_matmul_precision("highest"):
            got, lse = _flash_pallas(q, k, v, causal, 0.07, *self.BLOCKS, interpret=True,
                                     sub=self.SUB)
            whole, lse_whole = _flash_pallas(q, k, v, causal, 0.07, *self.BLOCKS,
                                             interpret=True, sub=self.BLOCKS)
            want = flash_attention_reference(q, k, v, causal, 0.07)
        assert got.shape == want.shape == (2, 1024, dv) and got.dtype == dtype
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)
        # the residual the backward reads does not depend on how the block is walked
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_whole), rtol=1e-5, atol=1e-5)

    def test_a_sub_tile_above_the_diagonal_reaches_nothing(self):
        """NaN keys and values from position 640 on. Query rows 512..639 are the first
        row chunk of the block pair (2, 1), whose key sub-tiles from 640 on lie wholly
        above the diagonal: a sub-tile that was computed and masked would still carry
        0 x NaN into the accumulator; one that is skipped carries nothing."""
        rng = np.random.default_rng(32)
        q, k, v = (jnp.array(rng.standard_normal((1, 1024, 64)), jnp.float32) for _ in range(3))
        poisoned_k = k.at[:, 640:].set(jnp.nan)
        poisoned_v = v.at[:, 640:].set(jnp.nan)
        clean, _ = _flash_pallas(q, k, v, True, 0.125, *self.BLOCKS, interpret=True, sub=self.SUB)
        got, lse = _flash_pallas(q, poisoned_k, poisoned_v, True, 0.125, *self.BLOCKS,
                                 interpret=True, sub=self.SUB)
        assert bool(jnp.all(jnp.isfinite(got[:, :640]))) and bool(jnp.all(jnp.isfinite(lse[:, :640])))
        np.testing.assert_allclose(np.asarray(got[:, :640]), np.asarray(clean[:, :640]),
                                   rtol=1e-6, atol=1e-6)

    def test_the_two_counters_count_traces(self):
        """``kernels.flash.fwd.overlapped`` / ``.serial``: one a trace, by the schedule the
        trace took; a second call of a traced shape counts nothing."""
        import heat_tpu as ht

        q = jnp.ones((1, 1024, 32), jnp.float32)
        was_on = ht.diagnostics.enabled()
        ht.diagnostics.enable()
        ht.diagnostics.reset()
        try:
            def counts():
                c = ht.diagnostics.report()["counters"]
                return (c.get("kernels.flash.fwd.overlapped", 0), c.get("kernels.flash.fwd.serial", 0))

            # a scale no other test uses: these are fresh traces whatever ran before
            _flash_pallas(q, q, q, True, 0.3125, 512, 1024, interpret=True)  # (256, 512) by rule
            assert counts() == (1, 0)
            _flash_pallas(q, q, q, True, 0.3125, 512, 1024, interpret=True)
            assert counts() == (1, 0)
            _flash_pallas(q, q, q, True, 0.3125, 128, 128, interpret=True)  # walked whole
            assert counts() == (1, 1)
            _flash_pallas(q, q, q, True, 0.3125, 128, 128, interpret=True)
            assert counts() == (1, 1)
        finally:
            ht.diagnostics.reset()
            if not was_on:
                ht.diagnostics.disable()


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_bwd_interpret_parity(self, causal):
        """Pallas backward (dq, dk, dv) matches autodiff of the dense reference."""
        from heat_tpu.core.kernels.flash_attention import _flash_bwd_pallas

        rng = np.random.default_rng(3)
        shape = (1, 2, 1024, 64)
        q, k, v = (jnp.array(rng.standard_normal(shape), jnp.float32) for _ in range(3))
        g = jnp.array(rng.standard_normal(shape), jnp.float32)
        scale = float(1.0 / np.sqrt(shape[-1]))

        out, lse = _flash_pallas(q, k, v, causal, scale, 512, 512, interpret=True)
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, out, g, lse, causal, scale, 512, 512, interpret=True
        )
        _, vjp = jax.vjp(lambda a, b, c: flash_attention_reference(a, b, c, causal), q, k, v)
        dq_r, dk_r, dv_r = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_r), rtol=2e-3, atol=2e-3)

    def test_bwd_cross_lengths_interpret(self):
        from heat_tpu.core.kernels.flash_attention import _flash_bwd_pallas

        rng = np.random.default_rng(4)
        q = jnp.array(rng.standard_normal((1, 1, 512, 64)), jnp.float32)
        k = jnp.array(rng.standard_normal((1, 1, 1024, 64)), jnp.float32)
        v = jnp.array(rng.standard_normal((1, 1, 1024, 64)), jnp.float32)
        g = jnp.array(rng.standard_normal((1, 1, 512, 64)), jnp.float32)
        scale = 0.125
        out, lse = _flash_pallas(q, k, v, False, scale, 512, 512, interpret=True)
        dq, dk, dv = _flash_bwd_pallas(q, k, v, out, g, lse, False, scale, 512, 512, interpret=True)
        _, vjp = jax.vjp(lambda a, b, c: flash_attention_reference(a, b, c, False, scale), q, k, v)
        dq_r, dk_r, dv_r = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_r), rtol=2e-3, atol=2e-3)

    def test_bwd_causal_longer_keys_zero_grads(self):
        """Causal with Tk > Tq: k-blocks past the last query get exactly-zero
        dk/dv (regression: the kv pair schedule skipped those blocks entirely,
        leaving the output buffer uninitialized)."""
        from heat_tpu.core.kernels.flash_attention import _flash_bwd_pallas

        rng = np.random.default_rng(5)
        q = jnp.array(rng.standard_normal((1, 1, 512, 64)), jnp.float32)
        k = jnp.array(rng.standard_normal((1, 1, 2048, 64)), jnp.float32)
        v = jnp.array(rng.standard_normal((1, 1, 2048, 64)), jnp.float32)
        g = jnp.array(rng.standard_normal((1, 1, 512, 64)), jnp.float32)
        scale = 0.125
        out, lse = _flash_pallas(q, k, v, True, scale, 512, 512, interpret=True)
        dq, dk, dv = _flash_bwd_pallas(q, k, v, out, g, lse, True, scale, 512, 512, interpret=True)
        _, vjp = jax.vjp(lambda a, b, c: flash_attention_reference(a, b, c, True, scale), q, k, v)
        dq_r, dk_r, dv_r = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_r), rtol=2e-3, atol=2e-3)
        # keys 512.. see no queries: exact zeros, not garbage
        assert float(jnp.max(jnp.abs(dk[:, :, 512:]))) == 0.0
        assert float(jnp.max(jnp.abs(dv[:, :, 512:]))) == 0.0

    def test_block_picker_falls_back_to_512(self):
        """512-multiple (but not 1024-multiple) shapes keep the flash path via
        the smaller block config instead of silently dropping to the XLA path."""
        from heat_tpu.core.kernels.flash_attention import _fwd_blocks

        assert _fwd_blocks(jnp.bfloat16, 4096, 4096) == (1024, 1024)
        assert _fwd_blocks(jnp.bfloat16, 1536, 1536) == (512, 512)
        assert _fwd_blocks(jnp.bfloat16, 512, 1024) == (512, 1024)
        assert _fwd_blocks(jnp.float32, 4096, 4096) == (512, 1024)
        assert _fwd_blocks(jnp.float32, 512, 512) == (512, 512)
        q = jnp.zeros((1, 1, 1536, 64), jnp.bfloat16)
        assert use_flash(q, q, q, None, interpret=True)

    def test_pair_budget_rejects_extreme_schedules(self):
        """The flattened pair schedule is O((T/b)²) SMEM entries; beyond the
        budget the gate must fall back rather than ship multi-MB prefetch
        arrays."""
        q = jnp.zeros((1, 1, 1 << 21, 64), jnp.bfloat16)
        assert not use_flash(q, q, q, None, interpret=True)

    def test_mask_bwd_parity_interpret(self):
        from heat_tpu.core.kernels.flash_attention import (
            _flash_bwd_pallas,
            _as_bias,
        )
        from heat_tpu.nn.attention import _dense_attention

        rng = np.random.default_rng(10)
        shape = (1, 1, 512, 64)
        q, k, v = (jnp.array(rng.standard_normal(shape), jnp.float32) for _ in range(3))
        g = jnp.array(rng.standard_normal(shape), jnp.float32)
        mask = jnp.array(rng.random((512, 512)) > 0.25)
        scale = 0.125
        bias = _as_bias(mask)
        out, lse = _flash_pallas(q, k, v, False, scale, 512, 512, interpret=True, bias=bias)
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, out, g, lse, False, scale, 512, 512, interpret=True, bias=bias
        )
        _, vjp = jax.vjp(
            lambda a, b, c: _dense_attention(a, b, c, mask=mask, scale=scale), q, k, v
        )
        dq_r, dk_r, dv_r = vjp(g)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(dq_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(dk_r), rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(dv_r), rtol=2e-3, atol=2e-3)

    def test_mask_gating(self):
        """2-D (Tq, Tk) masks keep the flash path; per-batch masks fall back."""
        q = jnp.zeros((1, 2, 1024, 64), jnp.float32)
        mask2d = jnp.zeros((1024, 1024), jnp.bool_)
        assert use_flash(q, q, q, mask2d, interpret=True)
        mask4d = jnp.zeros((1, 2, 1024, 1024), jnp.bool_)
        assert not use_flash(q, q, q, mask4d, interpret=True)
        assert not use_flash(q, q, q, jnp.zeros((1024, 512), jnp.bool_), interpret=True)
        # float biases have a gradient only the XLA path computes -> rejected here
        assert not use_flash(q, q, q, jnp.zeros((1024, 1024), jnp.float32), interpret=True)

    def test_lse_matches_reference(self):
        rng = np.random.default_rng(5)
        q, k, v = (jnp.array(rng.standard_normal((1, 1, 512, 64)), jnp.float32) for _ in range(3))
        _, lse = _flash_pallas(q, k, v, False, 0.125, 512, 512, interpret=True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.125
        want = jax.nn.logsumexp(s, axis=-1)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want), rtol=1e-4, atol=1e-4)


class TestTracedScale:
    def test_traced_scale_falls_back_to_xla(self):
        """A traced scale can't be the kernel's static arg — gate must reject it,
        and sdpa must still produce the right answer under jit."""
        from heat_tpu.nn.attention import scaled_dot_product_attention as sdpa

        q = jnp.zeros((1, 1, 1024, 64), jnp.float32)
        assert not use_flash(q, q, q, None, scale=jnp.float32(0.125), interpret=True)
        assert use_flash(q, q, q, None, scale=0.125, interpret=True)

        rng = np.random.default_rng(6)
        qv = jnp.array(rng.standard_normal((1, 1, 64, 16)), jnp.float32)
        want = sdpa(qv, qv, qv, scale=0.25)
        got = jax.jit(lambda a, s: sdpa(a, a, a, scale=s))(qv, jnp.float32(0.25))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


class TestProductionVJPPath:
    def test_custom_vjp_interpret_parity(self, monkeypatch):
        """The shipped flash_attention custom_vjp (512-block fwd, 256-block bwd)
        must produce dense-reference gradients — covers the defvjp wiring and the
        mixed fwd/bwd block configuration, not just the kernels in isolation."""
        from heat_tpu.core.kernels import flash_attention as fa

        # route the production entry points through interpret mode on CPU
        real_fwd, real_bwd = fa._flash_pallas, fa._flash_bwd_pallas
        monkeypatch.setattr(
            fa, "_flash_pallas",
            lambda *a, **kw: real_fwd(*a, **{**kw, "interpret": True}))
        monkeypatch.setattr(
            fa, "_flash_bwd_pallas",
            lambda *a, **kw: real_bwd(*a, **{**kw, "interpret": True}))

        rng = np.random.default_rng(7)
        q, k, v = (
            jnp.array(rng.standard_normal((1, 2, 1024, 64)), jnp.float32) for _ in range(3)
        )
        gf = jax.grad(
            lambda a, b, c: jnp.sum(fa.flash_attention(a, b, c, True) ** 2), argnums=(0, 1, 2)
        )(q, k, v)
        gr = jax.grad(
            lambda a, b, c: jnp.sum(flash_attention_reference(a, b, c, True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)
