"""Parity tests for the overlapped flash forward: a grid step walks its (bq, bk) block
as (bq / br) x (bk / bs) sub-tiles in one region, so that one sub-tile's contractions
issue under another's exp pass — see doc/source/flash_attention_perf.rst. These are the
shapes that guarded the one-step-skewed kernel this form replaced (PR 30); the sub-tile
is passed explicitly, since the blocks that fit these lengths are walked whole by rule."""

import unittest

import numpy as np

import jax
import jax.numpy as jnp

from heat_tpu.core.kernels import flash_attention as fa


class TestOverlappedFlashParity(unittest.TestCase):
    def run_case(self, b, h, tq, tk, d, causal, dtype, bq=256, bk=256, sub=(128, 128)):
        rng = np.random.default_rng(hash((b, h, tq, tk, d, causal)) % 2**32)
        q = jnp.asarray(rng.standard_normal((b, h, tq, d)), dtype)
        k = jnp.asarray(rng.standard_normal((b, h, tk, d)), dtype)
        v = jnp.asarray(rng.standard_normal((b, h, tk, d)), dtype)
        scale = float(1.0 / np.sqrt(d))
        out, lse = fa._flash_pallas(q, k, v, causal, scale, bq, bk,
                                    interpret=True, sub=sub)
        want = fa.flash_attention_reference(q, k, v, causal=causal)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            rtol=tol, atol=tol,
        )
        # the sub-tiled and the whole-block walk must agree on the LSE residual the
        # backward consumes
        _, lse0 = fa._flash_pallas(q, k, v, causal, scale, bq, bk,
                                   interpret=True, sub=(bq, bk))
        np.testing.assert_allclose(np.asarray(lse), np.asarray(lse0),
                                   rtol=1e-5, atol=1e-5)

    def test_causal_square(self):
        self.run_case(1, 2, 512, 512, 64, True, jnp.float32)

    def test_noncausal_square(self):
        self.run_case(1, 2, 512, 512, 64, False, jnp.float32)

    def test_cross_length_bf16(self):
        self.run_case(2, 1, 256, 512, 32, True, jnp.bfloat16)

    def test_single_pair_rows(self):
        # bq == tq, bk == tk: each row sweep is one step, first and last at once, and
        # the step's two key sub-tiles carry the whole recurrence
        self.run_case(1, 1, 128, 256, 32, True, jnp.float32, bq=128, bk=256)

    def test_bias_stream(self):
        rng = np.random.default_rng(5)
        t, d = 512, 64
        q = jnp.asarray(rng.standard_normal((1, 2, t, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 2, t, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 2, t, d)), jnp.float32)
        bias = jnp.where(
            jnp.asarray(rng.random((t, t)) > 0.2), 0.0, -1e30
        ).astype(jnp.float32)
        out, _ = fa._flash_pallas(q, k, v, False, 0.125, 256, 256,
                                  interpret=True, bias=bias, sub=(128, 128))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.125 + bias
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_schedule_invariants(self):
        for nq, nk, causal in [(4, 4, True), (4, 4, False), (2, 6, True), (1, 1, True)]:
            im, jm, fl = fa._pair_schedule(nq, nk, 128, 128, causal)
            # every row sweep starts once and ends once, in row order; the causal list
            # holds the blocks at or below the diagonal and masks those on it
            self.assertEqual(int((fl & 1 != 0).sum()), nq)
            self.assertEqual(int((fl & 2 != 0).sum()), nq)
            self.assertTrue((np.diff(im) >= 0).all())
            self.assertEqual(len(im), sum(min(i + 1, nk) for i in range(nq)) if causal else nq * nk)
            np.testing.assert_array_equal(fl & 4 != 0, (im == jm) if causal else np.zeros(len(im), bool))
        # the sub-tile is read off the block: what it divides and exceeds, it splits
        self.assertEqual(fa._sub_tiles(1024, 1024), (256, 512))
        self.assertEqual(fa._sub_tiles(512, 1024), (256, 512))
        self.assertEqual(fa._sub_tiles(512, 512), (256, 512))
        self.assertEqual(fa._sub_tiles(256, 384), (256, 384))
        self.assertEqual(fa._sub_tiles(128, 128), (128, 128))


if __name__ == "__main__":
    unittest.main()
