"""Sequence-parallel attention tests.

The ring schedule must be bit-for-bit-ish (fp32 accumulation) equivalent to dense
attention; MultiheadAttention must match torch.nn.MultiheadAttention with identical
weights. These run on the forced 8-device CPU mesh like everything else.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P
from functools import partial

import heat_tpu as ht
from heat_tpu.nn.attention import (
    MultiheadAttention,
    ring_attention,
    scaled_dot_product_attention,
    ulysses_attention,
    _dense_attention,
)


def _ref_attention(q, k, v, is_causal=False):
    """Plain numpy softmax attention, f64."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    scores = q @ np.swapaxes(k, -1, -2) / np.sqrt(q.shape[-1])
    if is_causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        # top-left aligned (position i attends keys <= i), matching torch sdpa
        mask = np.tril(np.ones((tq, tk), bool))
        scores = np.where(mask, scores, -np.inf)
    scores -= scores.max(-1, keepdims=True)
    p = np.exp(scores)
    p /= p.sum(-1, keepdims=True)
    return p @ v


class TestDenseSDPA:
    def test_matches_numpy(self):
        rng = np.random.default_rng(0)
        q = rng.standard_normal((2, 3, 16, 8), np.float32)
        k = rng.standard_normal((2, 3, 16, 8), np.float32)
        v = rng.standard_normal((2, 3, 16, 8), np.float32)
        out = scaled_dot_product_attention(jnp.array(q), jnp.array(k), jnp.array(v))
        np.testing.assert_allclose(np.asarray(out), _ref_attention(q, k, v), rtol=2e-5, atol=2e-5)

    def test_causal(self):
        rng = np.random.default_rng(1)
        q = rng.standard_normal((1, 2, 12, 4), np.float32)
        k = rng.standard_normal((1, 2, 12, 4), np.float32)
        v = rng.standard_normal((1, 2, 12, 4), np.float32)
        out = scaled_dot_product_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), is_causal=True
        )
        np.testing.assert_allclose(
            np.asarray(out), _ref_attention(q, k, v, is_causal=True), rtol=2e-5, atol=2e-5
        )

    def test_additive_and_bool_masks(self):
        rng = np.random.default_rng(2)
        q = rng.standard_normal((1, 1, 6, 4), np.float32)
        k = rng.standard_normal((1, 1, 6, 4), np.float32)
        v = rng.standard_normal((1, 1, 6, 4), np.float32)
        keep = np.triu(np.ones((6, 6), bool))
        out_bool = scaled_dot_product_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), attn_mask=jnp.array(keep)
        )
        add = np.where(keep, 0.0, -1e30).astype(np.float32)
        out_add = scaled_dot_product_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), attn_mask=jnp.array(add)
        )
        np.testing.assert_allclose(np.asarray(out_bool), np.asarray(out_add), rtol=1e-5, atol=1e-5)

    def test_dndarray_mask(self):
        """attn_mask given as a DNDarray is unwrapped like the other operands."""
        rng = np.random.default_rng(12)
        q = rng.standard_normal((1, 1, 6, 4), np.float32)
        keep = np.triu(np.ones((6, 6), bool))
        want = scaled_dot_product_attention(
            jnp.array(q), jnp.array(q), jnp.array(q), attn_mask=jnp.array(keep)
        )
        got = scaled_dot_product_attention(
            ht.array(q), ht.array(q), ht.array(q), attn_mask=ht.array(keep)
        )
        np.testing.assert_allclose(got.numpy(), np.asarray(want), rtol=1e-5, atol=1e-6)

    def test_mha_bool_mask_torch_convention(self):
        """torch.nn.MultiheadAttention bool attn_mask means True = NOT allowed —
        the inverse of sdpa's convention; ours must match torch's module."""
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(21)
        E, H, T, B = 16, 4, 6, 2
        x = rng.standard_normal((B, T, E)).astype(np.float32)
        tm = torch.nn.MultiheadAttention(E, H, batch_first=True, bias=True)
        hm = ht.nn.MultiheadAttention(E, H, batch_first=True, bias=True)
        sd = tm.state_dict()
        hm.params["in_proj_weight"] = jnp.asarray(sd["in_proj_weight"].numpy())
        hm.params["in_proj_bias"] = jnp.asarray(sd["in_proj_bias"].numpy())
        hm.params["out_proj_weight"] = jnp.asarray(sd["out_proj.weight"].numpy())
        hm.params["out_proj_bias"] = jnp.asarray(sd["out_proj.bias"].numpy())
        not_allowed = np.triu(np.ones((T, T), bool), k=1)
        t_out, _ = tm(
            torch.tensor(x), torch.tensor(x), torch.tensor(x),
            attn_mask=torch.tensor(not_allowed), need_weights=False,
        )
        h_out, _ = hm(ht.array(x), attn_mask=jnp.asarray(not_allowed))
        np.testing.assert_allclose(
            h_out.numpy(), t_out.detach().numpy(), rtol=1e-5, atol=1e-5
        )

    def test_mha_key_padding_mask_torch_parity(self):
        """torch key_padding_mask: (B, S) True = ignore that key for all queries."""
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(22)
        E, H, T, B = 16, 4, 6, 2
        x = rng.standard_normal((B, T, E)).astype(np.float32)
        tm = torch.nn.MultiheadAttention(E, H, batch_first=True, bias=True)
        hm = ht.nn.MultiheadAttention(E, H, batch_first=True, bias=True)
        sd = tm.state_dict()
        hm.params["in_proj_weight"] = jnp.asarray(sd["in_proj_weight"].numpy())
        hm.params["in_proj_bias"] = jnp.asarray(sd["in_proj_bias"].numpy())
        hm.params["out_proj_weight"] = jnp.asarray(sd["out_proj.weight"].numpy())
        hm.params["out_proj_bias"] = jnp.asarray(sd["out_proj.bias"].numpy())
        kpm = np.zeros((B, T), bool)
        kpm[0, 4:] = True  # first example: last two keys are padding
        kpm[1, 5:] = True
        t_out, _ = tm(
            torch.tensor(x), torch.tensor(x), torch.tensor(x),
            key_padding_mask=torch.tensor(kpm), need_weights=False,
        )
        h_out, _ = hm(ht.array(x), key_padding_mask=jnp.asarray(kpm))
        np.testing.assert_allclose(
            h_out.numpy(), t_out.detach().numpy(), rtol=1e-5, atol=1e-5
        )
        # combined with a bool attn_mask (both in torch conventions)
        not_allowed = np.triu(np.ones((T, T), bool), k=1)
        t_out2, _ = tm(
            torch.tensor(x), torch.tensor(x), torch.tensor(x),
            attn_mask=torch.tensor(not_allowed),
            key_padding_mask=torch.tensor(kpm), need_weights=False,
        )
        h_out2, _ = hm(
            ht.array(x), attn_mask=jnp.asarray(not_allowed),
            key_padding_mask=jnp.asarray(kpm),
        )
        np.testing.assert_allclose(
            h_out2.numpy(), t_out2.detach().numpy(), rtol=1e-5, atol=1e-5
        )

    def test_sdpa_gqa_and_dropout(self):
        """torch-signature extras: enable_gqa broadcasts grouped kv heads (exact
        torch parity); dropout_p keeps the output an unbiased estimate."""
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(30)
        B, Hq, Hkv, T, D = 2, 8, 2, 6, 4
        q = rng.standard_normal((B, Hq, T, D)).astype(np.float32)
        k = rng.standard_normal((B, Hkv, T, D)).astype(np.float32)
        v = rng.standard_normal((B, Hkv, T, D)).astype(np.float32)
        got = scaled_dot_product_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), is_causal=True, enable_gqa=True
        )
        want = torch.nn.functional.scaled_dot_product_attention(
            torch.tensor(q), torch.tensor(k), torch.tensor(v),
            is_causal=True, enable_gqa=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), want.numpy(), rtol=1e-5, atol=1e-5
        )
        bad_k = rng.standard_normal((B, 3, T, D)).astype(np.float32)  # 3 ∤ 8
        with pytest.raises(ValueError):
            scaled_dot_product_attention(
                jnp.array(q), jnp.array(bad_k), jnp.array(bad_k), enable_gqa=True
            )
        # dropout: mean over many keys approximates the dropless output; p=0.5
        # halves kept weights and rescales, so row sums of weights stay ~1 in
        # expectation — check unbiasedness loosely via the mean over seeds
        import jax as _jax

        base = scaled_dot_product_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                                            enable_gqa=True)
        outs = [
            np.asarray(
                scaled_dot_product_attention(
                    jnp.array(q), jnp.array(k), jnp.array(v), enable_gqa=True,
                    dropout_p=0.3, dropout_key=_jax.random.key(s),
                )
            )
            for s in range(30)
        ]
        diff = np.abs(np.mean(outs, axis=0) - np.asarray(base))
        # a 30-seed mean is a high-variance estimate for rows dominated by one
        # key; check the distribution, not the worst element
        assert np.median(diff) < 0.1, np.median(diff)
        assert np.mean(diff) < 0.15, np.mean(diff)
        with pytest.raises(ValueError):
            scaled_dot_product_attention(
                jnp.array(q), jnp.array(k), jnp.array(v), dropout_p=0.5,
                enable_gqa=True,
            )
        # torch accepts dropout_p=1.0 (every weight dropped -> all-zero output)
        full = scaled_dot_product_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), enable_gqa=True,
            dropout_p=1.0, dropout_key=_jax.random.key(0),
        )
        assert full.shape == q.shape[:-1] + (v.shape[-1],)
        np.testing.assert_array_equal(np.asarray(full), 0.0)
        with pytest.raises(ValueError):
            scaled_dot_product_attention(
                jnp.array(q), jnp.array(k), jnp.array(v), dropout_p=1.5,
                enable_gqa=True, dropout_key=_jax.random.key(0),
            )

    def test_mha_kdim_vdim_torch_parity(self):
        """torch's separate-projection path: kdim/vdim != embed_dim uses
        q/k/v_proj_weight params under torch's exact names."""
        torch = pytest.importorskip("torch")
        import heat_tpu as ht

        rng = np.random.default_rng(33)
        B, Tq, Tk, E, H, KD, VD = 2, 5, 7, 8, 2, 12, 6
        q = rng.standard_normal((B, Tq, E)).astype(np.float32)
        k = rng.standard_normal((B, Tk, KD)).astype(np.float32)
        v = rng.standard_normal((B, Tk, VD)).astype(np.float32)
        tm = torch.nn.MultiheadAttention(E, H, kdim=KD, vdim=VD, batch_first=True)
        hm = ht.nn.MultiheadAttention(E, H, kdim=KD, vdim=VD)
        sd = tm.state_dict()
        hm.params["q_proj_weight"] = jnp.asarray(sd["q_proj_weight"].numpy())
        hm.params["k_proj_weight"] = jnp.asarray(sd["k_proj_weight"].numpy())
        hm.params["v_proj_weight"] = jnp.asarray(sd["v_proj_weight"].numpy())
        hm.params["in_proj_bias"] = jnp.asarray(sd["in_proj_bias"].numpy())
        hm.params["out_proj_weight"] = jnp.asarray(sd["out_proj.weight"].numpy())
        hm.params["out_proj_bias"] = jnp.asarray(sd["out_proj.bias"].numpy())
        t_out, _ = tm(torch.tensor(q), torch.tensor(k), torch.tensor(v),
                      need_weights=False)
        h_out, _ = hm(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        np.testing.assert_allclose(
            np.asarray(h_out), t_out.detach().numpy(), rtol=1e-5, atol=1e-5
        )
        # init produces the torch param-name set
        fresh = hm.init(jax.random.key(0)) if hasattr(hm, "init") else {}
        assert {"q_proj_weight", "k_proj_weight", "v_proj_weight"} <= set(fresh)

    def test_mha_dropout(self):
        """torch semantics: dropout only in train mode; eval __call__ never drops;
        train mode needs an explicit PRNG key; dropless train == eval."""
        import heat_tpu as ht
        import jax as _jax

        rng = np.random.default_rng(31)
        B, T, E, H = 2, 6, 8, 2
        x = jnp.array(rng.standard_normal((B, T, E)).astype(np.float32))
        mha = ht.nn.MultiheadAttention(E, H, dropout=0.5)
        params = mha.params
        eval_out, _ = mha(x)
        # train w/o key raises; with key drops (differs from eval and across keys)
        with pytest.raises(ValueError):
            mha.apply(params, x, train=True)
        t1 = mha.apply(params, x, train=True, key=_jax.random.key(1))
        t2 = mha.apply(params, x, train=True, key=_jax.random.key(2))
        assert not np.allclose(np.asarray(t1), np.asarray(eval_out))
        assert not np.allclose(np.asarray(t1), np.asarray(t2))
        # train=False ignores dropout entirely
        np.testing.assert_array_equal(
            np.asarray(mha.apply(params, x)), np.asarray(eval_out)
        )
        with pytest.raises(ValueError):
            ht.nn.MultiheadAttention(E, H, dropout=-0.1)
        # torch-style __call__ honors train()/bound context: .train() without a
        # key fails loudly (no silent no-drop); a bound _ctx (what a parent
        # apply(..., train=True, key=...) installs) activates dropout
        mha.train()
        with pytest.raises(ValueError):
            mha(x)
        mha._ctx = (_jax.random.key(3), True)
        bound_out, _ = mha(x)
        assert not np.allclose(np.asarray(bound_out), np.asarray(eval_out))
        del mha._ctx
        mha.eval()
        again, _ = mha(x)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(eval_out))

    def test_torch_sdpa_parity(self):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(3)
        q = rng.standard_normal((2, 4, 10, 8), np.float32)
        k = rng.standard_normal((2, 4, 10, 8), np.float32)
        v = rng.standard_normal((2, 4, 10, 8), np.float32)
        want = torch.nn.functional.scaled_dot_product_attention(
            torch.tensor(q), torch.tensor(k), torch.tensor(v), is_causal=True
        ).numpy()
        got = scaled_dot_product_attention(
            jnp.array(q), jnp.array(k), jnp.array(v), is_causal=True
        )
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


class TestRingAttention:
    def _run_ring(self, q, k, v, is_causal):
        comm = ht.get_comm()
        mesh, axis = comm.mesh, comm.axis_name
        spec = P(None, None, axis, None)
        fn = shard_map(
            partial(ring_attention, axis_name=axis, is_causal=is_causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
        return fn(jnp.array(q), jnp.array(k), jnp.array(v))

    @pytest.mark.parametrize("is_causal", [False, True])
    def test_matches_dense(self, is_causal):
        rng = np.random.default_rng(4)
        n = ht.get_comm().size
        t = 8 * n
        q = rng.standard_normal((2, 2, t, 8), np.float32)
        k = rng.standard_normal((2, 2, t, 8), np.float32)
        v = rng.standard_normal((2, 2, t, 8), np.float32)
        out = self._run_ring(q, k, v, is_causal)
        np.testing.assert_allclose(
            np.asarray(out), _ref_attention(q, k, v, is_causal=is_causal), rtol=2e-4, atol=2e-4
        )

    def test_grad_matches_dense(self):
        rng = np.random.default_rng(5)
        n = ht.get_comm().size
        t = 4 * n
        q = jnp.array(rng.standard_normal((1, 2, t, 4), np.float32))
        k = jnp.array(rng.standard_normal((1, 2, t, 4), np.float32))
        v = jnp.array(rng.standard_normal((1, 2, t, 4), np.float32))
        comm = ht.get_comm()
        spec = P(None, None, comm.axis_name, None)
        ring = shard_map(
            partial(ring_attention, axis_name=comm.axis_name, is_causal=True),
            mesh=comm.mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
        g_ring = jax.grad(lambda a, b, c: jnp.sum(ring(a, b, c) ** 2), argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(
            lambda a, b, c: jnp.sum(_dense_attention(a, b, c, is_causal=True) ** 2),
            argnums=(0, 1, 2),
        )(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd), rtol=2e-4, atol=2e-4)

    def test_dndarray_dispatch(self):
        """sdpa on sequence-split DNDarrays runs the ring and matches dense."""
        rng = np.random.default_rng(6)
        n = ht.get_comm().size
        t = 4 * n
        q = rng.standard_normal((2, 2, t, 8), np.float32)
        k = rng.standard_normal((2, 2, t, 8), np.float32)
        v = rng.standard_normal((2, 2, t, 8), np.float32)
        hq = ht.array(q, split=2)
        hk = ht.array(k, split=2)
        hv = ht.array(v, split=2)
        out = scaled_dot_product_attention(hq, hk, hv, is_causal=True)
        assert isinstance(out, ht.DNDarray) and out.split == 2
        np.testing.assert_allclose(
            out.numpy(), _ref_attention(q, k, v, is_causal=True), rtol=2e-4, atol=2e-4
        )

    def test_zigzag_ring_causal_parity(self):
        """Zigzag causal ring (balanced chunk assignment, half the plain ring's
        FLOPs) matches dense causal attention after the layout round-trip."""
        import jax
        from functools import partial
        from jax.sharding import PartitionSpec as P

        if len(jax.devices()) < 2:
            pytest.skip("needs a distributed mesh")
        from heat_tpu.nn.attention import (
            _dense_attention,
            ring_attention_zigzag,
            zigzag_inverse,
            zigzag_order,
        )

        comm = ht.get_comm()
        p_ = comm.size
        B, H, T, D = 2, 2, 8 * p_, 8
        rng = np.random.default_rng(13)
        q = rng.standard_normal((B, H, T, D)).astype(np.float32)
        k = rng.standard_normal((B, H, T, D)).astype(np.float32)
        v = rng.standard_normal((B, H, T, D)).astype(np.float32)
        order, inv = zigzag_order(T, p_), zigzag_inverse(T, p_)
        assert np.array_equal(order[inv], np.arange(T))
        spec = P(None, None, comm.axis_name, None)
        fn = jax.jit(
            jax.shard_map(
                partial(ring_attention_zigzag, axis_name=comm.axis_name),
                mesh=comm.mesh, in_specs=(spec, spec, spec), out_specs=spec,
            )
        )
        qz, kz, vz = (jnp.asarray(x[..., order, :]) for x in (q, k, v))
        got = np.asarray(fn(qz, kz, vz))[..., inv, :]
        want = np.asarray(
            _dense_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), is_causal=True)
        )
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


class TestUlyssesAttention:
    @pytest.mark.parametrize("is_causal", [False, True])
    def test_matches_dense(self, is_causal):
        rng = np.random.default_rng(7)
        comm = ht.get_comm()
        n = comm.size
        t, h = 4 * n, n  # heads divisible by mesh size
        q = rng.standard_normal((2, h, t, 8), np.float32)
        k = rng.standard_normal((2, h, t, 8), np.float32)
        v = rng.standard_normal((2, h, t, 8), np.float32)
        spec = P(None, None, comm.axis_name, None)
        fn = shard_map(
            partial(ulysses_attention, axis_name=comm.axis_name, is_causal=is_causal),
            mesh=comm.mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
        out = fn(jnp.array(q), jnp.array(k), jnp.array(v))
        np.testing.assert_allclose(
            np.asarray(out), _ref_attention(q, k, v, is_causal=is_causal), rtol=2e-4, atol=2e-4
        )


class TestMultiheadAttention:
    def test_torch_parity_self_attention(self):
        torch = pytest.importorskip("torch")
        e, h = 16, 4
        mha = MultiheadAttention(e, h)
        mha.reset_parameters(seed=0)
        tm = torch.nn.MultiheadAttention(e, h, batch_first=True)
        with torch.no_grad():
            tm.in_proj_weight.copy_(torch.tensor(np.asarray(mha.params["in_proj_weight"])))
            tm.in_proj_bias.copy_(torch.tensor(np.asarray(mha.params["in_proj_bias"])))
            tm.out_proj.weight.copy_(torch.tensor(np.asarray(mha.params["out_proj_weight"])))
            tm.out_proj.bias.copy_(torch.tensor(np.asarray(mha.params["out_proj_bias"])))
        rng = np.random.default_rng(8)
        x = rng.standard_normal((2, 6, e), np.float32)
        want, _ = tm(torch.tensor(x), torch.tensor(x), torch.tensor(x), need_weights=False)
        got, _ = mha(jnp.array(x))
        np.testing.assert_allclose(np.asarray(got), want.detach().numpy(), rtol=2e-5, atol=2e-5)

    def test_torch_parity_cross_attention(self):
        torch = pytest.importorskip("torch")
        e, h = 8, 2
        mha = MultiheadAttention(e, h)
        mha.reset_parameters(seed=1)
        tm = torch.nn.MultiheadAttention(e, h, batch_first=True)
        with torch.no_grad():
            tm.in_proj_weight.copy_(torch.tensor(np.asarray(mha.params["in_proj_weight"])))
            tm.in_proj_bias.copy_(torch.tensor(np.asarray(mha.params["in_proj_bias"])))
            tm.out_proj.weight.copy_(torch.tensor(np.asarray(mha.params["out_proj_weight"])))
            tm.out_proj.bias.copy_(torch.tensor(np.asarray(mha.params["out_proj_bias"])))
        rng = np.random.default_rng(9)
        q = rng.standard_normal((1, 5, e), np.float32)
        kv = rng.standard_normal((1, 7, e), np.float32)
        want, _ = tm(torch.tensor(q), torch.tensor(kv), torch.tensor(kv), need_weights=False)
        got, _ = mha(jnp.array(q), jnp.array(kv), jnp.array(kv))
        np.testing.assert_allclose(np.asarray(got), want.detach().numpy(), rtol=2e-5, atol=2e-5)

    def test_in_module_system(self):
        """MultiheadAttention participates in Module containers / grad."""
        e = 8
        mha = ht.nn.MultiheadAttention(e, 2)
        params = mha.init(jax.random.key(0))
        x = jnp.ones((2, 4, e), jnp.float32)

        def loss(p):
            return jnp.sum(mha.apply(p, x) ** 2)

        g = jax.grad(loss)(params)
        assert g["in_proj_weight"].shape == (3 * e, e)
        assert bool(jnp.any(g["in_proj_weight"] != 0))

    def test_seq_split_dndarray(self):
        """Self-attention on a batch-split 3-D DNDarray stays correct (dense path:
        the (B,T,E) input's split is the batch axis, not the sequence)."""
        rng = np.random.default_rng(10)
        e = 8
        x = rng.standard_normal((4, 6, e), np.float32)
        mha = ht.nn.MultiheadAttention(e, 2)
        mha.reset_parameters(seed=3)
        want, _ = mha(jnp.array(x))
        got, _ = mha(ht.array(x, split=0))
        np.testing.assert_allclose(got.numpy(), np.asarray(want), rtol=1e-5, atol=1e-5)

    def test_ring_dispatch_on_seq_split(self, monkeypatch):
        """A sequence-split (B,T,E) input routes through the ring schedule, preserves
        the split, and matches the dense result."""
        from heat_tpu.nn import attention as att

        rng = np.random.default_rng(11)
        e = 8
        t = 4 * ht.get_comm().size
        x = rng.standard_normal((2, t, e), np.float32)
        mha = ht.nn.MultiheadAttention(e, 2)
        mha.reset_parameters(seed=4)
        want, _ = mha(jnp.array(x), is_causal=True)

        calls = []
        real = att._ring_sharded
        monkeypatch.setattr(att, "_ring_sharded", lambda *a, **kw: calls.append(1) or real(*a, **kw))
        got, _ = mha(ht.array(x, split=1), is_causal=True)
        assert calls, "sequence-split input did not take the ring path"
        assert isinstance(got, ht.DNDarray) and got.split == 1
        np.testing.assert_allclose(got.numpy(), np.asarray(want), rtol=2e-4, atol=2e-4)


class TestTransformerEncoder:
    @staticmethod
    def _map_params(hm_params, t_layer):
        sd = t_layer.state_dict()
        p = dict(hm_params)
        p["self_attn"] = {
            "in_proj_weight": jnp.asarray(sd["self_attn.in_proj_weight"].numpy()),
            "in_proj_bias": jnp.asarray(sd["self_attn.in_proj_bias"].numpy()),
            "out_proj_weight": jnp.asarray(sd["self_attn.out_proj.weight"].numpy()),
            "out_proj_bias": jnp.asarray(sd["self_attn.out_proj.bias"].numpy()),
        }
        for name in ("linear1", "linear2"):
            p[name] = {
                "weight": jnp.asarray(sd[f"{name}.weight"].numpy()).T,
                "bias": jnp.asarray(sd[f"{name}.bias"].numpy()),
            }
        for name in ("norm1", "norm2"):
            p[name] = {
                "weight": jnp.asarray(sd[f"{name}.weight"].numpy()),
                "bias": jnp.asarray(sd[f"{name}.bias"].numpy()),
            }
        return p

    @pytest.mark.parametrize("norm_first", [False, True])
    @pytest.mark.parametrize("activation", ["relu", "gelu"])
    def test_encoder_layer_torch_parity(self, norm_first, activation):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(40)
        B, T, E, H, FF = 2, 6, 8, 2, 16
        x = rng.standard_normal((B, T, E)).astype(np.float32)
        tl = torch.nn.TransformerEncoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, activation=activation,
            batch_first=True, norm_first=norm_first,
        ).eval()
        hl = ht.nn.TransformerEncoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, activation=activation,
            norm_first=norm_first,
        )
        params = self._map_params(hl.params, tl)
        want = tl(torch.tensor(x)).detach().numpy()
        got = np.asarray(hl.apply(params, jnp.asarray(x)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # causal self-attention path
        want_c = tl(
            torch.tensor(x),
            src_mask=torch.nn.Transformer.generate_square_subsequent_mask(T),
            is_causal=True,
        ).detach().numpy()
        got_c = np.asarray(hl.apply(params, jnp.asarray(x), is_causal=True))
        np.testing.assert_allclose(got_c, want_c, rtol=2e-5, atol=2e-5)

    def test_encoder_stack_torch_parity(self):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(41)
        B, T, E, H, FF, N = 2, 5, 8, 2, 12, 2
        x = rng.standard_normal((B, T, E)).astype(np.float32)
        tl = torch.nn.TransformerEncoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, batch_first=True
        )
        tenc = torch.nn.TransformerEncoder(
            tl, N, norm=torch.nn.LayerNorm(E)
        ).eval()
        henc = ht.nn.TransformerEncoder(
            ht.nn.TransformerEncoderLayer(E, H, dim_feedforward=FF, dropout=0.0),
            N, norm=ht.nn.LayerNorm(E),
        )
        params = dict(henc.params)
        for i, t_layer in enumerate(tenc.layers):
            params[str(i)] = self._map_params(params[str(i)], t_layer)
        nsd = tenc.norm.state_dict()
        params["norm"] = {
            "weight": jnp.asarray(nsd["weight"].numpy()),
            "bias": jnp.asarray(nsd["bias"].numpy()),
        }
        want = tenc(torch.tensor(x)).detach().numpy()
        got = np.asarray(henc.apply(params, jnp.asarray(x)))
        np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)

    def test_encoder_dropout_and_seq_split(self):
        """Dropout needs a key and perturbs outputs; sequence-split DNDarray input
        flows through (ring dispatch inside MHA) and keeps its split."""
        import jax as _jax

        rng = np.random.default_rng(42)
        B, T, E, H = 2, 8, 8, 2
        x = rng.standard_normal((B, T, E)).astype(np.float32)
        hl = ht.nn.TransformerEncoderLayer(E, H, dim_feedforward=16, dropout=0.3)
        base = np.asarray(hl.apply(hl.params, jnp.asarray(x)))
        with pytest.raises(ValueError):
            hl.apply(hl.params, jnp.asarray(x), train=True)
        t1 = np.asarray(
            hl.apply(hl.params, jnp.asarray(x), train=True, key=_jax.random.key(0))
        )
        assert not np.allclose(t1, base)
        # eval-style __call__ is deterministic and matches apply
        out1 = np.asarray(hl(jnp.asarray(x)))
        np.testing.assert_array_equal(out1, base)
        # sequence-split DNDarray end to end
        xs = ht.array(x, split=1)
        out_s = hl.apply(hl.params, xs)
        assert out_s.split == 1
        np.testing.assert_allclose(out_s.numpy(), base, rtol=2e-5, atol=2e-5)


class TestTransformerDecoder:
    @staticmethod
    def _map_attn(sd, prefix):
        return {
            "in_proj_weight": jnp.asarray(sd[f"{prefix}.in_proj_weight"].numpy()),
            "in_proj_bias": jnp.asarray(sd[f"{prefix}.in_proj_bias"].numpy()),
            "out_proj_weight": jnp.asarray(sd[f"{prefix}.out_proj.weight"].numpy()),
            "out_proj_bias": jnp.asarray(sd[f"{prefix}.out_proj.bias"].numpy()),
        }

    @classmethod
    def _map_params(cls, hm_params, t_layer):
        sd = t_layer.state_dict()
        p = dict(hm_params)
        p["self_attn"] = cls._map_attn(sd, "self_attn")
        p["multihead_attn"] = cls._map_attn(sd, "multihead_attn")
        for name in ("linear1", "linear2"):
            p[name] = {
                "weight": jnp.asarray(sd[f"{name}.weight"].numpy()).T,
                "bias": jnp.asarray(sd[f"{name}.bias"].numpy()),
            }
        for name in ("norm1", "norm2", "norm3"):
            p[name] = {
                "weight": jnp.asarray(sd[f"{name}.weight"].numpy()),
                "bias": jnp.asarray(sd[f"{name}.bias"].numpy()),
            }
        return p

    @pytest.mark.parametrize("norm_first", [False, True])
    def test_decoder_layer_torch_parity(self, norm_first):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(50)
        B, Tt, Tm, E, H, FF = 2, 5, 7, 8, 2, 16
        tgt = rng.standard_normal((B, Tt, E)).astype(np.float32)
        mem = rng.standard_normal((B, Tm, E)).astype(np.float32)
        tl = torch.nn.TransformerDecoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, batch_first=True,
            norm_first=norm_first,
        ).eval()
        hl = ht.nn.TransformerDecoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, norm_first=norm_first
        )
        params = self._map_params(hl.params, tl)
        want = tl(torch.tensor(tgt), torch.tensor(mem)).detach().numpy()
        got = np.asarray(hl.apply(params, jnp.asarray(tgt), jnp.asarray(mem)))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        # causal target self-attention + a memory key-padding mask
        mkpm = np.zeros((B, Tm), bool)
        mkpm[0, 5:] = True
        want_c = tl(
            torch.tensor(tgt), torch.tensor(mem),
            tgt_mask=torch.nn.Transformer.generate_square_subsequent_mask(Tt),
            tgt_is_causal=True,
            memory_key_padding_mask=torch.tensor(mkpm),
        ).detach().numpy()
        got_c = np.asarray(hl.apply(
            params, jnp.asarray(tgt), jnp.asarray(mem), tgt_is_causal=True,
            memory_key_padding_mask=jnp.asarray(mkpm),
        ))
        np.testing.assert_allclose(got_c, want_c, rtol=2e-5, atol=2e-5)

    def test_decoder_stack_torch_parity(self):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(51)
        B, Tt, Tm, E, H, FF, N = 2, 4, 6, 8, 2, 12, 2
        tgt = rng.standard_normal((B, Tt, E)).astype(np.float32)
        mem = rng.standard_normal((B, Tm, E)).astype(np.float32)
        tl = torch.nn.TransformerDecoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, batch_first=True
        )
        tdec = torch.nn.TransformerDecoder(tl, N, norm=torch.nn.LayerNorm(E)).eval()
        hdec = ht.nn.TransformerDecoder(
            ht.nn.TransformerDecoderLayer(E, H, dim_feedforward=FF, dropout=0.0),
            N, norm=ht.nn.LayerNorm(E),
        )
        params = dict(hdec.params)
        for i, t_layer in enumerate(tdec.layers):
            params[str(i)] = self._map_params(params[str(i)], t_layer)
        nsd = tdec.norm.state_dict()
        params["norm"] = {
            "weight": jnp.asarray(nsd["weight"].numpy()),
            "bias": jnp.asarray(nsd["bias"].numpy()),
        }
        want = tdec(torch.tensor(tgt), torch.tensor(mem)).detach().numpy()
        got = np.asarray(hdec.apply(params, jnp.asarray(tgt), jnp.asarray(mem)))
        np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)
        # torch-style __call__ matches, and dropout demands a key in train mode
        got2, = (np.asarray(hdec(jnp.asarray(tgt), jnp.asarray(mem))),)
        # fresh params in the stateful path -> only check shape/determinism
        assert got2.shape == want.shape
        hd = ht.nn.TransformerDecoderLayer(E, H, dropout=0.4)
        with pytest.raises(ValueError):
            hd.apply(hd.params, jnp.asarray(tgt), jnp.asarray(mem), train=True)


class TestTransformer:
    def test_transformer_torch_parity(self):
        """Full encoder-decoder wrapper vs torch.nn.Transformer with mapped
        weights, plus the causal-mask helper."""
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(60)
        B, Ts, Tt, E, H, FF, N = 2, 6, 4, 8, 2, 16, 2
        src = rng.standard_normal((B, Ts, E)).astype(np.float32)
        tgt = rng.standard_normal((B, Tt, E)).astype(np.float32)
        tm = torch.nn.Transformer(
            d_model=E, nhead=H, num_encoder_layers=N, num_decoder_layers=N,
            dim_feedforward=FF, dropout=0.0, batch_first=True,
        ).eval()
        hm = ht.nn.Transformer(
            d_model=E, nhead=H, num_encoder_layers=N, num_decoder_layers=N,
            dim_feedforward=FF, dropout=0.0,
        )
        params = dict(hm.params)
        enc_p = dict(params["encoder"])
        for i, t_layer in enumerate(tm.encoder.layers):
            enc_p[str(i)] = TestTransformerEncoder._map_params(enc_p[str(i)], t_layer)
        nsd = tm.encoder.norm.state_dict()
        enc_p["norm"] = {"weight": jnp.asarray(nsd["weight"].numpy()),
                         "bias": jnp.asarray(nsd["bias"].numpy())}
        dec_p = dict(params["decoder"])
        for i, t_layer in enumerate(tm.decoder.layers):
            dec_p[str(i)] = TestTransformerDecoder._map_params(dec_p[str(i)], t_layer)
        nsd = tm.decoder.norm.state_dict()
        dec_p["norm"] = {"weight": jnp.asarray(nsd["weight"].numpy()),
                         "bias": jnp.asarray(nsd["bias"].numpy())}
        params = {"encoder": enc_p, "decoder": dec_p}

        t_mask = torch.nn.Transformer.generate_square_subsequent_mask(Tt)
        h_mask = ht.nn.Transformer.generate_square_subsequent_mask(Tt)
        np.testing.assert_array_equal(np.asarray(h_mask), t_mask.numpy())
        want = tm(torch.tensor(src), torch.tensor(tgt),
                  tgt_mask=t_mask).detach().numpy()
        got = np.asarray(hm.apply(params, jnp.asarray(src), jnp.asarray(tgt),
                                  tgt_mask=h_mask))
        np.testing.assert_allclose(got, want, rtol=5e-5, atol=5e-5)
        # __call__ path with explicit params installed
        hm.params = params
        got2 = np.asarray(hm(jnp.asarray(src), jnp.asarray(tgt), tgt_mask=h_mask))
        np.testing.assert_array_equal(got2, got)


class TestTransformerDPIntegration:
    def test_encoder_under_dataparallel_optimizer(self):
        """TransformerEncoder inside a custom Module trains through the
        framework's own DataParallel/DataParallelOptimizer stack (step cache,
        batch-split DNDarrays, grads psum'd by XLA) — the cross-feature path no
        other test drives."""
        rng = np.random.default_rng(0)
        B, T, E, H, classes = 64, 12, 16, 4, 3
        x = rng.standard_normal((B, T, E)).astype(np.float32)
        y = rng.integers(0, classes, B).astype(np.int32)

        class Classifier(ht.nn.Module):
            def __init__(self):
                self.enc = ht.nn.TransformerEncoder(
                    ht.nn.TransformerEncoderLayer(
                        E, H, dim_feedforward=32, dropout=0.0
                    ), 2)
                self.head = ht.nn.Linear(E, classes)

            def init(self, key):
                k1, k2 = jax.random.split(key)
                return {"enc": self.enc.init(k1), "head": self.head.init(k2)}

            def apply(self, params, x, *, key=None, train=False):
                h = self.enc.apply(params["enc"], x, key=key, train=train)
                pooled = (
                    ht.mean(h, axis=-2) if isinstance(h, ht.DNDarray)
                    else h.mean(axis=-2)
                )
                return self.head.apply(params["head"], pooled)

        model = Classifier()
        model.reset_parameters(seed=0)
        opt = ht.optim.DataParallelOptimizer("adam", lr=1e-2)
        ht.nn.DataParallel(model, optimizer=opt)
        crit = ht.nn.CrossEntropyLoss()
        xb, yb = ht.array(x, split=0), ht.array(y, split=0)

        def loss_fn(params, xb, yb):
            return crit(model.apply(params, xb), yb)

        l0 = None
        for _ in range(40):
            l = opt.step(loss_fn, xb, yb)
            if l0 is None:
                l0 = float(l)
        pred = np.argmax(np.asarray(model.apply(model.params, jnp.asarray(x))), -1)
        acc = float((pred == y).mean())
        assert float(l) < l0 * 0.5
        assert acc > 0.9, acc


class TestTransformerFuzz:
    @pytest.mark.parametrize("case", range(8))
    def test_encoder_layer_hyperparam_fuzz(self, case):
        """Random (E, H, FF, norm_first, activation, batch_first) vs torch —
        including the (T, B, E) batch_first=False layout no other test drives."""
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(2000 + case)
        H = int(rng.choice([1, 2, 4]))
        E = H * int(rng.choice([2, 4, 8]))
        FF = int(rng.integers(4, 33))
        B, T = int(rng.integers(1, 4)), int(rng.integers(2, 9))
        norm_first = bool(rng.integers(0, 2))
        batch_first = bool(rng.integers(0, 2))
        activation = str(rng.choice(["relu", "gelu"]))
        shape = (B, T, E) if batch_first else (T, B, E)
        x = rng.standard_normal(shape).astype(np.float32)
        tl = torch.nn.TransformerEncoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, activation=activation,
            batch_first=batch_first, norm_first=norm_first,
        ).eval()
        hl = ht.nn.TransformerEncoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, activation=activation,
            batch_first=batch_first, norm_first=norm_first,
        )
        params = TestTransformerEncoder._map_params(hl.params, tl)
        got = np.asarray(hl.apply(params, jnp.asarray(x), is_causal=False))
        want = tl(torch.tensor(x)).detach().numpy()
        np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5,
                                   err_msg=f"case {case} bf={batch_first} nf={norm_first}")

    @pytest.mark.parametrize("case", range(4))
    def test_decoder_layer_hyperparam_fuzz(self, case):
        """Decoder twin of the encoder sweep: random hyperparams + both layouts."""
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(2100 + case)
        H = int(rng.choice([1, 2, 4]))
        E = H * int(rng.choice([2, 4, 8]))
        FF = int(rng.integers(4, 25))
        B, Tt, Tm = int(rng.integers(1, 4)), int(rng.integers(2, 7)), int(rng.integers(2, 9))
        # stratified so every (norm_first, batch_first) combination is drawn
        norm_first = bool(case % 2)
        batch_first = bool((case // 2) % 2)
        activation = str(rng.choice(["relu", "gelu"]))
        tshape = (B, Tt, E) if batch_first else (Tt, B, E)
        mshape = (B, Tm, E) if batch_first else (Tm, B, E)
        tgt = rng.standard_normal(tshape).astype(np.float32)
        mem = rng.standard_normal(mshape).astype(np.float32)
        tl = torch.nn.TransformerDecoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, activation=activation,
            batch_first=batch_first, norm_first=norm_first,
        ).eval()
        hl = ht.nn.TransformerDecoderLayer(
            E, H, dim_feedforward=FF, dropout=0.0, activation=activation,
            batch_first=batch_first, norm_first=norm_first,
        )
        params = TestTransformerDecoder._map_params(hl.params, tl)
        got = np.asarray(hl.apply(params, jnp.asarray(tgt), jnp.asarray(mem)))
        want = tl(torch.tensor(tgt), torch.tensor(mem)).detach().numpy()
        np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5,
                                   err_msg=f"case {case} bf={batch_first} nf={norm_first}")
