"""Exhaustive FFT parity sweep: fn × axis × n × norm × split against numpy.fft.

This sweep exists because the split-axis transform MUST take the explicit pencil
path (``fft._pencil_split``): XLA's SPMD FFT partitioner aborts the process on
sharded transform axes it can't divide. Every case here once crashed or must
never crash again.
"""

import numpy as np
import numpy.fft as nf
import pytest

import heat_tpu as ht

rng = np.random.default_rng(0)
X3 = rng.standard_normal((8, 12, 6))
CX = X3 + 1j * rng.standard_normal((8, 12, 6))

FNS_1D = ["fft", "ifft", "rfft", "hfft", "ihfft", "irfft"]
FNS_ND = ["fft2", "ifft2", "fftn", "rfftn", "irfftn"]


@pytest.mark.parametrize("split", [None, 0, 1, 2])
@pytest.mark.parametrize("fn", FNS_1D)
class TestFFT1DSweep:
    def test_axis_n_norm(self, fn, split):
        data = CX if fn in ("fft", "ifft", "hfft") else X3
        a = ht.array(data, split=split)
        for axis in (0, 1, -1):
            for n in (None, 5, 16):
                for norm in (None, "ortho", "forward"):
                    try:
                        want = getattr(nf, fn)(data, n=n, axis=axis, norm=norm)
                    except Exception:
                        continue
                    got = getattr(ht.fft, fn)(a, n=n, axis=axis, norm=norm)
                    assert got.split == split, f"{fn} axis={axis} lost split"
                    np.testing.assert_allclose(
                        got.numpy(), want, rtol=1e-4, atol=1e-5,
                        err_msg=f"{fn} axis={axis} n={n} norm={norm} split={split}",
                    )


@pytest.mark.parametrize("split", [None, 0, 1])
@pytest.mark.parametrize("fn", FNS_ND)
class TestFFTNDSweep:
    def test_axes(self, fn, split):
        data = CX if fn in ("fft2", "ifft2", "fftn") else X3
        a = ht.array(data, split=split)
        for axes in (None, (0, 1), (1, 2)):
            try:
                want = getattr(nf, fn)(data, axes=axes)
            except Exception:
                continue
            got = getattr(ht.fft, fn)(a, axes=axes)
            np.testing.assert_allclose(
                got.numpy(), want, rtol=1e-4, atol=1e-5,
                err_msg=f"{fn} axes={axes} split={split}",
            )


class TestPencilEdge:
    def test_all_axes_transformed_split0(self):
        """fftn over every axis of a split array replicates, transforms, resplits."""
        a = ht.array(CX, split=0)
        got = ht.fft.fftn(a)
        assert got.split == 0
        np.testing.assert_allclose(got.numpy(), nf.fftn(CX), rtol=1e-4, atol=1e-5)

    def test_1d_array_split0(self):
        v = rng.standard_normal(13) + 1j * rng.standard_normal(13)
        got = ht.fft.fft(ht.array(v, split=0))
        assert got.split == 0
        np.testing.assert_allclose(got.numpy(), nf.fft(v), rtol=1e-4, atol=1e-5)

    def test_hermitian_nd_split_on_transformed_axis(self):
        a = ht.array(X3, split=1)
        got = ht.fft.ihfftn(a, axes=(1, 2))
        np.testing.assert_allclose(
            got.numpy(), np.conj(nf.rfftn(X3, axes=(1, 2), norm="forward")),
            rtol=1e-4, atol=1e-5,
        )


class TestDeviceDtypeRefusal:
    """complex128 is the one dtype a TPU cannot hold (libtpu aborts the process):
    the call sites refuse it with a TypeError; complex64 and FFT run on the device."""

    def test_noop_off_tpu(self):
        from heat_tpu.core import devices as dv

        dv.require_device_dtype(np.complex128)  # the CPU mesh holds everything
        assert ht.array([1 + 2j]).dtype is ht.complex128

    def test_refuses_complex128_on_tpu(self, monkeypatch):
        import jax

        from heat_tpu.core import devices as dv

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        dv.require_device_dtype(np.complex64, np.float32, 1j)  # weak scalar stays c64
        for bad in ((np.complex128,), (np.float64, 1j), (np.complex64, np.float64)):
            with pytest.raises(TypeError, match="complex128"):
                dv.require_device_dtype(*bad)
        with pytest.raises(TypeError, match="complex128"):
            ht.array([1 + 2j])
        with pytest.raises(TypeError, match="complex128"):
            ht.fft.fft(ht.array(np.arange(8.0)))  # float64 in -> complex128 out
        z = ht.array(np.arange(4) + 1j, dtype=ht.complex64, split=0)
        w = z * (1 + 2j)  # the Python scalar is narrowed on the host
        assert w.dtype is ht.complex64
        np.testing.assert_allclose(w.numpy(), (np.arange(4) + 1j) * (1 + 2j), rtol=1e-6)
