"""Bring-up pieces on the CPU mesh: ``chip_smoke.py``'s phases at tiny sizes, its
and ``bench.py``'s refusal to run without a chip or past a failed phase, and the
compile-cache placement rule. (Named to collect last: the tier-1 budget is spent on
the suites before it.)"""

import functools
import os
import subprocess
import sys
import types

import pytest

import jax

import heat_tpu as ht
from heat_tpu.core import _bootstrap, _executor, diagnostics, resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402
import chip_smoke  # noqa: E402


@pytest.fixture
def books():
    """A clean ledger with diagnostics on, as chip_smoke.main starts with."""
    was = diagnostics.enabled()
    _executor.clear_executor_cache()
    diagnostics.reset()
    diagnostics.enable()
    yield
    if not was:
        diagnostics.disable()
    diagnostics.reset()
    _executor.clear_executor_cache()


@pytest.fixture
def flash_interpret(monkeypatch):
    """Route the production attention entry points through the interpreter."""
    import heat_tpu.core.kernels.flash_attention as fa
    import heat_tpu.nn.attention as att

    fwd, bwd = fa._flash_pallas, fa._flash_bwd_pallas
    monkeypatch.setattr(att, "use_flash", functools.partial(fa.use_flash, interpret=True))
    monkeypatch.setattr(fa, "_flash_pallas",
                        lambda *a, **kw: fwd(*a, **{**kw, "interpret": True}))
    monkeypatch.setattr(fa, "_flash_bwd_pallas",
                        lambda *a, **kw: bwd(*a, **{**kw, "interpret": True}))


class TestPhasesOnCpuMesh:
    def test_main_path_phases(self, books):
        chip_smoke.phase_split_algebra(n=64, n_int=1 << 10, platform="cpu")
        chip_smoke.phase_trainer(n=64, d=16, h=8, classes=4, steps=4, platform="cpu")
        chip_smoke.phase_matmul(n=64, block=16, platform="cpu")
        chip_smoke.phase_hsvd(m=64, n=256, rank=4, platform="cpu")
        chip_smoke.phase_fft_complex(n=64, d=8, platform="cpu")
        chip_smoke.phase_books()

    def test_kernel_phases_interpreted(self, books, flash_interpret):
        chip_smoke.phase_kmeans(n=4096, d=8, k=4, iters=3, slab=1024, platform="cpu",
                                interpret=True)
        chip_smoke.phase_attention(b=8, h=2, t=512, d=16, dtype="float32",
                                   platform="cpu", interpret=True)

    def test_server_phase(self, books):
        info = chip_smoke.phase_server(smoke=True, requests=8, concurrency=2)
        assert info["answered"] == 3 * (8 + 8)
        chip_smoke.phase_books()

    def test_mesh_phase(self, books):
        if len(jax.devices()) < 4 or len(jax.devices()) % 2:
            pytest.skip("the mesh phase wants an even mesh of at least 4 devices")
        chip_smoke.phase_mesh(n=1 << 12, platform="cpu")

    def test_wrong_platform_is_a_failure(self):
        x = ht.arange(8, split=0)
        with pytest.raises(chip_smoke.SmokeFailure, match="lives on"):
            chip_smoke._placed(x, "tpu")

    def test_books_fail_on_an_eager_fallback(self, books):
        resilience.arm_fault_plan(
            [{"site": "executor.execute", "on_call": 1, "count": 99, "kind": "raise"}]
        )
        try:
            for _ in range(3):  # past the suite's jit threshold
                (ht.arange(16, split=0) * 2.0).sum().parray
        finally:
            resilience.disarm_fault_plan()
        assert ht.executor_stats()["eager_fallbacks"] > 0
        with pytest.raises(chip_smoke.SmokeFailure, match="eager_fallbacks"):
            chip_smoke.phase_books()


class TestSmokeRefusals:
    def test_no_chip_is_a_nonzero_exit_with_no_result(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
        )
        assert proc.returncode != 0
        assert proc.stdout == ""
        assert "not a TPU" in proc.stderr

    def test_a_failed_phase_ends_the_run(self, monkeypatch, capsys):
        ran = []

        def boom():
            raise RuntimeError("phase blew up")

        fake = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
        monkeypatch.setattr(chip_smoke, "_device_gate", lambda: fake)
        monkeypatch.setattr(chip_smoke, "phase_split_algebra", lambda: ran.append("s") or {})
        monkeypatch.setattr(chip_smoke, "phase_trainer", boom)
        monkeypatch.setattr(chip_smoke, "phase_matmul", lambda: ran.append("m") or {})
        was = diagnostics.enabled()
        try:
            with pytest.raises(RuntimeError, match="phase blew up") as err:
                chip_smoke.main()
        finally:
            if not was:
                diagnostics.disable()
        assert ran == ["s"]  # nothing ran past the failure
        assert "phase 'trainer' failed" in "".join(err.value.__notes__)
        assert '"ok": true' not in capsys.readouterr().out


class TestBenchRefusals:
    def test_unknown_device_kind_raises(self):
        assert bench._peak_tflops("TPU v5 lite") == 197.0
        with pytest.raises(ValueError, match="no bf16 peak"):
            bench._peak_tflops("TPU v9 imaginary")

    def test_no_chip_is_a_nonzero_exit(self):
        with pytest.raises(SystemExit) as err:
            bench.main()
        assert err.value.code not in (0, None)

    def test_a_failed_phase_is_a_nonzero_exit(self, monkeypatch, capsys):
        dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
        monkeypatch.setattr(jax, "devices", lambda *a: [dev])

        def boom(*_):
            raise RuntimeError("matmul phase blew up")

        monkeypatch.setattr(bench, "_bench_matmul", boom)
        with pytest.raises(RuntimeError, match="matmul phase blew up"):
            bench.main()  # __main__ calls main() bare: the raise is the exit code
        assert capsys.readouterr().out == ""  # no null record, no cached replay


class TestCompileCachePlacement:
    def test_fixed_path_inside_the_checkout(self):
        assert _bootstrap.JAX_CACHE_DIR == os.path.join(REPO, ".jax_cache")
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_this_process_follows_the_rule(self):
        placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        assert jax.config.jax_compilation_cache_dir == (placed or _bootstrap.JAX_CACHE_DIR)

    def test_env_set_means_code_sets_nothing(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        _bootstrap.place_jax_cache()
        assert calls == []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        _bootstrap.place_jax_cache()
        assert calls == [("jax_compilation_cache_dir", _bootstrap.JAX_CACHE_DIR)]
