"""``spatial.cdist`` / ``manhattan`` / ``rbf`` as ONE compiled program a call
(``spatial/distance.py`` ``_program``): every split pair, the symmetric, ragged and
promoted cases against NumPy; the trace counter against the span counter; threads;
and the call nested in a caller's own ``jax.jit``."""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import diagnostics
from heat_tpu.core.communication import get_comm

P = get_comm().size
NX, NY, D = 2 * P, 3 * P, 2 * P  # every extent divides the mesh


def _ref(metric, x, y, sigma=1.5):
    diff = x[:, None, :].astype(np.float64) - y[None, :, :].astype(np.float64)
    if metric == "manhattan":
        return np.abs(diff).sum(-1)
    d = np.sqrt((diff**2).sum(-1))
    return d if metric == "cdist" else np.exp(-(d**2) / (2.0 * sigma * sigma))


def _call(metric, X, Y=None):
    if metric == "rbf":
        return ht.spatial.rbf(X, Y, sigma=1.5)
    return getattr(ht.spatial, metric)(X, Y)


def _data(nx, ny, d, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(nx, d)).astype(dtype), rng.normal(size=(ny, d)).astype(dtype)


def _out_split(x_split, y_split):
    return 0 if x_split == 0 else (1 if y_split == 0 else None)


def _check(result, ref, split, atol=2e-4):
    assert isinstance(result, ht.DNDarray)
    assert result.gshape == ref.shape
    assert result.split == split
    assert result.parray.sharding == result.comm.sharding(2, split)
    np.testing.assert_allclose(result.numpy(), ref, rtol=1e-4, atol=atol)


@pytest.mark.parametrize("metric", ["cdist", "manhattan", "rbf"])
@pytest.mark.parametrize("y_split", [None, 0, 1])
@pytest.mark.parametrize("x_split", [None, 0, 1])
def test_split_pairs_match_numpy(metric, x_split, y_split):
    x, y = _data(NX, NY, D)
    result = _call(metric, ht.array(x, split=x_split), ht.array(y, split=y_split))
    assert result.dtype is ht.float32
    _check(result, _ref(metric, x, y), _out_split(x_split, y_split))


@pytest.mark.parametrize("metric", ["cdist", "manhattan", "rbf"])
@pytest.mark.parametrize("split", [None, 0, 1])
def test_symmetric_call_matches_numpy(metric, split):
    x, _ = _data(NX, NY, D, seed=1)
    # the quadratic expansion leaves sqrt(eps * |x|²) where the diagonal's zeros belong
    _check(
        _call(metric, ht.array(x, split=split)),
        _ref(metric, x, x),
        _out_split(split, split),
        atol=5e-3 if metric == "cdist" else 2e-4,
    )


@pytest.mark.parametrize("metric", ["cdist", "manhattan", "rbf"])
@pytest.mark.parametrize("x_split,y_split", [(0, 0), (0, None), (None, 0), (1, 1)])
def test_ragged_extents_match_numpy(metric, x_split, y_split):
    x, y = _data(2 * P + 1, P + 3, P + 1, seed=2)
    result = _call(metric, ht.array(x, split=x_split), ht.array(y, split=y_split))
    _check(result, _ref(metric, x, y), _out_split(x_split, y_split))


@pytest.mark.parametrize(
    "x_dtype,y_dtype,expected",
    [
        (np.float64, np.float64, ht.float64),
        (np.float32, np.float64, ht.float64),
        (np.float64, np.float32, ht.float64),
        (np.int32, np.float32, ht.promote_types(ht.int32, ht.float32)),
        (np.float16, np.float32, ht.float32),
    ],
)
def test_promotion_is_part_of_the_program(x_dtype, y_dtype, expected):
    x, y = _data(NX, NY, D, seed=3, dtype=np.float64)
    x, y = (4 * x).astype(x_dtype), (4 * y).astype(y_dtype)
    result = ht.spatial.cdist(ht.array(x, split=0), ht.array(y, split=0))
    assert result.dtype is expected
    np.testing.assert_allclose(result.numpy(), _ref("cdist", x, y), rtol=1e-3, atol=2e-3)
    if expected is ht.float64:
        # float64 stays float64 all the way: far below float32's rounding
        np.testing.assert_allclose(result.numpy(), _ref("cdist", x, y), rtol=1e-9, atol=1e-9)


def test_rbf_sigma_is_an_argument_of_the_program():
    x, y = _data(NX, NY, D, seed=4)
    X, Y = ht.array(x, split=0), ht.array(y)
    for sigma in (0.5, 1.0, 3.0):
        np.testing.assert_allclose(
            ht.spatial.rbf(X, Y, sigma=sigma).numpy(),
            _ref("rbf", x, y, sigma=sigma),
            rtol=1e-4,
            atol=1e-5,
        )


@contextlib.contextmanager
def _metrics():
    diagnostics.enable()
    diagnostics.reset()
    try:
        yield
    finally:
        diagnostics.disable()
        diagnostics.reset()


def _cdist_counters():
    counters = diagnostics.report()["counters"]
    return tuple(
        counters.get(name, 0)
        for name in ("spatial.cdist.traces", "compile_n.spatial.cdist", "span_n.spatial.cdist")
    )


@pytest.mark.parametrize("metric", ["cdist", "manhattan", "rbf"])
def test_one_trace_and_one_compile_a_shape(metric):
    # shapes no other test of the process uses, so each is new to the program's cache
    base = {"cdist": 5, "manhattan": 6, "rbf": 7}[metric] * P
    x, y = _data(base, 2 * base, 3, seed=5)
    _, z = _data(base, 3 * base, 3, seed=6)
    X, Y, Z = ht.array(x, split=0), ht.array(y), ht.array(z)
    with _metrics():
        _call(metric, X, Y).parray
        assert _cdist_counters() == (1, 1, 1)
        for calls in (2, 3):  # a warmed shape: the span counts, the program does not move
            _call(metric, X, Y).parray
            assert _cdist_counters() == (1, 1, calls)
        _call(metric, X, Z).parray  # a new shape is one more of each
        assert _cdist_counters() == (2, 2, 4)
        if metric == "rbf":  # another sigma is the same program
            ht.spatial.rbf(X, Z, sigma=0.25).parray
            assert _cdist_counters() == (2, 2, 5)


def test_metrics_off_counts_nothing():
    diagnostics.disable()
    diagnostics.reset()
    x, y = _data(9 * P, 11 * P, 3, seed=7)  # a new shape: the trace runs with metrics off
    ht.spatial.cdist(ht.array(x, split=0), ht.array(y)).parray
    assert diagnostics.report()["counters"] == {}


def test_threads_get_the_single_thread_answers():
    n_threads, n_iters = 4, 6
    rng = np.random.default_rng(8)
    corpus = ht.array(rng.normal(size=(16 * P, 8)).astype(np.float32), split=0)
    queries = [
        ht.array(rng.normal(size=(4 + slot, 8)).astype(np.float32)) for slot in range(n_threads)
    ]

    def nearest(q):
        return np.asarray(ht.argmin(ht.spatial.cdist(q, corpus), axis=1).numpy())

    expected = [nearest(q) for q in queries[:2]] + [None, None]  # two shapes stay cold
    results, errors = [[] for _ in queries], []
    start = threading.Barrier(n_threads)

    def work(slot):
        try:
            start.wait(timeout=60)
            for _ in range(n_iters):
                results[slot].append(nearest(queries[slot]))
        except Exception as e:  # surfaced below: a thread's exception is otherwise lost
            errors.append(e)

    threads = [threading.Thread(target=work, args=(slot,)) for slot in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    for slot, q in enumerate(queries):
        want = expected[slot] if expected[slot] is not None else nearest(q)
        ref = np.argmin(_ref("cdist", q.numpy(), corpus.numpy()), axis=1)
        np.testing.assert_array_equal(want, ref)
        assert len(results[slot]) == n_iters
        for got in results[slot]:
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("metric", ["cdist", "manhattan", "rbf"])
@pytest.mark.parametrize("x_split,y_split", [(None, None), (0, 0), (None, 0)])
def test_inside_a_callers_jit_equals_the_eager_call(metric, x_split, y_split):
    x, y = _data(NX, NY, D, seed=9)
    eager = _call(metric, ht.array(x, split=x_split), ht.array(y, split=y_split))

    @jax.jit
    def nested(a, b):
        out = _call(metric, ht.array(a, split=x_split), ht.array(b, split=y_split))
        assert out.split == eager.split and out.gshape == eager.gshape
        return out.larray

    np.testing.assert_allclose(
        np.asarray(nested(jnp.asarray(x), jnp.asarray(y))), eager.numpy(), rtol=1e-6, atol=1e-6
    )
