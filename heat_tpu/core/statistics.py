"""Statistical operations (reference heat/core/statistics.py, 1993 LoC).

The reference's distributed statistics lean on custom MPI reduction ops — ``MPI_ARGMAX``/
``MPI_ARGMIN`` carry (value, index) payloads through an Allreduce
(``statistics.py:1370,1405``), and ``mean``/``var`` merge per-rank moments with a
numerically-stable pairwise update (``statistics.py:893,1850``). On TPU the global value
is a single sharded ``jax.Array``: one jnp reduction computes the same result and XLA
emits the cross-shard all-reduce, so the entire custom-op machinery disappears. Only the
split bookkeeping (which output dim still carries the mesh axis) survives, shared with
:mod:`._operations`.
"""

from __future__ import annotations

from builtins import max as builtins_max
from typing import Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import _operations, diagnostics, sanitation, types
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "bucketize",
    "cov",
    "digitize",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "maximum",
    "mean",
    "median",
    "min",
    "minimum",
    "percentile",
    "skew",
    "std",
    "var",
]


_wrap = _operations.wrap_result
_handle_out = _operations.handle_out


def _arg_reduce(op, x: DNDarray, axis, out, keepdims: bool) -> DNDarray:
    """Shared argmax/argmin logic (reference custom MPI ops ``statistics.py:1370-1405``)."""
    with diagnostics.span("statistics.argreduce", x) if diagnostics._enabled else diagnostics.NO_SPAN:
        sanitation.sanitize_in(x)
        if axis is None:
            result = op(x.larray.reshape(-1)).astype(jnp.int64)
            if keepdims:
                result = result.reshape((1,) * x.ndim)
            out_split = None
        else:
            axis = sanitize_axis(x.gshape, axis)
            result = op(x.larray, axis=axis).astype(jnp.int64)
            if keepdims:
                result = jnp.expand_dims(result, axis)
            out_split = _operations._out_split_reduce(x, axis, keepdims)
        return _handle_out(_wrap(result, x, out_split), out, x)


def argmax(x: DNDarray, axis: Optional[int] = None, out: Optional[DNDarray] = None, keepdims: bool = False) -> DNDarray:
    """Indices of maximum values (reference ``statistics.py:40``)."""
    return _arg_reduce(jnp.argmax, x, axis, out, keepdims)


def argmin(x: DNDarray, axis: Optional[int] = None, out: Optional[DNDarray] = None, keepdims: bool = False) -> DNDarray:
    """Indices of minimum values (reference ``statistics.py:109``)."""
    return _arg_reduce(jnp.argmin, x, axis, out, keepdims)


def average(
    x: DNDarray,
    axis: Optional[Union[int, Tuple[int, ...]]] = None,
    weights: Optional[DNDarray] = None,
    returned: bool = False,
):
    """Weighted average (reference ``statistics.py:178``)."""
    sanitation.sanitize_in(x)
    if weights is None:
        result = mean(x, axis)
        if returned:
            n = x.size if axis is None else np.prod(
                [x.gshape[a] for a in (axis if isinstance(axis, tuple) else (axis,))]
            )
            wsum = _wrap(jnp.full(result.gshape, float(n), result.larray.dtype), result, result.split)
            return result, wsum
        return result
    w = weights.larray if isinstance(weights, DNDarray) else jnp.asarray(weights)
    axis_s = sanitize_axis(x.gshape, axis) if axis is not None else None
    if tuple(w.shape) != tuple(x.gshape):
        if axis_s is None:
            raise TypeError("Axis must be specified when shapes of x and weights differ.")
        if isinstance(axis_s, tuple):
            raise TypeError("1D weights expect an integer axis.")
        if w.ndim != 1:
            raise TypeError("1D weights expected when shapes of x and weights differ.")
        if w.shape[0] != x.gshape[axis_s]:
            raise ValueError("Length of weights not compatible with specified axis.")
        shape = [1] * x.ndim
        shape[axis_s] = w.shape[0]
        wb = w.reshape(shape)
    else:
        wb = w
    num = jnp.sum(x.larray * wb, axis=axis_s)
    den = jnp.sum(jnp.broadcast_to(wb, x.gshape), axis=axis_s)
    if bool(jnp.any(den == 0)):
        raise ZeroDivisionError("Weights sum to zero, can't be normalized")
    result = num / den
    out_split = _operations._out_split_reduce(x, axis_s, False) if axis_s is not None else None
    res = _wrap(result, x, out_split)
    if returned:
        return res, _wrap(jnp.broadcast_to(den, result.shape).astype(result.dtype), x, out_split)
    return res


def bincount(x: DNDarray, weights: Optional[DNDarray] = None, minlength: int = 0) -> DNDarray:
    """Count occurrences of each value in a non-negative int array
    (reference ``statistics.py:240``)."""
    sanitation.sanitize_in(x)
    w = weights.larray if isinstance(weights, DNDarray) else weights
    if x.size and bool(jnp.any(x.larray < 0)):
        raise ValueError("bincount: input array must have no negative elements")
    length = int(jnp.max(x.larray)) + 1 if x.size else 0
    length = builtins_max(length, int(minlength))
    result = jnp.bincount(x.larray.reshape(-1), weights=None if w is None else w.reshape(-1), length=length)
    return _wrap(result, x, None)



def bucketize(input: DNDarray, boundaries, out_int32: bool = False, right: bool = False, out=None) -> DNDarray:
    """Index of the bucket each element falls into (reference ``statistics.py:289``,
    torch.bucketize semantics: boundaries are sorted bucket edges)."""
    sanitation.sanitize_in(input)
    b = boundaries.larray if isinstance(boundaries, DNDarray) else jnp.asarray(boundaries)
    side = "left" if not right else "right"
    # torch.bucketize(right=False) counts boundaries < v as numpy side='left'... torch's
    # right=False means v <= boundary ⇒ numpy searchsorted side='left'
    result = jnp.searchsorted(b, input.larray.reshape(-1), side=side).reshape(input.gshape)
    result = result.astype(jnp.int32 if out_int32 else jnp.int64)
    return _handle_out(_wrap(result, input, input.split), out, input)


def cov(m: DNDarray, y: Optional[DNDarray] = None, rowvar: bool = True, bias: bool = False, ddof: Optional[int] = None) -> DNDarray:
    """Estimate the covariance matrix (reference ``statistics.py:346``)."""
    sanitation.sanitize_in(m)
    if m.ndim > 2:
        raise ValueError("m has more than 2 dimensions")
    x = m.larray
    if x.ndim == 1:
        x = x.reshape(1, -1)
    if not rowvar and x.shape[0] != 1:
        x = x.T
    if y is not None:
        yv = y.larray if isinstance(y, DNDarray) else jnp.asarray(y)
        if yv.ndim > 2:
            raise ValueError("y has more than 2 dimensions")
        if yv.ndim == 1:
            yv = yv.reshape(1, -1)
        if not rowvar and yv.shape[0] != 1:
            yv = yv.T
        x = jnp.concatenate([x, yv], axis=0)
    if ddof is None:
        ddof = 0 if bias else 1
    n = x.shape[1]
    xm = x - jnp.mean(x, axis=1, keepdims=True)
    fact = builtins_max(n - ddof, 0)
    # full input precision: covariance entries cancel for correlated variables
    result = jnp.matmul(xm, xm.conj().T, precision=jax.lax.Precision.HIGHEST) / fact
    if result.shape == (1, 1):  # numpy returns a 0-d value for a single variable
        result = result.reshape(())
    return _wrap(result, m, None)


def digitize(x: DNDarray, bins, right: bool = False) -> DNDarray:
    """Indices of the bins each value belongs to (reference ``statistics.py:408``,
    numpy.digitize semantics)."""
    sanitation.sanitize_in(x)
    b = bins.larray if isinstance(bins, DNDarray) else jnp.asarray(bins)
    result = jnp.digitize(x.larray, b, right=right)
    return _wrap(result, x, x.split)


def histc(input: DNDarray, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None) -> DNDarray:
    """Histogram with equal-width bins (reference ``statistics.py:465``, torch.histc
    semantics: min==max ⇒ use data min/max; out-of-range elements ignored)."""
    sanitation.sanitize_in(input)
    lo, hi = float(min), float(max)
    data = input.larray.reshape(-1)
    if lo == hi:
        lo, hi = float(jnp.min(data)), float(jnp.max(data))
    hist, _ = jnp.histogram(data, bins=bins, range=(lo, hi))
    result = hist.astype(input.larray.dtype)
    return _handle_out(_wrap(result, input, None), out, input)


def histogram(a: DNDarray, bins: int = 10, range=None, normed=None, weights=None, density=None):
    """numpy-compatible histogram (reference ``statistics.py:522``)."""
    sanitation.sanitize_in(a)
    if normed is not None:
        raise NotImplementedError("'normed' is deprecated; use density instead")
    w = weights.larray.reshape(-1) if isinstance(weights, DNDarray) else weights
    hist, edges = jnp.histogram(a.larray.reshape(-1), bins=bins, range=range, weights=w, density=density)
    return _wrap(hist, a, None), _wrap(edges, a, None)


def kurtosis(x: DNDarray, axis: Optional[int] = None, unbiased: bool = True, Fischer: bool = True) -> DNDarray:
    """Kurtosis (fourth central moment; reference ``statistics.py:581``)."""
    sanitation.sanitize_in(x)
    if axis is not None and not isinstance(axis, (int, np.integer)):
        raise TypeError(f"axis must be None or an int, got {type(axis)}")
    axis_s = sanitize_axis(x.gshape, axis) if axis is not None else None
    v = x.larray.astype(jnp.promote_types(x.larray.dtype, jnp.float32))
    if axis_s is None:
        v = v.reshape(-1)
        axis_s = 0
        out_split = None
        n = v.shape[0]
    else:
        out_split = _operations._out_split_reduce(x, axis_s, False)
        n = x.gshape[axis_s]
    m = jnp.mean(v, axis=axis_s, keepdims=True)
    d = v - m
    m2 = jnp.mean(d**2, axis=axis_s)
    m4 = jnp.mean(d**4, axis=axis_s)
    g2 = m4 / (m2**2)
    if unbiased:
        k = ((n - 1) / ((n - 2) * (n - 3))) * ((n + 1) * g2 - 3 * (n - 1)) + 3
    else:
        k = g2
    if Fischer:
        k = k - 3
    return _wrap(k, x, out_split)


def max(x: DNDarray, axis=None, out=None, keepdims=None) -> DNDarray:  # noqa: A001
    """Maximum along axis (reference ``statistics.py:698``)."""
    return _operations.reduce_op(jnp.max, x, axis, out, bool(keepdims))


def maximum(x1, x2, out=None) -> DNDarray:
    """Elementwise maximum (reference ``statistics.py:762``)."""
    return _operations.binary_op(jnp.maximum, x1, x2, out)


def mean(x: DNDarray, axis=None, keepdims: bool = False) -> DNDarray:
    """Arithmetic mean (reference ``statistics.py:893``; the pairwise moment-merging
    Allreduce collapses into one global jnp.mean)."""
    return _operations.reduce_op(jnp.mean, x, axis, None, keepdims)


def median(x: DNDarray, axis: Optional[int] = None, keepdims: bool = False) -> DNDarray:
    """Median (reference ``statistics.py:1019``)."""
    return percentile(x, 50.0, axis=axis, keepdims=keepdims)


def min(x: DNDarray, axis=None, out=None, keepdims=None) -> DNDarray:  # noqa: A001
    """Minimum along axis (reference ``statistics.py:1129``)."""
    return _operations.reduce_op(jnp.min, x, axis, out, bool(keepdims))


def minimum(x1, x2, out=None) -> DNDarray:
    """Elementwise minimum (reference ``statistics.py:1192``)."""
    return _operations.binary_op(jnp.minimum, x1, x2, out)


def _percentile_from_sorted(sv, q_arr, axis, method, keepdims, n=None):
    """Percentiles from already-sorted values: gather the bracketing index planes and
    interpolate — O(q) gathered planes instead of materialising the sorted global.
    ``sv`` may be the padded physical form; ``n`` is the logical extent (pad slots sit
    past it and are never gathered)."""
    n = sv.shape[axis] if n is None else n
    qshape = q_arr.shape
    pos = q_arr.reshape(-1) / 100.0 * (n - 1)
    lo = jnp.clip(jnp.floor(pos), 0, n - 1).astype(jnp.int32)
    hi = jnp.clip(jnp.ceil(pos), 0, n - 1).astype(jnp.int32)
    if method == "lower":
        r = jnp.take(sv, lo, axis=axis)
    elif method == "higher":
        r = jnp.take(sv, hi, axis=axis)
    elif method == "nearest":
        # jnp.percentile's tie rule: the LOWER bracket wins at frac == 0.5 exactly
        # (jnp.rint's round-half-even gave layout-dependent answers — ADVICE r4)
        nearest = jnp.where(pos - lo <= 0.5, lo, hi)
        r = jnp.take(sv, jnp.clip(nearest, 0, n - 1).astype(jnp.int32), axis=axis)
    elif method == "midpoint":
        r = (jnp.take(sv, lo, axis=axis) + jnp.take(sv, hi, axis=axis)) / 2
    else:  # linear
        a = jnp.take(sv, lo, axis=axis)
        b = jnp.take(sv, hi, axis=axis)
        shape = [1] * a.ndim
        shape[axis] = pos.shape[0]
        frac = (pos - lo).astype(sv.dtype).reshape(shape)
        r = a + (b - a) * frac
    r = jnp.moveaxis(r, axis, 0)  # q dim to front, matching jnp.percentile layout
    rest = r.shape[1:]
    if keepdims:
        rest = rest[:axis] + (1,) + rest[axis:]
    return r.reshape(qshape + rest)


def percentile(
    x: DNDarray,
    q,
    axis: Optional[int] = None,
    out: Optional[DNDarray] = None,
    interpolation: str = "linear",
    keepdims: bool = False,
) -> DNDarray:
    """q-th percentile (reference ``statistics.py:1408``).

    Along a split reduction axis the order statistics come from the distributed
    merge-split sort (:mod:`heat_tpu.core.dist_sort`) followed by a gather of just the
    two bracketing index planes — O(n/P) memory per device, the property the
    reference's resplit+local-sort scheme provides. Other configurations are one
    global ``jnp.percentile``."""
    from . import dist_sort

    sanitation.sanitize_in(x)
    axis_s = sanitize_axis(x.gshape, axis) if axis is not None else None
    q_arr = jnp.asarray(q, dtype=jnp.float64)
    promoted = jnp.promote_types(x.parray.dtype, jnp.float32)
    # axis=None over a 1-D split array is the same reduction with axis=0
    eff_axis = 0 if (axis_s is None and x.ndim == 1) else axis_s
    use_dist = (
        eff_axis is not None
        and interpolation in ("linear", "lower", "higher", "nearest", "midpoint")
        and dist_sort.can_distribute_sort(x.comm, x.gshape, x.split, eff_axis, promoted)
    )
    if use_dist:
        # NaN inputs must yield NaN like jnp.percentile; the sorted-order-statistics
        # path would interpolate finite planes instead, so route those globally.
        # The reduction runs on the padded physical (pad slots are finite zeros).
        use_dist = not bool(jnp.isnan(x.parray).any())
    if use_dist:
        n_log = x.gshape[eff_axis]
        work = x.comm.shard(x.parray.astype(promoted), x.split)  # stays 1/P-local
        sv, _ = dist_sort.distributed_sort(
            x.comm, work, eff_axis, logical_n=n_log
        )
        result = _percentile_from_sorted(
            sv, q_arr, eff_axis, interpolation, keepdims, n=n_log
        )
        if axis_s is None:  # scalar-q + axis=None conventions already match (ndim-1 case)
            axis_s = eff_axis
    else:
        result = jnp.percentile(
            x.larray.astype(promoted),
            q_arr,
            axis=axis_s,
            method=interpolation,
            keepdims=keepdims,
        )
    out_split = _operations._out_split_reduce(x, axis_s, keepdims) if axis_s is not None else None
    if out_split is not None and np.ndim(q):  # leading q dim shifts the split
        out_split += np.ndim(q)
    return _handle_out(_wrap(result, x, out_split), out, x)


def skew(x: DNDarray, axis: Optional[int] = None, unbiased: bool = True) -> DNDarray:
    """Skewness (third central moment; reference ``statistics.py:1676``)."""
    sanitation.sanitize_in(x)
    if axis is not None and not isinstance(axis, (int, np.integer)):
        raise TypeError(f"axis must be None or an int, got {type(axis)}")
    axis_s = sanitize_axis(x.gshape, axis) if axis is not None else None
    v = x.larray.astype(jnp.promote_types(x.larray.dtype, jnp.float32))
    if axis_s is None:
        v = v.reshape(-1)
        axis_s = 0
        out_split = None
        n = v.shape[0]
    else:
        out_split = _operations._out_split_reduce(x, axis_s, False)
        n = x.gshape[axis_s]
    m = jnp.mean(v, axis=axis_s, keepdims=True)
    d = v - m
    m2 = jnp.mean(d**2, axis=axis_s)
    m3 = jnp.mean(d**3, axis=axis_s)
    g1 = m3 / (m2**1.5)
    if unbiased:
        g1 = g1 * ((n * (n - 1)) ** 0.5) / (n - 2)
    return _wrap(g1, x, out_split)


def std(x: DNDarray, axis=None, ddof: int = 0, **kwargs) -> DNDarray:
    """Standard deviation (reference ``statistics.py:1717``)."""
    return _operations.reduce_op(jnp.std, x, axis, None, kwargs.get("keepdims", False), ddof=ddof)


def var(x: DNDarray, axis=None, ddof: int = 0, **kwargs) -> DNDarray:
    """Variance (reference ``statistics.py:1850``; the Allreduce moment merge is one
    global jnp.var)."""
    return _operations.reduce_op(jnp.var, x, axis, None, kwargs.get("keepdims", False), ddof=ddof)
