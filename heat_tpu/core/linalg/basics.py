"""Basic linear algebra (reference heat/core/linalg/basics.py, 2404 LoC).

The reference's ``matmul`` (``basics.py:422-1100``) is a 700-line block-cyclic SUMMA with
hand-written Isend/Irecv pipelines per (a.split, b.split) case. On TPU the entire case
analysis collapses: ``jnp.matmul`` on sharded global arrays is partitioned by XLA SPMD,
which emits exactly the SUMMA-style collectives (all-gathers of panels, reduce-scatters /
all-reduces of partials) scheduled onto the MXU with overlap — this is the reference's
hot path made compiler-generated. Only the *output split bookkeeping* survives here.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from .. import _operations, diagnostics, factories, sanitation, types
from ..communication import get_comm
from ..dndarray import DNDarray
from ..stride_tricks import sanitize_axis
from . import comm_plan

__all__ = [
    "PARITY_PRECISION",
    "cross",
    "det",
    "dot",
    "inv",
    "matmul",
    "matrix_norm",
    "norm",
    "outer",
    "projection",
    "trace",
    "transpose",
    "tril",
    "triu",
    "vdot",
    "vecdot",
    "vector_norm",
]


# Per-op precision for numerics-parity-critical matmuls (the reference computes in
# full fp32/fp64 via torch). The MXU's bf16-input default is kept for the bulk
# compute path; decompositions and cancellation-prone kernels opt up to this.
PARITY_PRECISION = jax.lax.Precision.HIGHEST


def _contraction_precision(precision, *operands) -> Optional[jax.lax.Precision]:
    """Dtype-aware default precision for user-facing MXU contractions.

    An explicit ``precision`` always wins. Otherwise float32 operands get the
    full-f32 multi-pass MXU schedule so ``ht.matmul(f32, f32)`` matches numpy/torch
    to ~1e-7 like the reference (torch matmul is exact f32, ``basics.py:422``) —
    the MXU's native single-pass default would silently round inputs to bf16
    (~1e-2 error on unit-scale data). bf16/f16 inputs keep the fast native path;
    f64 is exact under any setting.
    """
    if precision is not None:
        return precision
    for o in operands:
        value = o.larray if isinstance(o, DNDarray) else o
        if getattr(value, "dtype", None) == jnp.float32:
            return jax.lax.Precision.HIGHEST
    return None


def _wrap_like(value: jax.Array, proto: DNDarray, split: Optional[int]) -> DNDarray:
    if split is not None and (split >= value.ndim or split < 0):
        split = None
    gshape = tuple(value.shape)
    value = proto.comm.shard(value, split)
    return DNDarray(
        value, gshape, types.canonical_heat_type(value.dtype), split, proto.device, proto.comm, True
    )


def matmul(
    a: DNDarray, b: DNDarray, allow_resplit: bool = False, precision=None
) -> DNDarray:
    """Matrix multiplication of distributed operands (reference ``basics.py:422``).

    Output split rule: a row-split ``a`` yields a row-split product; a column-split ``b``
    yields a column-split product; contraction-dim splits all-reduce away to ``None``;
    batch-dim splits are preserved (``HEAT_TPU_LINALG_PLAN=rs`` opts contraction-dim
    splits into a reduce-scatter with a ``split=0`` product instead).

    The data movement is chosen per call by the communication planner
    (:mod:`.comm_plan`): 2-D both-split pairs take the ring collective matmul
    (one panel in flight over ``ppermute``, the gathered operand never
    materialised); everything else defers to XLA SPMD's default (typically
    all-gather of the smaller panel riding ICI). ``HEAT_TPU_LINALG_PLAN``
    forces a plan; the choice is recorded as ``linalg.plan.*`` diagnostics.

    ``precision`` passes through to ``jnp.matmul`` — ``None`` picks a dtype-aware
    default (:func:`_contraction_precision`): full-f32 passes for float32 operands,
    the MXU-native fast path for bf16/f16.
    """
    with diagnostics.span("linalg.matmul", a) if diagnostics._enabled else diagnostics.NO_SPAN:
        sanitation.sanitize_in(a)
        sanitation.sanitize_in(b)
        precision = _contraction_precision(precision, a, b)
        planned = comm_plan.try_matmul(a, b, precision)
        if planned is not NotImplemented:
            return planned
        result = jnp.matmul(a.larray, b.larray, precision=precision)
        nd_out = result.ndim
        # position of a's row dim / b's col dim in the output (absent for 1-D operands)
        row_dim = nd_out - (2 if b.ndim >= 2 else 1) if a.ndim >= 2 else None
        col_dim = nd_out - 1 if b.ndim >= 2 else None
        split = None
        if a.ndim >= 2 and a.split == a.ndim - 2 and row_dim is not None and row_dim >= 0:
            split = row_dim
        elif b.ndim >= 2 and b.split == b.ndim - 1 and col_dim is not None and col_dim >= 0:
            split = col_dim
        elif a.split is not None and a.ndim >= 2 and a.split < a.ndim - 2:
            split = a.split  # batch dim
        elif b.split is not None and b.ndim >= 2 and b.split < b.ndim - 2:
            split = b.split
        if nd_out == 0:
            split = None
        return _wrap_like(result, a, split)


def dot(
    a: DNDarray, b: DNDarray, out: Optional[DNDarray] = None, precision=None
) -> Union[DNDarray, float]:
    """Dot product (reference ``basics.py:245``): inner product for 1-D, matmul for 2-D."""
    if isinstance(a, (int, float)) or isinstance(b, (int, float)) or a.ndim == 0 or b.ndim == 0:
        from .. import arithmetics

        return arithmetics.mul(a, b)
    if a.ndim == 1 and b.ndim == 1:
        result = jnp.dot(a.larray, b.larray, precision=_contraction_precision(precision, a, b))
        res = _wrap_like(result, a, None)
        return _operations.handle_out(res, out, a)
    ret = matmul(a, b, precision=precision)
    return _operations.handle_out(ret, out, a)


def vecdot(x1: DNDarray, x2: DNDarray, axis: Optional[int] = None, keepdims: bool = False) -> DNDarray:
    """Vector dot along an axis (reference ``basics.py`` vecdot)."""
    from .. import arithmetics

    m = arithmetics.mul(x1, x2)
    if axis is None:
        axis = m.ndim - 1
    return arithmetics.sum(m, axis=axis, keepdims=keepdims)


def vdot(x1: DNDarray, x2: DNDarray) -> DNDarray:
    """Conjugate dot of flattened inputs (reference ``basics.py`` vdot)."""
    result = jnp.vdot(x1.larray, x2.larray, precision=_contraction_precision(None, x1, x2))
    return _wrap_like(result, x1, None)


def outer(a: DNDarray, b: DNDarray, out: Optional[DNDarray] = None, split: Optional[int] = None) -> DNDarray:
    """Outer product (reference ``basics.py:1391`` — a ring algorithm there; a sharded
    broadcast-multiply here)."""
    sanitation.sanitize_in(a)
    sanitation.sanitize_in(b)
    result = jnp.outer(a.larray, b.larray)
    if split is None:
        split = 0 if a.split is not None else (1 if b.split is not None else None)
    res = _wrap_like(result, a, split)
    return _operations.handle_out(res, out, a)


def cross(
    a: DNDarray, b: DNDarray, axisa: int = -1, axisb: int = -1, axisc: int = -1, axis: int = -1
) -> DNDarray:
    """Cross product (reference ``basics.py`` cross)."""
    result = jnp.cross(a.larray, b.larray, axisa=axisa, axisb=axisb, axisc=axisc, axis=axis)
    return _wrap_like(result, a, a.split)


def det(a: DNDarray) -> DNDarray:
    """Determinant (reference ``basics.py:159`` — distributed LU there; XLA's LU here)."""
    sanitation.sanitize_in(a)
    if a.ndim < 2 or a.gshape[-1] != a.gshape[-2]:
        raise ValueError(f"last two dimensions must be square, got {a.gshape}")
    result = jnp.linalg.det(a.larray)
    return _wrap_like(result, a, None)


def inv(a: DNDarray) -> DNDarray:
    """Matrix inverse (reference ``basics.py:311`` — distributed Gauss-Jordan with Bcast;
    XLA's blocked LU-based inverse here, SPMD-partitioned over the mesh)."""
    sanitation.sanitize_in(a)
    if a.ndim < 2 or a.gshape[-1] != a.gshape[-2]:
        raise ValueError(f"last two dimensions must be square, got {a.gshape}")
    result = jnp.linalg.inv(a.larray)
    return _wrap_like(result, a, a.split)


def trace(a: DNDarray, offset: int = 0, axis1: int = 0, axis2: int = 1, dtype=None, out=None) -> Union[DNDarray, float]:
    """Sum along diagonals (reference ``basics.py:1642``)."""
    sanitation.sanitize_in(a)
    result = jnp.trace(a.larray, offset=offset, axis1=axis1, axis2=axis2)
    if dtype is not None:
        result = result.astype(types.canonical_heat_type(dtype).jax_type())
    res = _wrap_like(result, a, None)
    if out is not None:
        out.larray = res.larray
        return out
    if res.ndim == 0:
        return res.item()
    return res


def transpose(a: DNDarray, axes: Optional[Sequence[int]] = None) -> DNDarray:
    """Permute dimensions (reference ``basics.py:2057``): local permute + split remap."""
    sanitation.sanitize_in(a)
    if axes is None:
        axes = tuple(reversed(range(a.ndim)))
    else:
        axes = tuple(int(ax) + a.ndim if ax < 0 else int(ax) for ax in axes)
        if sorted(axes) != list(range(a.ndim)):
            raise ValueError(f"axes do not match tensor of dimension {a.ndim}")
    result = jnp.transpose(a.larray, axes)
    split = axes.index(a.split) if a.split is not None else None
    return _wrap_like(result, a, split)


def _tri_op(a: DNDarray, k: int, op) -> DNDarray:
    """Shared triangle logic (reference ``__tri_op`` ``basics.py:2127``)."""
    sanitation.sanitize_in(a)
    if a.ndim == 1:
        result = op(jnp.broadcast_to(a.larray, (a.gshape[0], a.gshape[0])), k=k)
        return _wrap_like(result, a, 0 if a.split is not None else None)
    return _operations.local_op(op, a, k=k)


def tril(m: DNDarray, k: int = 0) -> DNDarray:
    """Lower triangle (reference ``basics.py:2197``)."""
    return _tri_op(m, k, jnp.tril)


def triu(m: DNDarray, k: int = 0) -> DNDarray:
    """Upper triangle (reference ``basics.py:2220``)."""
    return _tri_op(m, k, jnp.triu)


def vector_norm(x: DNDarray, axis=None, keepdims: bool = False, ord=None) -> DNDarray:
    """Vector norm (reference ``basics.py:2315``)."""
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.gshape, axis)
    result = jnp.linalg.vector_norm(x.larray, axis=axis, keepdims=keepdims, ord=ord if ord is not None else 2)
    split = _operations._out_split_reduce(x, axis if axis is not None else None, keepdims)
    if axis is None:
        split = None
    return _wrap_like(result, x, split)


def matrix_norm(x: DNDarray, axis=None, keepdims: bool = False, ord=None) -> DNDarray:
    """Matrix norm (reference ``basics.py:1114``)."""
    sanitation.sanitize_in(x)
    if axis is None:
        if x.ndim < 2:
            raise ValueError("matrix_norm requires at least 2 dimensions")
        axis = (x.ndim - 2, x.ndim - 1)
    result = jnp.linalg.matrix_norm(x.larray, keepdims=keepdims, ord=ord if ord is not None else "fro")
    return _wrap_like(result, x, None)


def norm(x: DNDarray, axis=None, keepdims: bool = False, ord=None) -> DNDarray:
    """Unified norm entry (reference ``basics.py:1242``)."""
    sanitation.sanitize_in(x)
    if axis is None and ord is None:
        result = jnp.linalg.norm(x.larray.reshape(-1))
        return _wrap_like(result, x, None)
    axis = sanitize_axis(x.gshape, axis)
    if isinstance(axis, (tuple, list)) and len(axis) == 2:
        result = jnp.linalg.norm(x.larray, ord=ord, axis=tuple(axis), keepdims=keepdims)
        return _wrap_like(result, x, None)
    result = jnp.linalg.norm(x.larray, ord=ord, axis=axis, keepdims=keepdims)
    split = _operations._out_split_reduce(x, axis, keepdims) if axis is not None else None
    return _wrap_like(result, x, split)


def projection(a: DNDarray, b: DNDarray) -> DNDarray:
    """Projection of a onto b (reference ``basics.py`` projection)."""
    if a.ndim != 1 or b.ndim != 1:
        raise RuntimeError(f"projection gets 1-D vectors, got {a.ndim}-D and {b.ndim}-D")
    from .. import arithmetics

    scale = dot(a, b) / dot(b, b)
    return arithmetics.mul(scale, b)
