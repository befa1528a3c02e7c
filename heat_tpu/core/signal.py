"""Signal processing (reference heat/core/signal.py, 211 LoC).

The reference's distributed 1-D ``convolve`` pads, computes a halo size from the kernel's
local shape, exchanges halos with neighbouring ranks (``signal.py:107-120``, via
``DNDarray.get_halo``), and runs a local ``torch.conv1d`` per rank. The TPU form of that
halo pipeline is :func:`_convolve_overlap_add`: every shard convolves its chunk locally
and the (kernel-1)-wide boundary tail rides one ``ppermute`` hop to the next shard on
the ICI ring — overlap-add, the collective-permute dual of the reference's halo
exchange. Replicated or feature-split inputs fall back to one global ``jnp.convolve``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from . import types
from .dndarray import DNDarray

__all__ = ["convolve"]


def _convolve_overlap_add(comm, av: jax.Array, vv: jax.Array, n: int, m: int,
                          precision=None) -> jax.Array:
    """Distributed full convolution by overlap-add under ``shard_map``.

    Shard ``i`` holds ``c = n_pad/P`` samples and computes a local full convolution
    (length ``c+m-1``). The trailing ``m-1`` values overlap shard ``i+1``'s head: they
    are sent one hop down the ring (reference halo Isend/Irecv, ``dndarray.py:387-455``)
    and added. The global result is the shards' bodies back-to-back plus the last
    shard's tail — total length ``n+m-1`` after unpadding.
    """
    axis = comm.axis_name
    nproc = comm.size
    c = -(-n // nproc)
    n_pad = c * nproc
    if n_pad != n:
        av = jnp.pad(av, (0, n_pad - n))
    av = comm.shard(av, 0)

    def body(al, vl):
        y = jnp.convolve(al.reshape(-1), vl.reshape(-1), mode="full",
                         precision=precision)  # c+m-1
        tail = y[c:]  # my halo into the next shard's head
        recv = comm.ppermute(
            tail, [(i, i + 1) for i in range(nproc - 1)], axis_name=axis
        )
        out = y[:c].at[: m - 1].add(recv)
        return out, tail

    out, tails = jax.shard_map(
        body,
        mesh=comm.mesh,
        in_specs=(PartitionSpec(axis), PartitionSpec()),
        out_specs=(PartitionSpec(axis), PartitionSpec(axis)),
    )(av, vv)
    # bodies cover [0, n_pad); the final m-1 values come from the last shard's tail
    return jnp.concatenate([out, tails[-(m - 1) :]])[: n + m - 1]


def convolve(a, v, mode: str = "full") -> DNDarray:
    """Discrete linear convolution of two 1-D arrays (reference ``signal.py:16``)."""
    from . import factories

    if not isinstance(a, DNDarray):
        a = factories.array(a)
    if not isinstance(v, DNDarray):
        v = factories.array(v, comm=a.comm)
    if a.ndim != 1 or v.ndim != 1:
        raise ValueError("convolve requires 1-D inputs")
    if mode not in ("full", "same", "valid"):
        raise ValueError(f"unsupported mode {mode!r}")
    if mode == "same" and v.gshape[0] % 2 == 0:
        raise ValueError("mode 'same' is not supported for even-sized filter weights")
    if a.gshape[0] < v.gshape[0]:
        a, v = v, a
    dt = types.promote_types(a.dtype, v.dtype)
    av = a.larray.astype(dt.jax_type())
    vv = v.larray.astype(dt.jax_type())
    n, m = a.gshape[0], v.gshape[0]
    # the framework's contraction policy: float32 runs full-f32 passes (a TPU's
    # single-pass default rounds the inputs to bf16: 5e-3 off on unit-scale data)
    from .linalg.basics import _contraction_precision

    precision = _contraction_precision(None, av, vv)
    if a.split == 0 and a.is_distributed() and m >= 2 and m - 1 <= -(-n // a.comm.size):
        # distributed signal: explicit halo/overlap-add schedule on the ring
        full = _convolve_overlap_add(a.comm, av, vv, n, m, precision)
    else:
        full = jnp.convolve(av, vv, mode="full", precision=precision)
    if mode == "full":
        result = full
    elif mode == "same":
        off = (m - 1) // 2
        result = full[off : off + n]
    else:  # valid
        result = full[m - 1 : n]
    split = a.split
    out = a.comm.shard(result, split)
    return DNDarray(
        out, tuple(result.shape), types.canonical_heat_type(result.dtype), split,
        a.device, a.comm, True,
    )
