"""Array factories (reference heat/core/factories.py:20-1502).

The reference's central ingest chunked a global source per-rank with ``comm.chunk`` and
wrapped the local torch slice. Here factories materialise the global value with jnp and
lay it out over the mesh in one ``shard`` call — for large on-device constructions the
value is *created* sharded by XLA (fill/iota fuse with the sharding; no host round-trip).
"""

from __future__ import annotations

from typing import Any, Iterable, List, Optional, Sequence, Tuple, Type, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import types
from .communication import Communication, sanitize_comm
from .devices import Device, require_device_dtype, sanitize_device
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis, sanitize_shape

__all__ = [
    "arange",
    "array",
    "asarray",
    "empty",
    "empty_like",
    "eye",
    "from_partitioned",
    "from_partition_dict",
    "full",
    "full_like",
    "linspace",
    "logspace",
    "meshgrid",
    "ones",
    "ones_like",
    "zeros",
    "zeros_like",
]


def _wrap(
    value: jax.Array,
    dtype: Optional[Type[types.datatype]],
    split: Optional[int],
    device,
    comm,
    balanced: bool = True,
) -> DNDarray:
    device = sanitize_device(device)
    comm = sanitize_comm(comm)
    if dtype is not None:
        dtype = types.canonical_heat_type(dtype)
        if value.dtype != np.dtype(dtype.jax_type()):
            require_device_dtype(np.dtype(dtype.jax_type()))
            value = value.astype(dtype.jax_type())
    else:
        dtype = types.canonical_heat_type(value.dtype)
    split = sanitize_axis(value.shape, split)
    gshape = tuple(value.shape)
    value = comm.shard(value, split)
    return DNDarray(value, gshape, dtype, split, device, comm, balanced)


def arange(*args, dtype=None, split=None, device=None, comm=None) -> DNDarray:
    """``arange(stop)`` / ``arange(start, stop[, step])`` (reference ``factories.py:41``)."""
    num_args = len(args)
    if num_args == 1:
        start, stop, step = 0, args[0], 1
    elif num_args == 2:
        start, stop, step = args[0], args[1], 1
    elif num_args == 3:
        start, stop, step = args
    else:
        raise TypeError(f"function takes minimum one and at most 3 positional arguments ({num_args} given)")
    if dtype is None:
        # match the reference: all-int args → int32, otherwise default float
        if all(isinstance(a, (int, np.integer)) for a in (start, stop, step)):
            value = jnp.arange(start, stop, step, dtype=jnp.int32)
        else:
            value = jnp.arange(start, stop, step, dtype=jnp.float32)
    else:
        jt = types.canonical_heat_type(dtype).jax_type()
        require_device_dtype(np.dtype(jt))
        value = jnp.arange(start, stop, step, dtype=jt)
    return _wrap(value, dtype, split, device, comm)


def array(
    obj: Any,
    dtype=None,
    copy: Optional[bool] = None,
    ndmin: int = 0,
    order: str = "C",
    split: Optional[int] = None,
    is_split: Optional[int] = None,
    device=None,
    comm=None,
) -> DNDarray:
    """Central array ingest (reference ``factories.py:149``).

    Accepts nested sequences, numpy arrays, jax arrays, torch tensors and DNDarrays.
    ``split`` chunks a global source over the mesh; ``is_split`` declares ``obj`` to be
    this *process*'s pre-distributed chunk along that axis (reference ``:188`` infers the
    global shape by allgathering local shapes — in single-controller JAX the process owns
    every shard, so the local chunk is the global value).
    """
    if split is not None and is_split is not None:
        raise ValueError(f"split and is_split are mutually exclusive, got {split}, {is_split}")
    if order not in ("C", "K"):
        raise NotImplementedError("only row-major memory layout is supported on TPU")

    if isinstance(obj, DNDarray):
        comm = comm or obj.comm
        device = device or obj.device
        if split is None and is_split is None:
            split = obj.split
        value = obj.larray
    else:
        # torch tensors (CPU) convert via numpy; everything else through jnp/np
        if type(obj).__module__.startswith("torch"):
            obj = obj.detach().cpu().numpy()
        if isinstance(obj, jax.Array):
            value = obj
        else:
            np_value = np.asarray(obj)
            if dtype is None and np_value.dtype == np.float64 and not (
                isinstance(obj, np.ndarray) or isinstance(obj, np.generic)
            ):
                # python floats default to the framework float type (f32), like torch/heat
                np_value = np_value.astype(np.float32)
            if dtype is not None and np.iscomplexobj(np_value):
                target = np.dtype(types.canonical_heat_type(dtype).jax_type())
                if np.issubdtype(target, np.complexfloating):
                    # cast on the host: a complex128 value must never reach a
                    # TPU program (devices.require_device_dtype)
                    np_value = np_value.astype(target)
            require_device_dtype(np_value.dtype)
            value = jnp.asarray(np_value)

    while value.ndim < ndmin:
        value = value[jnp.newaxis]

    if is_split is not None:
        is_split = sanitize_axis(value.shape, is_split)
        if jax.process_count() > 1:
            # each process declared its own pre-distributed chunk: infer the global
            # shape by allgathering local shapes (reference factories.py:188) and
            # assemble without moving data off-host
            from jax.experimental import multihost_utils

            comm_obj = sanitize_comm(comm)
            np_value = np.asarray(value)
            all_shapes = np.asarray(
                multihost_utils.process_allgather(jnp.asarray(np.array(np_value.shape)))
            ).reshape(jax.process_count(), np_value.ndim)
            for d in range(np_value.ndim):
                if d != is_split and not np.all(all_shapes[:, d] == np_value.shape[d]):
                    raise ValueError(
                        f"is_split chunks disagree on non-split dim {d}: {all_shapes[:, d]}"
                    )
            gshape = list(np_value.shape)
            gshape[is_split] = int(all_shapes[:, is_split].sum())
            # jax can only assemble process-local chunks that match the even canonical
            # partition; the reference accepts arbitrary chunk sizes (factories.py:188)
            # — reject the unrepresentable case loudly rather than mis-assemble
            per_proc = gshape[is_split] // jax.process_count()
            if gshape[is_split] % jax.process_count() != 0 or not np.all(
                all_shapes[:, is_split] == per_proc
            ):
                raise NotImplementedError(
                    f"multi-controller is_split needs equal per-process chunks "
                    f"(got extents {all_shapes[:, is_split].tolist()}); pad or "
                    f"rebalance the local chunks before ingest"
                )
            garr = jax.make_array_from_process_local_data(
                comm_obj.sharding(np_value.ndim, is_split), np_value, tuple(gshape)
            )
            return _wrap(garr, dtype, is_split, device, comm)
        return _wrap(value, dtype, is_split, device, comm)
    return _wrap(value, dtype, split, device, comm)


def asarray(obj, dtype=None, copy=None, order="C", is_split=None, device=None) -> DNDarray:
    """Convert to DNDarray, no-copy when possible (reference ``factories.py:463``)."""
    if (
        is_split is None
        and copy is not True
        and isinstance(obj, DNDarray)
        and (dtype is None or obj.dtype is types.canonical_heat_type(dtype))
        and (device is None or obj.device == sanitize_device(device))
    ):
        return obj
    return array(obj, dtype=dtype, copy=copy, order=order, is_split=is_split, device=device)


def __factory(shape, dtype, split, maker, device, comm, order="C") -> DNDarray:
    """Shared logic of empty/ones/zeros/full (reference ``factories.py:699``)."""
    shape = sanitize_shape(shape)
    dtype = types.canonical_heat_type(dtype)
    require_device_dtype(np.dtype(dtype.jax_type()))
    value = maker(shape, dtype=dtype.jax_type())
    return _wrap(value, dtype, split, device, comm)


def empty(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Uninitialised array (reference ``factories.py:522``); XLA has no uninitialised
    allocation, so this is a zero fill fused into consumers."""
    return __factory(shape, dtype, split, jnp.zeros, device, comm, order)


def zeros(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Zeros (reference ``factories.py:1388``)."""
    return __factory(shape, dtype, split, jnp.zeros, device, comm, order)


def ones(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Ones (reference ``factories.py:1184``)."""
    return __factory(shape, dtype, split, jnp.ones, device, comm, order)


def full(shape, fill_value, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Constant fill (reference ``factories.py:957``)."""
    shape = sanitize_shape(shape)
    target = (
        np.result_type(fill_value)
        if dtype is None
        else np.dtype(types.canonical_heat_type(dtype).jax_type())
    )
    require_device_dtype(target)
    if dtype is None:
        value = jnp.full(shape, fill_value)
        if value.dtype == jnp.float64 and isinstance(fill_value, float):
            value = value.astype(jnp.float32)
    else:
        if isinstance(fill_value, complex) and np.issubdtype(target, np.complexfloating):
            # cast on the host: a Python complex would enter the program as a
            # (weak) complex128 scalar (devices.require_device_dtype)
            fill_value = target.type(fill_value)
        value = jnp.full(shape, fill_value, dtype=target)
    return _wrap(value, dtype, split, device, comm)


def __factory_like(a, dtype, split, factory, device, comm, **kwargs) -> DNDarray:
    """Shared logic of the *_like factories (reference ``factories.py:753``)."""
    shape = a.shape if isinstance(a, (DNDarray, np.ndarray, jax.Array)) else np.asarray(a).shape
    if dtype is None:
        try:
            dtype = types.heat_type_of(a)
        except TypeError:
            dtype = types.float32
    if split is None and isinstance(a, DNDarray):
        split = a.split
    if device is None and isinstance(a, DNDarray):
        device = a.device
    if comm is None and isinstance(a, DNDarray):
        comm = a.comm
    return factory(shape, dtype=dtype, split=split, device=device, comm=comm, **kwargs)


def _sanitize_order(order: str) -> None:
    """Same stance as :func:`array`: row-major only on TPU; anything else is loud."""
    if order not in ("C", "K", None):
        raise NotImplementedError("only row-major memory layout is supported on TPU")


def empty_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    _sanitize_order(order)
    return __factory_like(a, dtype, split, empty, device, comm)


def zeros_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    _sanitize_order(order)
    return __factory_like(a, dtype, split, zeros, device, comm)


def ones_like(a, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    _sanitize_order(order)
    return __factory_like(a, dtype, split, ones, device, comm)


def full_like(a, fill_value, dtype=None, split=None, device=None, comm=None, order="C") -> DNDarray:
    _sanitize_order(order)
    shape = a.shape if isinstance(a, (DNDarray, np.ndarray, jax.Array)) else np.asarray(a).shape
    if split is None and isinstance(a, DNDarray):
        split = a.split
    return full(shape, fill_value, dtype=dtype, split=split, device=device, comm=comm)


def eye(shape, dtype=types.float32, split=None, device=None, comm=None, order="C") -> DNDarray:
    """Identity-like 2-D array (reference ``factories.py:865``)."""
    _sanitize_order(order)
    if isinstance(shape, (int, np.integer)):
        n, m = int(shape), int(shape)
    else:
        shape = tuple(shape)
        if len(shape) == 1:
            n = m = int(shape[0])
        else:
            n, m = int(shape[0]), int(shape[1])
    dtype = types.canonical_heat_type(dtype)
    require_device_dtype(np.dtype(dtype.jax_type()))
    value = jnp.eye(n, m, dtype=dtype.jax_type())
    return _wrap(value, dtype, split, device, comm)


def linspace(
    start,
    stop,
    num: int = 50,
    endpoint: bool = True,
    retstep: bool = False,
    dtype=None,
    split=None,
    device=None,
    comm=None,
):
    """Evenly spaced samples (reference ``factories.py:1021``)."""
    num = int(num)
    if num < 0:
        raise ValueError(f"number of samples 'num' must be non-negative, got {num}")
    step = (stop - start) / max(1, num - (1 if endpoint else 0))
    value = jnp.linspace(start, stop, num, endpoint=endpoint)
    if dtype is None and value.dtype == jnp.float64:
        value = value.astype(jnp.float32)
    ht = _wrap(value, dtype, split, device, comm)
    if retstep:
        return ht, step
    return ht


def logspace(
    start, stop, num=50, endpoint=True, base=10.0, dtype=None, split=None, device=None, comm=None
) -> DNDarray:
    """Log-spaced samples (reference ``factories.py:1101``)."""
    value = jnp.logspace(start, stop, int(num), endpoint=endpoint, base=base)
    if dtype is None and value.dtype == jnp.float64:
        value = value.astype(jnp.float32)
    return _wrap(value, dtype, split, device, comm)


def meshgrid(*arrays: DNDarray, indexing: str = "xy") -> List[DNDarray]:
    """Coordinate matrices from coordinate vectors (reference ``factories.py:1140``).

    The reference splits the output along the dimension that carried a split input; same
    bookkeeping here.
    """
    if indexing not in ("xy", "ij"):
        raise ValueError(f"indexing must be 'xy' or 'ij', got {indexing}")
    arrs = [asarray(a) for a in arrays]
    split_in = next((i for i, a in enumerate(arrs) if a.split is not None), None)
    values = jnp.meshgrid(*[a.larray for a in arrs], indexing=indexing)
    out_split = None
    if split_in is not None and len(arrs) > 1:
        # dim order of the output: 'xy' swaps the first two dims
        out_split = split_in
        if indexing == "xy":
            if split_in == 0:
                out_split = 1
            elif split_in == 1:
                out_split = 0
    comm = arrs[0].comm if arrs else None
    device = arrs[0].device if arrs else None
    return [_wrap(v, None, out_split, device, comm) for v in values]


def from_partitioned(x, comm=None) -> DNDarray:
    """Build a DNDarray from an object exposing ``__partitioned__``
    (reference ``factories.py:823``)."""
    parts = x.__partitioned__ if not isinstance(x, dict) else x
    return from_partition_dict(parts, comm=comm)


def from_partition_dict(parted: dict, comm=None) -> DNDarray:
    """Build a DNDarray from a ``__partitioned__`` dict (reference ``factories.py:868``)."""
    comm = sanitize_comm(comm)
    shape = tuple(parted["shape"])
    getter = parted.get("get", lambda v: v)
    tiling = tuple(parted.get("partition_tiling", (1,) * len(shape)))
    split_dims = [i for i, t in enumerate(tiling) if t > 1]
    if len(split_dims) > 1:
        raise ValueError(f"Only one split-dimension allowed, got {len(split_dims)}")
    split = split_dims[0] if split_dims else None
    ordered = sorted(parted["partitions"].items(), key=lambda kv: kv[1]["start"])
    locals_ = [np.asarray(getter(p["data"])) for _, p in ordered if p["data"] is not None]
    if split is None:
        value = jnp.asarray(locals_[0])
    else:
        value = jnp.concatenate([jnp.asarray(l) for l in locals_], axis=split)
    if tuple(value.shape) != shape:
        raise ValueError(f"partitioned data of shape {tuple(value.shape)} does not match declared {shape}")
    return _wrap(value, None, split, None, comm)
