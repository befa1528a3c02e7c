"""Generic operation dispatch (reference heat/core/_operations.py:22-532).

The reference's four wrappers hand-roll type promotion, broadcasting, operand
redistribution and MPI reductions. Here the data is a global ``jax.Array``, so:

- ``__binary_op`` (reference ``:22-227``): the "dominant operand defines the output split"
  rule survives as *metadata*; the physical redistribution the reference performs via
  ``sanitize_distribution`` is replaced by XLA's sharding propagation — the jnp call
  simply computes, and the result is constrained to the chosen split.
- ``__reduce_op`` (reference ``:404-532``): the local-partial-then-Allreduce dance becomes
  one jnp reduction; XLA emits the all-reduce over the mesh axis when the reduction
  crosses the split dimension. Neutral-element handling for empty shards (reference
  ``:450-459``) is unnecessary — XLA reduces over the global value.
- ``__cum_op`` (reference ``:230-328``): local cumop + Exscan + combine becomes one jnp
  cumulative op; XLA lowers the cross-shard carry.
- ``__local_op`` (reference ``:331``): elementwise jnp call, split unchanged.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import _executor, _result_cache, diagnostics, profiler, sanitation, types
from .communication import get_comm
from .devices import get_device, promoted_dtype, require_device_dtype
from .dndarray import DNDarray
from .stride_tricks import broadcast_shapes, sanitize_axis

__all__ = ["binary_op", "local_op", "reduce_op", "cum_op", "wrap_result", "handle_out"]

Scalar = (int, float, bool, complex, np.number, np.bool_)


def _profiled_dispatch(family: str):
    """Wrap one of the four dispatch wrappers in an ``ht.profiler`` slice so
    every framework-level op attributes to the ambient request scope
    (``profiler.request``). Idle cost is the wrapper indirection plus one
    module-attribute read — nothing is ever injected into traced bodies, so
    compiled HLO is identical with the profiler on, off, or never used (the
    dispatch ops/s baseline gate enforces the idle cost in CI)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapped(operation, *args, **kwargs):
            if not profiler._active:
                return fn(operation, *args, **kwargs)
            with profiler.scope(
                "dispatch", f"{family}:{_executor._op_label(operation)}"
            ):
                return fn(operation, *args, **kwargs)

        return wrapped

    return deco


# --------------------------------------------------------------------- padded layout
# Ragged split extents (n % P != 0) are stored physically padded to ceil(n/P)*P so
# shards are a true 1/P (SURVEY §7; DNDarray.parray). ``larray`` on such an array
# eagerly slices the padding off, which GSPMD resolves to a REPLICATED value — O(n)
# per device. The wrappers below therefore compute directly on the padded physical
# value whenever the operand pattern allows it, so ragged compute is O(n/P) like the
# reference's chunk-local ops (reference ``_operations.py:22-227``).
#
# Physical invariant: **pad slots always hold zero.** ``comm.shard`` zero-pads, and
# every padded-path op re-masks its result (one ``where`` against a length-m iota —
# XLA fuses it into the producing op, so pads never round-trip through HBM as
# garbage). Guards like ``jnp.isnan(x.parray).any()`` stay exact under it.


# shared with the deferred-graph force in _executor (defined there to avoid a
# circular import); re-exported here for the wrappers and their tests
_pad_mask = _executor._pad_mask
_zero_pads = _executor._zero_pads


def _staged_spec(family, operation, fn_kwargs, xval, gshape, split, comm,
                 **extra):
    """The JSON-able replay description of one staged ``l``/``r``/``c``
    signature — the persistent compile cache's portable fingerprint source
    (``_compile_cache``). None when the op is not a plain ``jax.numpy`` name
    (the rule that guarantees a warm process rebuilds the SAME signature key
    real traffic will look up) or the kwargs do not round-trip through JSON
    (raises; the lookup counts it as a warmup-spec gap)."""
    import json

    name = getattr(operation, "__name__", None)
    if not name or getattr(jnp, name, None) is not operation:
        return None
    if fn_kwargs and json.loads(json.dumps(fn_kwargs)) != fn_kwargs:
        # kwargs must survive the JSON round-trip VALUE-identically: a tuple
        # kwarg serialises fine but replays as a list, which kwargs_sig
        # rejects as unhashable — the signature could never be warmed, so
        # it is not recorded at all (counted as a warmup-spec gap)
        return None
    if extra:
        json.dumps(extra)  # raises (caught by lookup) when not portable
    mesh = comm.mesh
    spec = {
        "family": family, "op": name,
        "kwargs": dict(fn_kwargs) if fn_kwargs else {},
        "gshape": list(gshape), "split": split,
        "dtype": np.dtype(xval.dtype).str, "phys": list(xval.shape),
        "mesh": {"shape": list(mesh.devices.shape),
                 "axes": list(mesh.axis_names)},
    }
    spec.update(extra)
    return spec


def _note_pad_waste(gshape, split: Optional[int], comm) -> None:
    """Gauge the padded-layout waste of the ``(gshape, split)`` family this
    dispatch touched (ht.diagnostics pad_waste). Callers gate on
    ``diagnostics._enabled`` so the disabled cost is one attribute read."""
    if split is None:
        return
    diagnostics.record_pad_waste(gshape, split, comm.padded_dim(gshape[split]))


def _is_complexish(*ts) -> bool:
    for t in ts:
        if isinstance(t, DNDarray) and jnp.issubdtype(t.dtype.jax_type(), jnp.complexfloating):
            return True
        if isinstance(t, complex) and not isinstance(t, bool):
            return True
    return False


def _padded_physical_operands(pair, out_shape, out_split, comm):
    """Physical (padded) operand values for the ragged binary fast path, or ``None``
    when this operand pattern can't ride it. Each operand is either

    - a scalar (broadcasts over pads harmlessly),
    - full-extent along the out split dim → its padded physical value (``parray`` if
      already laid out, else ``comm.shard`` pads it into the layout), or
    - broadcast along the out split dim (dim absent or extent 1) and itself unpadded
      → its logical value.
    """
    nd = len(out_shape)
    ops = []
    for t, arr in pair:
        if np.isscalar(t):
            ops.append(t)
            continue
        pos = out_split - (nd - arr.ndim)
        if pos >= 0 and pos < arr.ndim and arr.gshape[pos] == out_shape[out_split]:
            if arr._is_padded():
                if arr.split == pos:
                    ops.append(arr.parray)
                    continue
                return None  # padded along a different dim: no cheap physical form
            ops.append(comm.shard(arr.larray, pos))
            continue
        if (pos < 0 or arr.gshape[pos] == 1) and not arr._is_padded():
            ops.append(arr.larray)
            continue
        return None
    return ops


def _ensure_dndarray(x, device=None, comm=None) -> DNDarray:
    from . import factories

    if isinstance(x, DNDarray):
        return x
    if type(x) is complex:
        # a Python complex is weakly typed: its wrapper (shape/split bookkeeping
        # only — the op itself takes the scalar) must not be a complex128 array,
        # which a TPU refuses (devices.require_device_dtype)
        x = np.complex64(x)
    return factories.array(x, device=device, comm=comm)


def wrap_result(value, proto: DNDarray, split: Optional[int]) -> DNDarray:
    """Wrap a raw jax value in a DNDarray with ``proto``'s device/comm, normalising an
    out-of-range split to None and laying the value out accordingly (ragged split
    extents store physically padded — comm.shard)."""
    if split is not None and (value.ndim == 0 or split >= value.ndim or split < 0):
        split = None
    gshape = tuple(value.shape)
    value = proto.comm.shard(value, split)
    return DNDarray(
        value,
        gshape,
        types.canonical_heat_type(value.dtype),
        split,
        proto.device,
        proto.comm,
        True,
    )


def handle_out(res: DNDarray, out: Optional[DNDarray], proto: DNDarray) -> DNDarray:
    """Write ``res`` into a user-provided ``out`` buffer, casting to its dtype."""
    if out is None:
        return res
    sanitation.sanitize_out(out, res.gshape, res.split, proto.device)
    out._rebind_physical(proto.comm.shard(_safe_astype(res.larray, out.dtype.jax_type()), out.split))
    return out


def _safe_astype(value, jax_dtype):
    """``value.astype(jax_dtype)``, refusing a target dtype the device cannot hold
    (``devices.require_device_dtype``)."""
    require_device_dtype(jax_dtype)
    return value.astype(jax_dtype)


def _complex_operands(*vals):
    """Operands of an eagerly dispatched op, checked against what the device can
    hold (``devices.require_device_dtype`` on the promoted result type). Python
    complex scalars of a complex64 result are narrowed on the host: jnp would pass
    them into the program as (weak) complex128 scalars, which a TPU cannot compile."""
    rt = promoted_dtype(*vals)
    require_device_dtype(rt)
    if rt == np.complex64:
        return tuple(np.complex64(v) if isinstance(v, complex) else v for v in vals)
    return vals


def _out_split_binary(out_shape: Tuple[int, ...], *operands: DNDarray) -> Optional[int]:
    """Dominant-operand split rule (reference ``_operations.py:71-75``): a split operand
    beats an unsplit one; a split on a non-broadcast dim beats a split on a broadcast dim;
    the first operand beats the second."""
    nd = len(out_shape)
    best = None
    for arr in operands:
        if not isinstance(arr, DNDarray) or arr.split is None:
            continue
        s = arr.split + (nd - arr.ndim)
        broadcasted = arr.gshape[arr.split] == 1 and out_shape[s] != 1
        if not broadcasted:
            return s
        if best is None:
            best = s
    return best


# ----------------------------------------------------------------- staged executor
# The four wrappers stage their whole chain — compute → pad re-mask → dtype cast →
# physical pad — as ONE signature-cached jit program (_executor), with the output
# NamedSharding applied by the program itself, so the epilogues genuinely fuse into
# the producing op instead of running as separate XLA executions. Signatures the
# stager rejects (and HEAT_TPU_EAGER_DISPATCH=1) fall through to the eager code
# below, which is the original dispatch path, unchanged.


class _StageBail(Exception):
    """Raised inside a build-time shape probe: this signature takes the eager path."""


# --------------------------------------------------------- deferred (fused) dispatch
# Supported elementwise ops do not execute at call time at all: they append a node
# to the executor's expression graph (see _executor.Deferred) and the whole chain
# compiles/replays as ONE program when the result's physical value is first read.
# Only the strictly slot-aligned case defers — every array operand shares one
# (gshape, split, comm) family — so no broadcasting, slicing or re-layout ever
# happens inside a fused graph; everything else takes the immediate one-op staged
# paths below.


def _binary_defer(operation, t1, t2, fn_kwargs):
    """Append a binary op to the expression graph; NotImplemented → staged/eager."""
    proto = None
    raw = []
    for t in (t1, t2):
        if isinstance(t, DNDarray):
            if proto is None:
                proto = t
            elif (
                t.gshape != proto.gshape
                or t.split != proto.split
                or t.comm is not proto.comm
            ):
                return NotImplemented
            payload = t._payload
            raw.append(("d" if isinstance(payload, _executor.Deferred) else "a", payload))
        elif np.isscalar(t):
            raw.append(("s", t))
        else:
            return NotImplemented
    if proto is None:
        return NotImplemented
    node = _executor.defer_node(
        operation, fn_kwargs, raw, proto.gshape, proto.split, proto.comm
    )
    if node is _executor.UNSUPPORTED:
        return NotImplemented
    res = DNDarray(
        node, proto.gshape, types.canonical_heat_type(node.dtype), proto.split,
        proto.device, proto.comm, True,
    )
    # liveness registry: while this DNDarray lives, any program that executes
    # the node must emit (memoise) its value — the user can still read it
    _executor.note_wrapped(node, res)
    return res


def _local_defer(operation, x, fn_kwargs):
    """Append an elementwise op to the expression graph; NotImplemented → staged."""
    payload = x._payload
    node = _executor.defer_node(
        operation, fn_kwargs,
        [("d" if isinstance(payload, _executor.Deferred) else "a", payload)],
        x.gshape, x.split, x.comm,
    )
    if node is _executor.UNSUPPORTED:
        return NotImplemented
    res = DNDarray(
        node, x.gshape, types.canonical_heat_type(node.dtype), x.split,
        x.device, x.comm, x.balanced,
    )
    _executor.note_wrapped(node, res)
    return res


def _pad_physical(value, padded_shape: Tuple[int, ...], split: int):
    """Zero-pad ``value``'s split dimension to the physical padded extent inside a
    traced program — the staged form of ``comm.shard``'s ragged concatenate."""
    if tuple(value.shape) == tuple(padded_shape):
        return value
    pad_shape = (
        padded_shape[:split]
        + (padded_shape[split] - value.shape[split],)
        + padded_shape[split + 1 :]
    )
    return jnp.concatenate([value, jnp.zeros(pad_shape, value.dtype)], axis=split)


def _lslice(gshape) -> Tuple[slice, ...]:
    return tuple(slice(0, s) for s in gshape)


def _replicated(value, comm):
    """Constrain a traced value to the replicated layout. Applied after an in-program
    logical slice of a padded operand so a staged reduction/scan sees the same
    (replicated) operand layout the eager path materialises — keeping the partial
    reduction order, and therefore the float bits, identical to eager dispatch."""
    return jax.lax.with_sharding_constraint(value, comm.sharding(value.ndim, None))


def _binary_jit(
    operation, t1, t2, a, b, out, where, fn_kwargs, out_shape, out_split, comm, device
):
    """Stage a binary op through the executor; NotImplemented → eager path."""
    op = _executor.op_sig(operation)
    kwsig = _executor.kwargs_sig(fn_kwargs)
    if op is _executor.UNSUPPORTED or kwsig is _executor.UNSUPPORTED:
        return NotImplemented
    if out is not None and jnp.issubdtype(out.dtype.jax_type(), jnp.complexfloating):
        return NotImplemented  # complex targets take the eager path (_safe_astype checks them)
    nd = len(out_shape)
    phys_shape = comm.padded_shape(out_shape, out_split)

    # ragged fast path: identical operand staging to the eager padded route, with
    # the re-mask fused into the producing op
    if out is None and where is None and phys_shape != tuple(out_shape):
        phys = _padded_physical_operands(((t1, a), (t2, b)), out_shape, out_split, comm)
        if phys is not None:
            key = (
                "b.pad", op, kwsig, tuple(out_shape), out_split, comm.mesh,
                tuple(_executor.operand_sig(p) for p in phys),
            )

            def build():
                def body(x1, x2):
                    r = operation(x1, x2, **fn_kwargs)
                    return _zero_pads(r, out_shape, out_split)

                return body, comm.sharding(nd, out_split), None, None

            prog = _executor.lookup(key, build)
            if prog is None:
                return NotImplemented
            try:
                value = prog(*phys)
            except Exception as exc:
                # compile/execute failure: replay the same math on the eager
                # path below (no donation involved — always safe)
                if not _executor.fallback_after_failure(key, prog, exc):
                    raise
                return NotImplemented
            if diagnostics._enabled:
                _note_pad_waste(out_shape, out_split, comm)
            return DNDarray(
                value, tuple(out_shape), types.canonical_heat_type(value.dtype),
                out_split, device or get_device(), comm, True,
            )

    # logical path: operands enter physically (padded layouts sliced in-program)
    vals, slices, sigs = [], [], []
    for t, arr in ((t1, a), (t2, b)):
        if np.isscalar(t):
            vals.append(t)
            slices.append(None)
            sigs.append((_executor.operand_sig(t), None))
        else:
            vals.append(arr.parray)
            sl = arr.gshape if arr._is_padded() else None
            slices.append(sl)
            sigs.append((_executor.operand_sig(arr.parray), sl))
    w_sig = None
    if where is not None:
        if isinstance(where, DNDarray):
            wv = where.parray
            wsl = where.gshape if where._is_padded() else None
        else:
            wv = jnp.asarray(where)
            wsl = None
        wshape = wsl if wsl is not None else tuple(wv.shape)
        try:
            if broadcast_shapes(wshape, out_shape) != tuple(out_shape):
                return NotImplemented  # where broadcasts beyond the result shape
        except ValueError:
            return NotImplemented
        vals.append(wv)
        slices.append(wsl)
        w_sig = (_executor.operand_sig(wv), wsl)
    out_sig = None
    donate = False
    if out is not None:
        sanitation.sanitize_out(out, out_shape, out_split, device)
        donate = sanitation.sanitize_donation(out, vals)
        out_sig = (_executor.operand_sig(out.parray), out._is_padded())
    key = (
        "b.log", op, kwsig, tuple(out_shape), out_split, comm.mesh,
        tuple(sigs), w_sig, out_sig,
    )
    has_where = where is not None
    has_out = out is not None
    out_dtype = out.dtype.jax_type() if has_out else None
    out_padded = has_out and out._is_padded()

    def build():
        op_slices = [None if g is None else _lslice(g) for g in slices]
        base_slice = _lslice(out_shape) if out_padded else None

        def body(*argv):
            xs = [
                v if sl is None else v[sl]
                for v, sl in zip(argv[: len(op_slices)], op_slices)
            ]
            r = operation(xs[0], xs[1], **fn_kwargs)
            if has_where:
                w = xs[2]
                if has_out:
                    base = argv[-1] if base_slice is None else argv[-1][base_slice]
                else:
                    base = jnp.zeros(out_shape, r.dtype)
                r = jnp.where(w, r, base)
            if has_out:
                r = r.astype(out_dtype)
            if phys_shape != tuple(out_shape):
                r = _pad_physical(r, phys_shape, out_split)
            return r

        donate_index = len(op_slices) if has_out else None
        return body, comm.sharding(nd, out_split), donate_index, None

    prog = _executor.lookup(key, build)
    if prog is None:
        return NotImplemented
    if diagnostics._enabled and phys_shape != tuple(out_shape):
        _note_pad_waste(out_shape, out_split, comm)
    try:
        if has_out:
            if donate and _result_cache._enabled:
                # out= donation consumes the destination buffer: drop every
                # memoised result aliasing it before XLA invalidates it
                _result_cache.note_donation((id(out.parray),))
            value = prog(*vals, out.parray, donate=donate)
            out._rebind_physical(value)
            return out
        value = prog(*vals)
    except Exception as exc:
        # the eager path re-runs the op unless a donated out buffer was
        # already invalidated by the failed call (then replay would be a lie)
        if not _executor.fallback_after_failure(
            key, prog, exc, donated=(out.parray,) if has_out and donate else ()
        ):
            raise
        return NotImplemented
    return DNDarray(
        value, tuple(out_shape), types.canonical_heat_type(value.dtype),
        out_split, device or get_device(), comm, True,
    )


def _local_jit(operation, x, out, fn_kwargs):
    """Stage an elementwise op through the executor; NotImplemented → eager path."""
    op = _executor.op_sig(operation)
    kwsig = _executor.kwargs_sig(fn_kwargs)
    if op is _executor.UNSUPPORTED or kwsig is _executor.UNSUPPORTED:
        return NotImplemented
    if out is not None and jnp.issubdtype(out.dtype.jax_type(), jnp.complexfloating):
        return NotImplemented
    comm = x.comm
    xval = x.parray
    x_padded = x._is_padded()
    gshape, split = x.gshape, x.split
    out_sig = None
    if out is not None:
        out_sig = (np.dtype(out.dtype.jax_type()).str,)
    key = (
        "l", op, kwsig, _executor.operand_sig(xval), tuple(gshape), split,
        comm.mesh, out_sig,
    )
    has_out = out is not None
    out_dtype = out.dtype.jax_type() if has_out else None

    def build():
        aval = jax.ShapeDtypeStruct(xval.shape, xval.dtype)
        lsl = _lslice(gshape) if x_padded else None
        if x_padded and not has_out:
            # padded fast path: same decision rule as the eager route — result
            # keeps the physical shape and stays non-complex
            probe = jax.eval_shape(lambda v: operation(v, **fn_kwargs), aval)
            if tuple(probe.shape) == tuple(xval.shape) and not jnp.issubdtype(
                probe.dtype, jnp.complexfloating
            ):

                def body(v):
                    r = operation(v, **fn_kwargs)
                    return _zero_pads(r, gshape, split)

                return body, comm.sharding(len(gshape), split), None, ("fast", gshape, split)

        def logical(v):
            if lsl is not None:
                v = v[lsl]
            return operation(v, **fn_kwargs)

        try:
            probe = jax.eval_shape(logical, aval)
        except Exception as exc:
            # unstageable signature: the eager path below re-runs the op and
            # surfaces the real error if there is one. Counted + explained in
            # ht.diagnostics (exception type + op label), never silent.
            if diagnostics._enabled:
                diagnostics.record_fallback(
                    "dispatch.local",
                    f"{_executor._op_label(operation)}: {type(exc).__name__}: {exc}",
                )
            return _executor.UNSUPPORTED
        rshape = tuple(probe.shape)
        if jnp.issubdtype(probe.dtype, jnp.complexfloating):
            return _executor.UNSUPPORTED  # complex results take the eager path (comm.shard checks them)
        if has_out:
            if rshape != tuple(gshape):
                return _executor.UNSUPPORTED
            phys = comm.padded_shape(gshape, split)

            def body(v, ob):
                r = logical(v).astype(out_dtype)
                if phys != tuple(gshape):
                    r = _pad_physical(r, phys, split)
                return r

            return body, comm.sharding(len(gshape), split), 1, ("out", gshape, split)
        if split is not None and split >= len(rshape):
            return _executor.UNSUPPORTED  # eager raises on the out-of-range spec
        phys = comm.padded_shape(rshape, split)

        def body(v):
            r = logical(v)
            if phys != rshape:
                r = _pad_physical(r, phys, split)
            return r

        return body, comm.sharding(len(rshape), split), None, ("wrap", rshape, split)

    prog = _executor.lookup(
        key, build,
        spec=lambda: None if has_out else _staged_spec(
            "l", operation, fn_kwargs, xval, gshape, split, comm
        ),
    )
    if prog is None:
        return NotImplemented
    if diagnostics._enabled and x_padded:
        _note_pad_waste(gshape, split, comm)
    kind, rshape, rsplit = prog.meta
    if kind == "out":
        sanitation.sanitize_out(out, gshape, split, x.device)
        donate = sanitation.sanitize_donation(out, [xval])
        if donate and _result_cache._enabled:
            # out= donation consumes the destination buffer: drop every
            # memoised result aliasing it before XLA invalidates it
            _result_cache.note_donation((id(out.parray),))
        try:
            value = prog(xval, out.parray, donate=donate)
        except Exception as exc:
            if not _executor.fallback_after_failure(
                key, prog, exc, donated=(out.parray,) if donate else ()
            ):
                raise
            return NotImplemented
        out._rebind_physical(value)
        return out
    try:
        # the scheduler-routed call: batches concurrent same-signature staged
        # dispatches (ISSUE 15); a direct prog(xval) when the path is idle
        value = _executor.call_staged(key, prog, xval)
    except Exception as exc:
        if not _executor.fallback_after_failure(key, prog, exc):
            raise
        return NotImplemented
    return DNDarray(
        value, tuple(rshape), types.canonical_heat_type(value.dtype), rsplit,
        x.device, x.comm, x.balanced,
    )


def _reduce_jit(operation, x, axis, out_split, out, keepdims, fn_kwargs):
    """Stage a reduction through the executor; NotImplemented → eager path."""
    op = _executor.op_sig(operation)
    kwsig = _executor.kwargs_sig(fn_kwargs)
    if op is _executor.UNSUPPORTED or kwsig is _executor.UNSUPPORTED:
        return NotImplemented
    if out is not None and jnp.issubdtype(out.dtype.jax_type(), jnp.complexfloating):
        return NotImplemented
    comm = x.comm
    xval = x.parray
    x_padded = x._is_padded()
    gshape, split = x.gshape, x.split
    has_out = out is not None
    out_dtype = out.dtype.jax_type() if has_out else None
    key = (
        "r", op, kwsig, _executor.operand_sig(xval), tuple(gshape), split, axis,
        keepdims, comm.mesh,
        (np.dtype(out_dtype).str,) if has_out else None,
    )

    def build():
        aval = jax.ShapeDtypeStruct(xval.shape, xval.dtype)
        if x_padded and not has_out:
            meta_box = {}

            def probe(v):
                r = _padded_reduce_value(
                    operation, v, gshape, split, axis, out_split, keepdims, fn_kwargs
                )
                if r is None:
                    raise _StageBail()
                meta_box["shape"], meta_box["split"] = r[1], r[2]
                return r[0]

            try:
                rsd = jax.eval_shape(probe, aval)
                if jnp.issubdtype(rsd.dtype, jnp.complexfloating):
                    raise _StageBail()

                def body(v):
                    return _padded_reduce_value(
                        operation, v, gshape, split, axis, out_split, keepdims, fn_kwargs
                    )[0]

                return (
                    body,
                    comm.sharding(len(rsd.shape), meta_box["split"]),
                    None,
                    ("wrap", meta_box["shape"], meta_box["split"]),
                )
            except _StageBail:
                pass

        lsl = _lslice(gshape) if x_padded else None

        def logical(v):
            if lsl is not None:
                # replicate like the eager larray materialisation so the staged
                # reduction combines partials in the same order (bit parity)
                v = _replicated(v[lsl], comm)
            return operation(v, axis=axis, keepdims=keepdims, **fn_kwargs)

        try:
            rsd = jax.eval_shape(logical, aval)
        except Exception as exc:
            if diagnostics._enabled:
                diagnostics.record_fallback(
                    "dispatch.reduce",
                    f"{_executor._op_label(operation)}: {type(exc).__name__}: {exc}",
                )
            return _executor.UNSUPPORTED
        rshape = tuple(rsd.shape)
        if jnp.issubdtype(rsd.dtype, jnp.complexfloating):
            return _executor.UNSUPPORTED
        fsplit = out_split if (out_split is None or out_split < len(rshape)) else None
        phys = comm.padded_shape(rshape, fsplit)
        if has_out:

            def body(v, ob):
                r = logical(v).astype(out_dtype)
                if phys != rshape:
                    r = _pad_physical(r, phys, fsplit)
                return r

            return body, comm.sharding(len(rshape), fsplit), 1, ("out", rshape, fsplit)

        def body(v):
            r = logical(v)
            if phys != rshape:
                r = _pad_physical(r, phys, fsplit)
            return r

        return body, comm.sharding(len(rshape), fsplit), None, ("wrap", rshape, fsplit)

    prog = _executor.lookup(
        key, build,
        spec=lambda: None if has_out else _staged_spec(
            "r", operation, fn_kwargs, xval, gshape, split, comm,
            axis=axis, keepdims=keepdims, out_split=out_split,
        ),
    )
    if prog is None:
        return NotImplemented
    if diagnostics._enabled and x_padded:
        _note_pad_waste(gshape, split, comm)
    kind, rshape, fsplit = prog.meta
    if kind == "out":
        sanitation.sanitize_out(out, rshape, fsplit, x.device)
        donate = sanitation.sanitize_donation(out, [xval])
        if donate and _result_cache._enabled:
            # out= donation consumes the destination buffer: drop every
            # memoised result aliasing it before XLA invalidates it
            _result_cache.note_donation((id(out.parray),))
        try:
            value = prog(xval, out.parray, donate=donate)
        except Exception as exc:
            if not _executor.fallback_after_failure(
                key, prog, exc, donated=(out.parray,) if donate else ()
            ):
                raise
            return NotImplemented
        out._rebind_physical(value)
        return out
    try:
        value = _executor.call_staged(key, prog, xval)
    except Exception as exc:
        if not _executor.fallback_after_failure(key, prog, exc):
            raise
        return NotImplemented
    return DNDarray(
        value, tuple(rshape), types.canonical_heat_type(value.dtype), fsplit,
        x.device, x.comm, True,
    )


def _cum_jit(operation, x, axis, out, target, fn_kwargs):
    """Stage a cumulative op through the executor; NotImplemented → eager path."""
    op = _executor.op_sig(operation)
    kwsig = _executor.kwargs_sig(fn_kwargs)
    if op is _executor.UNSUPPORTED or kwsig is _executor.UNSUPPORTED:
        return NotImplemented
    if target is not None and jnp.issubdtype(target, jnp.complexfloating):
        return NotImplemented
    if out is not None and jnp.issubdtype(out.dtype.jax_type(), jnp.complexfloating):
        return NotImplemented
    comm = x.comm
    xval = x.parray
    x_padded = x._is_padded()
    gshape, split = x.gshape, x.split
    nd = len(gshape)
    has_out = out is not None
    out_dtype = out.dtype.jax_type() if has_out else None
    key = (
        "c", op, kwsig, _executor.operand_sig(xval), tuple(gshape), split, axis,
        np.dtype(target).str if target is not None else None, comm.mesh,
        (np.dtype(out_dtype).str,) if has_out else None,
    )

    def build():
        lsl = _lslice(gshape) if x_padded else None
        if x_padded and not has_out:

            def body(v):
                if target is not None:
                    v = v.astype(target)
                r = operation(v, axis=axis, **fn_kwargs)
                return _zero_pads(r, gshape, split)

            return body, comm.sharding(nd, split), None, ("fast",)
        phys = comm.padded_shape(gshape, split)

        def logical(v):
            if lsl is not None:
                v = _replicated(v[lsl], comm)
            if target is not None:
                v = v.astype(target)
            return operation(v, axis=axis, **fn_kwargs)

        if has_out:

            def body(v, ob):
                r = logical(v).astype(out_dtype)
                if phys != tuple(gshape):
                    r = _pad_physical(r, phys, split)
                return r

            return body, comm.sharding(nd, split), 1, ("out",)

        def body(v):
            r = logical(v)
            if phys != tuple(gshape):
                r = _pad_physical(r, phys, split)
            return r

        return body, comm.sharding(nd, split), None, ("wrap",)

    prog = _executor.lookup(
        key, build,
        spec=lambda: None if has_out else _staged_spec(
            "c", operation, fn_kwargs, xval, gshape, split, comm,
            axis=axis,
            target=np.dtype(target).str if target is not None else None,
        ),
    )
    if prog is None:
        return NotImplemented
    if diagnostics._enabled and x_padded:
        _note_pad_waste(gshape, split, comm)
    if prog.meta == ("out",):
        sanitation.sanitize_out(out, gshape, split, x.device)
        donate = sanitation.sanitize_donation(out, [xval])
        if donate and _result_cache._enabled:
            # out= donation consumes the destination buffer: drop every
            # memoised result aliasing it before XLA invalidates it
            _result_cache.note_donation((id(out.parray),))
        try:
            value = prog(xval, out.parray, donate=donate)
        except Exception as exc:
            if not _executor.fallback_after_failure(
                key, prog, exc, donated=(out.parray,) if donate else ()
            ):
                raise
            return NotImplemented
        out._rebind_physical(value)
        return out
    try:
        value = _executor.call_staged(key, prog, xval)
    except Exception as exc:
        if not _executor.fallback_after_failure(key, prog, exc):
            raise
        return NotImplemented
    return DNDarray(
        value, tuple(gshape), types.canonical_heat_type(value.dtype), split,
        x.device, x.comm, x.balanced,
    )


@_profiled_dispatch("binary")
def binary_op(
    operation: Callable,
    t1,
    t2,
    out: Optional[DNDarray] = None,
    where=None,
    fn_kwargs: Optional[dict] = None,
) -> DNDarray:
    """Apply a binary jnp operation with Heat's split/type semantics
    (reference ``__binary_op`` ``_operations.py:22``)."""
    fn_kwargs = fn_kwargs or {}
    if np.isscalar(t1) and np.isscalar(t2) and out is None and where is None:
        t1r, t2r = _complex_operands(t1, t2)
        res = operation(jnp.asarray(t1r), jnp.asarray(t2r), **fn_kwargs)
        from . import factories

        return factories.array(res)
    comm = None
    device = None
    for t in (t1, t2):
        if isinstance(t, DNDarray):
            comm, device = t.comm, t.device
            break
    # fused deferral first: the aligned elementwise case never wraps scalars into
    # DNDarrays (a per-call device_put) and never executes — it grows the graph
    if (
        out is None
        and where is None
        and _executor.executor_enabled()
        and not _is_complexish(t1, t2)
    ):
        res = _binary_defer(operation, t1, t2, fn_kwargs)
        if res is not NotImplemented:
            return res
    a = _ensure_dndarray(t1, device, comm)
    b = _ensure_dndarray(t2, device, comm)

    out_shape = broadcast_shapes(a.gshape, b.gshape)
    out_split = _out_split_binary(out_shape, a, b)
    use_comm = comm or get_comm()

    if _executor.executor_enabled() and not _is_complexish(t1, t2, a, b):
        res = _binary_jit(
            operation, t1, t2, a, b, out, where, fn_kwargs,
            out_shape, out_split, use_comm, device,
        )
        if res is not NotImplemented:
            return res

    # ragged fast path: compute on the padded physical values so per-device memory
    # stays O(n/P) (the logical slice below resolves to a replicated value)
    if (
        out is None
        and where is None
        and out_split is not None
        and use_comm.padded_dim(out_shape[out_split]) != out_shape[out_split]
        and not _is_complexish(t1, t2, a, b)
    ):
        phys = _padded_physical_operands(((t1, a), (t2, b)), out_shape, out_split, use_comm)
        if phys is not None:
            if diagnostics._enabled:
                _note_pad_waste(out_shape, out_split, use_comm)
            result = operation(phys[0], phys[1], **fn_kwargs)
            result = _zero_pads(result, out_shape, out_split)
            result = use_comm.shard(result, out_split)
            return DNDarray(
                result,
                out_shape,
                types.canonical_heat_type(result.dtype),
                out_split,
                device or get_device(),
                use_comm,
                True,
            )

    # promote: scalars stay weakly typed so jnp's promotion matches numpy/heat
    x1 = a.larray if not np.isscalar(t1) else t1
    x2 = b.larray if not np.isscalar(t2) else t2
    x1, x2 = _complex_operands(x1, x2)
    result = operation(x1, x2, **fn_kwargs)

    if where is not None:
        w = where.larray if isinstance(where, DNDarray) else jnp.asarray(where)
        base = out.larray if out is not None else jnp.zeros(out_shape, result.dtype)
        require_device_dtype(result, base)
        result = jnp.where(w, result, base)

    if out is not None:
        sanitation.sanitize_out(out, out_shape, out_split, device)
        result = use_comm.shard(_safe_astype(result, out.dtype.jax_type()), out.split)
        out._rebind_physical(result)
        return out
    result = use_comm.shard(result, out_split)
    return DNDarray(
        result,
        out_shape,
        types.canonical_heat_type(result.dtype),
        out_split,
        device or get_device(),
        use_comm,
        True,
    )


@_profiled_dispatch("local")
def local_op(
    operation: Callable, x: DNDarray, out: Optional[DNDarray] = None, no_cast: bool = False, **fn_kwargs
) -> DNDarray:
    """Elementwise operation, no communication (reference ``__local_op`` ``:331``)."""
    sanitation.sanitize_in(x)
    if _executor.executor_enabled() and not _is_complexish(x):
        if out is None:
            res = _local_defer(operation, x, fn_kwargs)
            if res is not NotImplemented:
                return res
        res = _local_jit(operation, x, out, fn_kwargs)
        if res is not NotImplemented:
            return res
    if x._is_padded() and out is None and not _is_complexish(x):
        # ragged fast path: elementwise on the padded physical value keeps shards 1/P;
        # pad slots compute garbage in registers and are re-zeroed by the fused mask
        result = operation(x.parray, **fn_kwargs)
        if tuple(result.shape) == tuple(x.parray.shape) and not jnp.issubdtype(
            result.dtype, jnp.complexfloating
        ):
            if diagnostics._enabled:
                _note_pad_waste(x.gshape, x.split, x.comm)
            result = _zero_pads(result, x.gshape, x.split)
            result = x.comm.shard(result, x.split)
            return DNDarray(
                result,
                x.gshape,
                types.canonical_heat_type(result.dtype),
                x.split,
                x.device,
                x.comm,
                x.balanced,
            )
    result = operation(x.larray, **fn_kwargs)
    if out is not None:
        sanitation.sanitize_out(out, x.gshape, x.split, x.device)
        out._rebind_physical(x.comm.shard(_safe_astype(result, out.dtype.jax_type()), out.split))
        return out
    gshape = tuple(result.shape)
    result = x.comm.shard(result, x.split)
    return DNDarray(
        result, gshape, types.canonical_heat_type(result.dtype), x.split, x.device, x.comm, x.balanced
    )


def _out_split_reduce(
    x: DNDarray, axis: Optional[Union[int, Tuple[int, ...]]], keepdims: bool
) -> Optional[int]:
    """Split bookkeeping for reductions (reference ``_operations.py:492-501``)."""
    if x.split is None:
        return None
    if axis is None:
        return None
    axes = axis if isinstance(axis, tuple) else (axis,)
    if x.split in axes:
        return None
    if keepdims:
        return x.split
    return x.split - sum(1 for ax in axes if ax < x.split)


_REDUCE_NEUTRAL = {
    jnp.sum: "zero",
    jnp.nansum: "zero",
    jnp.any: "zero",
    jnp.prod: "one",
    jnp.nanprod: "one",
    jnp.all: "one",
    jnp.max: "lowest",
    jnp.nanmax: "lowest",
    jnp.min: "highest",
    jnp.nanmin: "highest",
}


def _neutral_scalar(kind: str, dtype):
    """The identity element of a reduction for ``dtype`` (reference neutral-element
    table for empty shards, ``_operations.py:450-459``; here it fills pad slots)."""
    if kind == "zero":
        return jnp.zeros((), dtype)
    if kind == "one":
        return jnp.ones((), dtype)
    if jnp.issubdtype(dtype, jnp.bool_):
        return jnp.asarray(kind == "highest", bool)
    if jnp.issubdtype(dtype, jnp.integer):
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.min if kind == "lowest" else info.max, dtype)
    return jnp.asarray(-jnp.inf if kind == "lowest" else jnp.inf, dtype)


def _padded_reduce_value(
    operation, phys, gshape, split, axis, out_split, keepdims, fn_kwargs
):
    """The value half of :func:`_padded_reduce`: reduce a padded physical value
    ``phys`` (concrete or traced — shape checks are static) without materialising
    the logical (replicated) form, or return None when ``operation`` has no
    pad-safe form. Returns ``(value, out_shape, final_split)``; the caller lays
    the value out (``comm.shard`` eagerly, ``out_shardings`` when staged)."""
    axes = (
        tuple(range(len(gshape))) if axis is None
        else (axis if isinstance(axis, tuple) else (axis,))
    )
    if split not in axes:
        # the padded dim survives: pad rows reduce to garbage in output pad slots,
        # which the mask re-zeroes; logical slots never mix with pads
        if out_split is None:
            return None
        result = operation(phys, axis=axis, keepdims=keepdims, **fn_kwargs)
        if keepdims:
            out_shape = tuple(1 if i in axes else s for i, s in enumerate(gshape))
        else:
            out_shape = tuple(s for i, s in enumerate(gshape) if i not in axes)
        if out_split >= len(out_shape):
            return None
        expected = out_shape[:out_split] + (phys.shape[split],) + out_shape[out_split + 1 :]
        if tuple(result.shape) != expected:
            return None
        result = _zero_pads(result, out_shape, out_split)
        return result, out_shape, out_split
    # the padded dim is reduced away: fill pad slots with the op's neutral element
    mask = _pad_mask(phys.shape, gshape[split], split)
    n_count = int(np.prod([gshape[ax] for ax in axes])) if axes else 1
    if operation is jnp.mean:
        # sum/n, not mean*(m/n): one rounding, and exact for n == 1
        masked0 = jnp.where(mask, phys, jnp.zeros((), phys.dtype))
        result = jnp.sum(masked0, axis=axis, keepdims=keepdims, **fn_kwargs) / n_count
    elif operation in (jnp.std, jnp.var):
        if any(k != "ddof" for k in fn_kwargs):
            # e.g. dtype= would be silently dropped here while the logical path
            # honors it — bail out so results stay layout-independent (ADVICE r5 #3)
            return None
        masked0 = jnp.where(mask, phys, jnp.zeros((), phys.dtype))
        mu = jnp.sum(masked0, axis=axis, keepdims=True) / n_count
        d = jnp.where(mask, phys.astype(mu.dtype) - mu, jnp.zeros((), mu.dtype))
        ddof = fn_kwargs.get("ddof", 0)
        v = jnp.sum(d * d, axis=axis, keepdims=keepdims) / (n_count - ddof)
        result = jnp.sqrt(v) if operation is jnp.std else v
    else:
        kind = _REDUCE_NEUTRAL.get(operation)
        if kind is None:
            return None
        masked = jnp.where(mask, phys, _neutral_scalar(kind, phys.dtype))
        result = operation(masked, axis=axis, keepdims=keepdims, **fn_kwargs)
    return result, tuple(result.shape), out_split


def _padded_reduce(operation, x: DNDarray, axis, out_split, keepdims, fn_kwargs):
    """Reduce a padded-physical array without materialising the logical (replicated)
    value — or return None when ``operation`` has no pad-safe form. Mean/std/var get
    count-corrected forms (pad slots must not inflate the element count)."""
    r = _padded_reduce_value(
        operation, x.parray, x.gshape, x.split, axis, out_split, keepdims, fn_kwargs
    )
    if r is None:
        return None
    if diagnostics._enabled:
        _note_pad_waste(x.gshape, x.split, x.comm)
    result, out_shape, final_split = r
    result = x.comm.shard(result, final_split)
    return DNDarray(
        result, out_shape, types.canonical_heat_type(result.dtype), final_split,
        x.device, x.comm, True,
    )


@_profiled_dispatch("reduce")
def reduce_op(
    operation: Callable,
    x: DNDarray,
    axis: Optional[Union[int, Sequence[int]]] = None,
    out: Optional[DNDarray] = None,
    keepdims: bool = False,
    **fn_kwargs,
) -> DNDarray:
    """Apply a reduction with Heat's split bookkeeping (reference ``__reduce_op`` ``:404``).

    The reference's local-partial + ``Allreduce`` with a custom MPI op is replaced by a
    single global jnp reduction; XLA inserts the cross-shard all-reduce when ``axis``
    covers the split dimension.
    """
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.gshape, axis)
    out_split = _out_split_reduce(x, axis, keepdims)
    if _executor.executor_enabled() and not _is_complexish(x):
        res = _reduce_jit(operation, x, axis, out_split, out, keepdims, fn_kwargs)
        if res is not NotImplemented:
            return res
    if x._is_padded() and out is None:
        res = _padded_reduce(operation, x, axis, out_split, keepdims, fn_kwargs)
        if res is not None:
            return res
    result = operation(x.larray, axis=axis, keepdims=keepdims, **fn_kwargs)
    out_shape = tuple(result.shape)
    if out_split is not None and out_split >= len(out_shape):
        out_split = None
    if out is not None:
        sanitation.sanitize_out(out, out_shape, out_split, x.device)
        out._rebind_physical(x.comm.shard(_safe_astype(result, out.dtype.jax_type()), out.split))
        return out
    result = x.comm.shard(result, out_split)
    return DNDarray(
        result, out_shape, types.canonical_heat_type(result.dtype), out_split, x.device, x.comm, True
    )


@_profiled_dispatch("cum")
def cum_op(
    operation: Callable,
    x: DNDarray,
    axis: int,
    out: Optional[DNDarray] = None,
    dtype=None,
    **fn_kwargs,
) -> DNDarray:
    """Cumulative operation along ``axis`` (reference ``__cum_op`` ``:230``): one jnp call;
    XLA lowers the cross-shard prefix carry that the reference built from ``Exscan``."""
    sanitation.sanitize_in(x)
    axis = sanitize_axis(x.gshape, axis)
    if axis is None:
        raise NotImplementedError("cumulative operations require an explicit axis")
    target = types.canonical_heat_type(dtype).jax_type() if dtype is not None else None
    if _executor.executor_enabled() and not _is_complexish(x):
        res = _cum_jit(operation, x, axis, out, target, fn_kwargs)
        if res is not NotImplemented:
            return res
    if (
        x._is_padded()
        and out is None
        and (target is None or not jnp.issubdtype(target, jnp.complexfloating))
    ):
        # ragged fast path: layout padding sits at the END of the global split dim, so
        # a prefix op along any axis never reads pad slots before logical ones
        if diagnostics._enabled:
            _note_pad_waste(x.gshape, x.split, x.comm)
        value = x.parray if target is None else _safe_astype(x.parray, target)
        result = operation(value, axis=axis, **fn_kwargs)
        result = _zero_pads(result, x.gshape, x.split)
        result = x.comm.shard(result, x.split)
        return DNDarray(
            result, x.gshape, types.canonical_heat_type(result.dtype), x.split,
            x.device, x.comm, x.balanced,
        )
    value = x.larray
    if target is not None:
        # numpy semantics: dtype is the ACCUMULATOR type — cast before the scan so
        # e.g. an int8 cumsum with dtype=int64 accumulates without overflow
        value = _safe_astype(value, target)
    result = operation(value, axis=axis, **fn_kwargs)
    if out is not None:
        sanitation.sanitize_out(out, x.gshape, x.split, x.device)
        out._rebind_physical(x.comm.shard(_safe_astype(result, out.dtype.jax_type()), out.split))
        return out
    result = x.comm.shard(result, x.split)
    return DNDarray(
        result, x.gshape, types.canonical_heat_type(result.dtype), x.split, x.device, x.comm, x.balanced
    )


# Parity aliases matching the reference's private names (used by its op modules).
__binary_op = binary_op
__local_op = local_op
__reduce_op = reduce_op
__cum_op = cum_op
