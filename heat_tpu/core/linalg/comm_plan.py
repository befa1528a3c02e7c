"""Communication planner for distributed contractions (ISSUE 20).

``linalg.matmul`` historically delegated every byte of data movement to XLA
SPMD's default strategy — typically an all-gather of one full operand
(O(global) logical bytes on the wire, O(n·k) per-device peak memory for the
gathered panel) or, for contraction-dim splits, an all-reduce of the full
replicated product. This module adds a small cost model that picks, per call,
among four plans:

``xla``
    Today's behaviour: ``jnp.matmul`` on the sharded global arrays, movement
    chosen by the partitioner. Modeled wire bytes: replicating every split
    operand, ``(P−1)·|operand|`` each (the gather-both fallback), or
    ``2(P−1)·|C|`` for the contraction-split all-reduce.
``ring``
    SUMMA-style ring collective matmul (van de Geijn & Watts; the TPU
    collective-matmul decomposition of Wang et al., ASPLOS 2023): one panel of
    the rotating operand in flight via ``MeshCommunication.ring_shift``
    (a ``ppermute``) inside a single ``shard_map``'d program, partial-product
    accumulation overlapped with the next panel's shift. Per-device peak
    memory is O(n²/P + one panel) — the gathered operand is never
    materialised — and total wire bytes are ``(P−1)·|rotating operand|``,
    i.e. each device receives ``(P−1)/P`` of it.
``rs``
    Reduce-scatter contraction for contraction-dim splits: the local partial
    product is combined with ``psum_scatter`` straight into a ``split=0``
    result — ``(P−1)·|C|`` wire bytes, half the all-reduce's ``2(P−1)·|C|``,
    and the replicated result buffer is never allocated. Because this changes
    the result split (``None`` → ``0``), it is **never** chosen by ``auto``;
    consumers that keep the product sharded opt in with
    ``HEAT_TPU_LINALG_PLAN=rs``.
``resplit``
    ``all_to_all`` resplit for split→split layout changes
    (:meth:`~..dndarray.DNDarray.resplit_`): each device exchanges only the
    ``1/P`` tile every peer needs — ``(P−1)/P·|array|`` total wire bytes
    instead of the gather-based path's ``(P−1)·|array|``.

Plan selection honours the memoised ``HEAT_TPU_LINALG_PLAN`` knob
(:func:`.._executor.linalg_plan`; ``auto``/``xla``/``ring``/``rs``), the
chosen plan is recorded through ht.diagnostics (``linalg.plan.<kind>``
counters plus modeled ``linalg.bytes.<kind>`` wire bytes — recorded per call
at dispatch time, unlike the trace-time per-collective records), and every
staged body rides the signature-cached executor (compile-cache/AOT-warmup
family ``"mm"`` included). The bodies are pure functions of their operands —
knob reads and counter writes stay in the host-side wrappers.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import _executor, diagnostics, types
from ..dndarray import DNDarray

__all__ = ["Plan", "plan_matmul", "try_matmul", "try_resplit"]


class Plan(NamedTuple):
    """One planned contraction: the chosen ``kind`` (``xla``/``ring``/``rs``),
    the execution ``variant`` within it, and the modeled total wire bytes of
    the plan (``nbytes``) and of the gather-both-operands fallback
    (``baseline``)."""

    kind: str
    variant: str
    nbytes: int
    baseline: int


# ring variants by (a.split, b.split): which operand rotates and how the
# product is assembled. rs variants by the same key: where the local partial
# comes from before the reduce-scatter.
_RING_VARIANTS = {(0, 0): "rA", (1, 1): "rB", (0, 1): "rC"}
_RS_VARIANTS = {(1, 0): "s10", (None, 0): "sN0", (1, None): "s1N"}


def _phys_bytes(comm, gshape, split, dtype) -> int:
    """Padded-physical bytes of one global operand."""
    size = 1
    for extent in comm.padded_shape(gshape, split):
        size *= int(extent)
    return size * int(np.dtype(dtype).itemsize)


def _plannable_dtype(x: DNDarray) -> bool:
    dt = np.dtype(x.dtype.jax_type() if hasattr(x.dtype, "jax_type") else x.dtype)
    # jnp's lattice, not numpy's: bfloat16 is no subtype of np.floating
    return jnp.issubdtype(dt, jnp.floating) or jnp.issubdtype(dt, jnp.integer)


def _structural(a, b):
    """The shared communicator when (a, b) is a plannable distributed 2-D
    contraction — single-controller, single mesh axis, size > 1, real/integer
    dtypes, conformable shapes — else ``None``."""
    if not (isinstance(a, DNDarray) and isinstance(b, DNDarray)):
        return None
    if a.ndim != 2 or b.ndim != 2:
        return None
    comm = a.comm
    if comm is not b.comm or comm.size <= 1 or len(comm.axis_names) != 1:
        return None
    if not _executor.executor_enabled():
        return None
    if a.gshape[1] != b.gshape[0]:
        return None
    if not (_plannable_dtype(a) and _plannable_dtype(b)):
        return None
    return comm


def plan_matmul(a: DNDarray, b: DNDarray) -> Optional[Plan]:
    """The communication plan for ``matmul(a, b)``, or ``None`` when the pair
    is not a plannable distributed contraction (the caller takes the XLA path
    without recording a plan).

    Policy: ``auto`` picks ``ring`` whenever a ring variant applies (both
    operands split along a non-contraction-compatible pair) and ``xla``
    otherwise; ``ring``/``rs`` force their plan where eligible, falling back
    to ``xla``; ``xla`` always defers to the partitioner. ``rs`` is never
    chosen by ``auto`` because it changes the result split (``None`` → ``0``).
    """
    comm = _structural(a, b)
    if comm is None:
        return None
    if a.split is None and b.split is None:
        return None  # purely local: nothing to plan, nothing to record
    P = comm.size
    baseline = 0
    if a.split is not None:
        baseline += (P - 1) * _phys_bytes(comm, a.gshape, a.split, a.dtype.jax_type())
    if b.split is not None:
        baseline += (P - 1) * _phys_bytes(comm, b.gshape, b.split, b.dtype.jax_type())
    knob = _executor.linalg_plan()
    key = (a.split, b.split)

    ring_variant = _RING_VARIANTS.get(key)
    if ring_variant is not None and knob in ("auto", "ring"):
        rot_op = a if ring_variant == "rB" else b
        nbytes = (P - 1) * _phys_bytes(
            comm, rot_op.gshape, rot_op.split, rot_op.dtype.jax_type()
        )
        return Plan("ring", ring_variant, nbytes, baseline)

    rs_variant = _RS_VARIANTS.get(key)
    if rs_variant is not None and knob == "rs":
        m, n = a.gshape[0], b.gshape[1]
        out_dt = np.promote_types(
            np.dtype(a.dtype.jax_type()), np.dtype(b.dtype.jax_type())
        )
        c_bytes = comm.padded_dim(m) * n * int(out_dt.itemsize)
        return Plan("rs", rs_variant, (P - 1) * c_bytes, baseline)

    return Plan("xla", "", _xla_bytes(comm, a, b, baseline), baseline)


def _xla_bytes(comm, a: DNDarray, b: DNDarray, baseline: int) -> int:
    """Modeled wire bytes of the partitioner's default: the contraction-split
    all-reduce (``2(P−1)·|C|``) when both splits land on the contraction pair,
    the gather-both fallback otherwise."""
    if (a.split, b.split) in _RS_VARIANTS:
        P = comm.size
        out_dt = np.promote_types(
            np.dtype(a.dtype.jax_type()), np.dtype(b.dtype.jax_type())
        )
        return 2 * (P - 1) * a.gshape[0] * b.gshape[1] * int(out_dt.itemsize)
    return baseline


def _record(plan: Plan) -> None:
    """Count the executed plan: ``linalg.plan.<kind>`` occurrences plus the
    modeled wire bytes of the plan and of the gather-both fallback. Host-side
    and per call — cached program replays count too, unlike the trace-time
    ``record_collective`` entries."""
    if not diagnostics._enabled:
        return
    diagnostics.counter(f"linalg.plan.{plan.kind}")
    diagnostics.counter(f"linalg.bytes.{plan.kind}", plan.nbytes)
    diagnostics.counter("linalg.bytes.gather_baseline", plan.baseline)


def try_matmul(a: DNDarray, b: DNDarray, precision) -> Any:
    """Plan and, when the plan is ``ring``/``rs``, execute ``matmul(a, b)``
    through the staged executor. Returns the result DNDarray, or
    ``NotImplemented`` for the caller's XLA-SPMD path (plan ``xla``, an
    unplannable pair, or a staged path still warming up / quarantined —
    the executed plan is what gets recorded)."""
    with diagnostics.span("linalg.plan", a) if diagnostics._enabled else diagnostics.NO_SPAN:
        plan = plan_matmul(a, b)
    if plan is None:
        return NotImplemented
    if plan.kind != "xla":
        res = _execute(plan, a, b, precision)
        if res is not NotImplemented:
            _record(plan)
            return res
        plan = Plan("xla", "", _xla_bytes(a.comm, a, b, plan.baseline), plan.baseline)
    _record(plan)
    return NotImplemented


# ------------------------------------------------------------- staged bodies
def _pad_to(x, target: int, axis: int):
    """Zero-pad local axis ``axis`` up to ``target`` (a no-op when already
    there) — keeps every panel slice aligned with the peer's padded extent."""
    extent = x.shape[axis]
    if extent == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - extent)
    return jnp.pad(x, pads)


def _ring_body(variant: str, comm, agshape, bgshape, precision):
    """The shard_map'd ring program: P−1 ``ring_shift`` hops of one panel of
    the rotating operand, each overlapped with the accumulation of the
    previous panel's partial product; the last panel is consumed without a
    wasted hop (the ``_ring_pairwise`` idiom in spatial/distance.py)."""
    P = comm.size
    ax = comm.axis_name
    m, k = agshape
    n = bgshape[1]
    kp = comm.padded_dim(k)
    ck = kp // P
    np_p = comm.padded_dim(n)
    cn = np_p // P

    if variant == "rA":
        # a split 0, b split 0 (contraction dim): rotate b's row panels.
        def block(al, bl):
            idx = jax.lax.axis_index(ax)
            al = _pad_to(al, kp, 1)
            out0 = jnp.zeros((al.shape[0], bl.shape[1]), jnp.result_type(al, bl))

            def contrib(i, bblk, out):
                src = (idx - i) % P
                panel = jax.lax.dynamic_slice_in_dim(al, src * ck, ck, axis=1)
                return out + jnp.matmul(panel, bblk, precision=precision)

            def step(i, carry):
                bblk, out = carry
                out = contrib(i, bblk, out)
                return comm.ring_shift(bblk, 1, axis_name=ax), out

            bblk, out = jax.lax.fori_loop(0, P - 1, step, (bl, out0))
            return contrib(P - 1, bblk, out)

        in_splits, out_split = (0, 0), 0
    elif variant == "rB":
        # a split 1 (contraction dim), b split 1: rotate a's column panels.
        def block(al, bl):
            idx = jax.lax.axis_index(ax)
            bl = _pad_to(bl, kp, 0)
            out0 = jnp.zeros((al.shape[0], bl.shape[1]), jnp.result_type(al, bl))

            def contrib(i, ablk, out):
                src = (idx - i) % P
                rows = jax.lax.dynamic_slice_in_dim(bl, src * ck, ck, axis=0)
                return out + jnp.matmul(ablk, rows, precision=precision)

            def step(i, carry):
                ablk, out = carry
                out = contrib(i, ablk, out)
                return comm.ring_shift(ablk, 1, axis_name=ax), out

            ablk, out = jax.lax.fori_loop(0, P - 1, step, (al, out0))
            return contrib(P - 1, ablk, out)

        in_splits, out_split = (1, 1), 1
    elif variant == "rC":
        # a split 0, b split 1: rotate b's column panels into their output slot.
        def block(al, bl):
            idx = jax.lax.axis_index(ax)
            out0 = jnp.zeros((al.shape[0], np_p), jnp.result_type(al, bl))

            def contrib(i, bblk, out):
                src = (idx - i) % P
                d = jnp.matmul(al, bblk, precision=precision)
                col0 = (src * cn).astype(jnp.int32)
                return jax.lax.dynamic_update_slice(out, d, (jnp.int32(0), col0))

            def step(i, carry):
                bblk, out = carry
                out = contrib(i, bblk, out)
                return comm.ring_shift(bblk, 1, axis_name=ax), out

            bblk, out = jax.lax.fori_loop(0, P - 1, step, (bl, out0))
            return contrib(P - 1, bblk, out)[:, :n]

        in_splits, out_split = (0, 1), 0
    else:  # pragma: no cover - planner only emits the three variants above
        raise ValueError(f"unknown ring variant {variant!r}")

    def body(pa, pb):
        return jax.shard_map(
            block, mesh=comm.mesh,
            in_specs=(comm.spec(2, in_splits[0]), comm.spec(2, in_splits[1])),
            out_specs=comm.spec(2, out_split), check_vma=False,
        )(pa, pb)

    return body, out_split


def _rs_body(variant: str, comm, agshape, bgshape, precision):
    """The reduce-scatter contraction: the device-local partial product of one
    contraction-dim tile, ``psum_scatter``'d straight into a ``split=0``
    result — the replicated product is never allocated."""
    P = comm.size
    ax = comm.axis_name
    m, k = agshape
    n = bgshape[1]
    kp = comm.padded_dim(k)
    ck = kp // P
    mp = comm.padded_dim(m)
    a_split = {"s10": 1, "sN0": None, "s1N": 1}[variant]
    b_split = {"s10": 0, "sN0": 0, "s1N": None}[variant]

    def block(al, bl):
        idx = jax.lax.axis_index(ax)
        if variant == "sN0":
            al = jax.lax.dynamic_slice_in_dim(_pad_to(al, kp, 1), idx * ck, ck, axis=1)
        elif variant == "s1N":
            bl = jax.lax.dynamic_slice_in_dim(_pad_to(bl, kp, 0), idx * ck, ck, axis=0)
        part = jnp.matmul(al, bl, precision=precision)
        part = _pad_to(part, mp, 0)
        return comm.psum_scatter(part, scatter_axis=0, axis_name=ax)

    def body(pa, pb):
        return jax.shard_map(
            block, mesh=comm.mesh,
            in_specs=(comm.spec(2, a_split), comm.spec(2, b_split)),
            out_specs=comm.spec(2, 0), check_vma=False,
        )(pa, pb)

    return body, 0


def _prec_name(precision) -> Optional[str]:
    return None if precision is None else precision.name


def _mesh_spec(comm) -> dict:
    return {
        "shape": list(comm.mesh.devices.shape),
        "axes": list(comm.mesh.axis_names),
    }


def _execute(plan: Plan, a: DNDarray, b: DNDarray, precision) -> Any:
    """Run the planned ``ring``/``rs`` program through the staged executor.
    ``NotImplemented`` when the signature is still under the jit threshold,
    unsupported, or quarantined after a failure — the caller falls back to
    the XLA path (and records plan ``xla``)."""
    comm = a.comm
    pa, pb = a.parray, b.parray
    pname = _prec_name(precision)
    key = (
        "mm", plan.kind, plan.variant, a.gshape, b.gshape, comm.mesh,
        _executor.operand_sig(pa), _executor.operand_sig(pb), pname,
    )
    maker = _ring_body if plan.kind == "ring" else _rs_body

    def build():
        body, out_split = maker(plan.variant, comm, a.gshape, b.gshape, precision)
        return body, comm.sharding(2, out_split), None, None

    def spec():
        return {
            "family": "mm", "kind": plan.kind, "variant": plan.variant,
            "a_gshape": list(a.gshape), "a_split": a.split,
            "a_dtype": np.dtype(pa.dtype).str, "a_phys": list(pa.shape),
            "b_gshape": list(b.gshape), "b_split": b.split,
            "b_dtype": np.dtype(pb.dtype).str, "b_phys": list(pb.shape),
            "precision": pname, "mesh": _mesh_spec(comm),
        }

    prog = _executor.lookup(key, build, label=f"mm.{plan.kind}.{plan.variant}", spec=spec)
    if prog is None:
        return NotImplemented
    try:
        value = prog(pa, pb)
    except Exception as exc:  # noqa: BLE001 - accounted, then replayed or re-raised
        if not _executor.fallback_after_failure(key, prog, exc):
            raise
        return NotImplemented
    _, out_split = maker(plan.variant, comm, a.gshape, b.gshape, precision)
    out_gshape = (a.gshape[0], b.gshape[1])
    return DNDarray(
        value, out_gshape, types.canonical_heat_type(value.dtype),
        out_split, a.device, comm, True,
    )


# --------------------------------------------------------- all_to_all resplit
def resplit_eligible(x: DNDarray, axis: Optional[int]) -> bool:
    """Whether the split→split layout change ``x.resplit(axis)`` can ride the
    ``all_to_all`` program instead of the gather-based path."""
    return (
        isinstance(x, DNDarray)
        and axis is not None
        and x.split is not None
        and axis != x.split
        and x.comm.size > 1
        and len(x.comm.axis_names) == 1
        and _executor.executor_enabled()
        and _plannable_dtype(x)
        and _executor.linalg_plan() != "xla"
    )


def try_resplit(x: DNDarray, axis: int) -> Any:
    """The physical array of ``x`` re-laid-out from ``split=x.split`` to
    ``split=axis`` via one ``all_to_all`` — each device exchanges only the
    tiles its peers need, ``(P−1)/P·|array|`` total wire bytes vs the
    gather-based path's ``(P−1)·|array|``. Returns the padded-physical
    ``jax.Array`` for the new split, or ``NotImplemented`` for the caller's
    gather-based fallback."""
    if not resplit_eligible(x, axis):
        return NotImplemented
    comm = x.comm
    src, dst = x.split, axis
    gshape = x.gshape
    pv = x.parray
    nd = len(gshape)
    dst_p = comm.padded_dim(gshape[dst])
    src_extent = gshape[src]

    def build():
        def block(lv):
            lv = _pad_to(lv, dst_p, dst)
            out = comm.all_to_all(lv, split_axis=dst, concat_axis=src)
            if out.shape[src] != src_extent:
                out = jax.lax.slice_in_dim(out, 0, src_extent, axis=src)
            return out

        def body(val):
            return jax.shard_map(
                block, mesh=comm.mesh,
                in_specs=(comm.spec(nd, src),),
                out_specs=comm.spec(nd, dst), check_vma=False,
            )(val)

        return body, comm.sharding(nd, dst), None, None

    def spec():
        return {
            "family": "mm", "kind": "resplit",
            "gshape": list(gshape), "split": src, "dst": dst,
            "dtype": np.dtype(pv.dtype).str, "phys": list(pv.shape),
            "mesh": _mesh_spec(comm),
        }

    key = ("mm", "resplit", gshape, src, dst, comm.mesh, _executor.operand_sig(pv))
    prog = _executor.lookup(key, build, label=f"mm.resplit.{src}->{dst}", spec=spec)
    if prog is None:
        return NotImplemented
    try:
        value = prog(pv)
    except Exception as exc:  # noqa: BLE001 - accounted, then replayed or re-raised
        if not _executor.fallback_after_failure(key, prog, exc):
            raise
        return NotImplemented
    if diagnostics._enabled:
        P = comm.size
        phys = _phys_bytes(comm, gshape, src, x.dtype.jax_type())
        diagnostics.counter("linalg.plan.resplit")
        diagnostics.counter("linalg.bytes.resplit", (P - 1) * phys // P)
        diagnostics.counter("linalg.bytes.resplit_gather_baseline", (P - 1) * phys)
    return value


# ------------------------------------------------------------- warmup replay
def replay_warmup(spec: dict, zeros_dnd) -> bool:
    """Re-enter the recorded family-``"mm"`` program over zero-filled operands
    of the recorded signature (the AOT-warmup tier of the persistent compile
    cache). ``zeros_dnd(gshape, split, dtype_str)`` is
    ``_compile_cache._zeros_dnd``. False when the recorded physical layout no
    longer matches this topology."""
    if spec.get("kind") == "resplit":
        x = zeros_dnd(spec["gshape"], spec["split"], spec["dtype"])
        if list(x.parray.shape) != list(spec["phys"]):
            return False
        return try_resplit(x, spec["dst"]) is not NotImplemented
    a = zeros_dnd(spec["a_gshape"], spec["a_split"], spec["a_dtype"])
    b = zeros_dnd(spec["b_gshape"], spec["b_split"], spec["b_dtype"])
    if list(a.parray.shape) != list(spec["a_phys"]) or list(b.parray.shape) != list(spec["b_phys"]):
        return False
    pname = spec.get("precision")
    precision = None if pname is None else jax.lax.Precision[pname]
    plan = Plan(spec["kind"], spec["variant"], 0, 0)
    return _execute(plan, a, b, precision) is not NotImplemented
