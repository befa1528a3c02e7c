"""Communication layer: the TPU-native replacement for the reference's MPI wrapper.

The reference (heat/core/communication.py:84-2064) wraps every MPI primitive so that
process-local torch tensors can be used as send/recv buffers, with derived datatypes for
strided buffers, GPU staging, and axis-permutation tricks so any axis can be the
concatenation axis of a collective.

On TPU none of that machinery is needed: arrays are *global* ``jax.Array``s laid out over a
``jax.sharding.Mesh``, and XLA SPMD materialises the collectives (all-reduce, all-gather,
all-to-all, collective-permute) over ICI/DCN directly from sharding annotations. What
remains of the communication layer is therefore small and explicit:

- a :class:`Communication` object owning the device ``Mesh`` and its axis name,
- the canonical chunking rule :meth:`Communication.chunk` (reference
  ``communication.py:157-215``) used for lshape maps and parallel I/O,
- sharding helpers that translate Heat's ``split`` axis into a ``NamedSharding``,
- thin functional collectives (:meth:`Allreduce`-style names kept for parity) that are
  usable *inside* ``jax.shard_map`` blocks for algorithms with explicit communication
  schedules (hSVD merge tree, ring cdist, TSQR).

Multi-host bootstrap is ``jax.distributed.initialize`` instead of ``mpirun``, driven
by the environment contract of ``_bootstrap`` at import — see :func:`initialize`.
"""

from __future__ import annotations

import math
import os
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import _bootstrap, diagnostics, forensics, profiler, resilience, supervision, telemetry
from .devices import require_device_dtype


def _guarded(site, fn, *args, **kwargs):
    """Run one collective (or layout) invocation under ht.supervision,
    ht.resilience, ht.profiler, and ht.telemetry.

    Idle fast path: one module-attribute read per subsystem. When the
    supervision plane is armed (multi-process jobs by default), the abort
    sentinel is polled before AND after the invocation — a peer failure
    raises typed ``PeerFailed`` on this rank instead of entering a collective
    its dead peer will never join — and, with
    ``HEAT_TPU_COLLECTIVE_TIMEOUT_S`` set, the invocation window is armed on
    the collective watchdog (``supervision.watch``). When a fault plan
    is armed or a site policy is registered, the call goes through
    ``resilience.guard`` — injected faults fire per attempt and the site
    policy retries. When the profiler is active the invocation is additionally
    recorded as a ``collective`` slice attributed to the ambient request scope
    — collectives run at trace time, so the slice nests inside the program's
    ``compile`` slice. When telemetry collection is on, the whole invocation
    (retries included) is timed into a :func:`telemetry.collective_window` —
    the per-(site, seq) enter/exit record the cross-process merge turns into
    skew histograms and straggler attribution. All of it is host-side timing
    only; nothing enters the traced body, so the compiled HLO never changes
    (the byte-parity contracts in ``tests/test_resilience.py``,
    ``tests/test_profiler.py`` and ``tests/test_supervision.py``)."""
    if supervision._armed:
        with supervision.watch(site):
            return _guarded_telemetry(site, fn, *args, **kwargs)
    return _guarded_telemetry(site, fn, *args, **kwargs)


def _guarded_telemetry(site, fn, *args, **kwargs):
    if telemetry._collecting:
        with telemetry.collective_window(site):
            return _guarded_forensics(site, fn, *args, **kwargs)
    return _guarded_forensics(site, fn, *args, **kwargs)


def _guarded_forensics(site, fn, *args, **kwargs):
    # request-forensics leg: time the whole invocation (retries included)
    # onto the ambient request's lifecycle record. Auxiliary timing only —
    # collectives run at trace time, nested inside the compile stage, so the
    # reducer reports this beside the stages rather than summing it.
    if forensics._enabled:
        with forensics.collective_timer(site):
            return _guarded_run(site, fn, *args, **kwargs)
    return _guarded_run(site, fn, *args, **kwargs)


def _guarded_run(site, fn, *args, **kwargs):
    if profiler._active:
        with profiler.scope("collective", site):
            if resilience._active:
                return resilience.guard(site, fn, *args, **kwargs)
            return fn(*args, **kwargs)
    if resilience._active:
        return resilience.guard(site, fn, *args, **kwargs)
    return fn(*args, **kwargs)

__all__ = [
    "Communication",
    "MeshCommunication",
    "COMM_WORLD",
    "COMM_SELF",
    "get_comm",
    "use_comm",
    "sanitize_comm",
    "initialize",
]

# The default mesh axis name carried by every split DNDarray dimension.
MESH_AXIS = "d"


def _payload_bytes(x) -> int:
    """Per-participant payload bytes of a collective operand — works on concrete
    arrays AND tracers (collectives run inside shard_map/jit traces, so the
    diagnostics hooks see abstract values; shape/dtype are always static)."""
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return int(np.dtype(type(x)).itemsize) if np.isscalar(x) else 0
    size = 1
    for s in shape:
        size *= int(s)
    return size * np.dtype(dtype).itemsize


class Communication:
    """Base class / protocol for communication backends (reference ``communication.py:84``)."""

    @staticmethod
    def is_distributed() -> bool:
        raise NotImplementedError()

    def chunk(self, shape, split, rank=None):
        raise NotImplementedError()


class MeshCommunication(Communication):
    """A communicator backed by a 1-D ``jax.sharding.Mesh`` over a set of devices.

    Replaces ``MPICommunication`` (reference ``communication.py:116``). ``rank``/``size``
    keep their meaning as *shard index* / *number of shards* along the mesh axis; in a
    multi-controller deployment ``process_rank`` additionally reports the host process.
    """

    def __init__(
        self,
        devices: Optional[Sequence[jax.Device]] = None,
        axis_name: str = MESH_AXIS,
        mesh_shape: Optional[Sequence[int]] = None,
        axis_names: Optional[Sequence[str]] = None,
    ):
        if devices is None:
            devices = jax.devices()
        self._devices: List[jax.Device] = list(devices)
        if mesh_shape is None:
            self.axis_names: Tuple[str, ...] = (axis_name,)
            self.mesh = Mesh(np.array(self._devices), self.axis_names)
            self.axis_name = axis_name
        else:
            # N-D mesh (reference DASO's node-local × global hierarchy maps to the
            # ici × dcn axes of a 2-D device mesh, SURVEY §2.4). A ``split`` dimension
            # is sharded over ALL axes jointly; per-axis collectives go through the
            # ``axis_name=`` argument of the collective helpers.
            self.axis_names = tuple(axis_names or ("dcn", "ici"))
            if len(self.axis_names) != len(tuple(mesh_shape)):
                raise ValueError(
                    f"axis_names {self.axis_names} does not match mesh_shape {mesh_shape}"
                )
            self.mesh = Mesh(np.array(self._devices).reshape(tuple(mesh_shape)), self.axis_names)
            # collectives over a multi-axis comm default to reducing over all axes
            self.axis_name = self.axis_names if len(self.axis_names) > 1 else self.axis_names[0]

    @classmethod
    def hierarchical(
        cls,
        n_nodes: int,
        devices: Optional[Sequence[jax.Device]] = None,
        axis_names: Sequence[str] = ("dcn", "ici"),
    ) -> "MeshCommunication":
        """A 2-D (slow × fast) communicator: ``n_nodes`` groups over the slow ``dcn``
        axis, remaining devices per group on the fast ``ici`` axis.

        This is the TPU shape of the reference DASO's hierarchy — torch-DDP inside a
        node, skipped MPI syncs across nodes (reference ``optim/dp_optimizer.py:64-155``).
        """
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        if n_nodes <= 0 or len(devices) % n_nodes != 0:
            raise ValueError(
                f"cannot split {len(devices)} devices into {n_nodes} equal node groups"
            )
        return cls(devices, mesh_shape=(n_nodes, len(devices) // n_nodes), axis_names=axis_names)

    # ------------------------------------------------------------------ topology
    @property
    def size(self) -> int:
        """Number of shards along the mesh axis (≙ MPI world size)."""
        return len(self._devices)

    @property
    def rank(self) -> int:
        """Index of this controller's first device along the mesh (0 in single-controller)."""
        proc = jax.process_index()
        for i, d in enumerate(self._devices):
            if d.process_index == proc:
                return i
        return 0

    @property
    def process_rank(self) -> int:
        return jax.process_index()

    @property
    def devices(self) -> List[jax.Device]:
        return self._devices

    @property
    def is_hierarchical(self) -> bool:
        return len(self.axis_names) > 1

    @property
    def n_nodes(self) -> int:
        """Size of the slow (first) mesh axis — 1 on a flat mesh."""
        return int(self.mesh.shape[self.axis_names[0]]) if self.is_hierarchical else 1

    @property
    def node_size(self) -> int:
        """Devices per node group — the fast-axis extent."""
        return self.size // self.n_nodes

    @staticmethod
    def is_distributed() -> bool:
        return len(jax.devices()) > 1

    def __repr__(self) -> str:
        return f"MeshCommunication(size={self.size}, axis={self.axis_name!r})"

    # ------------------------------------------------------------------ chunking
    def chunk(
        self, shape: Sequence[int], split: Optional[int], rank: Optional[int] = None
    ) -> Tuple[int, Tuple[int, ...], Tuple[slice, ...]]:
        """Calculate the chunk of the global ``shape`` owned by ``rank`` along ``split``.

        Mirrors reference ``communication.py:157-215`` but uses the XLA-canonical
        *ceil-division* rule (shard ``i`` owns ``[i*c, min((i+1)*c, n))`` with
        ``c = ceil(n / size)``) instead of MPI-Heat's front-loaded remainder rule, so that
        the metadata agrees with how ``NamedSharding`` actually lays shards out in HBM.

        Returns ``(offset, local_shape, slices)``.
        """
        if rank is None:
            rank = self.rank
        shape = tuple(int(s) for s in shape)
        if split is None:
            return 0, shape, tuple(slice(0, s) for s in shape)
        split = int(split)
        n = shape[split]
        c = -(-n // self.size) if n else 0  # ceil division; 0-size stays 0
        start = min(rank * c, n)
        end = min((rank + 1) * c, n)
        lshape = shape[:split] + (end - start,) + shape[split + 1 :]
        slices = tuple(
            slice(start, end) if i == split else slice(0, s) for i, s in enumerate(shape)
        )
        return start, lshape, slices

    def counts_displs_shape(
        self, shape: Sequence[int], split: int
    ) -> Tuple[Tuple[int, ...], Tuple[int, ...], Tuple[int, ...]]:
        """Per-rank counts/displacements along ``split`` (reference ``communication.py:216``)."""
        counts, displs = [], []
        for r in range(self.size):
            offset, lshape, _ = self.chunk(shape, split, rank=r)
            counts.append(lshape[split])
            displs.append(offset)
        _, lshape, _ = self.chunk(shape, split)
        return tuple(counts), tuple(displs), tuple(lshape)

    def lshape_map(self, shape: Sequence[int], split: Optional[int]) -> np.ndarray:
        """(size, ndim) array of every shard's local shape (reference ``dndarray.py:304``)."""
        out = np.empty((self.size, len(shape)), dtype=np.int64)
        for r in range(self.size):
            _, lshape, _ = self.chunk(shape, split, rank=r)
            out[r] = lshape
        return out

    # ------------------------------------------------------------------ sharding
    def spec(self, ndim: int, split: Optional[int]) -> PartitionSpec:
        """The ``PartitionSpec`` encoding Heat's ``split`` for an ``ndim``-d array.

        On a multi-axis mesh the split dimension is sharded over all axes jointly
        (major-to-minor), so ``size`` shards exist either way."""
        if split is None:
            return PartitionSpec()
        entries = [None] * ndim
        entries[split] = self.axis_names if len(self.axis_names) > 1 else self.axis_names[0]
        return PartitionSpec(*entries)

    def sharding(self, ndim: int, split: Optional[int]) -> NamedSharding:
        """The ``NamedSharding`` encoding Heat's ``split`` for an ``ndim``-d array."""
        return NamedSharding(self.mesh, self.spec(ndim, split))

    def padded_dim(self, n: int) -> int:
        """The physical extent of a split dimension: ``n`` rounded up to a multiple of
        the mesh size, so every shard holds exactly ``ceil(n/P)`` elements."""
        n = int(n)
        c = -(-n // self.size) if n else 0
        return c * self.size

    def padded_shape(
        self, shape: Sequence[int], split: Optional[int]
    ) -> Tuple[int, ...]:
        """The physical shape of a logical ``shape`` laid out along ``split``: the
        split dimension rounded up to :meth:`padded_dim`, every other dimension
        unchanged. Equals ``shape`` for ``split=None`` and divisible extents. The
        static half of :meth:`shard` — the dispatch executor (``_executor``) uses
        it to stage the physical pad inside a jitted program."""
        shape = tuple(int(s) for s in shape)
        if split is None or split >= len(shape):
            return shape
        return shape[:split] + (self.padded_dim(shape[split]),) + shape[split + 1 :]

    def shard(self, array: jax.Array, split: Optional[int]) -> jax.Array:
        """Lay ``array`` out with dimension ``split`` sharded over the mesh.

        This is the physical half of ``resplit_`` (reference ``dndarray.py:1407``): XLA
        emits the all-gather / all-to-all / slice that the reference hand-writes.

        ``array`` is a *logical* value. Ragged extents (``n % P != 0``) return a
        **padded physical** value — the split dimension zero-padded to
        :meth:`padded_dim` so a true 1/P ``NamedSharding`` applies (jax.Array cannot
        represent uneven shards, and GSPMD resolves a forced ragged constraint to
        replication) — the padded-chunks representation SURVEY §7 prescribes. Callers
        wrap the result together with the logical gshape (``DNDarray`` keeps the
        logical/physical distinction); a padded input (whose extent is already a
        multiple of P) passes through the divisible path unchanged, so the operation
        is idempotent on physical values.
        """
        if jnp.issubdtype(getattr(array, "dtype", None), jnp.complexfloating):
            require_device_dtype(array.dtype)
        if diagnostics._enabled:
            # counts every layout REQUEST with its logical payload: an operand
            # that already matches the target (the early return below) costs no
            # device movement but is still one counted shard call — the counter
            # tracks the framework's layout traffic, not XLA's wire bytes
            diagnostics.record_collective(
                "shard", self.axis_name, self.size, _payload_bytes(array)
            )
        target = self.sharding(array.ndim, split)
        if isinstance(array, jax.Array):
            try:
                if array.sharding == target:
                    return array
            except AttributeError:
                pass  # tracer under jit: device_put below becomes a sharding constraint
        ragged = split is not None and array.shape[split] % self.size != 0
        if jax.process_count() > 1:
            # multi-controller: a host value can only populate addressable shards —
            # build per-shard via callback (each process fills only its own devices);
            # an existing global array reshard compiles to the XLA collective.
            if isinstance(array, jax.Array) and not array.is_fully_addressable:
                return _pad_reshard(array, target, split, self.padded_dim(array.shape[split]) if ragged else None)
            np_value = np.asarray(array)
            if ragged:
                widths = [(0, 0)] * np_value.ndim
                widths[split] = (0, self.padded_dim(np_value.shape[split]) - np_value.shape[split])
                np_value = np.pad(np_value, widths)
            return _guarded(
                "comm.shard", jax.make_array_from_callback,
                np_value.shape, target, lambda idx: np_value[idx],
            )
        if not ragged:
            return _guarded("comm.shard", jax.device_put, array, target)
        m = self.padded_dim(array.shape[split])
        pad_shape = array.shape[:split] + (m - array.shape[split],) + array.shape[split + 1 :]
        padded = jnp.concatenate(
            [jnp.asarray(array), jnp.zeros(pad_shape, jnp.asarray(array).dtype)], axis=split
        )
        return _guarded("comm.shard", jax.device_put, padded, target)

    # ------------------------------------------------------------------ collectives
    # Functional collectives usable inside shard_map blocks. Names kept close to the
    # reference's MPI surface (communication.py:541-1996) for discoverability, but these
    # are *pure functions of device-local values*, not buffer mutations.
    #
    # Every collective reports (op, mesh axis, participants, logical bytes) to
    # ht.diagnostics when metrics are enabled. The hooks run at Python call time —
    # inside a shard_map/jit trace that is TRACE time, so a cached executable's
    # replays are not re-counted (documented in doc/source/observability.rst).
    # Nested convenience forms count both layers (scan also records its inner
    # exscan, scatter its inner broadcast).
    def _axis_participants(self, axis_name=None) -> int:
        """Static shard count of the (possibly tuple-valued) named axis."""
        name = axis_name or self.axis_name
        names = (name,) if isinstance(name, str) else tuple(name)
        try:
            return int(np.prod([self.mesh.shape[n] for n in names]))
        except (KeyError, TypeError):
            return self.size

    def _record_collective(self, op: str, axis_name, x) -> None:
        """Report one collective's logical bytes (= per-participant payload ×
        participants) to ht.diagnostics and/or the forensics cost meters —
        each consumer gated on its own switch here. Callers gate on
        ``diagnostics._enabled or forensics._enabled`` so the disabled cost
        stays one attribute read per plane."""
        participants = self._axis_participants(axis_name)
        nbytes = _payload_bytes(x) * participants
        if diagnostics._enabled:
            diagnostics.record_collective(
                op, axis_name or self.axis_name, participants, nbytes,
            )
        if forensics._enabled:
            # bytes only: the invocation's wall time is recorded by the
            # _guarded_forensics leg around the actual dispatch
            forensics.note_collective(op, 0.0, nbytes=nbytes)

    def psum(self, x, axis_name: Optional[str] = None):
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("psum", axis_name, x)
        return _guarded("comm.psum", jax.lax.psum, x, axis_name or self.axis_name)

    Allreduce = psum

    def pmax(self, x, axis_name: Optional[str] = None):
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("pmax", axis_name, x)
        return _guarded("comm.pmax", jax.lax.pmax, x, axis_name or self.axis_name)

    def pmin(self, x, axis_name: Optional[str] = None):
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("pmin", axis_name, x)
        return _guarded("comm.pmin", jax.lax.pmin, x, axis_name or self.axis_name)

    def all_gather(self, x, axis: int = 0, axis_name: Optional[str] = None, tiled: bool = True):
        """Allgather along array axis ``axis`` (reference ``__allgather_like``
        ``communication.py:1047-1128``; the axis-permutation machinery there is subsumed
        by ``jax.lax.all_gather(axis=...)``)."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("all_gather", axis_name, x)
        return _guarded(
            "comm.all_gather", jax.lax.all_gather,
            x, axis_name or self.axis_name, axis=axis, tiled=tiled,
        )

    Allgather = all_gather

    def psum_scatter(
        self, x, scatter_axis: int = 0, axis_name: Optional[str] = None, tiled: bool = True,
    ):
        """Reduce-scatter (reference ``Reduce_scatter`` / ``__reduce_like`` with a
        scattered result): sums ``x`` across the axis and leaves each participant
        only its 1/P tile along array axis ``scatter_axis`` — the (P−1)/P-byte
        half of an all-reduce, for consumers that keep the result sharded (the
        comm-plan ``rs`` contraction plan)."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("psum_scatter", axis_name, x)
        return _guarded(
            "comm.psum_scatter", jax.lax.psum_scatter,
            x, axis_name or self.axis_name, scatter_dimension=scatter_axis,
            tiled=tiled,
        )

    Reduce_scatter = psum_scatter

    def all_to_all(self, x, split_axis: int, concat_axis: int, axis_name: Optional[str] = None):
        """Alltoall (reference ``__alltoall_like`` ``communication.py:1236``)."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("all_to_all", axis_name, x)
        return _guarded(
            "comm.all_to_all", jax.lax.all_to_all,
            x, axis_name or self.axis_name, split_axis=split_axis,
            concat_axis=concat_axis, tiled=True,
        )

    Alltoall = all_to_all

    def ppermute(self, x, perm, axis_name: Optional[str] = None):
        """Point-to-point send/recv pattern (reference Send/Recv ``communication.py:541-707``)."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("ppermute", axis_name, x)
        return _guarded(
            "comm.ppermute", jax.lax.ppermute,
            x, axis_name or self.axis_name, perm=perm,
        )

    def ring_shift(self, x, shift: int = 1, axis_name: Optional[str] = None):
        """Rotate shards around the ring — the TPU form of the reference's ring algorithms
        (``spatial/distance.py:209``)."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("ring_shift", axis_name, x)
        n = self.size
        perm = [(i, (i + shift) % n) for i in range(n)]
        return _guarded(
            "comm.ring_shift", jax.lax.ppermute,
            x, axis_name or self.axis_name, perm=perm,
        )

    def broadcast(self, x, root: int = 0, axis_name: Optional[str] = None):
        """Bcast from shard ``root`` (reference ``communication.py:736``).

        Binomial-tree dissemination over ``ppermute``: ⌈log₂P⌉ rounds, P−1 unit
        payloads on the wire in total — the MPI tree shape. (The naive masked-psum
        spelling is a full-payload all-reduce: ~2× payload per link and no
        latency win at pod scale.) Multi-axis communicators keep the psum form,
        whose all-axis reduction is what their semantics need.
        """
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("broadcast", axis_name, x)
        return _guarded("comm.broadcast", self._broadcast_impl, x, root, axis_name)

    def _broadcast_impl(self, x, root, axis_name):
        name = axis_name or self.axis_name
        if not isinstance(name, str):
            idx = jax.lax.axis_index(name)
            src = jnp.where(idx == root, x, jnp.zeros_like(x))
            return jax.lax.psum(src, name)
        p = jax.lax.psum(1, name)
        idx = jax.lax.axis_index(name)
        # tree slots are relabeled relative to the root (slot = (idx - root) mod p),
        # so no physical pre/post-rotation rounds are needed for root != 0
        slot = (idx - root) % p
        val = jnp.where(slot == 0, x, jnp.zeros_like(x))
        h = 1
        while h < p:
            # slots [0, h) hold the value; each forwards to its mirror slot + h
            pairs = [
                ((i + root) % p, (i + h + root) % p) for i in range(min(h, p - h))
            ]
            recv = jax.lax.ppermute(val, name, perm=pairs)
            val = jnp.where(slot < h, val, val + recv)
            h <<= 1
        return val

    Bcast = broadcast

    def exscan(self, x, axis_name: Optional[str] = None):
        """Exclusive prefix-sum over shards (reference Exscan ``communication.py:1004``).

        Hillis–Steele doubling over ``ppermute``: ⌈log₂P⌉+1 rounds of unit
        payload, O(log P) latency — versus the naive ``all_gather`` + masked-sum
        form whose per-device payload is P×. Works for any P (not just powers of
        two); shard 0 receives the additive identity.
        """
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("exscan", axis_name, x)
        return _guarded("comm.exscan", self._exscan_impl, x, axis_name)

    def _exscan_impl(self, x, axis_name):
        name = axis_name or self.axis_name
        if not isinstance(name, str):
            idx = jax.lax.axis_index(name)
            full = jax.lax.all_gather(x, name, axis=0)
            mask = (jnp.arange(self.size) < idx).reshape((-1,) + (1,) * (full.ndim - 1))
            return jnp.sum(full * mask.astype(full.dtype), axis=0)
        p = jax.lax.psum(1, name)
        # right-shift by one (slot 0 gets zeros), then inclusive doubling scan
        acc = jax.lax.ppermute(x, name, perm=[(i, i + 1) for i in range(p - 1)])
        d = 1
        while d < p:
            acc = acc + jax.lax.ppermute(acc, name, perm=[(i, i + d) for i in range(p - d)])
            d <<= 1
        return acc

    Exscan = exscan

    def scan(self, x, axis_name: Optional[str] = None):
        """Inclusive prefix-sum over shards (reference Scan ``communication.py:1881``):
        the exclusive scan plus the local contribution."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("scan", axis_name, x)
        return self.exscan(x, axis_name) + x

    Scan = scan

    def reduce(self, x, root: int = 0, axis_name: Optional[str] = None):
        """Sum-reduce with the result significant only at shard ``root`` (reference
        Reduce ``communication.py:1823``): SPMD collectives are symmetric, so this
        is the all-reduce with non-root shards zeroed — the rooted contract without
        a second collective."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("reduce", axis_name, x)
        name = axis_name or self.axis_name
        total = _guarded("comm.reduce", jax.lax.psum, x, name)
        idx = jax.lax.axis_index(name)
        return jnp.where(idx == root, total, jnp.zeros_like(total))

    Reduce = reduce

    def gather(self, x, axis: int = 0, root: int = 0, axis_name: Optional[str] = None):
        """Gather shards to ``root`` (reference Gather ``communication.py:1299``):
        the all-gather with non-root shards zeroed — rooted semantics on a
        symmetric collective."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("gather", axis_name, x)
        name = axis_name or self.axis_name
        full = _guarded("comm.gather", jax.lax.all_gather, x, name, axis=axis, tiled=True)
        idx = jax.lax.axis_index(name)
        return jnp.where(idx == root, full, jnp.zeros_like(full))

    Gather = gather

    def scatter(self, x, axis: int = 0, root: int = 0, axis_name: Optional[str] = None):
        """Scatter ``root``'s value in equal chunks along ``axis`` (reference
        Scatter ``communication.py:1936``). Binomial-tree broadcast of the full
        payload followed by a local slice: XLA has no rooted scatter primitive, so
        the wire cost is the broadcast's P−1 full payloads rather than MPI's 1/P
        chunks — acceptable because every framework path that needs 1/P placement
        uses shardings (``comm.shard``), not this rooted op."""
        if diagnostics._enabled or forensics._enabled:
            self._record_collective("scatter", axis_name, x)
        name = axis_name or self.axis_name
        full = self.broadcast(x, root=root, axis_name=name)
        idx = jax.lax.axis_index(name)
        # the size of the NAMED axis (a sub-axis on hierarchical meshes), which is
        # static at trace time — dynamic_slice needs a static chunk size
        names = (name,) if isinstance(name, str) else tuple(name)
        axsize = int(np.prod([self.mesh.shape[n] for n in names]))
        if full.shape[axis] % axsize:
            raise ValueError(
                f"scatter: extent {full.shape[axis]} along axis {axis} is not "
                f"divisible by the {axsize}-shard axis {name!r} (MPI_Scatter "
                f"semantics require exact chunks)"
            )
        c = full.shape[axis] // axsize
        return jax.lax.dynamic_slice_in_dim(full, idx * c, c, axis=axis)

    Scatter = scatter

    # ------------------------------------------------------------------ misc parity
    def Split(self, color=0, key: int = 0) -> "MeshCommunication":
        """Sub-communicator by colour (reference MPI ``Comm.Split``, ``communication.py:465``).

        MPI's Split is collective — each rank passes its own colour. In single-controller
        JAX one call sees every shard, so ``color`` may be a sequence assigning a colour
        per shard index; the sub-communicator returned is the group containing shard
        ``self.rank``. A scalar colour means every shard shares it (≙ MPI dup).
        """
        axis = self.axis_names[-1] if self.is_hierarchical else self.axis_name
        if np.isscalar(color):
            if self.is_hierarchical:  # true dup: keep the mesh topology
                return MeshCommunication(
                    self._devices,
                    mesh_shape=self.mesh.devices.shape,
                    axis_names=self.axis_names,
                )
            return MeshCommunication(self._devices, axis_name=axis)
        colors = list(color)
        if len(colors) != self.size:
            raise ValueError(f"need one color per shard ({self.size}), got {len(colors)}")
        mine = colors[self.rank]
        devs = [d for i, d in enumerate(self._devices) if colors[i] == mine]
        return MeshCommunication(devs, axis_name=axis)


# A jitted, cached reshard for ragged (non-divisible) dims: GSPMD pads internally.
_pad_cache: dict = {}


def _pad_reshard(
    array: jax.Array, target: NamedSharding, split: Optional[int], padded: Optional[int]
) -> jax.Array:
    """Reshard a (possibly non-addressable) global array, zero-padding a ragged split
    dimension to ``padded`` inside the jitted program so the output satisfies a true
    1/P NamedSharding."""
    if diagnostics._enabled:
        diagnostics.record_collective(
            "_pad_reshard", target.mesh.axis_names, target.mesh.size,
            _payload_bytes(array),
        )
    key = (target, array.ndim, split, padded)  # NamedSharding hashes mesh + devices,
    # so two same-shape meshes over different device sets cannot collide
    fn = _pad_cache.get(key)
    if fn is None:
        if padded is None:
            fn = jax.jit(lambda x: x, out_shardings=target)
        else:

            def _pad(x):
                widths = [(0, 0)] * x.ndim
                widths[split] = (0, padded - x.shape[split])
                return jnp.pad(x, widths)

            fn = jax.jit(_pad, out_shardings=target)
        _pad_cache[key] = fn
    return _guarded("comm.reshard", fn, array)


# Every build_world() gets its own barrier id + KV namespace: coordination KV keys
# are namespace-scoped per use, and SPMD symmetry keeps the counter in step on every
# process, so a re-join re-anchors instead of failing the handshake. Every wait goes
# through the supervised wrappers: bounded (supervision.coord_timeout_ms),
# sentinel-abortable, and typed (resilience.CoordinationTimeout / PeerFailed).
_handshake_generation = 0


def _telemetry_bootstrap() -> None:
    """Stamp this process's rank into ht.telemetry and, on multi-process jobs,
    run the boot-time clock-offset handshake: a coordination-service barrier
    (the supervised KV form), then every process samples
    ``time.monotonic_ns()`` and publishes it through the distributed KV store
    (one logical allgather of the anchors) — the zero point that lets
    ``telemetry.merge`` align trace timestamps across ranks. The handshake
    rides the ``jax.distributed`` coordination channel, never an XLA
    computation, so it works on every backend (CPU meshes included) and
    cannot touch any compiled program — HLO-untouched by construction.
    Accuracy is the barrier's exit skew (sub-millisecond on one host,
    network-RTT across hosts; the docs state the caveat). Afterwards the
    supervision plane is armed for the job (heartbeats + sentinel polling)
    and this process's rank is stamped for ``rank``-targeted fault plans."""
    global _handshake_generation
    try:
        index, nprocs = jax.process_index(), jax.process_count()
        telemetry.set_process_info(index, nprocs)
        resilience.set_fault_rank(index)
        if nprocs > 1 and os.environ.get("HEAT_TPU_TELEMETRY_HANDSHAKE") != "0":
            co = supervision._require_coordinator()
            gen = _handshake_generation
            _handshake_generation += 1  # ht: ignore[lock-racing-increment] -- bootstrap-only: build_world() runs at package import, in initialize() and in the elastic restart, all single-threaded launch paths; SPMD symmetry (not thread-safety) is what keeps the counter aligned
            # boot-time liveness wait, capped at the old 60 s handshake
            # budget: the supervision plane is not armed yet (auto_arm runs
            # after the handshake), so a peer that died pre-handshake cannot
            # be sentinel-aborted mid-wait — letting this wait default to
            # the full 600 s coordination budget would stall every
            # survivor's boot 10x longer than pre-supervision. The unified
            # knob still bounds it downward (HEAT_TPU_COORD_TIMEOUT_MS
            # below 60 s shortens the handshake too).
            boot_ms = min(supervision.coord_timeout_ms(), 60_000)
            supervision.kv_barrier(
                f"heat_tpu/telemetry/clock/{gen}",
                nprocs=nprocs, rank=index, timeout_ms=boot_ms,
                site="telemetry.handshake", coordinator=co,
            )
            anchor = time.monotonic_ns()
            co.set(f"heat_tpu/telemetry/anchor/{gen}/{index}", str(anchor))
            anchors = [
                int(supervision.kv_wait(
                    f"heat_tpu/telemetry/anchor/{gen}/{i}", boot_ms,
                    site="telemetry.handshake", coordinator=co,
                ))
                for i in range(nprocs)
            ]
            telemetry.record_clock_anchor(anchor, anchors)
    except Exception as exc:
        # a failed handshake must never block the job: the shards fall back
        # to unaligned per-process anchors, and the degradation is accounted
        # in the always-on resilience event stream
        diagnostics.record_resilience_event(
            "telemetry.handshake", "degraded", f"{type(exc).__name__}: {exc}"
        )
    supervision.auto_arm()


# --------------------------------------------------------------------------- singletons
COMM_WORLD: MeshCommunication
"""World communicator over all visible devices (reference ``MPI_WORLD`` ``communication.py:2013``)."""

COMM_SELF: MeshCommunication
"""Single-device communicator (reference ``MPI_SELF`` ``communication.py:2014``)."""


def build_world() -> None:
    """(Re)build what this module derives from the world: the communicators, the
    padding programs compiled for the old mesh, and the telemetry stamp / clock
    handshake (which ends in ``supervision.auto_arm()``). Steps (d) and (e) of
    ``_bootstrap.run()``, and the tail of :func:`initialize` and of an elastic restart."""
    global COMM_WORLD, COMM_SELF, __default_comm
    COMM_WORLD = MeshCommunication()
    COMM_SELF = MeshCommunication(jax.devices()[:1])
    __default_comm = COMM_WORLD
    _pad_cache.clear()
    with diagnostics.startup("bootstrap.world.telemetry"):
        _telemetry_bootstrap()


def get_comm() -> MeshCommunication:
    """Return the current default communicator (reference ``communication.py:2020``)."""
    return __default_comm


def use_comm(comm: Optional[MeshCommunication] = None) -> None:
    """Set the default communicator (reference ``communication.py:2050``)."""
    global __default_comm
    if comm is None:
        comm = COMM_WORLD
    if not isinstance(comm, Communication):
        raise TypeError(f"expected a Communication object, got {type(comm)}")
    __default_comm = comm


def sanitize_comm(comm: Optional[Communication]) -> MeshCommunication:
    """Validate ``comm`` or fall back to the default (reference ``devices.py`` analogue)."""
    if comm is None:
        return get_comm()
    if not isinstance(comm, Communication):
        raise TypeError(f"expected a Communication object, got {type(comm)}")
    return comm


def initialize(**kwargs) -> None:
    """Join a ``jax.distributed`` job (keywords of ``jax.distributed.initialize``,
    which replaces the reference's ``mpirun -np N python script.py``) and rebuild the
    world singletons over it: steps (c) to (e) of ``_bootstrap.run``.

    This is NOT how a job is started: a process can join only while it has no XLA
    backend, and ``import heat_tpu`` creates one (a ``RuntimeError`` here says so). The
    launch path is the ``HEAT_TPU_COORDINATOR_ADDRESS`` / ``HEAT_TPU_NUM_PROCESSES`` /
    ``HEAT_TPU_PROCESS_ID`` environment, honoured at import (``_bootstrap``). This call
    is for the re-join after ``supervision.teardown_distributed`` dropped the backend
    (the elastic restart); ``_bootstrap.join`` says when the runtime is supervised.

    Multi-controller contract (every process runs the same program, SPMD):

    - compute on DNDarrays is global — XLA emits the cross-host collectives; nothing
      special to do;
    - collection (``numpy()``/``tolist()``/``item()``/printing) performs a cross-host
      ``process_allgather`` and returns the identical global value on every process;
    - ``ht.save*`` gathers and writes from process 0 only (see ``io._is_writer``);
      ``ht.load*`` reads the file on every process (shared filesystem assumed, like
      the reference's MPI-IO setups) and populates only addressable shards;
    - per-process ingest of pre-distributed data uses ``ht.array(..., is_split=k)``.
    """
    _bootstrap.join(**kwargs)
    build_world()
