"""Direct tests of the communication layer (reference dedicates 2,494 LoC to testing
its MPI wrapper, heat/core/tests/test_communication.py; these are the TPU equivalents:
the collective helpers are exercised for real inside ``shard_map`` blocks on the test
mesh, plus the chunk rule, sharding specs, sub-communicators, and the ring-cdist
consumer)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import heat_tpu as ht
from heat_tpu.core.communication import MeshCommunication, get_comm


comm = get_comm()
AX = comm.axis_name


def smap(fn, in_specs, out_specs):
    return jax.shard_map(fn, mesh=comm.mesh, in_specs=in_specs, out_specs=out_specs)


class TestChunking:
    def test_chunk_ceil_division(self):
        n = 3 * comm.size + 1
        sizes = [comm.chunk((n,), 0, rank=r)[1][0] for r in range(comm.size)]
        assert sum(sizes) == n
        # ceil rule: shard r owns [r*c, min((r+1)*c, n)) with c = ceil(n/p)
        c = -(-n // comm.size)
        expect = [min(c, max(0, n - r * c)) for r in range(comm.size)]
        assert sizes == expect

    def test_chunk_none_split(self):
        offset, lshape, slices = comm.chunk((4, 5), None)
        assert offset == 0 and lshape == (4, 5)
        assert slices == (slice(0, 4), slice(0, 5))

    def test_counts_displs(self):
        counts, displs, lshape = comm.counts_displs_shape((comm.size * 2 + 1, 3), 0)
        assert sum(counts) == comm.size * 2 + 1
        assert displs[0] == 0
        for i in range(1, comm.size):
            assert displs[i] == displs[i - 1] + counts[i - 1]

    def test_lshape_map(self):
        m = comm.lshape_map((comm.size * 3, 4), 0)
        assert m.shape == (comm.size, 2)
        assert (m[:, 0] == 3).all() and (m[:, 1] == 4).all()

    def test_spec(self):
        assert comm.spec(3, None) == P()
        assert comm.spec(3, 1) == P(None, AX, None)


class TestCollectives:
    """Each helper runs inside a real shard_map block on the test mesh."""

    def test_psum(self):
        x = jnp.arange(comm.size, dtype=jnp.float32)
        out = smap(lambda v: comm.psum(v), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.full(comm.size, x.sum()))

    def test_pmax_pmin(self):
        x = jnp.arange(comm.size, dtype=jnp.float32) + 1
        mx = smap(lambda v: comm.pmax(v), P(AX), P(AX))(x)
        mn = smap(lambda v: comm.pmin(v), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(mx), np.full(comm.size, comm.size))
        np.testing.assert_allclose(np.asarray(mn), np.full(comm.size, 1.0))

    def test_all_gather(self):
        x = jnp.arange(comm.size * 2, dtype=jnp.float32)
        out = smap(
            lambda v: comm.all_gather(v, axis=0)[None], P(AX), P(AX, None)
        )(x)
        for r in range(comm.size):
            np.testing.assert_allclose(np.asarray(out[r]), np.asarray(x))

    def test_all_to_all(self):
        n = comm.size
        x = jnp.arange(n * n, dtype=jnp.float32).reshape(n, n)
        # each shard holds a row; all_to_all splitting columns/concatenating rows
        # transposes the block layout
        out = smap(
            lambda v: comm.all_to_all(v, split_axis=1, concat_axis=0),
            P(AX, None),
            P(None, AX),
        )(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x).T.reshape(n, n).T)

    def test_ppermute_shift(self):
        x = jnp.arange(comm.size, dtype=jnp.float32)
        perm = [(i, (i + 1) % comm.size) for i in range(comm.size)]
        out = smap(lambda v: comm.ppermute(v, perm), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.roll(np.asarray(x), 1))

    def test_ring_shift(self):
        x = jnp.arange(comm.size, dtype=jnp.float32)
        out = smap(lambda v: comm.ring_shift(v, 1), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.roll(np.asarray(x), 1))

    def test_broadcast(self):
        root = comm.size - 1
        x = jnp.arange(comm.size, dtype=jnp.float32)
        out = smap(lambda v: comm.broadcast(v, root=root), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.full(comm.size, float(root)))

    def test_exscan(self):
        x = jnp.ones(comm.size, dtype=jnp.float32)
        out = smap(lambda v: comm.exscan(v), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.arange(comm.size))

    def test_scan(self):
        # inclusive prefix against the numpy cumsum oracle, non-uniform values
        x = (jnp.arange(comm.size, dtype=jnp.float32) + 1.0) * 2.0
        out = smap(lambda v: comm.scan(v), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.cumsum(np.asarray(x)))

    def test_reduce_rooted(self):
        x = jnp.arange(comm.size, dtype=jnp.float32) + 1.0
        for root in (0, comm.size - 1):
            out = smap(lambda v: comm.reduce(v, root=root), P(AX), P(AX))(x)
            want = np.zeros(comm.size, np.float32)
            want[root] = float(np.asarray(x).sum())
            np.testing.assert_allclose(np.asarray(out), want)

    def test_gather_rooted(self):
        n = comm.size
        x = jnp.arange(2 * n, dtype=jnp.float32)
        root = n - 1
        out = smap(
            lambda v: comm.gather(v, axis=0, root=root)[None], P(AX), P(AX, None)
        )(x)
        for r in range(n):
            want = np.asarray(x) if r == root else np.zeros(2 * n, np.float32)
            np.testing.assert_allclose(np.asarray(out[r]), want)

    def test_scatter(self):
        n = comm.size
        buf = jnp.arange(2 * n, dtype=jnp.float32)

        # every shard offers a buffer; MPI semantics: only root's content matters
        def block(v):
            mine = jnp.where(jax.lax.axis_index(AX) == 1, v, -v)
            return comm.scatter(mine, axis=0, root=1)

        out = smap(block, P(), P(AX))(buf)  # shard r receives chunk r of root's buf
        np.testing.assert_allclose(np.asarray(out), np.asarray(buf))

    def test_mpi_rooted_aliases(self):
        assert comm.Scan == comm.scan and comm.Reduce == comm.reduce
        assert comm.Gather == comm.gather and comm.Scatter == comm.scatter


class TestSplit:
    def test_scalar_color_dup(self):
        dup = comm.Split()
        assert dup.size == comm.size
        assert dup.axis_name == comm.axis_name

    @pytest.mark.skipif(len(jax.devices()) % 2 != 0, reason="needs even device count")
    def test_two_color_split(self):
        half = comm.size // 2
        colors = [0] * half + [1] * (comm.size - half)
        sub = comm.Split(colors)
        assert sub.size == half
        assert sub.devices == comm.devices[:half]

    def test_bad_color_count(self):
        with pytest.raises(ValueError):
            comm.Split([0] * (comm.size + 1))


class TestRingCdist:
    """The shard_map ring consumer of ppermute (reference ring _dist distance.py:209)."""

    def _data(self, nx, ny, d=5):
        kx, ky = jax.random.key(0), jax.random.key(1)
        x = np.asarray(jax.random.normal(kx, (nx, d), jnp.float32))
        y = np.asarray(jax.random.normal(ky, (ny, d), jnp.float32))
        return x, y

    def _ref_cdist(self, x, y):
        return np.sqrt(((x[:, None, :] - y[None, :, :]) ** 2).sum(-1))

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a distributed mesh")
    def test_ring_path_matches_numpy(self):
        nx, ny = 2 * comm.size, 3 * comm.size
        x, y = self._data(nx, ny)
        X = ht.array(x, split=0)
        Y = ht.array(y, split=0)
        d = ht.spatial.cdist(X, Y)
        assert d.split == 0
        np.testing.assert_allclose(d.numpy(), self._ref_cdist(x, y), rtol=1e-3, atol=2e-3)

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a distributed mesh")
    def test_ring_self_distance(self):
        n = 2 * comm.size
        x, _ = self._data(n, n)
        X = ht.array(x, split=0)
        d = ht.spatial.cdist(X)
        np.testing.assert_allclose(d.numpy(), self._ref_cdist(x, x), rtol=1e-3, atol=2e-3)

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a distributed mesh")
    def test_ring_manhattan(self):
        nx, ny = 2 * comm.size, comm.size
        x, y = self._data(nx, ny)
        d = ht.spatial.manhattan(ht.array(x, split=0), ht.array(y, split=0))
        ref = np.abs(x[:, None, :] - y[None, :, :]).sum(-1)
        np.testing.assert_allclose(d.numpy(), ref, rtol=1e-3, atol=2e-3)

    def test_ragged_falls_back(self):
        # sizes that do not divide the mesh take the SPMD-global path; same numbers
        nx, ny = 2 * comm.size + 1, comm.size + 1
        x, y = self._data(nx, ny)
        d = ht.spatial.cdist(ht.array(x, split=0), ht.array(y, split=0))
        np.testing.assert_allclose(d.numpy(), self._ref_cdist(x, y), rtol=1e-3, atol=2e-3)

    def test_feature_split_accepted(self):
        # split=1 inputs are a contraction — previously rejected with
        # NotImplementedError("Input split was not 0")
        x, y = self._data(6, 4, d=max(comm.size, 2))
        d = ht.spatial.cdist(ht.array(x, split=1), ht.array(y, split=1))
        assert d.split is None
        np.testing.assert_allclose(d.numpy(), self._ref_cdist(x, y), rtol=1e-3, atol=2e-3)

    def test_y_split_only(self):
        x, y = self._data(5, 3 * max(comm.size, 1))
        d = ht.spatial.cdist(ht.array(x, split=None), ht.array(y, split=0))
        assert d.split == 1 or not ht.array(y, split=0).is_distributed()
        np.testing.assert_allclose(d.numpy(), self._ref_cdist(x, y), rtol=1e-3, atol=2e-3)


class TestAliases:
    def test_mpi_names(self):
        x = jnp.arange(comm.size, dtype=jnp.float32)
        out = smap(lambda v: comm.Allreduce(v), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.full(comm.size, x.sum()))
        out = smap(lambda v: comm.Bcast(v, root=0), P(AX), P(AX))(x)
        np.testing.assert_allclose(np.asarray(out), np.zeros(comm.size))
        out = smap(lambda v: comm.Exscan(v), P(AX), P(AX))(jnp.ones(comm.size, jnp.float32))
        np.testing.assert_allclose(np.asarray(out), np.arange(comm.size))

    def test_allgather_axis1(self):
        n = comm.size
        x = jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)
        # each shard holds one row as a (2, 1) column; gathering along axis=1
        # reassembles the transposed matrix identically on every shard
        out = smap(
            lambda v: comm.all_gather(v.T, axis=1)[None], P(AX, None), P(AX, None, None)
        )(x)
        for r in range(n):
            np.testing.assert_allclose(np.asarray(out[r]), np.asarray(x).T)

    def test_exscan_int(self):
        x = jnp.full(comm.size, 2, dtype=jnp.int32)
        out = smap(lambda v: comm.exscan(v), P(AX), P(AX))(x)
        np.testing.assert_array_equal(np.asarray(out), 2 * np.arange(comm.size))


class TestHierarchicalCollectives:
    """Per-axis collectives on a 2-D (dcn, ici) mesh — the DASO substrate."""

    @pytest.fixture
    def hcomm(self):
        if len(jax.devices()) < 4 or len(jax.devices()) % 2 != 0:
            pytest.skip("needs an even device count >= 4")
        return MeshCommunication.hierarchical(2)

    def test_axis_scoped_psum(self, hcomm):
        dcn, ici = hcomm.axis_names
        n_nodes, node_size = hcomm.n_nodes, hcomm.node_size
        x = jnp.arange(hcomm.size, dtype=jnp.float32).reshape(n_nodes, node_size)

        def body(v):
            return (
                hcomm.psum(v, axis_name=ici),
                hcomm.psum(v, axis_name=dcn),
                hcomm.psum(v, axis_name=(dcn, ici)),
            )

        fast, slow, both = jax.shard_map(
            body,
            mesh=hcomm.mesh,
            in_specs=P(dcn, ici),
            out_specs=(P(dcn, ici), P(dcn, ici), P(dcn, ici)),
        )(x)
        xn = np.asarray(x)
        # psum over ici: row sums replicated across the row
        np.testing.assert_allclose(
            np.asarray(fast), np.repeat(xn.sum(1, keepdims=True), node_size, 1)
        )
        # psum over dcn: column sums replicated down the column
        np.testing.assert_allclose(
            np.asarray(slow), np.repeat(xn.sum(0, keepdims=True), n_nodes, 0)
        )
        np.testing.assert_allclose(np.asarray(both), np.full_like(xn, xn.sum()))

    def test_scatter_sub_axis(self, hcomm):
        """scatter over the ici sub-axis must chunk by THAT axis's size, not the
        whole mesh size (regression: elements past size//mesh_size were dropped)."""
        dcn, ici = hcomm.axis_names
        n_nodes, node_size = hcomm.n_nodes, hcomm.node_size
        buf = jnp.arange(2 * node_size, dtype=jnp.float32)

        def body(v):
            return hcomm.scatter(v, axis=0, root=0, axis_name=ici)

        out = jax.shard_map(
            body, mesh=hcomm.mesh, in_specs=P(), out_specs=P(ici)
        )(buf)
        np.testing.assert_allclose(np.asarray(out), np.asarray(buf))
        with pytest.raises(ValueError):
            jax.shard_map(
                lambda v: hcomm.scatter(v, axis=0, axis_name=ici),
                mesh=hcomm.mesh, in_specs=P(), out_specs=P(ici),
            )(jnp.arange(2 * node_size + 1, dtype=jnp.float32))

    def test_topology_properties(self, hcomm):
        assert hcomm.is_hierarchical
        assert hcomm.n_nodes == 2
        assert hcomm.n_nodes * hcomm.node_size == hcomm.size
        # a split dim shards over all axes jointly
        spec = hcomm.spec(2, 0)
        assert spec == P(hcomm.axis_names, None)

    def test_hierarchical_dup(self, hcomm):
        dup = hcomm.Split()
        assert dup.is_hierarchical
        assert dup.n_nodes == hcomm.n_nodes


class TestBringUp:
    """The launch contract of ``core/_bootstrap.py`` as a live process sees it
    (``tests/test_multiprocess.py`` launches real jobs through it)."""

    def test_initialize_after_import_names_the_contract(self):
        world = get_comm()
        with pytest.raises(RuntimeError, match="HEAT_TPU_COORDINATOR_ADDRESS"):
            ht.initialize(coordinator_address="localhost:1", num_processes=2, process_id=0)
        assert get_comm() is world and jax.process_count() == 1

    @pytest.mark.parametrize("given", [{}, {"HEAT_TPU_NUM_PROCESSES": "2"},
                                       {"HEAT_TPU_PROCESS_ID": "0"}])
    def test_partial_launch_contract_is_an_error(self, monkeypatch, given):
        from heat_tpu.core import _bootstrap

        for name in _bootstrap._CONTRACT[1:]:
            monkeypatch.delenv(name, raising=False)
        monkeypatch.setenv("HEAT_TPU_COORDINATOR_ADDRESS", "localhost:1")
        for name, value in given.items():
            monkeypatch.setenv(name, value)
        missing = [n for n in _bootstrap._CONTRACT[1:] if n not in given]
        with pytest.raises(RuntimeError, match=" and ".join(missing) + " (is|are) not"):
            _bootstrap.run()
