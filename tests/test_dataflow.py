"""Tests for ``heat_tpu.analysis.dataflow`` — the interprocedural engine the
SPMD/layout rule families (ISSUE 12) are built on — plus a violating AND a
conforming fixture per new rule, compiled through throwaway package trees
exactly like ``tests/test_analysis.py`` does.

Three layers:

- **call graph**: edges through same-module calls, ``module_alias.fn``
  imports, ``self.method`` resolution, and the ``_executor.lookup``
  ``build()``-callback convention (the returned closure is indexed like any
  other def); cycles terminate with the ``cyclic`` flag instead of hanging
  or blowing the stack; decorated defs are still nodes.
- **summaries**: collective emission sequences are ordered, expand through
  resolved calls, stay stable across two independent builds of the same
  tree, and serialize/deserialize byte-identically (what the incremental
  cache stores).
- **rule fixtures**: every new rule id fires on its minimal violating
  snippet and stays silent on the conforming twin.
"""

from __future__ import annotations

import json
import os
import tempfile
import textwrap
import unittest

from heat_tpu.analysis import dataflow
from heat_tpu.analysis.engine import Universe

from tests.test_analysis import run_fixture, rule_ids


def build_universe(files):
    """A Universe + Dataflow over a throwaway package tree; returns
    ``(tmpdir_handle, universe, dataflow)`` — keep the handle alive while
    using them."""
    td = tempfile.TemporaryDirectory()
    pkg = os.path.join(td.name, "heat_tpu")
    for rel, src in files.items():
        path = os.path.join(pkg, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            fh.write(textwrap.dedent(src))
    uni = Universe(pkg)
    return td, uni, dataflow.get(uni)


class TestCallGraph(unittest.TestCase):
    def test_cross_module_and_self_method_edges(self):
        td, uni, df = build_universe({
            "core/a.py": """
                from . import b

                class Worker:
                    def run(self, comm, v):
                        return self.step(comm, v)

                    def step(self, comm, v):
                        return b.emit(comm, v)
            """,
            "core/b.py": """
                def emit(comm, v):
                    return comm.psum(v)
            """,
        })
        with td:
            edges = set(df.edges())
            self.assertIn(
                ("heat_tpu.core.a:Worker.run", "heat_tpu.core.a:Worker.step"),
                edges,
            )
            self.assertIn(
                ("heat_tpu.core.a:Worker.step", "heat_tpu.core.b:emit"),
                edges,
            )
            # the summary propagated interprocedurally through both hops
            (run_info,) = df.lookup("heat_tpu.core.a", "Worker.run")
            self.assertEqual(run_info.seq, ("comm.psum",))

    def test_cycles_terminate_and_mark_cyclic(self):
        td, uni, df = build_universe({
            "core/a.py": """
                def ping(comm, v, n):
                    comm.psum(v)
                    return pong(comm, v, n - 1)

                def pong(comm, v, n):
                    return ping(comm, v, n)
            """,
        })
        with td:
            (ping,) = df.lookup("heat_tpu.core.a", "ping")
            (pong,) = df.lookup("heat_tpu.core.a", "pong")
            self.assertTrue(ping.cyclic or pong.cyclic)
            # the direct emission is still summarized; may_emit closes over
            # the cycle so callers know SOMETHING is emitted
            self.assertIn("comm.psum", ping.seq)
            self.assertTrue(ping.may_emit)
            self.assertTrue(pong.may_emit)

    def test_decorated_defs_are_nodes(self):
        td, uni, df = build_universe({
            "core/a.py": """
                import functools

                def deco(fn):
                    @functools.wraps(fn)
                    def wrapped(*a, **k):
                        return fn(*a, **k)
                    return wrapped

                @deco
                def guarded(comm, v):
                    return comm.all_gather(v)
            """,
        })
        with td:
            (info,) = df.lookup("heat_tpu.core.a", "guarded")
            self.assertEqual(info.seq, ("comm.all_gather",))

    def test_build_callback_convention_reaches_traced_set(self):
        # the engine's lookup()-protocol seeding (the function a build()
        # returns is the program body) must keep working with the dataflow
        # pass loaded — trace-purity findings prove the traced set
        bad = run_fixture({"core/x.py": """
            import os

            def stage():
                def build():
                    def body(v):
                        os.environ.get("KNOB")
                        return v
                    return body, None, None, None
                return build
        """})
        self.assertIn("trace-env-read", rule_ids(bad))

    def test_rank_taint_converges_over_deep_caller_first_chains(self):
        # review-hardened: the global taint fixpoint must run to
        # convergence, not a fixed round count — callers defined BEFORE
        # callees make each round propagate only one hop
        chain = "\n\n".join(
            f"def h{i}():\n    return h{i - 1}()" for i in range(8, 1, -1)
        )
        src = (
            "import jax\n\n"
            "def f(comm, v):\n"
            "    if h8():\n"
            "        return comm.psum(v)\n"
            "    return v\n\n"
            f"{chain}\n\n"
            "def h1():\n"
            "    return jax.process_index() == 0\n"
        )
        td, uni, df = build_universe({"core/x.py": src})
        with td:
            (top,) = df.lookup("heat_tpu.core.x", "h8")
            self.assertTrue(top.returns_tainted)

    def test_rank_taint_through_helper_returns(self):
        td, uni, df = build_universe({
            "core/io.py": """
                import jax

                def _is_writer():
                    return jax.process_index() == 0

                def save(comm, v):
                    writer = _is_writer()
                    return writer
            """,
        })
        with td:
            (helper,) = df.lookup("heat_tpu.core.io", "_is_writer")
            self.assertTrue(helper.returns_tainted)
            (save,) = df.lookup("heat_tpu.core.io", "save")
            self.assertIn("writer", save.tainted_names)
            self.assertTrue(save.returns_tainted)


class TestSummaryStability(unittest.TestCase):
    FILES = {
        "core/a.py": """
            from . import b

            def outer(comm, v):
                v = comm.shard(v, 0)
                v = b.inner(comm, v)
                return comm.all_gather(v)
        """,
        "core/b.py": """
            def inner(comm, v):
                comm.psum(v)
                return comm.ppermute(v, [(0, 1)])
        """,
    }

    def test_two_builds_agree_and_serialize(self):
        td1, _, df1 = build_universe(self.FILES)
        td2, _, df2 = build_universe(self.FILES)
        with td1, td2:
            s1, s2 = df1.module_summaries(), df2.module_summaries()
            self.assertEqual(s1, s2)
            # byte-stable through JSON (what the incremental cache stores)
            self.assertEqual(
                json.dumps(s1, sort_keys=True), json.dumps(s2, sort_keys=True)
            )
            outer = s1["heat_tpu/core/a.py"]["outer"]
            self.assertEqual(
                outer["seq"],
                ["comm.shard", "comm.psum", "comm.ppermute", "comm.all_gather"],
            )
            self.assertFalse(outer["cyclic"])

    def test_sequence_cap_truncates_not_hangs(self):
        fan = "\n".join(
            f"    comm.psum(v{i})" if False else f"    comm.psum(v)"
            for i in range(dataflow.MAX_SEQ + 8)
        )
        td, _, df = build_universe({
            "core/a.py": f"def f(comm, v):\n{fan}\n    return v\n",
        })
        with td:
            (info,) = df.lookup("heat_tpu.core.a", "f")
            self.assertLessEqual(len(info.seq), dataflow.MAX_SEQ + 1)
            self.assertEqual(info.seq[-1], dataflow.ELLIPSIS)


class TestSpmdRuleFixtures(unittest.TestCase):
    def test_rank_guarded_collective_interprocedural(self):
        bad = run_fixture({"core/x.py": """
            import jax

            def helper(comm, v):
                return comm.psum(v)

            def f(comm, v):
                if jax.process_index() == 0:
                    return helper(comm, v)
                return v
        """})
        self.assertIn("spmd-divergent-collective", rule_ids(bad))

    def test_symmetric_early_return_is_clean(self):
        # the io/checkpoint idiom: the guard covers only host-local work,
        # BOTH paths reach the same closing barrier
        good = run_fixture({"core/x.py": """
            import jax
            from jax.experimental import multihost_utils

            def _is_writer():
                return jax.process_index() == 0

            def save(write):
                if not _is_writer():
                    multihost_utils.sync_global_devices("t")
                    return
                write()
                multihost_utils.sync_global_devices("t")
        """})
        self.assertNotIn("spmd-divergent-collective", rule_ids(good))

    def test_early_exit_skipping_later_collective(self):
        bad = run_fixture({"core/x.py": """
            import jax

            def f(comm, v):
                if jax.process_index() == 0:
                    return v
                return comm.psum(v)
        """})
        self.assertIn("spmd-divergent-collective", rule_ids(bad))

    def test_rank_dependent_loop_bound(self):
        bad = run_fixture({"core/x.py": """
            def f(comm, v):
                for _ in range(comm.rank):
                    v = comm.psum(v)
                return v
        """})
        self.assertIn("spmd-divergent-collective", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            def f(comm, v):
                for _ in range(comm.size):
                    v = comm.psum(v)
                return v
        """})
        self.assertNotIn("spmd-divergent-collective", rule_ids(good))

    def test_serialized_writer_rounds_are_clean(self):
        # io._serialized_shard_write's shape: the rank guard covers only
        # host-local writes; the barrier is outside and every rank hits it
        good = run_fixture({"core/x.py": """
            import jax
            from jax.experimental import multihost_utils

            def write_rounds(nproc, write_my_shards):
                for p in range(nproc):
                    if jax.process_index() == p:
                        write_my_shards()
                    multihost_utils.sync_global_devices(f"round{p}")
        """})
        self.assertNotIn("spmd-divergent-collective", rule_ids(good))

    def test_collective_in_except_handler(self):
        bad = run_fixture({"core/x.py": """
            def f(comm, v):
                try:
                    return v + 1
                except ValueError:
                    return comm.all_gather(v)
        """})
        self.assertIn("spmd-collective-in-except", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            def f(comm, v):
                try:
                    return comm.all_gather(v) + 1
                except ValueError:
                    return None
        """})
        self.assertNotIn("spmd-collective-in-except", rule_ids(good))

    def test_except_collective_through_helper(self):
        bad = run_fixture({"core/x.py": """
            def rebuild(comm, v):
                return comm.shard(v, 0)

            def f(comm, v):
                try:
                    return v + 1
                except ValueError:
                    return rebuild(comm, v)
        """})
        self.assertIn("spmd-collective-in-except", rule_ids(bad))


class TestLayoutRuleFixtures(unittest.TestCase):
    def test_shard_claim_mismatch(self):
        bad = run_fixture({"core/x.py": """
            def f(comm, value, DNDarray):
                value = comm.shard(value, None)
                return DNDarray(value, value.shape, None, 0, None, comm, True)
        """})
        self.assertIn("layout-shard-claim-mismatch", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            def f(comm, value, DNDarray):
                value = comm.shard(value, 0)
                return DNDarray(value, value.shape, None, 0, None, comm, True)
        """})
        self.assertNotIn("layout-shard-claim-mismatch", rule_ids(good))

    def test_symbolic_splits_not_guessed_at(self):
        # out_split vs x.split may be equal at runtime: only LITERAL
        # disagreements are flagged (conservative by design)
        good = run_fixture({"core/x.py": """
            def f(comm, value, out_split, x, DNDarray):
                value = comm.shard(value, out_split)
                return DNDarray(value, value.shape, None, x.split, None, comm, True)
        """})
        self.assertNotIn("layout-shard-claim-mismatch", rule_ids(good))

    def test_resplit_roundtrip(self):
        bad = run_fixture({"core/x.py": """
            def f(comm, value):
                v = comm.shard(value, 0)
                return comm.shard(v, 1)
        """})
        self.assertIn("layout-resplit-roundtrip", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            def f(comm, value):
                v = comm.shard(value, 0)
                return comm.shard(v, 0)  # idempotent re-layout: allowed
        """})
        self.assertNotIn("layout-resplit-roundtrip", rule_ids(good))

    def test_pad_mask_dropped_and_masked(self):
        bad = run_fixture({"core/x.py": """
            import jax.numpy as jnp

            def f(x, DNDarray):
                result = jnp.exp(x.parray)
                result = x.comm.shard(result, x.split)
                return DNDarray(result, x.gshape, x.dtype, x.split, x.device, x.comm, True)
        """})
        self.assertIn("layout-pad-mask-dropped", rule_ids(bad))
        good = run_fixture({"core/x.py": """
            import jax.numpy as jnp

            def _zero_pads(r, gshape, split):
                return r

            def f(x, DNDarray):
                result = jnp.exp(x.parray)
                result = _zero_pads(result, x.gshape, x.split)
                result = x.comm.shard(result, x.split)
                return DNDarray(result, x.gshape, x.dtype, x.split, x.device, x.comm, True)
        """})
        self.assertNotIn("layout-pad-mask-dropped", rule_ids(good))

    def test_parray_metadata_reads_are_not_data(self):
        good = run_fixture({"core/x.py": """
            import jax.numpy as jnp

            def f(x, value, DNDarray):
                new = jnp.asarray(value, dtype=x.parray.dtype)
                new = x.comm.shard(new, x.split)
                return DNDarray(new, x.gshape, x.dtype, x.split, x.device, x.comm, True)
        """})
        self.assertNotIn("layout-pad-mask-dropped", rule_ids(good))

    def test_pad_taint_through_alias_and_operator_compute(self):
        # review-hardened shapes: aliasing .parray to a name, and operator
        # computes (BinOp) — both must taint exactly like the direct call
        alias = run_fixture({"core/x.py": """
            import jax.numpy as jnp

            def f(x, wrap_result):
                p = x.parray
                y = jnp.exp(p)
                return wrap_result(y, x, x.split)
        """})
        self.assertIn("layout-pad-mask-dropped", rule_ids(alias))
        binop = run_fixture({"core/x.py": """
            def f(x, wrap_result):
                y = x.parray + 1
                return wrap_result(y, x, x.split)
        """})
        self.assertIn("layout-pad-mask-dropped", rule_ids(binop))
        # a BARE alias carries zero pads (the invariant) — wrapping it is fine
        bare = run_fixture({"core/x.py": """
            def f(x, wrap_result):
                p = x.parray
                return wrap_result(p, x, x.split)
        """})
        self.assertNotIn("layout-pad-mask-dropped", rule_ids(bare))

    def test_contract_violation_and_stale(self):
        bad = run_fixture({"core/_operations.py": """
            def wrap_result(value, proto, split):
                value = proto.comm.shard(value, split)
                return DNDarray(value, value.shape, None, None, proto.device, proto.comm, True)
        """})
        self.assertIn("layout-contract", rule_ids(bad))
        good = run_fixture({"core/_operations.py": """
            def wrap_result(value, proto, split):
                value = proto.comm.shard(value, split)
                return DNDarray(value, value.shape, None, split, proto.device, proto.comm, True)
        """})
        self.assertNotIn("layout-contract", rule_ids(good))
        # a contracted module present with the function renamed -> stale
        stale = run_fixture({"core/dist_sort.py": """
            def distributed_sort_v2(comm, value):
                return value
        """})
        self.assertIn("layout-contract-stale", rule_ids(stale))


if __name__ == "__main__":
    unittest.main()
