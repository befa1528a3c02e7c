"""Process and world-mesh bring-up: what ``import heat_tpu`` does to the process,
in one place and one stated order. ``core/__init__.py`` calls :func:`run` before
it imports anything else.

A process can join a ``jax.distributed`` job only while it has no XLA backend: one
that creates it (``jax.devices()``, ``jax.default_backend()``, ...) before step (c)
comes up alone, whatever the launcher said. Hence only ``jax``, ``os``, ``sys`` and the
stdlib-only ``diagnostics`` and ``supervision`` are imported here at load; analysis rule
``import-backend-touch`` and its runtime twin in ``tests/test_analysis.py`` hold
the line. The launch contract is the environment, as ``mpirun``'s is::

    HEAT_TPU_COORDINATOR_ADDRESS=host:port \\
    HEAT_TPU_NUM_PROCESSES=N HEAT_TPU_PROCESS_ID=i python program.py

or ``jax.distributed.initialize`` called by the program before its first
``import heat_tpu``: a client that exists is respected.

Each step is a phase of the start-up record (``diagnostics.startup``,
``ht.diagnostics.report()["startup"]``): what a process pays before its first line
of work, by name.
"""

from __future__ import annotations

import os
import sys

from . import diagnostics, supervision

with diagnostics.startup("import.jax", jax_preimported="jax" in sys.modules):
    import jax
    from jax._src import xla_bridge as _xla_bridge

#: ``<checkout>/.jax_cache`` (gitignored). The directory is part of JAX's cache key,
#: so it is never a temp name, a pid or a time.
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_CONTRACT = ("HEAT_TPU_COORDINATOR_ADDRESS", "HEAT_TPU_NUM_PROCESSES", "HEAT_TPU_PROCESS_ID")


def run() -> None:
    """The bring-up, steps (a) to (e)."""
    with diagnostics.startup("bootstrap"):
        with diagnostics.startup("bootstrap.config"):
            # (a) float64/complex128/int64 availability (the reference supports f64 via
            # torch); the *default* float stays float32: factories pass explicit dtypes.
            jax.config.update("jax_enable_x64", True)
            place_jax_cache()  # (b)
        with diagnostics.startup("bootstrap.join"):
            _join_from_environment()  # (c)
        # (d) + (e) only now may the backend exist (importing ``devices`` creates it):
        # the world singletons, the telemetry stamp / clock handshake, ``auto_arm()``
        with diagnostics.startup("bootstrap.world"):
            # ``backend_created``: the program had made it before this import (~0 then)
            with diagnostics.startup("bootstrap.world.backend",
                                     backend_created=_xla_bridge.backends_are_initialized()):
                jax.devices()
            from . import communication

            communication.build_world()


def _join_from_environment() -> None:
    """Step (c), the environment contract: all three or none."""
    env = [os.environ.get(name) for name in _CONTRACT]
    if env[0]:
        missing = [name for name, value in zip(_CONTRACT, env) if not value]
        if missing:
            raise RuntimeError(
                f"HEAT_TPU_COORDINATOR_ADDRESS is set but {' and '.join(missing)} "
                f"{'is' if len(missing) == 1 else 'are'} not; the multi-controller "
                f"launch contract needs all three of {', '.join(_CONTRACT)}"
            )
        if supervision._distributed_client() is None:
            join(coordinator_address=env[0], num_processes=int(env[1]),
                 process_id=int(env[2]))


def place_jax_cache() -> None:
    """JAX's persistent compilation cache is always on: where the operator placed it
    (``JAX_COMPILATION_CACHE_DIR``) code sets nothing, else at :data:`JAX_CACHE_DIR`."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)


def join(**kwargs) -> None:
    """Step (c): make this process part of a ``jax.distributed`` job. With the
    supervision plane enabled (the default) and the explicit triple given, the
    runtime is built SUPERVISED (``supervision`` module docstring: typed peer
    failures, elastic restart); auto-detected launches (TPU/Slurm arguments
    omitted) keep the stock ``jax.distributed.initialize``."""
    if _xla_bridge.backends_are_initialized() and supervision._distributed_client() is None:
        raise RuntimeError(
            "this process already has an XLA backend (`import heat_tpu` creates it), "
            "so it can no longer join a distributed job. Launch every process with "
            f"{', '.join(_CONTRACT)} set, which `import heat_tpu` honours, or call "
            "jax.distributed.initialize() before the first `import heat_tpu`"
        )
    if supervision.enabled() and {"coordinator_address", "num_processes",
                                  "process_id"}.issubset(kwargs):
        supervision.bootstrap_distributed(
            kwargs["coordinator_address"], int(kwargs["num_processes"]),
            int(kwargs["process_id"]),
        )
    else:
        jax.distributed.initialize(**kwargs)
