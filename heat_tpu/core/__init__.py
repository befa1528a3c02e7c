"""heat_tpu core: distributed n-D arrays over JAX/XLA (reference heat/core/__init__.py)."""

from . import _bootstrap

# the bring-up: nothing above this call may touch the XLA backend (``import-backend-touch``)
_bootstrap.run()

from . import diagnostics

with diagnostics.startup("import.core"):  # the rest of this file
    from . import profiler
    from . import forensics
    from . import resilience
    from . import telemetry
    from .forensics import explain
    from .communication import *
    from ._executor import (
        executor_stats,
        reset_executor_stats,
        clear_executor_cache,
        reload_env_knobs,
        executor_warmup,
        executor_save_warmup,
        rebuild_scheduler,
    )
    from .constants import *
    from .devices import *
    from .types import *
    from .stride_tricks import *
    from .dndarray import *
    from .memory import *
    from .sanitation import *
    from .factories import *
    from .printing import *
    from .arithmetics import *
    from .rounding import *
    from .trigonometrics import *
    from .exponential import *
    from .relational import *
    from .logical import *
    from .complex_math import *
    from .statistics import *
    from .manipulations import *
    from .indexing import *
    from .signal import *
    from .tiling import *
    from .base import *
    from .io import *
    from .checkpoint import *
    from . import checkpoint
    from . import io
    from . import random
    from . import linalg
    from .linalg import *  # promoted to the flat namespace like the reference
    from .version import __version__

    from . import (
        arithmetics,
        base,
        communication,
        complex_math,
        constants,
        devices,
        dndarray,
        exponential,
        factories,
        indexing,
        logical,
        manipulations,
        memory,
        printing,
        random,
        relational,
        rounding,
        sanitation,
        signal,
        statistics,
        stride_tricks,
        tiling,
        trigonometrics,
        types,
        version,
    )
