"""heat_tpu — a TPU-native distributed n-D tensor framework.

A ground-up re-design of the capabilities of Heat (Helmholtz Analytics Toolkit,
https://github.com/helmholtz-analytics/heat) for TPU: global ``jax.Array``s over a
device mesh replace process-local torch tensors over MPI, and XLA SPMD replaces the
hand-written collective choreography. Usage mirrors the reference::

    import heat_tpu as ht
    x = ht.arange(10, split=0)
    x.sum()
"""

import time as _time

_FIRST = _time.perf_counter()  # the start-up record's ``import_s`` counts from here

# The reference computes every matmul in full fp32/fp64 (torch on CPU/GPU). TPU MXUs
# default to bf16-input passes — fast, and the right default for the framework's bulk
# compute path. fp32-sensitive algorithms (QR, hSVD, CG/Lanczos, cdist's quadratic
# expansion) request jax.lax.Precision.HIGHEST per-op instead of a global brake; see
# heat_tpu.core.linalg.basics.PARITY_PRECISION.

from .core import *
from .core import __version__
from .core import diagnostics

with diagnostics.startup("import.packages"):
    from .core import forensics
    from .core import ops
    from .core import profiler
    from .core import resilience
    from .core import supervision
    from . import telemetry
    from . import core
    from . import fft
    from . import utils
    from . import spatial
    from . import cluster
    from . import classification
    from . import naive_bayes
    from . import regression
    from . import preprocessing
    from . import graph
    from . import datasets
    from . import sparse
    from . import nn
    from . import optim
    from . import serving

diagnostics.startup_imported(_FIRST)
