"""Neural networks (reference heat/nn/). The reference's ``__getattr__`` falls through
to ``torch.nn`` (``nn/__init__.py:18-31``); torch layers cannot execute on TPU, so the
native module system in :mod:`.modules` is the fallthrough surface here."""

from .data_parallel import *
from .modules import *
from .attention import *
from .recurrent import *
from .hyper_connections import *
from .moe import *
from .scoring import *
from .xing4 import *
from .trinity import *
from .kda import *
from .ling import *
from .deepseek_v32 import *
from .kimi_linear import *
from . import (attention, data_parallel, deepseek_v32, functional, hyper_connections, kda,
               kimi_linear, ling, modules, moe, recurrent, scoring, trinity, xing4)
