"""Neural-network modules (reference heat/nn/: falls through to ``torch.nn``,
``nn/__init__.py:18-31``).

The reference trains *torch* modules locally and glues them together with MPI gradient
hooks. Torch modules cannot execute on TPU, so the TPU build ships a small native
module system in the idiomatic JAX shape: a module is a *structure* whose parameters
live in an explicit pytree, ``init(key)`` creates them, ``apply(params, x)`` is a pure
function jittable end-to-end. A convenience stateful veneer (``__call__`` using the
internally held params) preserves the torch-like feel of the reference examples.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..core import diagnostics
from ..core.dndarray import DNDarray

__all__ = [
    "Module",
    "Linear",
    "Embedding",
    "Conv2d",
    "ConvTranspose2d",
    "MaxPool2d",
    "AvgPool2d",
    "AdaptiveAvgPool2d",
    "AdaptiveMaxPool2d",
    "Conv1d",
    "MaxPool1d",
    "AvgPool1d",
    "BatchNorm1d",
    "BatchNorm2d",
    "GroupNorm",
    "InstanceNorm2d",
    "LayerNorm",
    "RMSNorm",
    "GatedMLP",
    "ReLU",
    "ReLU6",
    "LeakyReLU",
    "PReLU",
    "GELU",
    "ELU",
    "SiLU",
    "Mish",
    "Softplus",
    "Hardtanh",
    "Tanh",
    "Sigmoid",
    "Softmax",
    "LogSoftmax",
    "Identity",
    "Flatten",
    "Unflatten",
    "Dropout",
    "Dropout2d",
    "Remat",
    "remat",
    "Sequential",
    "ModuleList",
    "MSELoss",
    "L1Loss",
    "NLLLoss",
    "CrossEntropyLoss",
    "BCELoss",
    "BCEWithLogitsLoss",
    "SmoothL1Loss",
    "HuberLoss",
]


def _to_value(x):
    return x.larray if isinstance(x, DNDarray) else x


def contract(spec: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """``einsum(spec, x, w)`` accumulated and returned in float32, with the operands'
    precision stated: bfloat16 operands ride the MXU as they are (one pass), float32
    operands multiply at ``Precision.HIGHEST`` and not at the MXU default, which would
    round them to bfloat16 first. The caller rounds the result to its activation type."""
    exact = x.dtype == jnp.float32 or w.dtype == jnp.float32
    return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST if exact else None)


def gated_silu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """``W_down(silu(W_gate x) * W_up x)`` on ``x`` (..., d), in ``x``'s type; the three
    contractions accumulate in float32 (:func:`contract`)."""
    gate = contract("...d,dh->...h", x, w_gate)
    up = contract("...d,dh->...h", x, w_up)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    return contract("...h,hd->...d", h, w_down).astype(x.dtype)


def normal_weight(key, shape, dtype, scale: float) -> jax.Array:
    """``N(0, scale^2)`` drawn in float32 and rounded to ``dtype``."""
    return (jax.random.normal(key, shape, jnp.float32) * jnp.float32(scale)).astype(dtype)


class Module:
    """Base module: explicit-parameter pytrees + pure ``apply``.

    Two authoring styles, both jit/grad-safe:

    - *leaf/container style*: override ``init``/``apply`` (see :class:`Linear`).
    - *torch style* (the reference's UX — its examples subclass ``ht.nn.Module`` and
      write an imperative ``forward``, ``examples/nn/mnist.py:23-45``): assign
      submodules as attributes in ``__init__`` and override ``forward(x)``. The
      default ``init`` collects attribute submodules in definition order; the default
      ``apply`` binds the params pytree (and the PRNG/train context) onto the
      submodules, then calls ``forward`` — inside which ``self.conv1(x)`` etc. route
      through the bound tracers, keeping the whole thing a pure function of
      ``(params, x)``.
    """

    def named_submodules(self) -> List[Tuple[str, "Module"]]:
        """Attribute submodules in definition order (torch's registration order)."""
        return [(k, v) for k, v in vars(self).items() if isinstance(v, Module)]

    def init(self, key: jax.Array) -> Any:
        """Create this module's parameter pytree."""
        subs = self.named_submodules()
        if not subs:
            return ()
        keys = jax.random.split(key, len(subs))
        return {name: m.init(k) for (name, m), k in zip(subs, keys)}

    def forward(self, x):
        """Torch-style forward over bound submodules; override in subclasses."""
        raise NotImplementedError()

    def apply(self, params: Any, x: jax.Array, *, key: Optional[jax.Array] = None, train: bool = False) -> jax.Array:
        """Pure forward pass."""
        if type(self).forward is not Module.forward:
            self._bind(params, key, train)
            return _to_value(self.forward(x))
        raise NotImplementedError()

    def _bind(self, params, key, train: bool) -> None:
        subs = self.named_submodules()
        keys = (
            jax.random.split(key, max(len(subs), 1))
            if key is not None
            else [None] * len(subs)
        )
        for (name, m), k in zip(subs, keys):
            m._params = params[name]
            m._ctx = (k, train)
            if isinstance(m, ModuleList):
                # list containers are never .apply()'d themselves — forward code
                # indexes into them — so their children must be bound here
                m._bind(params[name], k, train)

    # ------------------------------------------------------------- stateful veneer
    @property
    def params(self):
        if not hasattr(self, "_params"):
            self._params = self.init(jax.random.key(0))
        return self._params

    @params.setter
    def params(self, value):
        self._params = value

    def reset_parameters(self, seed: int = 0) -> None:
        """Re-create parameters from a seed — every process derives identical values,
        the property the reference enforces by seed-broadcast + param Bcast
        (``nn/data_parallel.py:105-106``)."""
        self._params = self.init(jax.random.key(seed))

    def train(self, mode: bool = True) -> "Module":
        """Set train/eval mode (torch semantics); affects Dropout/BatchNorm defaults."""
        self._train_mode = mode
        for _, m in self.named_submodules():
            m.train(mode)
        return self

    def eval(self) -> "Module":
        return self.train(False)

    def _resolve_ctx(self, key=None, train: Optional[bool] = None):
        """Resolve the PRNG key / train flag for a stateful-veneer call: explicit
        arguments win, then the ``_ctx`` a parent ``apply`` bound, then the
        ``.train()``/``.eval()`` mode, defaulting to eval."""
        ctx = getattr(self, "_ctx", None)
        if ctx is not None:
            if key is None:
                key = ctx[0]
            if train is None:
                train = ctx[1]
        if train is None:
            train = getattr(self, "_train_mode", False)
        return key, train

    def __call__(self, x, *, key=None, train: Optional[bool] = None):
        with diagnostics.span("nn.forward", x) if diagnostics._enabled else diagnostics.NO_SPAN:
            key, train = self._resolve_ctx(key, train)
            value = self.apply(self.params, _to_value(x), key=key, train=train)
            if isinstance(x, DNDarray) and not isinstance(value, DNDarray):
                from ..core._operations import wrap_result

                # a split survives whenever its axis still exists with the same
                # global extent (batch through convs/embedding, sequence through
                # norms/linear); axes the op consumed or resized fall back to
                # replicated. split is a layout over a global array, so a
                # false-positive keep is a layout choice, never wrong data.
                keep = None
                if (
                    x.split is not None
                    and x.split < value.ndim
                    and value.shape[x.split] == x.shape[x.split]
                ):
                    keep = x.split
                return wrap_result(value, x, keep)
            return value


class Linear(Module):
    """Affine layer y = x W + b (torch.nn.Linear semantics, torch's default
    LeCun-style uniform init with bound 1/sqrt(in_features))."""

    def __init__(self, in_features: int, out_features: int, bias: bool = True):
        self.in_features = in_features
        self.out_features = out_features
        self.bias = bias

    def init(self, key):
        k1, k2 = jax.random.split(key)
        bound = 1.0 / np.sqrt(self.in_features)
        # float32 params regardless of the global x64 flag — the TPU-native precision
        w = jax.random.uniform(
            k1, (self.in_features, self.out_features), jnp.float32, -bound, bound
        )
        if not self.bias:
            return {"weight": w}
        b = jax.random.uniform(k2, (self.out_features,), jnp.float32, -bound, bound)
        return {"weight": w, "bias": b}

    def apply(self, params, x, *, key=None, train=False):
        v = _to_value(x)
        y = v @ params["weight"]
        if self.bias:
            y = y + params["bias"]
        if isinstance(x, DNDarray):
            from ..core._operations import wrap_result

            # the feature axis is mixed by the product; leading splits survive
            keep = x.split if (x.split is not None and x.split < x.ndim - 1) else None
            return wrap_result(y, x, keep)
        return y


class Conv2d(Module):
    """2-D convolution, torch.nn.Conv2d semantics: input (N, C, H, W), weight
    (out, in/groups, kH, kW), LeCun-style uniform init with bound 1/sqrt(fan_in)."""

    def __init__(
        self,
        in_channels: int,
        out_channels: int,
        kernel_size,
        stride=1,
        padding=0,
        dilation=1,
        groups: int = 1,
        bias: bool = True,
    ):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.groups = groups
        self.bias = bias

    def init(self, key):
        from . import functional as F

        k1, k2 = jax.random.split(key)
        kh, kw = self.kernel_size
        fan_in = self.in_channels // self.groups * kh * kw
        bound = 1.0 / np.sqrt(fan_in)
        w = jax.random.uniform(
            k1,
            (self.out_channels, self.in_channels // self.groups, kh, kw),
            jnp.float32,
            -bound,
            bound,
        )
        if not self.bias:
            return {"weight": w}
        b = jax.random.uniform(k2, (self.out_channels,), jnp.float32, -bound, bound)
        return {"weight": w, "bias": b}

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.conv2d(
            x,
            params["weight"],
            params.get("bias"),
            stride=self.stride,
            padding=self.padding,
            dilation=self.dilation,
            groups=self.groups,
        )


class MaxPool2d(Module):
    def __init__(self, kernel_size, stride=None, padding=0):
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.max_pool2d(x, self.kernel_size, self.stride, self.padding)


class AvgPool2d(Module):
    def __init__(self, kernel_size, stride=None, padding=0):
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.avg_pool2d(x, self.kernel_size, self.stride, self.padding)


class Conv1d(Module):
    """1-D convolution, torch.nn.Conv1d semantics: input (N, C, L), weight
    (out, in/groups, k), LeCun-style uniform init with bound 1/sqrt(fan_in)."""

    def __init__(self, in_channels: int, out_channels: int, kernel_size,
                 stride=1, padding=0, dilation=1, groups: int = 1,
                 bias: bool = True):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = (
            kernel_size if isinstance(kernel_size, int) else kernel_size[0]
        )
        self.stride = stride
        self.padding = padding
        self.dilation = dilation
        self.groups = groups
        self.bias = bias

    def init(self, key):
        k1, k2 = jax.random.split(key)
        fan_in = self.in_channels // self.groups * self.kernel_size
        bound = 1.0 / np.sqrt(fan_in)
        w = jax.random.uniform(
            k1,
            (self.out_channels, self.in_channels // self.groups, self.kernel_size),
            jnp.float32, -bound, bound,
        )
        if not self.bias:
            return {"weight": w}
        b = jax.random.uniform(k2, (self.out_channels,), jnp.float32, -bound, bound)
        return {"weight": w, "bias": b}

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.conv1d(
            x, params["weight"], params.get("bias"), self.stride, self.padding,
            self.dilation, self.groups,
        )


class MaxPool1d(Module):
    def __init__(self, kernel_size, stride=None, padding=0):
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.max_pool1d(x, self.kernel_size, self.stride, self.padding)


class AvgPool1d(Module):
    def __init__(self, kernel_size, stride=None, padding=0):
        self.kernel_size = kernel_size
        self.stride = stride
        self.padding = padding

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.avg_pool1d(x, self.kernel_size, self.stride, self.padding)


class _BatchNorm(Module):
    """Shared BatchNorm1d/2d machinery (torch semantics).

    ``weight``/``bias`` are learnable params; running statistics are module buffers.
    Training normalizes by batch statistics; eval by the stored running statistics.
    The running buffers are updated only from *eager* (non-traced) calls — inside a
    jitted step the statistics are traced values that cannot be written back to
    Python state (jax arrays are immutable; torch's in-place buffer mutation has no
    functional equivalent), so jitted training keeps using batch stats.
    """

    def __init__(self, num_features: int, eps: float = 1e-5, momentum: float = 0.1,
                 affine: bool = True, track_running_stats: bool = True):
        self.num_features = num_features
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        self.track_running_stats = track_running_stats
        self.running_mean = jnp.zeros((num_features,), jnp.float32)
        self.running_var = jnp.ones((num_features,), jnp.float32)

    def init(self, key):
        if not self.affine:
            return ()
        return {
            "weight": jnp.ones((self.num_features,), jnp.float32),
            "bias": jnp.zeros((self.num_features,), jnp.float32),
        }

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        weight = params.get("weight") if self.affine else None
        bias = params.get("bias") if self.affine else None
        running = self.track_running_stats and not train
        out, mean, var = F.batch_norm(
            x,
            self.running_mean if running else None,
            self.running_var if running else None,
            weight,
            bias,
            training=train or not self.track_running_stats,
            eps=self.eps,
        )
        if train and self.track_running_stats and not isinstance(mean, jax.core.Tracer):
            m = self.momentum
            n = x.shape[0] * (x.size // (x.shape[0] * self.num_features))
            unbias = n / max(n - 1, 1)
            self.running_mean = (1 - m) * self.running_mean + m * mean
            self.running_var = (1 - m) * self.running_var + m * var * unbias
        return out


class BatchNorm1d(_BatchNorm):
    """torch.nn.BatchNorm1d over (N, C) or (N, C, L) inputs."""


class BatchNorm2d(_BatchNorm):
    """torch.nn.BatchNorm2d over (N, C, H, W) inputs."""


class LayerNorm(Module):
    def __init__(self, normalized_shape, eps: float = 1e-5, elementwise_affine: bool = True):
        self.normalized_shape = (
            (normalized_shape,) if isinstance(normalized_shape, int) else tuple(normalized_shape)
        )
        self.eps = eps
        self.elementwise_affine = elementwise_affine

    def init(self, key):
        if not self.elementwise_affine:
            return ()
        return {
            "weight": jnp.ones(self.normalized_shape, jnp.float32),
            "bias": jnp.zeros(self.normalized_shape, jnp.float32),
        }

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        weight = params.get("weight") if self.elementwise_affine else None
        bias = params.get("bias") if self.elementwise_affine else None
        return F.layer_norm(x, self.normalized_shape, weight, bias, self.eps)


class RMSNorm(Module):
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, computed in float32 and
    returned in the input's type (no mean subtracted, no bias). The weight starts at
    one; ``init_std`` > 0 draws it as ``1 + init_std * N(0, 1)`` instead, for models that
    run on seeded weights and want the weight's place in the equations to show."""

    def __init__(self, dim: int, eps: float = 1e-6, init_std: float = 0.0):
        self.dim = dim
        self.eps = eps
        self.init_std = init_std

    def init(self, key):
        w = jnp.ones((self.dim,), jnp.float32)
        if self.init_std:
            w = w + jnp.float32(self.init_std) * jax.random.normal(key, (self.dim,), jnp.float32)
        return {"weight": w}

    def apply(self, params, x, *, key=None, train=False):
        v = _to_value(x)
        v32 = v.astype(jnp.float32)
        ms = jnp.mean(v32 * v32, axis=-1, keepdims=True)
        out = (v32 * jax.lax.rsqrt(ms + jnp.float32(self.eps)) * params["weight"]).astype(v.dtype)
        if isinstance(x, DNDarray):
            from ..core._operations import wrap_result

            keep = x.split if (x.split is not None and x.split < x.ndim - 1) else None
            return wrap_result(out, x, keep)
        return out


class GatedMLP(Module):
    """Gated-SiLU feed-forward ``W_down(silu(W_gate x) * W_up x)``, no bias. Parameters
    are stored in ``dtype``; every contraction accumulates in float32 (:func:`contract`)
    and activations stay in the input's type."""

    def __init__(self, dim: int, hidden: int, dtype=jnp.float32):
        self.dim = dim
        self.hidden = hidden
        self.dtype = jnp.dtype(dtype)

    def init(self, key):
        kg, ku, kd = jax.random.split(key, 3)
        return {
            "w_gate": normal_weight(kg, (self.dim, self.hidden), self.dtype, self.dim ** -0.5),
            "w_up": normal_weight(ku, (self.dim, self.hidden), self.dtype, self.dim ** -0.5),
            "w_down": normal_weight(kd, (self.hidden, self.dim), self.dtype, self.hidden ** -0.5),
        }

    def apply(self, params, x, *, key=None, train=False):
        out = gated_silu(_to_value(x), params["w_gate"], params["w_up"], params["w_down"])
        if isinstance(x, DNDarray):
            from ..core._operations import wrap_result

            keep = x.split if (x.split is not None and x.split < x.ndim - 1) else None
            return wrap_result(out, x, keep)
        return out


class ReLU(Module):
    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.relu(x)


class LeakyReLU(Module):
    def __init__(self, negative_slope: float = 0.01):
        self.negative_slope = negative_slope

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.leaky_relu(x, self.negative_slope)


class GELU(Module):
    """torch.nn.GELU: exact erf form by default, ``approximate='tanh'`` for the
    fast approximation (jax.nn.gelu's default is the tanh form — not torch's)."""

    def __init__(self, approximate: str = "none"):
        if approximate not in ("none", "tanh"):
            raise ValueError(f"approximate must be 'none' or 'tanh', got {approximate!r}")
        self.approximate = approximate

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.gelu(x, approximate=self.approximate)


class ELU(Module):
    def __init__(self, alpha: float = 1.0):
        self.alpha = alpha

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.elu(x, self.alpha)


class Softmax(Module):
    def __init__(self, dim: int = -1):
        self.dim = dim

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.softmax(x, dim=self.dim)


class Identity(Module):
    def apply(self, params, x, *, key=None, train=False):
        return x


class Tanh(Module):
    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.tanh(x)


class Sigmoid(Module):
    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.sigmoid(x)


class LogSoftmax(Module):
    def __init__(self, dim: int = -1):
        self.dim = dim

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.log_softmax(x, dim=self.dim)


class Flatten(Module):
    """torch.nn.Flatten: flatten dims [start_dim, end_dim] (defaults keep batch)."""

    def __init__(self, start_dim: int = 1, end_dim: int = -1):
        self.start_dim = start_dim
        self.end_dim = end_dim

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.flatten(x, self.start_dim, self.end_dim)


class Dropout(Module):
    def __init__(self, p: float = 0.5):
        self.p = p

    def apply(self, params, x, *, key=None, train=False):
        if not train or self.p == 0.0:
            return x
        if key is None:
            raise ValueError("Dropout in train mode needs an explicit PRNG key")
        from . import functional as F

        return F.dropout(x, self.p, training=True, key=key)


class Dropout2d(Module):
    """Channel dropout (torch.nn.Dropout2d): zeroes whole feature maps."""

    def __init__(self, p: float = 0.5):
        self.p = p

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        if not train or self.p == 0.0:
            return x
        return F.dropout2d(x, self.p, training=True, key=key)


class Remat(Module):
    """Gradient checkpointing wrapper: recompute the wrapped module's forward during
    the backward pass instead of storing activations (``jax.checkpoint``) — the
    HBM-for-FLOPs trade that makes long sequences / deep nets fit on TPU. No torch
    equivalent in the reference (torch.utils.checkpoint is the analogue)."""

    def __init__(self, module: Module):
        self.module = module

    def named_submodules(self):
        return [("module", self.module)]

    def init(self, key):
        return self.module.init(key)

    def apply(self, params, x, *, key=None, train=False):
        import functools

        fn = functools.partial(self.module.apply, key=key, train=train)
        return jax.checkpoint(fn)(params, x)


def remat(module: Module) -> Remat:
    """Functional alias for :class:`Remat`."""
    return Remat(module)


class Sequential(Module):
    """Chained modules (torch.nn.Sequential semantics)."""

    def __init__(self, *layers: Module):
        self.layers = list(layers)

    def named_submodules(self):
        return [(str(i), m) for i, m in enumerate(self.layers)]

    def init(self, key):
        keys = jax.random.split(key, max(len(self.layers), 1))
        return [layer.init(k) for layer, k in zip(self.layers, keys)]

    def apply(self, params, x, *, key=None, train=False):
        keys = (
            jax.random.split(key, max(len(self.layers), 1))
            if key is not None
            else [None] * len(self.layers)
        )
        for layer, p, k in zip(self.layers, params, keys):
            x = layer.apply(p, x, key=k, train=train)
        return x


# ------------------------------------------------------------------------- losses
class MSELoss:
    """Mean squared error. The global mean over a batch sharded on the mesh makes the
    gradient all-reduce implicit — this IS the reference's blocking Allreduce hook
    (``nn/data_parallel.py:220-238``), emitted by XLA instead of written by hand."""

    def __init__(self, reduction: str = "mean"):
        self.reduction = reduction

    def __call__(self, pred, target):
        from . import functional as F

        return F.mse_loss(pred, target, reduction=self.reduction)


class L1Loss:
    """Mean absolute error (torch.nn.L1Loss semantics)."""

    def __init__(self, reduction: str = "mean"):
        self.reduction = reduction

    def __call__(self, pred, target):
        from . import functional as F

        return F.l1_loss(pred, target, reduction=self.reduction)


class NLLLoss:
    """Negative log likelihood over log-probabilities (torch.nn.NLLLoss semantics
    incl. per-class ``weight``, ``ignore_index`` and ``reduction``)."""

    def __init__(self, weight=None, ignore_index: int = -100,
                 reduction: str = "mean"):
        self.weight = weight
        self.ignore_index = ignore_index
        self.reduction = reduction

    def __call__(self, log_probs, target):
        from . import functional as F

        return F.nll_loss(log_probs, target, self.weight, self.ignore_index,
                          self.reduction)


class CrossEntropyLoss:
    """Softmax cross-entropy on raw logits (torch.nn.CrossEntropyLoss semantics
    incl. ``weight``, ``ignore_index``, ``reduction`` and ``label_smoothing``)."""

    def __init__(self, weight=None, ignore_index: int = -100,
                 reduction: str = "mean", label_smoothing: float = 0.0):
        self.weight = weight
        self.ignore_index = ignore_index
        self.reduction = reduction
        self.label_smoothing = label_smoothing

    def __call__(self, logits, target):
        from . import functional as F

        return F.cross_entropy(logits, target, self.weight, self.ignore_index,
                               self.reduction, self.label_smoothing)


class Embedding(Module):
    """Lookup table (torch.nn.Embedding semantics: N(0,1) init; the ``padding_idx``
    row is zeroed at init)."""

    def __init__(self, num_embeddings: int, embedding_dim: int,
                 padding_idx: Optional[int] = None):
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.padding_idx = padding_idx

    def init(self, key):
        w = jax.random.normal(key, (self.num_embeddings, self.embedding_dim), jnp.float32)
        if self.padding_idx is not None:
            w = w.at[self.padding_idx].set(0.0)
        return {"weight": w}

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.embedding(x, params["weight"], self.padding_idx)


class ConvTranspose2d(Module):
    """torch.nn.ConvTranspose2d semantics: weight (in, out/groups, kH, kW),
    LeCun-style uniform init with bound 1/sqrt(out/groups * kH * kW) — torch uses
    the same fan computation for the transposed conv."""

    def __init__(self, in_channels: int, out_channels: int, kernel_size, stride=1,
                 padding=0, output_padding=0, groups: int = 1, bias: bool = True,
                 dilation=1):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
        self.stride = stride
        self.padding = padding
        self.output_padding = output_padding
        self.groups = groups
        self.bias = bias
        self.dilation = dilation

    def init(self, key):
        k1, k2 = jax.random.split(key)
        kh, kw = self.kernel_size
        fan_in = self.out_channels // self.groups * kh * kw
        bound = 1.0 / np.sqrt(fan_in)
        w = jax.random.uniform(
            k1,
            (self.in_channels, self.out_channels // self.groups, kh, kw),
            jnp.float32,
            -bound,
            bound,
        )
        if not self.bias:
            return {"weight": w}
        b = jax.random.uniform(k2, (self.out_channels,), jnp.float32, -bound, bound)
        return {"weight": w, "bias": b}

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.conv_transpose2d(
            x,
            params["weight"],
            params.get("bias"),
            stride=self.stride,
            padding=self.padding,
            output_padding=self.output_padding,
            groups=self.groups,
            dilation=self.dilation,
        )


class AdaptiveAvgPool2d(Module):
    def __init__(self, output_size):
        self.output_size = output_size

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.adaptive_avg_pool2d(x, self.output_size)


class AdaptiveMaxPool2d(Module):
    def __init__(self, output_size):
        self.output_size = output_size

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.adaptive_max_pool2d(x, self.output_size)


class GroupNorm(Module):
    """torch.nn.GroupNorm: per-group normalization over (N, C, *)."""

    def __init__(self, num_groups: int, num_channels: int, eps: float = 1e-5,
                 affine: bool = True):
        self.num_groups = num_groups
        self.num_channels = num_channels
        self.eps = eps
        self.affine = affine

    def init(self, key):
        if not self.affine:
            return ()
        return {
            "weight": jnp.ones((self.num_channels,), jnp.float32),
            "bias": jnp.zeros((self.num_channels,), jnp.float32),
        }

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        weight = params.get("weight") if self.affine else None
        bias = params.get("bias") if self.affine else None
        return F.group_norm(x, self.num_groups, weight, bias, self.eps)


class InstanceNorm2d(Module):
    """torch.nn.InstanceNorm2d (default config: no affine, no running stats) —
    per-sample, per-channel normalization = GroupNorm with one group per channel."""

    def __init__(self, num_features: int, eps: float = 1e-5, affine: bool = False):
        self.num_features = num_features
        self.eps = eps
        self.affine = affine

    def init(self, key):
        if not self.affine:
            return ()
        return {
            "weight": jnp.ones((self.num_features,), jnp.float32),
            "bias": jnp.zeros((self.num_features,), jnp.float32),
        }

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        weight = params.get("weight") if self.affine else None
        bias = params.get("bias") if self.affine else None
        return F.group_norm(x, self.num_features, weight, bias, self.eps)


class ReLU6(Module):
    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.hardtanh(x, 0.0, 6.0)


class PReLU(Module):
    """torch.nn.PReLU: leaky-relu with a learnable per-channel (or scalar) slope."""

    def __init__(self, num_parameters: int = 1, init: float = 0.25):
        self.num_parameters = num_parameters
        self.init_value = init

    def init(self, key):
        return {"weight": jnp.full((self.num_parameters,), self.init_value, jnp.float32)}

    def apply(self, params, x, *, key=None, train=False):
        a = params["weight"]
        if self.num_parameters > 1 and x.ndim > 1:
            a = a.reshape((1, -1) + (1,) * (x.ndim - 2))
        v = _to_value(x)
        out = jnp.where(v >= 0, v, a * v)
        if isinstance(x, DNDarray):
            from ..core._operations import wrap_result

            return wrap_result(out, x, x.split)
        return out


class SiLU(Module):
    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.silu(x)


class Mish(Module):
    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.mish(x)


class Softplus(Module):
    def __init__(self, beta: float = 1.0, threshold: float = 20.0):
        self.beta = beta
        self.threshold = threshold

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.softplus(x, self.beta, self.threshold)


class Hardtanh(Module):
    def __init__(self, min_val: float = -1.0, max_val: float = 1.0):
        self.min_val = min_val
        self.max_val = max_val

    def apply(self, params, x, *, key=None, train=False):
        from . import functional as F

        return F.hardtanh(x, self.min_val, self.max_val)


class Unflatten(Module):
    """torch.nn.Unflatten: expand one dim into a shape."""

    def __init__(self, dim: int, unflattened_size):
        self.dim = dim
        self.unflattened_size = tuple(unflattened_size)

    def apply(self, params, x, *, key=None, train=False):
        d = self.dim if self.dim >= 0 else x.ndim + self.dim
        shape = tuple(x.shape[:d]) + self.unflattened_size + tuple(x.shape[d + 1 :])
        v = _to_value(x)
        out = v.reshape(shape)
        if isinstance(x, DNDarray):
            from ..core._operations import wrap_result

            keep = x.split if (x.split is not None and x.split < d) else None
            return wrap_result(out, x, keep)
        return out


class ModuleList(Module):
    """torch.nn.ModuleList: an indexable container registered like a submodule.
    Holds no forward logic of its own — subclass forward code indexes into it."""

    def __init__(self, modules: Optional[Sequence[Module]] = None):
        self.layers = list(modules or [])

    def named_submodules(self):
        return [(str(i), m) for i, m in enumerate(self.layers)]

    def append(self, module: Module) -> "ModuleList":
        self.layers.append(module)
        return self

    def __iter__(self):
        return iter(self.layers)

    def __len__(self):
        return len(self.layers)

    def __getitem__(self, idx):
        return self.layers[idx]

    def init(self, key):
        keys = jax.random.split(key, max(len(self.layers), 1))
        return [m.init(k) for m, k in zip(self.layers, keys)]

    def _bind(self, params, key, train):
        keys = (
            jax.random.split(key, max(len(self.layers), 1))
            if key is not None
            else [None] * len(self.layers)
        )
        for m, p, k in zip(self.layers, params, keys):
            m._params = p
            m._ctx = (k, train)
            if isinstance(m, ModuleList):  # nested lists bind their children too
                m._bind(p, k, train)

    def apply(self, params, x, *, key=None, train=False):
        raise NotImplementedError("ModuleList is a container; index into it in forward()")


class BCELoss:
    """Binary cross-entropy on probabilities (torch.nn.BCELoss semantics incl.
    elementwise ``weight`` and ``reduction``)."""

    def __init__(self, weight=None, reduction: str = "mean"):
        self.weight = weight
        self.reduction = reduction

    def __call__(self, pred, target):
        from . import functional as F

        return F.binary_cross_entropy(pred, target, weight=self.weight,
                                      reduction=self.reduction)


class BCEWithLogitsLoss:
    """Sigmoid + BCE in one numerically-stable op (torch semantics incl.
    ``weight``, ``reduction`` and ``pos_weight``)."""

    def __init__(self, weight=None, reduction: str = "mean", pos_weight=None):
        self.weight = weight
        self.reduction = reduction
        self.pos_weight = pos_weight

    def __call__(self, pred, target):
        from . import functional as F

        return F.binary_cross_entropy_with_logits(
            pred, target, weight=self.weight, reduction=self.reduction,
            pos_weight=self.pos_weight,
        )


class SmoothL1Loss:
    def __init__(self, reduction: str = "mean", beta: float = 1.0):
        self.reduction = reduction
        self.beta = beta

    def __call__(self, pred, target):
        from . import functional as F

        return F.smooth_l1_loss(pred, target, reduction=self.reduction,
                                beta=self.beta)


class HuberLoss:
    def __init__(self, reduction: str = "mean", delta: float = 1.0):
        self.reduction = reduction
        self.delta = delta

    def __call__(self, pred, target):
        from . import functional as F

        return F.huber_loss(pred, target, reduction=self.reduction,
                            delta=self.delta)
