"""Device registry (reference heat/core/devices.py:14-181, re-targeted at TPU).

The reference maps Heat devices onto torch devices with a round-robin GPU→rank rule
(``devices.py:114-118``). Here a :class:`Device` names a JAX platform; actual placement of
distributed arrays is governed by the mesh in :mod:`heat_tpu.core.communication`, so the
device object is a label + default-platform selector rather than an address.
"""

from __future__ import annotations

from typing import Optional, Union

import numpy as np

import jax

__all__ = ["Device", "cpu", "tpu", "gpu", "get_device", "use_device", "sanitize_device"]


class Device:
    """Implements a compute device. ``device_type`` is a JAX platform name
    (``"cpu"``, ``"tpu"``, ``"gpu"``); ``device_id`` selects among local devices.

    Mirrors reference ``heat/core/devices.py:17-94``.
    """

    def __init__(self, device_type: str, device_id: int = 0):
        self.__device_type = device_type.strip().lower()
        self.__device_id = int(device_id)

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> int:
        return self.__device_id

    @property
    def torch_device(self) -> str:
        """Interop shim (reference ``devices.py:59`` returns the torch device
        *string*): heat_tpu data lives in jax, so this is the torch device a host
        copy would land on — always ``"cpu"`` (TPUs have no torch backing here).
        The str is valid everywhere torch accepts a device argument."""
        return "cpu"

    @property
    def jax_device(self) -> Optional[jax.Device]:
        """The concrete ``jax.Device`` this label resolves to, or None if absent."""
        try:
            devs = jax.devices(self.__device_type)
        except RuntimeError:
            return None
        if not devs:
            return None
        return devs[self.__device_id % len(devs)]

    def __repr__(self) -> str:
        return f"device({str(self)!r})"

    def __str__(self) -> str:
        return f"{self.__device_type}:{self.__device_id}"

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return self.device_type == other.device_type and self.device_id == other.device_id
        if isinstance(other, str):
            return str(self) == other or self.device_type == other
        return NotImplemented

    def __hash__(self) -> int:
        return hash(str(self))


cpu = Device("cpu")
"""The host CPU device (reference ``devices.py:95``)."""

# TPU/GPU singletons exist whenever the platform is present; the default accelerator
# platform is whatever jax initialised with.
_default_platform = jax.default_backend()

tpu = Device("tpu") if _default_platform not in ("cpu", "gpu") else Device(_default_platform)
gpu = tpu  # alias for source compatibility with reference code written for ``ht.gpu``

__default_device = Device(_default_platform)


def get_device() -> Device:
    """Return the current default device (reference ``devices.py:160``)."""
    return __default_device


def use_device(device: Optional[Union[str, Device]] = None) -> None:
    """Set the default device (reference ``devices.py:171``)."""
    global __default_device
    __default_device = sanitize_device(device)


def sanitize_device(device: Optional[Union[str, Device]]) -> Device:
    """Validate ``device`` or fall back to the default (reference ``devices.py:130``)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    if isinstance(device, str):
        dev = device.strip().lower()
        if ":" in dev:
            kind, _, idx = dev.partition(":")
            return Device(kind, int(idx))
        if dev in ("cpu", "tpu", "gpu"):
            return Device(dev)
    raise ValueError(f"Unknown device, must be 'cpu', 'tpu' or 'gpu', got {device!r}")


# ------------------------------------------------------------ device dtype support
def promoted_dtype(*dtypes_or_values):
    """numpy-style promoted dtype of ``dtypes_or_values`` (arrays, scalars, dtypes;
    Python scalars stay weak), or None when they do not promote."""
    import jax.numpy as jnp

    if not dtypes_or_values:
        return None
    try:
        return np.result_type(*[getattr(v, "dtype", v) for v in dtypes_or_values])
    except TypeError:
        try:
            return np.dtype(jnp.result_type(*dtypes_or_values))
        except TypeError:
            return None


def require_device_dtype(*dtypes_or_values) -> None:
    """Raise ``TypeError`` when the promoted dtype of ``dtypes_or_values`` is one the
    default backend cannot hold. The one refused type is complex128 on a TPU: libtpu
    fails a CHECK in its lowering on any program that carries a complex128 value and
    aborts the whole process (measured on TPU v5e, jax 0.9.0 / libtpu 0.0.34), so the
    refusal has to happen here, before the value reaches the compiler. complex64 and
    FFT run on the device."""
    if jax.default_backend() == "tpu" and promoted_dtype(*dtypes_or_values) == np.complex128:
        raise TypeError(
            "complex128 is not supported on TPU (the TPU compiler aborts the process "
            "on complex128 programs); use complex64, e.g. dtype=ht.complex64"
        )
