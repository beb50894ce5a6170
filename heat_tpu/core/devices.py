"""Device abstraction over JAX backends.

TPU-native re-design of reference heat/core/devices.py:17-167: the reference
exposes ``cpu``/``gpu`` singletons (GPU chosen round-robin by MPI rank,
devices.py:98-102) plus a mutable global default. Here a :class:`Device` names
a JAX *backend* ("cpu" or "tpu"); actual placement of every array is governed
by the mesh/sharding in :mod:`heat_tpu.core.communication`, not per-rank device
ids — single-controller JAX drives all chips of the backend at once.
"""

from __future__ import annotations

from typing import Optional, Union

import jax

__all__ = ["Device", "cpu", "tpu", "gpu", "get_device", "sanitize_device", "use_device"]


class Device:
    """Represents a compute backend on which arrays live.

    Parameters
    ----------
    device_type : str
        "cpu" or "tpu" (``"gpu"`` is accepted as an alias for the accelerator
        backend for reference-API compatibility).
    device_id : int
        Kept for API parity; placement is mesh-driven, so this is always 0.
    """

    def __init__(self, device_type: str, device_id: int = 0):
        self.__device_type = device_type
        self.__device_id = device_id

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> int:
        return self.__device_id

    def jax_devices(self):
        """All JAX devices of this backend (may raise if backend missing)."""
        return jax.devices(self.__device_type)

    def __repr__(self) -> str:
        return f"device({self.__str__()!r})"

    def __str__(self) -> str:
        return f"{self.device_type}:{self.device_id}"

    def __eq__(self, other) -> bool:
        if isinstance(other, Device):
            return self.device_type == other.device_type
        if isinstance(other, str):
            try:
                return self.device_type == sanitize_device(other).device_type
            except ValueError:
                return NotImplemented
        return NotImplemented

    def __hash__(self):
        return hash(self.device_type)


cpu = Device("cpu")
"""The host CPU backend."""


def _accelerator_type() -> Optional[str]:
    backend = jax.default_backend()
    return backend if backend != "cpu" else None


tpu = Device("tpu")
"""The TPU backend (driven as a whole mesh, not per-rank round-robin as in
reference devices.py:98-102)."""

# Reference-API alias: scripts written against the reference say ht.gpu.
gpu = tpu

__default_device: Optional[Device] = None


def get_device() -> Device:
    """The currently-selected default device (reference devices.py:139)."""
    global __default_device
    if __default_device is None:
        __default_device = tpu if _accelerator_type() else cpu
    return __default_device


def sanitize_device(device: Optional[Union[str, Device]]) -> Device:
    """Normalize a device spec to a :class:`Device` (reference devices.py:146)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    name = str(device).strip().lower().split(":")[0]
    if name == "cpu":
        return cpu
    if name in ("tpu", "gpu", "cuda"):
        return tpu
    raise ValueError(f"Unknown device, must be 'cpu' or 'tpu', got {device!r}")


def use_device(device: Optional[Union[str, Device]] = None) -> None:
    """Set the globally-used default device (reference devices.py:157-167)."""
    global __default_device
    __default_device = sanitize_device(device)
