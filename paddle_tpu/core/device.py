"""Device management.

TPU-native analog of the reference's Place/DeviceContext machinery
(ref: paddle/phi/backends/device_manager.h, paddle/phi/common/place.h).
On TPU the runtime (PJRT, via JAX) owns streams/allocators, so this layer is a
thin facade: named places, device listing, and the default-device switch.
"""
from __future__ import annotations

import jax


class Place:
    """A device place, e.g. Place('tpu', 0). ref: paddle/phi/common/place.h"""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        """The jax device this place names. Raises when the host has no
        such device: a place never resolves to another platform."""
        devs = jax.devices(self.device_type)  # RuntimeError: no such backend
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"no device {self.device_type}:{self.device_id} on this "
                f"host: it has {len(devs)} {self.device_type} device(s)")
        return devs[self.device_id]


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CPUPlace(device_id: int = 0) -> Place:
    return Place("cpu", device_id)


def one_chip_env(chip: int) -> dict:
    """libtpu's own variables that give a child process exactly chip
    ``chip`` of this host. A chip belongs to one process at a time, so a
    parent that starts several TPU children (fleet replicas, launch
    workers) hands each a different one and stays off JAX itself."""
    return {"TPU_VISIBLE_CHIPS": str(int(chip)),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


def env_wants_cpu(env) -> bool:
    """True when ``env`` tells JAX to use the CPU (no chip to hand out)."""
    return env.get("JAX_PLATFORMS", "").split(",")[0] == "cpu"


_current_device: Place | None = None


def set_device(device: str) -> Place:
    """set_device('tpu') / 'tpu:0' / 'cpu'. Raises when this host has no
    such device. ref: python/paddle/device/__init__.py"""
    global _current_device
    kind, _, idx = device.partition(":")
    place = Place(kind, int(idx or 0))
    place.jax_device()
    _current_device = place
    return _current_device


def get_device() -> str:
    p = _get_place()
    return f"{p.device_type}:{p.device_id}"


def _get_place() -> Place:
    global _current_device
    if _current_device is None:
        _current_device = Place(jax.devices()[0].platform, 0)
    return _current_device


def device_count(device_type: str | None = None) -> int:
    if device_type is None:
        return len(jax.devices())
    return len([d for d in jax.devices() if d.platform == device_type])


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())
