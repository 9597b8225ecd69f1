"""paddle.device equivalent (ref: python/paddle/device/__init__.py).

TPU build notes: PJRT owns devices; streams/events are XLA's async
dispatch, so Stream/Event/synchronize are thin wrappers over the
dispatch queue (the reference's CUDA stream objects have no TPU
analog — XLA schedules).
"""
from __future__ import annotations

import contextlib

import jax

from ..core.device import (  # noqa: F401
    CPUPlace, Place, TPUPlace, device_count, get_device,
    is_compiled_with_cuda, is_compiled_with_tpu, set_device)

__all__ = [
    "get_cudnn_version", "set_device", "get_device", "XPUPlace",
    "IPUPlace", "is_compiled_with_xpu", "is_compiled_with_ipu",
    "is_compiled_with_cinn", "is_compiled_with_cuda",
    "is_compiled_with_rocm", "is_compiled_with_distribute",
    "is_compiled_with_custom_device", "get_all_device_type",
    "get_all_custom_device_type", "get_available_device",
    "get_available_custom_device", "Stream", "Event", "current_stream",
    "set_stream", "stream_guard", "synchronize",
    "memory_stats", "memory_allocated", "max_memory_allocated",
    "memory_reserved", "max_memory_reserved",
    "reset_max_memory_allocated", "reset_peak_memory_stats",
    "empty_cache", "program_memory_analysis",
]


def get_cudnn_version():
    """None on non-CUDA builds (ref: device/__init__.py)."""
    return None


def XPUPlace(dev_id: int = 0):
    raise RuntimeError("this build has no XPU backend (TPU-native)")


def IPUPlace():
    raise RuntimeError("this build has no IPU backend (TPU-native)")


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    # XLA plays CINN's role and is always present
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    return True


def is_compiled_with_custom_device(device_type: str) -> bool:
    """TPU is this build's 'custom device' in reference terms."""
    return device_type == "tpu"


def _platforms():
    plats = []
    for d in jax.devices():
        if d.platform not in plats:
            plats.append(d.platform)
    return plats


def get_all_device_type():
    return ["cpu"] + [p for p in _platforms() if p != "cpu"]


def get_all_custom_device_type():
    return [p for p in _platforms() if p not in ("cpu", "gpu")]


def get_available_device():
    out = []
    for i, d in enumerate(jax.devices()):
        out.append(f"{d.platform}:{i}")
    return out or ["cpu"]


def get_available_custom_device():
    return [d for d in get_available_device()
            if d.split(":")[0] not in ("cpu", "gpu")]


class Stream:
    """Execution stream handle (ref: device/__init__.py Stream). XLA
    owns scheduling on TPU; the object carries identity + sync only."""

    def __init__(self, device=None, priority=2):
        self.device = device or get_device()
        self.priority = priority

    def synchronize(self):
        synchronize(self.device)

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def record_event(self, event=None):
        event = event or Event()
        event.record(self)
        return event


class Event:
    """Cross-stream sync point (ref: device/__init__.py Event)."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device or get_device()
        self._recorded_on = None

    def record(self, stream=None):
        self._recorded_on = stream

    def query(self) -> bool:
        return True  # XLA dispatch: enqueued work completes in order

    def synchronize(self):
        synchronize(self.device)


_current_streams: dict = {}


def current_stream(device=None):
    key = device or get_device()
    if key not in _current_streams:
        _current_streams[key] = Stream(key)
    return _current_streams[key]


def set_stream(stream):
    prev = current_stream(stream.device)
    _current_streams[stream.device] = stream
    return prev


@contextlib.contextmanager
def stream_guard(stream):
    prev = set_stream(stream)
    try:
        yield
    finally:
        set_stream(prev)


def synchronize(device=None):
    """Block until enqueued device work completes (ref: device
    synchronize): realized by fetching a tiny value through the same
    queue — the only ordered barrier XLA exposes."""
    import jax.numpy as jnp
    jax.block_until_ready(jnp.zeros(()))


# ---------------------------------------------------------------------------
# live device-memory observability
# (ref: python/paddle/device/cuda/__init__.py:233 max_memory_allocated over
#  paddle/phi/core/memory/stats.h current/peak counters; here the counters
#  come from PJRT memory_stats when the platform reports them, else from
#  the framework's op-boundary tracker in core/memory.py backed by the
#  native MemStats registry)
# ---------------------------------------------------------------------------

def _resolve_device(device=None):
    devs = jax.devices()
    if device is None:
        return devs[0]
    if isinstance(device, int):
        return devs[device]
    if hasattr(device, "platform"):  # already a jax device
        return device
    spec = str(device)
    if ":" in spec:
        return devs[int(spec.split(":")[1])]
    return devs[0]


def memory_stats(device=None):
    """Full stat dict for one device: allocated/reserved current+peak,
    plus the raw PJRT dict under ``"pjrt"`` when the backend has one."""
    from ..core import memory as _memory
    return _memory.stats_for(_resolve_device(device))


def memory_allocated(device=None) -> int:
    """Bytes of live device buffers right now (exact: PJRT counters or a
    live-array scan). ref: device/cuda/__init__.py memory_allocated."""
    return memory_stats(device)["allocated.current"]


def max_memory_allocated(device=None) -> int:
    """High-water mark of allocated bytes since start / last reset.
    ref: device/cuda/__init__.py:233."""
    return memory_stats(device)["allocated.peak"]


def memory_reserved(device=None) -> int:
    """Bytes reserved from the platform allocator (== allocated where
    PJRT doesn't report a separate reservation pool)."""
    return memory_stats(device)["reserved.current"]


def max_memory_reserved(device=None) -> int:
    return memory_stats(device)["reserved.peak"]


def reset_max_memory_allocated(device=None) -> None:
    """Peak watermark := current (reference ResetPeakValue semantics)."""
    from ..core import memory as _memory
    d = _resolve_device(device)
    _memory.reconcile(d)
    _memory.reset_peak(d)


def reset_peak_memory_stats(device=None) -> None:
    reset_max_memory_allocated(device)


def empty_cache() -> None:
    """Release cached host-side objects (PJRT owns device memory; the
    analog of the reference's allocator-cache flush is dropping dead
    Python references + XLA's compilation caches stay warm)."""
    import gc
    gc.collect()


def program_memory_analysis(compiled_or_fn, *example_args):
    """Per-device memory breakdown of a compiled XLA program: dict with
    argument/output/temp/alias/generated-code bytes and a ``peak_hbm``
    estimate (args + outputs + temps - aliased). jit-internal temps are
    invisible to the live counters — this is the API that sees them.

    Accepts a ``jax.stages.Compiled``, a jitted fn + example args (will
    lower+compile), or any object with ``memory_analysis()``.
    """
    obj = compiled_or_fn
    if example_args:
        obj = jax.jit(obj) if not hasattr(obj, "lower") else obj
        obj = obj.lower(*example_args).compile()
    ma = obj.memory_analysis()
    out = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    out["peak_hbm"] = (out["argument_bytes"] + out["output_bytes"]
                       + out["temp_bytes"] - out["alias_bytes"])
    return out
