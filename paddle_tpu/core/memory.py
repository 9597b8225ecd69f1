"""Live device-memory accounting.

TPU-native analog of the reference's allocator stat counters
(ref: paddle/phi/core/memory/stats.h, exposed as
paddle.device.cuda.max_memory_allocated —
ref: python/paddle/device/cuda/__init__.py:233).

On GPU the reference hooks its own allocator, so current/peak are exact
at allocation granularity. Here PJRT owns device memory, so the design
layers three sources:

1. ``device.memory_stats()`` from PJRT — exact allocator counters when
   the platform reports them (TPU backends do; the CPU backend returns
   ``None``).
2. An op-boundary tracker (this module): every eager ``apply_op`` output
   and ``to_tensor`` registers its ``jax.Array`` buffer here; a
   ``weakref.finalize`` decrements on buffer death. Current/peak live in
   the native MemStats counters (``_native/native.cpp`` MemStats) when
   the native runtime is built, with a pure-Python fallback.
3. ``jax.live_arrays()`` — an exact on-demand scan used to reconcile the
   tracker (catches arrays created outside the op funnel, e.g. raw jnp
   calls in user code).

jit-internal temporaries never appear in (2)/(3) — they are XLA's, and
are reported per-executable by :func:`program_memory_analysis` over
``Compiled.memory_analysis()``.
"""
from __future__ import annotations

import threading
import weakref
from typing import Dict, Optional

import jax
from jax.sharding import SingleDeviceSharding

from .._native import lib as _native
from ..observability import metrics as _om

_ALLOC = "allocated"

# id(buffer) set currently tracked: dedups multiple Tensor wrappers over
# one jax.Array (detach/alias) — a buffer is counted once.
_tracked: set = set()
_lock = threading.Lock()

# pure-Python fallback counters {key: [current, peak]} when the native
# runtime is unavailable
_py_stats: Dict[str, list] = {}


_key_cache: Dict = {}


def _key(device) -> str:
    k = _key_cache.get(device)
    if k is None:
        k = _key_cache[device] = f"{_ALLOC}.{device.platform}:{device.id}"
    return k


def _update(key: str, delta: int) -> None:
    if _native is not None:
        _native.stat_update(key, int(delta))
        return
    with _lock:
        e = _py_stats.setdefault(key, [0, 0])
        e[0] += delta
        if e[0] > e[1]:
            e[1] = e[0]


def _get(key: str):
    if _native is not None:
        return _native.stat_get(key)
    with _lock:
        e = _py_stats.get(key, [0, 0])
        return e[0], e[1]


def _reset_peak(key: str) -> None:
    if _native is not None:
        _native.stat_reset_peak(key)
        return
    with _lock:
        e = _py_stats.get(key)
        if e is not None:
            e[1] = e[0]


def _set_current(key: str, cur: int) -> None:
    if _native is not None:
        _native.stat_set_current(key, int(cur))
        return
    with _lock:
        e = _py_stats.setdefault(key, [0, 0])
        e[0] = cur
        if e[0] > e[1]:
            e[1] = e[0]


def _per_device_bytes(arr) -> Dict[str, int]:
    """{stat key: bytes} for one array, from sharding math only — never
    materializes per-shard wrapper arrays (``addressable_shards[i].data``
    creates cached ArrayImpls that a live-array scan would then double
    count)."""
    sh = arr.sharding
    shard_elems = 1
    for d in sh.shard_shape(arr.shape):
        shard_elems *= d
    nbytes = shard_elems * arr.dtype.itemsize
    agg: Dict[str, int] = {}
    for dev in sh.addressable_devices:
        k = _key(dev)
        agg[k] = agg.get(k, 0) + nbytes
    return agg


def _on_free(buf_id: int, per_device) -> None:
    with _lock:
        _tracked.discard(buf_id)
    for key, nbytes in per_device:
        try:
            _update(key, -nbytes)
        except Exception:
            pass  # interpreter shutdown


def track(arr) -> None:
    """Register a device buffer with the allocation counters.

    Called from the eager op funnel (core.autograd.apply_op) and
    to_tensor on every concrete ``jax.Array`` output. Tracers and
    already-seen buffers are skipped. Cost is ~1µs (one finalizer);
    this sits inside the measured eager dispatch budget.
    """
    if isinstance(arr, jax.core.Tracer) or not isinstance(arr, jax.Array):
        return
    buf_id = id(arr)
    with _lock:
        if buf_id in _tracked:
            return
        _tracked.add(buf_id)
    try:
        if type(arr.sharding) is SingleDeviceSharding:
            # single-device fast path (the eager hot loop): no
            # shard-shape math, one cached key lookup
            per_device = [(_key(arr.device), arr.nbytes)]
        else:
            per_device = list(_per_device_bytes(arr).items())
    except Exception:
        with _lock:
            _tracked.discard(buf_id)
        return
    for key, nbytes in per_device:
        _update(key, nbytes)
    weakref.finalize(arr, _on_free, buf_id, per_device)


def live_bytes(device=None) -> Dict[str, int]:
    """Exact per-device bytes of all live jax.Arrays (on-demand scan).

    Cached per-shard wrapper arrays (``ArrayImpl._arrays`` members) are
    aliases of their parent's buffers and are excluded; if the internal
    attribute is unavailable no wrappers were ever materialized by this
    module, so the unfiltered sum is already alias-free.
    """
    arrays = jax.live_arrays()
    shard_ids: set = set()
    for a in arrays:
        try:
            for b in (getattr(a, "_arrays", None) or []):
                if b is not a:
                    shard_ids.add(id(b))
        except Exception:
            break
    out: Dict[str, int] = {}
    for a in arrays:
        if id(a) in shard_ids:
            continue
        try:
            for k, nbytes in _per_device_bytes(a).items():
                out[k] = out.get(k, 0) + nbytes
        except Exception:
            continue
    if device is not None:
        k = _key(device)
        return {k: out.get(k, 0)}
    return out


def reconcile(device=None) -> None:
    """Snap tracker current to the exact live-array scan (keeps peak
    monotone: SetCurrent raises peak if the scan exceeds it)."""
    for key, nbytes in live_bytes(device).items():
        _set_current(key, nbytes)


# Per-device peak-reset emulation for PJRT-backed stats: PJRT exposes a
# process-lifetime peak_bytes_in_use with no reset. After a reset we
# report max(watermark of bytes_in_use observed at stats queries since
# the reset, pjrt_peak if it exceeded its value AT the reset — a new
# global maximum can only have happened after the reset).
# {key: [pjrt_peak_at_reset, observed_watermark_since]}
_pjrt_reset: Dict[str, list] = {}


def stats_for(device) -> Optional[Dict[str, int]]:
    """Per-device stat dict, or the PJRT dict when the platform has one."""
    pjrt = None
    try:
        pjrt = device.memory_stats()
    except Exception:
        pjrt = None
    if pjrt:
        key = _key(device)
        cur = int(pjrt.get("bytes_in_use", 0))
        peak = int(pjrt.get("peak_bytes_in_use", 0))
        rst = _pjrt_reset.get(key)
        if rst is not None:
            rst[1] = max(rst[1], cur)
            peak = peak if peak > rst[0] else rst[1]
        return {
            "allocated.current": cur,
            "allocated.peak": peak,
            "reserved.current": int(pjrt.get("bytes_reserved", cur)),
            "reserved.peak": int(pjrt.get("peak_bytes_reserved", peak)),
            "pjrt": dict(pjrt),
        }
    key = _key(device)
    # the live-array scan is ground truth for CURRENT (the op-funnel
    # tracker misses raw jnp arrays in both directions — creation AND
    # death); snap to it unconditionally. PEAK stays a high-water mark:
    # SetCurrent only ever raises it.
    exact = live_bytes(device)[key]
    _set_current(key, exact)
    cur, peak = _get(key)
    return {
        "allocated.current": int(cur),
        "allocated.peak": int(peak),
        "reserved.current": int(cur),
        "reserved.peak": int(peak),
        "pjrt": None,
    }


# snapshot-time registry view over the op-funnel tracker counters —
# nothing added to the per-buffer track() hot path
def _collect_memory():
    cur: Dict[str, int] = {}
    peak: Dict[str, int] = {}
    for key in list({k for k in _key_cache.values()}):
        c, p = _get(key)
        label = key[len(_ALLOC) + 1:]  # "cpu:0", "tpu:3", ...
        cur[label] = int(c)
        peak[label] = int(p)
    out = {}
    if cur:
        out["memory.tracked_bytes"] = cur
        out["memory.tracked_peak_bytes"] = peak
    return out


_om.register_collector("memory", _collect_memory)


def reset_peak(device) -> None:
    key = _key(device)
    _reset_peak(key)
    try:
        pjrt = device.memory_stats()
    except Exception:
        pjrt = None
    if pjrt:
        _pjrt_reset[key] = [int(pjrt.get("peak_bytes_in_use", 0)),
                            int(pjrt.get("bytes_in_use", 0))]
