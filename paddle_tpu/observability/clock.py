"""The one host clock of the telemetry planes: monotonic microseconds.

Flight events, step-timeline counters and ``profiler.RecordEvent``'s native
spans all stamp with :func:`now_us`, so one chrome export lays them on one
timebase. It is the native host tracer's clock once the extension is loaded
(never triggering its build), ``time.perf_counter`` before; on Linux both
read CLOCK_MONOTONIC. The resolved native function is cached: the flight
recorder's append must not pay a ``sys.modules`` lookup per event.
"""
from __future__ import annotations

import sys
import time

__all__ = ["now_us"]

_native_now = None


def now_us() -> float:
    global _native_now
    f = _native_now
    if f is not None:
        return f()
    lib = getattr(sys.modules.get("paddle_tpu._native"), "lib", None)
    if lib is not None:
        _native_now = lib.tracer_now
        return _native_now()
    return time.perf_counter() * 1e6
