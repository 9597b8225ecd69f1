"""The host's own pauses, on the device trace's clock: the Python collector.

CPython's collector runs on whichever thread allocated past its threshold,
and holds the interpreter lock for the whole collection. A serving loop
whose fetch has its tokens cannot return while another thread collects, and
the device, one launch ahead, runs dry. So every collection is recorded
where the device's idle time can be laid against it.

One ``gc.callbacks`` hook (:func:`install`, called when
``paddle_tpu.observability`` is imported; a second call is a no-op), gated
by ``FLAGS_metrics`` like every instrument (off: neither span nor count):

- span ``host.gc`` (``generation``; ``collected`` and ``uncollectable`` at
  its close), a ``profiler.RecordEvent`` on the thread that collected: the
  xplane keeps threads as lines, so a collection on a client thread during
  the loop's fetch shows as such (the benchmark's ``readers/host.py``:
  ``gc_idle_share.*``, ``gc_ms_in_window.*``);
- by generation, in three lists of this module: ``counts``,
  ``seconds`` and ``pause_max`` (seconds). A serving fetch reads them at
  its two ends (``serving.py`` ``_FetchWatch``); the registry reads them at
  snapshot time as ``host.gc_collections_total{generation}``,
  ``host.gc_seconds_total{generation}`` and
  ``host.gc_pause_max_seconds{generation}`` (``/metrics``); and
  ``GenerationServer.stats()["host"]["gc"]``, which every benchmark runner
  logs at the end of a run, is :func:`gc_stats`.

The hook takes no lock: a collection can start inside any ``with lock:``
block of its thread, the metric instruments' own included, and a hook that
waited there would wait for good. Under the interpreter lock one ``+=`` on a
list item is enough, and the collector never runs two collections at once.
"""
from __future__ import annotations

import gc
import sys
import time

from . import metrics as _metrics

__all__ = ["install", "gc_stats", "counts", "seconds", "pause_max"]

counts = [0, 0, 0]              # collections, by generation
seconds = [0.0, 0.0, 0.0]       # seconds they held the interpreter
pause_max = [0.0, 0.0, 0.0]     # the longest, in seconds
# the collection in progress: (span or None, start, generation)
_open = None
_flag = _metrics.flag_info()
_record_event = None    # profiler.RecordEvent once that module is loaded


def _span(generation: int):
    """A begun ``host.gc`` span, or None before ``paddle_tpu.profiler`` is
    imported (it imports this package: the hook looks it up, never imports
    it from inside a collection)."""
    global _record_event
    if _record_event is None:
        mod = sys.modules.get("paddle_tpu.profiler")
        _record_event = getattr(mod, "RecordEvent", None)
        if _record_event is None:
            return None
    span = _record_event("host.gc", generation=generation)
    span.begin()
    return span


def _on_gc(phase: str, info: dict) -> None:
    global _open
    if phase == "start":
        if _flag.value:
            g = info["generation"]
            _open = (_span(g), time.perf_counter(), g)
        return
    if _open is None:       # the switch was off when it started
        return
    span, t0, g = _open
    _open = None
    dt = time.perf_counter() - t0
    if span is not None:
        span.set(collected=info["collected"],
                 uncollectable=info["uncollectable"])
        span.end()
    counts[g] += 1
    seconds[g] += dt
    if dt > pause_max[g]:
        pause_max[g] = dt


def _collect() -> dict:
    return {"host.gc_collections_total": dict(enumerate(counts)),
            "host.gc_seconds_total": dict(enumerate(seconds)),
            "host.gc_pause_max_seconds": dict(enumerate(pause_max))}


def install() -> None:
    """Put the hook in ``gc.callbacks`` once; a hook this module left there
    before (a reload) is replaced, not doubled."""
    gc.callbacks[:] = [cb for cb in gc.callbacks if not (
        getattr(cb, "__module__", None) == __name__
        and getattr(cb, "__qualname__", None) == "_on_gc")]
    gc.callbacks.append(_on_gc)
    _metrics.register_collector("host.gc", _collect, label="generation")


def gc_stats() -> list:
    """``[collections, seconds, longest ms]`` by generation."""
    return [[counts[g], round(seconds[g], 6),
             round(1e3 * pause_max[g], 3)] for g in range(3)]
