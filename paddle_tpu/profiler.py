"""Profiler: host-span tracer + device (XLA) profiler, two-plane design.

ref: python/paddle/profiler/profiler.py:358 (Profiler context manager with
scheduler states), paddle/fluid/platform/profiler/host_tracer.h:26
(RecordEvent spans), chrometracing_logger.cc (Chrome trace export). The
host plane is the C++ tracer in paddle_tpu._native; the device plane is
jax.profiler (XLA/xplane), which TensorBoard renders — the same division
the reference draws between HostTracer and CudaTracer/CUPTI. RecordEvent
is the one span primitive and writes both: the native plane while a
Profiler runs, and a host span beside the device operations, on their
clock, while a jax.profiler trace runs.
"""
from __future__ import annotations

import json
import os
from typing import Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from ._native import lib as _lib
from .observability.clock import now_us

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget",
           "export_chrome_tracing"]


class ProfilerTarget:
    CPU = "cpu"
    TPU = "tpu"
    GPUTrace = "gpu"  # reference-compat alias


class RecordEvent:
    """Host-span annotation (ref: paddle.profiler.RecordEvent; native analog
    platform/profiler/event_tracing.h RecordEvent). Usable as context
    manager or begin()/end() pair. ``attrs`` are small counts or ids.

    One object writes both planes. While a ``Profiler`` runs, the span
    lands in the native host tracer (``export_chrome_tracing``); while a
    ``jax.profiler`` trace runs (``Profiler(targets=[TPU])``, or
    ``jax.profiler.start_trace``), it is a ``TraceAnnotation`` in the
    ``/host:CPU`` plane of the same ``.xplane.pb`` as the device
    operations, on their clock, with ``attrs`` as the event's stats. With
    neither running it costs about a microsecond, so the program's own
    spans (``serving.*``, ``train.*``, ``jit.compile``) are always there
    and no switch turns them on.

    Reentrant: a second ``begin()`` before ``end()`` nests (each ``end``
    closes the most recent open ``begin``, LIFO) instead of silently
    dropping the first span's start."""

    def __init__(self, name: str, /, **attrs):
        self.name = name
        self.attrs = attrs
        self._open: list = []       # (native start or None, annotation)

    def begin(self):
        native = _lib is not None and _lib.tracer_enabled()
        ann = _TraceAnnotation(self.name, **self.attrs)
        ann.__enter__()
        self._open.append((now_us() if native else None, ann))

    def set(self, **attrs):
        """Attach counts that are known only once the work is done to the
        innermost open span (the xplane's stats; the native plane keeps
        names and times only)."""
        if self._open:
            self._open[-1][1].set_metadata(**attrs)

    def end(self):
        if not self._open:
            return
        start, ann = self._open.pop()
        ann.__exit__(None, None, None)
        if start is not None:
            _lib.tracer_record(self.name, start, now_us())

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    """ref: paddle.profiler.Profiler — start/stop/step, export.

    targets including TPU adds the XLA device trace (jax.profiler), viewable
    in TensorBoard; the host plane always records via the native tracer.
    """

    def __init__(self, targets=None, on_trace_ready=None, timer_only=False,
                 profile_memory=False, scheduler=None):
        self.targets = targets or [ProfilerTarget.CPU]
        self.on_trace_ready = on_trace_ready
        self.timer_only = bool(timer_only)
        self._device_dir: Optional[str] = None
        self._running = False
        self._step_count = 0
        self._step_t0: Optional[float] = None

    def start(self):
        if _lib is not None:
            _lib.tracer_start()
            self._step_t0 = now_us()
        # timer_only (ref: Profiler(timer_only=True) — step timing
        # without event collection) keeps the cheap host plane but skips
        # the device (XLA) trace entirely
        if not self.timer_only and (
                ProfilerTarget.TPU in self.targets
                or ProfilerTarget.GPUTrace in self.targets):
            import jax
            self._device_dir = os.environ.get(
                "PADDLE_TPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
            try:
                jax.profiler.start_trace(self._device_dir)
            except Exception:
                self._device_dir = None
        self._running = True
        return self

    def stop(self):
        if not self._running:
            return
        if _lib is not None:
            _lib.tracer_stop()
        if self._device_dir is not None:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
        self._running = False
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    def step(self):
        """Mark a step boundary: the window since start()/the previous
        step() lands in the host trace as a ``ProfileStep#N`` span (ref:
        profiler.py RecordEvent(\"ProfileStep#{id}\") around each
        scheduler step) — summary() and the chrome export then break
        time down per step instead of one undifferentiated run."""
        self._step_count += 1
        if _lib is not None and _lib.tracer_enabled() \
                and self._step_t0 is not None:
            now = now_us()
            _lib.tracer_record(f"ProfileStep#{self._step_count}",
                               self._step_t0, now)
            self._step_t0 = now

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- export -------------------------------------------------------------
    def export(self, path: str, format: str = "json"):
        export_chrome_tracing(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated host-span statistics table (ref:
        profiler/profiler_statistic.py op summary: calls, total, avg,
        max, min, ratio)."""
        if _lib is None:
            return "native tracer unavailable"
        data = json.loads(_lib.tracer_dump())
        agg = {}
        grand = 0.0
        for e in data.get("traceEvents", []):
            if e.get("ph") == "C":
                continue  # timeline counter events are not spans
            dur = float(e.get("dur", 0.0))
            rec = agg.setdefault(e["name"], [0, 0.0, 0.0, float("inf")])
            rec[0] += 1
            rec[1] += dur
            rec[2] = max(rec[2], dur)
            rec[3] = min(rec[3], dur)
            grand += dur
        if not agg:
            return ("no events recorded (host tracer buffer is empty — "
                    "was the profiler started, and did any RecordEvent/"
                    "step() run inside it?)")
        units = {"ms": 1e3, "us": 1.0, "s": 1e6}
        if time_unit not in units:
            raise ValueError(
                f"time_unit must be one of {sorted(units)}, "
                f"got {time_unit!r}")
        unit = units[time_unit]
        u = time_unit
        lines = [f"{'name':<36} {'calls':>7} {f'total_{u}':>11} "
                 f"{f'avg_{u}':>10} {f'max_{u}':>10} {f'min_{u}':>10} "
                 f"{'ratio':>7}"]
        for name, (calls, total, mx, mn) in sorted(
                agg.items(), key=lambda kv: -kv[1][1]):
            lines.append(
                f"{name:<36} {calls:>7} {total / unit:>11.3f} "
                f"{total / calls / unit:>10.3f} {mx / unit:>10.3f} "
                f"{mn / unit:>10.3f} "
                f"{(total / grand if grand else 0.0):>6.1%}")
        return "\n".join(lines)


def export_chrome_tracing(path: str, worker_name=None):
    """Write the host plane as chrome://tracing JSON
    (ref: chrometracing_logger.cc), merged with the step-timeline
    plane — every live ``observability.timeline.StepTimer``'s per-step
    phase counter events (``"ph": "C"``) — and the flight recorder's
    event trail (``observability.flight``, instant events ``"ph": "i"``)
    so ONE file carries spans, metric time series AND the last-N
    black-box events (chrome://tracing / Perfetto render counters as
    stacked area tracks and instants as marks)."""
    if _lib is None:
        raise RuntimeError("native tracer unavailable")
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    dump = _lib.tracer_dump()
    extra = []
    try:
        from .observability import timeline as _timeline
        extra.extend(_timeline.chrome_events())
    except Exception:
        pass
    try:
        from .observability import flight as _flight
        extra.extend(_flight.chrome_events())
    except Exception:
        pass
    if extra:
        data = json.loads(dump)
        data.setdefault("traceEvents", []).extend(extra)
        dump = json.dumps(data)
    with open(path, "w") as f:
        f.write(dump)
    return path
