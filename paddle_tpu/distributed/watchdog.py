"""Collective/step watchdog: timeout detection for enqueued device work.

ref: paddle/phi/core/distributed/comm_task_manager.h:37-57 (CommTaskManager
background loop: per-collective start/end events, timeout detection, error
propagation, async trace dump enabled by FLAGS_enable_async_trace,
process_group_nccl.cc:156). TPU mapping: the unit of watching is the
compiled program (collectives live inside it), so the watchdog monitors
host-observed completion of each enqueued step; on timeout it dumps the
native host-tracer buffer and invokes the abort callback — the role the
reference fills by aborting NCCL comms.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Callable, Optional

from ..observability import flight as _flight
from ..observability import metrics as _om

__all__ = ["Watchdog", "WatchdogTimeout", "WatchdogBusy",
           "collective_span", "install_watchdog", "uninstall_watchdog"]

# span completions feed the process registry (the reference's
# comm_task_manager per-collective attribution, now queryable without a
# trace dump): latency histogram per span name + timeout counters
_M_span_s = _om.histogram(
    "watchdog.span_seconds",
    "Completed watchdog span durations (collectives, steps) by name")
_M_timeouts = _om.counter(
    "watchdog.timeouts_total", "Spans/steps that exceeded the timeout")


def _flight_dump(note: str):
    """A hung collective/step must leave forensics behind, not just a
    counter bump: freeze the flight ring next to the host-trace dump
    (counted in observability.dumps_total{trigger="watchdog"}).
    Best-effort — a failing dump must not mask the timeout itself."""
    try:
        return _flight.dump(trigger="watchdog", note=note)
    except Exception:  # noqa: BLE001
        return None


class WatchdogTimeout(RuntimeError):
    pass


class WatchdogBusy(WatchdogTimeout):
    """A previous timed-out step is still running. Subclasses
    WatchdogTimeout so existing handlers still fire, but lets retry logic
    distinguish 'refused to start' from a fresh hang."""


class Watchdog:
    """Wrap blocking step executions with a timeout monitor.

        wd = Watchdog(timeout=300.0)
        loss = wd.run(lambda: float(step(x, y)))     # raises on hang

    The callable must block until device completion (a host value
    transfer)."""

    def __init__(self, timeout: float = 600.0,
                 on_timeout: Optional[Callable[[], None]] = None,
                 trace_path: Optional[str] = None):
        self.timeout = timeout
        self.on_timeout = on_timeout
        self.trace_path = trace_path
        self._task_counter = 0
        self._stuck_thread: Optional[threading.Thread] = None
        # named spans (ref: comm_task_manager.h CommTask start/end events):
        # open spans keyed by id, completed spans in a ring for attribution
        self._span_lock = threading.Lock()
        self._open_spans: dict = {}
        self._span_counter = 0
        self._recent_spans: deque = deque(maxlen=32)
        self._monitor: Optional[threading.Thread] = None
        self._monitor_stop = threading.Event()
        self.timed_out_spans: list = []

    # -- named spans --------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """Track one named operation (a collective, a step). On timeout
        the monitor names it, dumps the host trace, and fires on_timeout
        — the reference's per-CommTask attribution
        (ref: comm_task_manager.h:37-57)."""
        with self._span_lock:
            self._span_counter += 1
            sid = self._span_counter
            # [name, start, timed_out_flag] — a timed-out span stays OPEN
            # (the thread is still blocked) and is merely flagged, so
            # open_span_report keeps showing the hang until it resolves
            self._open_spans[sid] = [name, time.monotonic(), False]
        try:
            yield
        finally:
            with self._span_lock:
                entry = self._open_spans.pop(sid, None)
            if entry is not None:
                name_, t0, flagged = entry
                dt = time.monotonic() - t0
                _M_span_s.observe(dt, name=name_)
                with self._span_lock:
                    self._recent_spans.append(
                        (name_ + (" [timed out]" if flagged else ""), dt))

    def open_span_report(self) -> str:
        with self._span_lock:
            now = time.monotonic()
            opens = [f"{n}{' [TIMED OUT]' if flagged else ''} "
                     f"({now - t0:.1f}s open)"
                     for n, t0, flagged in self._open_spans.values()]
            recent = [f"{n} ({dt * 1e3:.0f}ms)"
                      for n, dt in list(self._recent_spans)[-5:]]
        return (f"open spans: {opens or ['<none>']}; "
                f"recent: {recent or ['<none>']}")

    def start_monitor(self, interval: float = 1.0):
        """Background loop that attributes hangs to the oldest open span
        (a blocked collective cannot raise for itself)."""
        if self._monitor is not None:
            return self
        self._monitor_stop.clear()

        def loop():
            while not self._monitor_stop.wait(interval):
                with self._span_lock:
                    now = time.monotonic()
                    expired = [(sid, e[0], now - e[1]) for sid, e
                               in self._open_spans.items()
                               if now - e[1] > self.timeout and not e[2]]
                for sid, name, age in expired:
                    with self._span_lock:
                        entry = self._open_spans.get(sid)
                        if entry is None or entry[2]:
                            continue
                        entry[2] = True  # flag in place; span stays open
                    _M_timeouts.inc()
                    _flight.record("watchdog", "timeout", span=name,
                                   open_s=round(age, 1))
                    dump = self._dump_trace()
                    fdump = _flight_dump(
                        f"span {name!r} open {age:.0f}s")
                    self.timed_out_spans.append((name, age, dump))
                    import sys
                    sys.stderr.write(
                        f"[watchdog] operation {name!r} exceeded "
                        f"{self.timeout:.0f}s (open {age:.0f}s)"
                        + (f"; trace dumped to {dump}" if dump else "")
                        + (f"; flight dump {fdump}" if fdump else "")
                        + "\n")
                    if self.on_timeout is not None:
                        try:
                            self.on_timeout()
                        except BaseException:
                            pass
        self._monitor = threading.Thread(target=loop, daemon=True)
        self._monitor.start()
        return self

    def stop_monitor(self):
        self._monitor_stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=5)
            self._monitor = None

    def _dump_trace(self):
        """Async trace dump on failure (ref: FLAGS_enable_async_trace)."""
        try:
            from .._native import lib
            if lib is not None and self.trace_path:
                with open(self.trace_path, "w") as f:
                    f.write(lib.tracer_dump())
                return self.trace_path
        except Exception:
            pass
        return None

    def run(self, fn: Callable, *args, **kwargs):
        """NOTE a Python thread cannot be killed: on timeout the worker may
        STILL complete later and land its side effects (the reference
        aborts the NCCL comm from on_timeout — do the equivalent abort in
        your callback). A subsequent run() while the timed-out worker is
        still alive refuses to start, so a retry can never double-apply an
        update on top of a late-finishing one."""
        if self._stuck_thread is not None:
            if self._stuck_thread.is_alive():
                raise WatchdogBusy(
                    "previous timed-out step is still running; refusing "
                    "to launch another (restart the process or abort the "
                    "device work from on_timeout)")
            self._stuck_thread = None
        self._task_counter += 1
        task_id = self._task_counter
        result = {}
        done = threading.Event()

        def worker():
            try:
                result["value"] = fn(*args, **kwargs)
            except BaseException as e:  # propagate into the caller
                result["error"] = e
            finally:
                done.set()

        t = threading.Thread(target=worker, daemon=True)
        start = time.monotonic()
        t.start()
        if not done.wait(self.timeout):
            self._stuck_thread = t
            _M_timeouts.inc()
            _flight.record("watchdog", "timeout", task=task_id,
                           timeout_s=self.timeout)
            dump = self._dump_trace()
            fdump = _flight_dump(f"step {task_id} exceeded "
                                 f"{self.timeout:.0f}s")
            abort_err = None
            if self.on_timeout is not None:
                try:
                    self.on_timeout()
                except BaseException as e:  # the timeout must still surface
                    abort_err = e
            raise WatchdogTimeout(
                f"step {task_id} exceeded {self.timeout:.0f}s "
                f"(started {time.monotonic() - start:.0f}s ago)"
                + (f"; host trace dumped to {dump}" if dump else "")
                + (f"; flight dump {fdump}" if fdump else "")
                + (f"; on_timeout callback itself failed: {abort_err!r}"
                   if abort_err is not None else "")) from abort_err
        if "error" in result:
            raise result["error"]
        return result["value"]


# -- global collective instrumentation ---------------------------------------
# collective.py wraps every eager collective in collective_span(); with no
# installed watchdog the wrapper is free (nullcontext).

_installed: Optional[Watchdog] = None


def install_watchdog(timeout: float = 600.0,
                     on_timeout: Optional[Callable[[], None]] = None,
                     trace_path: Optional[str] = None) -> Watchdog:
    """Install a process-wide watchdog whose monitor attributes hangs to
    the named collective/step spans (ref: FLAGS_enable_async_trace +
    CommTaskManager background loop)."""
    global _installed
    if _installed is not None:
        _installed.stop_monitor()
    _installed = Watchdog(timeout, on_timeout, trace_path).start_monitor()
    return _installed


def uninstall_watchdog():
    global _installed
    if _installed is not None:
        _installed.stop_monitor()
        _installed = None


def collective_span(name: str):
    if _installed is None:
        return contextlib.nullcontext()
    return _installed.span(name)
