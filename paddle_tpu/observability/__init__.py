"""paddle_tpu.observability — the unified telemetry runtime.

One process-wide metrics registry (``Counter`` / ``Gauge`` /
``Histogram``, kill-switchable via ``FLAGS_metrics``, default on) that
every subsystem registers into at import time, a step-timeline
plane (``timeline.StepTimer``) whose counter events merge into
``profiler.export_chrome_tracing``, and an always-on flight recorder
(``flight``: bounded black-box event journal + crash-forensics dumps,
``FLAGS_flight_recorder``; see ``python -m paddle_tpu.observability
--flight``), and the host's own pauses (``host``: every Python garbage
collection as a ``host.gc`` span and a count by generation, hooked into
``gc.callbacks`` on import).

Quick tour::

    import paddle_tpu as paddle
    from paddle_tpu import observability as obs

    # ... train / serve ...
    obs.snapshot()             # nested dict: dispatch/fusion/checkpoint/
                               # serving/... counters in one place
    obs.render_prometheus()    # text exposition format for a scraper
    srv = obs.start_metrics_server(port=9464)   # GET /metrics

Subsystems surfaced (each keeps its legacy ``stats()`` as a view):
``dispatch.*`` (op counts, jit pair compiles), ``fusion.*`` (chains,
cache hits, flush reasons), ``collectives.*`` / ``watchdog.*`` (span
latency, bytes, timeouts), ``store.*`` (op retries), ``checkpoint.*``
(saves, bytes, seconds, corrupt_skipped), ``serving.*`` (admissions,
token latency, queue depth), ``memory.*``, ``faults.*``, ``step.*``.
"""
from __future__ import annotations

from . import metrics, timeline  # noqa: F401
from .metrics import (  # noqa: F401
    Counter, Gauge, Histogram, Registry, Scope, DEFAULT_BUCKETS,
    counter, gauge, histogram, scope, default_registry, enabled,
    register_collector, snapshot, render_prometheus,
)
from .timeline import StepTimer  # noqa: F401
from . import flight  # noqa: F401  (after metrics/timeline: it uses both)
from . import host  # noqa: F401

host.install()

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Scope",
    "DEFAULT_BUCKETS", "counter", "gauge", "histogram", "scope",
    "default_registry", "enabled", "register_collector", "snapshot",
    "render_prometheus", "StepTimer", "metrics", "timeline", "flight", "host",
    "start_metrics_server",
]


def start_metrics_server(port: int = 0, host: str = "127.0.0.1",
                         registry=None):
    """Serve ``/metrics`` (Prometheus text) + ``/metrics.json`` on a
    stdlib HTTP daemon thread; returns a handle with ``.url`` and
    ``.close()``. Lazy import keeps ``http.server`` off the package
    import path."""
    from .http import start_metrics_server as _start
    return _start(port=port, host=host, registry=registry)
