"""Process-wide metrics runtime: Counter / Gauge / Histogram + Registry.

The reference treats observability as a first-class plane (HostTracer
spans, ``memory/stats.h`` current/peak counters, ``comm_task_manager``
per-collective attribution). paddle_tpu grew the same signals as five
incompatible ad-hoc ``stats()`` dicts; this module is the uniform layer
they all migrate onto:

- **Instruments** are lock-cheap and kill-switchable: every mutation
  first checks ``FLAGS_metrics`` (one cached attribute read) and
  returns immediately when metrics are off.
- **Labels** ride as kwargs (``counter.inc(op="add")``); label values
  keep their Python type internally (the fusion chain-length view needs
  int keys back) and stringify only at exposition time.
- **Registry** holds instruments by dotted name (``serving.admitted_total``)
  plus *collectors* — zero-hot-path-cost callbacks polled only at
  ``snapshot()`` / ``render_prometheus()`` time, used to surface
  pre-existing counters (op dispatch counts, fault-injection tallies,
  memory watermarks) without adding a single instruction to their hot
  paths.
- ``snapshot()`` returns one nested JSON-able dict; ``render_prometheus()``
  emits Prometheus text exposition format (v0.0.4).

This module depends only on ``core.flags`` and stdlib so any subsystem
(including ``core.autograd``'s dispatch funnel) can import it at module
load without cycles.
"""
from __future__ import annotations

import bisect
import math
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core.flags import _registry as _flag_registry  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "Scope",
    "default_registry", "enabled", "flag_info", "counter", "gauge",
    "histogram", "scope", "register_collector", "snapshot",
    "render_prometheus", "DEFAULT_BUCKETS",
]

# Fixed log-spaced buckets: half-decade steps over 1µs .. 100s — wide
# enough for µs-scale dispatch and 10s-scale checkpoint persists with
# one shared shape (fixed buckets keep every Histogram cell a flat
# int list, no per-observation allocation).
DEFAULT_BUCKETS: Tuple[float, ...] = tuple(
    round(10.0 ** (e / 2.0), 12) for e in range(-12, 5))


_metrics_flag = None  # resolved _FlagInfo (registry identity is stable)


def enabled() -> bool:
    """FLAGS_metrics value via a cached flag-info object — the same
    one-attribute-read pattern autograd uses for check_nan_inf."""
    global _metrics_flag
    if _metrics_flag is None:
        _metrics_flag = _flag_registry["metrics"]
    return bool(_metrics_flag.value)


def flag_info():
    """The live FLAGS_metrics registry entry (identity is stable): hot
    paths cache it once and branch on ``.value`` inline — the cheapest
    legal kill-switch check (one global + one attribute read)."""
    global _metrics_flag
    if _metrics_flag is None:
        _metrics_flag = _flag_registry["metrics"]
    return _metrics_flag


def _label_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, Any], ...]:
    if len(labels) == 1:  # the common case: one (k, v) pair, no sort
        return tuple(labels.items())
    return tuple(sorted(labels.items()))


class _Instrument:
    """Shared cell bookkeeping: () is the unlabeled cell, labeled cells
    key on sorted (name, value) tuples."""

    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        from ..analysis.locks import make_lock
        self._lock = make_lock(f"metrics.instrument:{name}")
        self._cells: Dict[Tuple, Any] = {}

    # -- introspection ---------------------------------------------------
    def series(self) -> Dict[Tuple, Any]:
        """{label-key tuple: cell snapshot} — () = unlabeled."""
        with self._lock:
            return dict(self._cells)

    def reset(self) -> None:
        with self._lock:
            self._cells.clear()

    def __repr__(self):
        return f"{type(self).__name__}({self.name!r})"


class Counter(_Instrument):
    """Monotonic counter. ``inc(n)`` unlabeled, ``inc(op="add")``
    labeled; mixing both works (separate cells).

    The unlabeled cell is the plain attribute ``_v`` so measured hot
    paths (the op-dispatch funnel) can count with ONE guarded attribute
    add — ``if flag.value: counter._v += 1`` — instead of a method call
    + lock (~1µs, >5% of a cached CPU dispatch). ``_v += n`` under the
    GIL can lose an increment across racing threads; telemetry
    tolerates that, the dispatch budget does not tolerate the lock."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._v = 0  # unlabeled fast cell (see class docstring)

    def inc(self, n: float = 1, **labels) -> None:
        if not enabled():
            return
        if not labels:
            self._v += n  # lock-free on purpose (class docstring)
            return
        key = _label_key(labels)
        with self._lock:
            self._cells[key] = self._cells.get(key, 0) + n

    def value(self, **labels):
        if not labels:
            return self._v
        key = _label_key(labels)
        with self._lock:
            return self._cells.get(key, 0)

    def series(self) -> Dict[Tuple, Any]:
        with self._lock:
            out = dict(self._cells)
        if self._v or not out:
            out[()] = self._v
        return out

    def reset(self) -> None:
        with self._lock:
            self._cells.clear()
            self._v = 0


class Gauge(_Instrument):
    """Point-in-time value; ``set_function`` installs a pull callback
    evaluated only at snapshot/exposition time (queue depths, cache
    sizes — zero hot-path cost)."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._fn: Optional[Callable[[], float]] = None

    def set(self, v: float, **labels) -> None:
        if not enabled():
            return
        key = _label_key(labels) if labels else ()
        with self._lock:
            self._cells[key] = v

    def inc(self, n: float = 1, **labels) -> None:
        if not enabled():
            return
        key = _label_key(labels) if labels else ()
        with self._lock:
            self._cells[key] = self._cells.get(key, 0) + n

    def dec(self, n: float = 1, **labels) -> None:
        self.inc(-n, **labels)

    def set_function(self, fn: Callable[[], float]) -> None:
        self._fn = fn

    def value(self, **labels):
        if self._fn is not None and not labels:
            try:
                return self._fn()
            except Exception:  # noqa: BLE001 — a dead pull fn reads 0
                return 0
        key = _label_key(labels) if labels else ()
        with self._lock:
            return self._cells.get(key, 0)

    def series(self) -> Dict[Tuple, Any]:
        out = super().series()
        if self._fn is not None and () not in out:
            out[()] = self.value()
        return out


class _HistCell:
    __slots__ = ("counts", "sum", "count", "min", "max")

    def __init__(self, nbuckets: int):
        self.counts = [0] * (nbuckets + 1)  # +1 = the +Inf bucket
        self.sum = 0.0
        self.count = 0
        self.min = math.inf
        self.max = -math.inf


class Histogram(_Instrument):
    """Fixed-bucket histogram (log-spaced by default). ``observe(v)``
    is one bisect + three adds under the lock."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "", buckets=None):
        super().__init__(name, help)
        self.buckets: Tuple[float, ...] = tuple(
            sorted(buckets)) if buckets else DEFAULT_BUCKETS

    def observe(self, v: float, **labels) -> None:
        if not enabled():
            return
        v = float(v)
        key = _label_key(labels) if labels else ()
        i = bisect.bisect_left(self.buckets, v)
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells[key] = _HistCell(len(self.buckets))
            cell.counts[i] += 1
            cell.sum += v
            cell.count += 1
            if v < cell.min:
                cell.min = v
            if v > cell.max:
                cell.max = v

    # -- views -----------------------------------------------------------
    def _cell_dict(self, cell: _HistCell) -> Dict[str, Any]:
        nonzero = {}
        for le, c in zip(self.buckets, cell.counts):
            if c:
                nonzero[_fmt_num(le)] = c
        if cell.counts[-1]:
            nonzero["+Inf"] = cell.counts[-1]
        return {
            "count": cell.count,
            "sum": round(cell.sum, 9),
            "avg": round(cell.sum / cell.count, 9) if cell.count else 0.0,
            "min": cell.min if cell.count else 0.0,
            "max": cell.max if cell.count else 0.0,
            "buckets": nonzero,  # per-bucket (not cumulative) counts
        }

    def value(self, **labels) -> Dict[str, Any]:
        key = _label_key(labels) if labels else ()
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                return {"count": 0, "sum": 0.0, "avg": 0.0,
                        "min": 0.0, "max": 0.0, "buckets": {}}
            return self._cell_dict(cell)


def _fmt_num(v) -> str:
    """Compact numeric literal valid in both exposition values and
    JSON-ish snapshots (1e-06, 0.25, 3)."""
    if isinstance(v, float) and v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v) if not isinstance(v, float) else format(v, "g")


def _escape_help(s: str) -> str:
    return s.replace("\\", "\\\\").replace("\n", "\\n")


def _escape_label(s: str) -> str:
    return (s.replace("\\", "\\\\").replace("\n", "\\n")
            .replace('"', '\\"'))


def _sanitize(name: str) -> str:
    out = []
    for i, ch in enumerate(name):
        if ch.isalnum() and (i > 0 or not ch.isdigit()) or ch in "_:":
            out.append(ch)
        else:
            out.append("_")
    return "".join(out)


def _labels_str(key: Tuple[Tuple[str, Any], ...]) -> str:
    if not key:
        return ""
    inner = ",".join(
        f'{_sanitize(k)}="{_escape_label(str(v))}"' for k, v in key)
    return "{" + inner + "}"


class Scope:
    """Named-scope instrument factory: ``scope("serving").counter("x")``
    creates/fetches ``serving.x`` in the parent registry."""

    def __init__(self, registry: "Registry", prefix: str):
        self._registry = registry
        self._prefix = prefix.rstrip(".")

    def _full(self, name: str) -> str:
        return f"{self._prefix}.{name}"

    def counter(self, name: str, help: str = "") -> Counter:
        return self._registry.counter(self._full(name), help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._registry.gauge(self._full(name), help)

    def histogram(self, name: str, help: str = "",
                  buckets=None) -> Histogram:
        return self._registry.histogram(self._full(name), help, buckets)

    def scope(self, prefix: str) -> "Scope":
        return Scope(self._registry, self._full(prefix))


class Registry:
    """Central instrument table + snapshot-time collectors.

    Instrument creation is get-or-create by dotted name (idempotent —
    re-imports and multiple component instances share one instrument);
    asking for an existing name with a different type raises.
    """

    def __init__(self):
        from ..analysis.locks import make_lock
        self._lock = make_lock("metrics.registry", rlock=True)
        self._instruments: "OrderedDict[str, _Instrument]" = OrderedDict()
        self._collectors: "OrderedDict[str, Callable]" = OrderedDict()

    # -- creation --------------------------------------------------------
    def _get_or_create(self, cls, name: str, help: str, **kw):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is not None:
                if not isinstance(inst, cls):
                    raise TypeError(
                        f"metric {name!r} already registered as "
                        f"{type(inst).__name__}, requested {cls.__name__}")
                return inst
            inst = cls(name, help, **kw)
            self._instruments[name] = inst
            return inst

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets=None) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def scope(self, prefix: str) -> Scope:
        return Scope(self, prefix)

    def register_collector(self, name: str, fn: Callable,
                           label: str = "key") -> None:
        """``fn() -> {dotted_name: number | {label_value: number}}``,
        polled only at snapshot/exposition time; ``label`` names the
        label of a dict's values in the exposition. Re-registering a name
        replaces the callback (module reload safety)."""
        with self._lock:
            self._collectors[name] = (fn, label)

    def get(self, name: str) -> Optional[_Instrument]:
        with self._lock:
            return self._instruments.get(name)

    def instruments(self) -> List[_Instrument]:
        with self._lock:
            return list(self._instruments.values())

    def reset(self) -> None:
        """Zero every instrument cell (collectors are external views and
        keep their own state). Test/bench convenience."""
        for inst in self.instruments():
            inst.reset()

    # -- collection ------------------------------------------------------
    def _collected(self, labels: Optional[Dict[str, str]] = None
                   ) -> Dict[str, Any]:
        """Every collector's view, merged; ``labels`` (if given) gets each
        name's label name."""
        out: Dict[str, Any] = {}
        with self._lock:
            items = list(self._collectors.values())
        for fn, label in items:
            try:
                part = fn() or {}
            except Exception:  # noqa: BLE001 — one bad view can't kill all
                continue
            out.update(part)
            if labels is not None:
                labels.update(dict.fromkeys(part, label))
        return out

    # -- snapshot --------------------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """One nested dict over every instrument + collector: dotted
        names split into sub-dicts (``serving.admitted_total`` lands at
        ``snap["serving"]["admitted_total"]``)."""
        flat: Dict[str, Any] = {}
        for inst in self.instruments():
            series = inst.series()
            if isinstance(inst, Histogram):
                if not series:
                    flat[inst.name] = inst.value()
                elif tuple(series) == ((),):
                    flat[inst.name] = inst.value()
                else:
                    flat[inst.name] = {
                        (key[0][1] if len(key) == 1 else
                         ",".join(f"{k}={v}" for k, v in key)):
                        inst._cell_dict(cell)
                        for key, cell in series.items()}
            else:
                if not series:
                    flat[inst.name] = (inst.value()
                                       if isinstance(inst, Gauge) else 0)
                elif tuple(series) == ((),):
                    flat[inst.name] = series[()]
                else:
                    out = {}
                    for key, v in series.items():
                        if key == ():
                            out["_total"] = v
                        elif len(key) == 1:
                            out[key[0][1]] = v
                        else:
                            out[",".join(f"{k}={lv}" for k, lv in key)] = v
                    flat[inst.name] = out
        flat.update(self._collected())
        nested: Dict[str, Any] = {}
        for name, v in flat.items():
            parts = name.split(".")
            d = nested
            for p in parts[:-1]:
                nxt = d.get(p)
                if not isinstance(nxt, dict):
                    nxt = d[p] = {}
                d = nxt
            d[parts[-1]] = v
        return nested

    # -- exposition ------------------------------------------------------
    def render_prometheus(self) -> str:
        """Prometheus text exposition format v0.0.4."""
        lines: List[str] = []
        for inst in self.instruments():
            mname = _sanitize(inst.name.replace(".", "_"))
            if inst.help:
                lines.append(f"# HELP {mname} {_escape_help(inst.help)}")
            lines.append(f"# TYPE {mname} {inst.kind}")
            series = inst.series()
            if isinstance(inst, Histogram):
                if not series:
                    series = {(): _HistCell(len(inst.buckets))}
                for key, cell in series.items():
                    cum = 0
                    for le, c in zip(inst.buckets, cell.counts):
                        cum += c
                        lk = key + (("le", _fmt_num(le)),)
                        lines.append(
                            f"{mname}_bucket{_labels_str(lk)} {cum}")
                    cum += cell.counts[-1]
                    lk = key + (("le", "+Inf"),)
                    lines.append(f"{mname}_bucket{_labels_str(lk)} {cum}")
                    lines.append(
                        f"{mname}_sum{_labels_str(key)} "
                        f"{_fmt_num(float(cell.sum))}")
                    lines.append(
                        f"{mname}_count{_labels_str(key)} {cell.count}")
            else:
                if not series:
                    series = {(): inst.value()
                              if isinstance(inst, Gauge) else 0}
                for key, v in series.items():
                    lines.append(
                        f"{mname}{_labels_str(key)} "
                        f"{_fmt_num(float(v))}")
        # collectors render as untyped counters
        labels: Dict[str, str] = {}
        for name, v in sorted(self._collected(labels).items()):
            mname = _sanitize(name.replace(".", "_"))
            lines.append(f"# TYPE {mname} counter")
            if isinstance(v, dict):
                # one label, named by the collector ("key" by default)
                label = _sanitize(labels[name])
                for lv, n in sorted(v.items(), key=lambda kv: str(kv[0])):
                    lines.append(
                        f'{mname}{{{label}="{_escape_label(str(lv))}"}} '
                        f"{_fmt_num(float(n))}")
            else:
                lines.append(f"{mname} {_fmt_num(float(v))}")
        return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# default (process-wide) registry + module-level conveniences
# ---------------------------------------------------------------------------

_default = Registry()


def default_registry() -> Registry:
    return _default


def counter(name: str, help: str = "") -> Counter:
    return _default.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return _default.gauge(name, help)


def histogram(name: str, help: str = "", buckets=None) -> Histogram:
    return _default.histogram(name, help, buckets)


def scope(prefix: str) -> Scope:
    return _default.scope(prefix)


def register_collector(name: str, fn: Callable, label: str = "key") -> None:
    _default.register_collector(name, fn, label)


def snapshot() -> Dict[str, Any]:
    return _default.snapshot()


def render_prometheus() -> str:
    return _default.render_prometheus()
