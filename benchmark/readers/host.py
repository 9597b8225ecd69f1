"""Readers of the host's own pauses: the program's `host.*` spans
(`paddle_tpu/observability/host.py`: `host.gc`, one a Python garbage
collection, on the thread that collected), laid against the device's idle
time and the traced window.

`lib/program_spans.py` keeps only the scheduler's prefixes, so these spans
are read here: the run's `.xplane.pb` is parsed once for both readers and
kept under the key `program_spans.load` uses. Spans of every thread count:
a collection on the client thread holds the loop's fetch as surely as one on
the loop's own. Each reader returns None where the trace holds no such span,
as a parent commit's does.
"""
from __future__ import annotations

import os

from benchmark.lib import program_spans, trace_reduce

PREFIX = "host."

_loaded = {}        # (file, mtime, devices) -> what `_load` returned


def read_host_spans(pd) -> list:
    """(start_ns, end_ns, name) of every host event named `host.*`."""
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


def reduce_host(devices, bench_spans, spans, n_devices=1):
    """The window, each used device's idle gaps in it, and the `host.*`
    spans; None without a window or without such a span."""
    window = [s for s in bench_spans if s[2] == trace_reduce.WINDOW_SPAN]
    if not spans or not window:
        return None
    lo, hi = window[0][0], window[0][1]
    gaps = []
    for ops in devices[:n_devices] or [[]]:
        busy = trace_reduce.union((max(s, lo), min(e, hi)) for s, e, *_ in ops
                                  if e > lo and s < hi)
        gaps.append(trace_reduce.gaps_of(busy, lo, hi))
    return {"window_ns": (lo, hi), "gaps": gaps, "spans": spans}


def _load(obs):
    if not obs["trace"]:
        return None
    try:
        path = trace_reduce.find_xplane(program_spans.TRACE_DIR)
    except FileNotFoundError:
        return None
    n = obs["cell"]["chips"]
    key = (path, os.path.getmtime(path), n)
    if key not in _loaded:
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        devices, bench_spans = trace_reduce.read_planes(pd)
        _loaded.clear()
        _loaded[key] = reduce_host(devices, bench_spans, read_host_spans(pd), n)
    return _loaded[key]


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_under(obs, prefix):
    """Device idle time of the window that some span named `prefix*` covers
    (on any thread, overlapping spans once), a mean over the cell's devices,
    as a share of the window. Never more than the device's idle share."""
    red = _load(obs)
    spans = red and [(s, e) for s, e, name in red["spans"]
                     if name.startswith(prefix)]
    if not spans:
        return None
    lo, hi = red["window_ns"]
    cover = trace_reduce.union((max(s, lo), min(e, hi)) for s, e in spans
                               if e > lo and s < hi)
    idle = sum(_overlap(g, cover) for g in red["gaps"]) / len(red["gaps"])
    return 100.0 * idle / (hi - lo)


def spans_ms(obs, prefix):
    """Summed time of the spans named `prefix*` inside the window, all
    threads, whether or not the device waited; 0.0 where the trace has such
    spans and none falls in the window."""
    red = _load(obs)
    spans = red and [(s, e) for s, e, name in red["spans"]
                     if name.startswith(prefix)]
    if not spans:
        return None
    lo, hi = red["window_ns"]
    return 1e-6 * sum(max(0, min(e, hi) - max(s, lo)) for s, e in spans)
