"""The program's own spans in a traced run, laid against the device's idle
time.

`paddle_tpu.profiler.RecordEvent` writes `serving.*`, `train.*` and
`jit.compile` spans into the `/host:CPU` plane of the same `.xplane.pb` as the
device operations, with its keyword arguments as the event's stats.
`trace_reduce.read_planes` keeps only the runner's `bench.*` spans, so the
readers of `readers/spans.py` open the trace again through this module, once
per run: `run.py` has reduced it and not yet removed it when readers run.

Idle time is apportioned exactly. Every idle nanosecond of the window goes to
the innermost program span that covers it (the span's self time: the latest
opened of the spans open at that instant), or to no span. The parts therefore
add up to the window's idle time, to the nanosecond.

A trace of a program without such spans (a parent commit) yields `None`
everywhere: its readers report nothing.
"""
from __future__ import annotations

import os

from benchmark.lib import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")     # where run.py writes it
PREFIXES = ("serving.", "train.", "jit.")

_loaded = {}        # (file, mtime, devices) -> what `load` returned


def read_spans(pd) -> list:
    """(start_ns, end_ns, name, stats) of every host event whose name starts
    with one of `PREFIXES`, sorted by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats)))
    out.sort(key=lambda s: (s[0], -s[1]))
    return out


def owner_segments(spans) -> list:
    """Sorted, non-overlapping (start, end, name): each instant that some
    span covers, given to the innermost one, which is the latest opened of
    those open then (of two opened together, the one that closes first).
    Spans of several threads need not nest; the rule still names one."""
    marks = []
    for i, (s, e, *_rest) in enumerate(spans):
        if e > s:
            marks.append((s, 1, i))
            marks.append((e, 0, i))
    marks.sort()                      # at one instant, closes before opens
    out, open_, at = [], set(), None
    for t, opens, i in marks:
        if open_ and t > at:
            top = max(open_, key=lambda j: (spans[j][0], -spans[j][1]))
            name = spans[top][2]
            if out and out[-1][2] == name and out[-1][1] == at:
                out[-1][1] = t
            else:
                out.append([at, t, name])
        at = t
        if opens:
            open_.add(i)
        else:
            open_.discard(i)
    return [tuple(seg) for seg in out]


def apportion(gaps, spans) -> dict:
    """Nanoseconds of the sorted, disjoint `gaps` by the name of the
    innermost span that covers them; `None` keys what no span covers."""
    segs = owner_segments(spans)
    by, k = {}, 0
    for g0, g1 in gaps:
        at = g0
        while k < len(segs) and segs[k][1] <= g0:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < g1:
            s0, s1, name = segs[j]
            lo, hi = max(s0, g0), min(s1, g1)
            if lo > at:
                by[None] = by.get(None, 0.0) + (lo - at)
            by[name] = by.get(name, 0.0) + (hi - lo)
            at = hi
            j += 1
        if g1 > at:
            by[None] = by.get(None, 0.0) + (g1 - at)
    return by


def reduce_spans(devices, bench_spans, spans, n_devices=1):
    """The window, its idle nanoseconds by owning span (a mean over the
    devices), and the program spans that lie wholly inside it. None where
    the trace holds no program span or no window."""
    window = [s for s in bench_spans if s[2] == trace_reduce.WINDOW_SPAN]
    if not spans or not window:
        return None
    lo, hi = window[0][0], window[0][1]
    used = devices[:n_devices] or [[]]
    idle = {}
    for ops in used:
        busy = trace_reduce.union((max(s, lo), min(e, hi)) for s, e, *_ in ops
                                  if e > lo and s < hi)
        for name, ns in apportion(trace_reduce.gaps_of(busy, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + ns / len(used)
    return {"window_ns": (lo, hi), "idle_ns": idle,
            "spans": [s for s in spans if s[0] >= lo and s[1] <= hi]}


def load(trace_dir=TRACE_DIR, n_devices=1):
    """`reduce_spans` of the newest trace under `trace_dir`, parsed once."""
    try:
        path = trace_reduce.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path), n_devices)
    if key not in _loaded:
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        devices, bench_spans = trace_reduce.read_planes(pd)
        _loaded.clear()
        _loaded[key] = reduce_spans(devices, bench_spans, read_spans(pd),
                                    n_devices)
    return _loaded[key]
