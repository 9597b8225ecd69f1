"""From a profiler trace (`.xplane.pb`) to what the per-layer readers need:
device busy intervals and their union, the idle share, time by operation, and
the idle gaps named by what the host was doing in them.

Read with `jax.profiler.ProfileData`, nothing else. A device is a plane named
`/device:TPU:<n>`; its operations are the events of its `XLA Ops` line. (A
trace recorded on the CPU has no such plane: there the events that carry an
`hlo_op` stat on the host's threads stand in, so the reduction can be tested
here. A CPU trace never yields a device metric: run.py reads none in a
rehearsal.) Host spans are `jax.profiler.TraceAnnotation`s; the one named
`bench.window` bounds the traced window, other `bench.*` spans name the gaps.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
# operations that only enclose others on the ops line: their time is their
# children's, so they are left out of the time by operation (not of the union)
_CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_dir(trace_dir: str, n_devices: int = 1) -> dict:
    import jax
    return reduce_profile(
        jax.profiler.ProfileData.from_file(find_xplane(trace_dir)), n_devices)


def _text_stats(ev) -> str:
    return " ".join(str(v) for _, v in ev.stats if isinstance(v, str))


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def short_name(text: str) -> str:
    """On the TPU an operation's event is named by its whole HLO line. Keep
    the instruction's name, its opcode (a custom call's target with it) and
    the shape of its first output: enough to tell operations apart in a
    breakdown, short enough for a ledger line."""
    if " = " not in text:
        return text[:120]
    name, rest = text.split(" = ", 1)
    shape = _SHAPE.search(rest)
    if rest.startswith("("):            # a tuple of outputs: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        after = rest[i + 1:].lstrip()
        many = "+"
    else:
        after = rest.split(" ", 1)[1] if " " in rest else ""
        many = ""
    opcode = after.split("(", 1)[0].strip() or "?"
    target = _TARGET.search(rest)
    out = f"{name.lstrip('%')} {opcode} {shape.group(0) if shape else ''}{many}"
    return (out + (f" {target.group(1)}" if target else "")).strip()[:120]


def read_planes(pd) -> tuple:
    """(device op events per device, host spans) as plain tuples:
    ops (start_ns, end_ns, name, text of its string stats),
    spans (start_ns, end_ns, name)."""
    devices, host_ops, spans = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name,
                            _text_stats(e)) for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
                    elif e.duration_ns > 0 and any(
                            k == "hlo_op" for k, _ in e.stats):
                        host_ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                         e.name, _text_stats(e)))
    if not devices and host_ops:
        devices = [host_ops]
    return devices, spans


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps_of(busy: list, lo: float, hi: float) -> list:
    """The complement of merged intervals `busy` within [lo, hi)."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def name_gap(gap, spans, default: str) -> str:
    """The host span that covers most of a gap."""
    best, best_cover = default, 0.0
    for s, e, name in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce_events(devices: list, spans: list, n_devices: int = 1,
                  unattributed: str = "host, unattributed") -> dict:
    window = [s for s in spans if s[2] == WINDOW_SPAN]
    every = [(s, e) for ops in devices for s, e, *_ in ops]
    if window:
        lo, hi = window[0][0], window[0][1]
    elif every:
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    else:
        raise ValueError("the trace holds no device operation and no window")
    named = [s for s in spans if s[2] != WINDOW_SPAN]
    busy_ns, by_op, gap_by, n_ops = 0.0, {}, {}, 0
    for ops in devices[:n_devices] or [[]]:
        clipped = [(max(s, lo), min(e, hi)) for s, e, *_ in ops
                   if e > lo and s < hi]
        merged = union(clipped)
        busy_ns += sum(e - s for s, e in merged)
        for gap in gaps_of(merged, lo, hi):
            label = name_gap(gap, named, unattributed)
            gap_by[label] = gap_by.get(label, 0.0) + (gap[1] - gap[0])
        for s, e, name, text in ops:
            if e <= lo or s >= hi:
                continue
            n_ops += 1
            base = name.lstrip("%").split(".")[0].split(" ")[0]
            if base in _CONTAINERS:
                continue
            # `text` is what a pattern may match besides the short name: the
            # event's string stats, never the HLO line's operands (a consumer
            # of a kernel's output names the kernel there)
            rec = by_op.setdefault(short_name(name), {
                "seconds": 0.0, "count": 0, "text": text})
            rec["seconds"] += (min(e, hi) - max(s, lo)) * 1e-9
            rec["count"] += 1
    n = max(min(n_devices, len(devices)), 1)
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns * 1e-9 / n
    top = sorted(by_op.items(), key=lambda kv: -kv[1]["seconds"])
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "n_devices": n, "n_ops": n_ops, "ops": by_op,
            "top_ops": [[k, v["seconds"] / n] for k, v in top[:10]],
            "idle_gaps": [[k, v * 1e-9 / n] for k, v in
                          sorted(gap_by.items(), key=lambda kv: -kv[1])[:10]],
            "window_ns": (lo, hi)}


def reduce_profile(pd, n_devices: int = 1) -> dict:
    devices, spans = read_planes(pd)
    return reduce_events(devices, spans, n_devices)


def op_seconds(reduced: dict, pattern: str) -> tuple:
    """(seconds, events) of the operations whose own name (as `short_name`
    gives it) or string stats hold `pattern`, per device."""
    hit = [v for k, v in reduced["ops"].items()
           if pattern in k or pattern in v["text"]]
    n = reduced["n_devices"]
    return sum(v["seconds"] for v in hit) / n, sum(v["count"] for v in hit) / n


def describe(pd, limit: int = 12) -> str:
    """The shape of a trace, for a look by hand: planes, lines, first events."""
    out = []
    for plane in pd.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            out.append(f"  LINE {line.name!r}: {len(evs)} events, "
                       f"{lo:.0f}..{hi:.0f} ns")
            if plane.name.startswith("/device:") or any(
                    e.name.startswith("bench.") for e in evs[:2000]):
                for e in evs[:limit]:
                    out.append(f"      {e.name!r} start {e.start_ns:.0f} "
                               f"dur {e.duration_ns:.0f} {dict(e.stats)}")
    return "\n".join(out)
