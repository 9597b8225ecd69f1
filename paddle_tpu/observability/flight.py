"""Flight recorder: the always-on black-box event journal.

Metrics (``observability.metrics``) answer "how much" and the analysis
plane answers "where will capture break"; this module answers **"what
just happened"** when a step hangs, a request dies, or the process
crashes. It keeps a fixed-capacity ring of structured events — host
monotonic-µs timestamp on the same timebase the step timeline uses,
category, name, recording thread, an optional ``trace_id`` and a small
attrs dict — that every subsystem appends into from its existing
observer seams: fusion chain flushes and program compiles, device→host
syncs, fused-optimizer donations and fallbacks, whole-step jit builds,
SOT capture lifecycle events (``sot`` category: segment_compile /
capture_compile / guard_miss / retrace / fallback-by-reason — a
production guard-miss storm reads straight out of a dump), eager
collectives (op/bytes/duration) plus the captured distributed step's
bucketed gradient sync (``collective`` category: one ``grad_bucket``
event per bucket per step — index/payload bytes/grad count, the T3
overlap-efficiency numerator — and a ``dist_step`` summary carrying
the step's host dispatch duration), checkpoint save/restore/
corruption-fallback, elastic membership transitions, watchdog timeouts
and the per-request serving lifecycle (submit → queued → admitted →
[prefill_chunk per turn at the chunked prefill, with the prompt tokens
``start``/``tokens`` it ran and its ``bucket`` → prefilled] → decode →
finished/expired/rejected, keyed by ``trace_id``), plus the paged KV block pool's allocator
(``block_alloc`` / ``block_free`` / ``block_exhausted`` — a pool
running dry reads straight out of a dump next to the starved
requests' queue time) and its prefix-sharing radix cache
(``prefix_hit`` with the tokens a request's admission skipped,
``prefix_cow`` for each boundary-block copy-on-write clone,
``prefix_evict`` when LRU pressure reclaims a cached prefix block —
how much prefill the tree absorbed, and what it cost, per request),
the hot-start plane (``warmup`` category:
cache_configured / bundle_exported / bundle_failed-by-reason /
prewarm summary / per-program captured_step+serving_program replays
— a boot that compiled fresh instead of hitting the executable cache
reads straight out of its dump), zero-downtime weight hot-swaps
(``serving`` ``swap_begin`` / ``swap_end`` pairs bracketing the step
boundary the new weights installed at, with the in-flight count and
the ok/rejected verdict), and the self-healing serving plane:
``supervisor`` events (attached / loop_death / recover — per
recovered request, with its committed-token count / quarantine with
reason=poison / restart with backoff + streak / give_up /
abort_drain) journal every decode-loop crash-or-stall recovery,
``admission`` events (engage_/release_brownout_spec,
engage_/release_brownout_prefill, engage_/release_shed,
shed / shed_static / deadline_reject / release_clear) journal every
adaptive-admission decision with the evidence it was decided on, and
``rollout`` events (begin / canary_probe with the divergence /
stage_ok / rollback / halted-by-reason / end) journal a canary weight
rollout stage by stage — a bad deploy reads straight out of the
canary's dump. The fleet fabric journals as ``fleet`` events
(router_up / submit / dispatch with replica+epoch / finished/failed/
shed terminals — exactly one per request / replica_dead with reason /
failover with the committed-token count / stale_drop — a fenced
zombie's late answer / quarantined / resurrect_attempt / resurrected /
degraded): a replica SIGKILL and its recovery read as one trace.

Recording is on by default (``FLAGS_flight_recorder``) because an
append costs the same class of work as a ``Counter`` bump — one cached
flag read, one clock read, one tuple, one GIL-atomic ``deque.append``.

Crash forensics: :func:`dump` freezes the ring as a JSONL file (header
line + one event per line) and best-effort merges the host-tracer
chrome trace next to it (``<dump>.trace.json`` via
``profiler.export_chrome_tracing``, which also embeds these events as
instant marks) so ONE artifact carries spans, metric series and the
last-N event trail. Triggers: explicit ``dump()``, the unhandled
exception hooks and optional signal handler installed by
:func:`install_crash_hooks`, and watchdog timeouts
(``distributed/watchdog.py`` dumps automatically). Every dump bumps
``observability.dumps_total{trigger=...}``.

Reading a dump: ``python -m paddle_tpu.observability --flight [path]``.
"""
from __future__ import annotations

import json
import os
import signal as _signal
import sys
import tempfile
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from ..core.flags import _registry as _flag_registry, define_flag
from . import metrics as _metrics
from .clock import now_us as _now_us

__all__ = [
    "record", "enabled", "events", "clear", "dropped", "appended",
    "dump", "last_dump_path", "find_dumps", "load_dump",
    "render_events", "chrome_events", "install_crash_hooks",
    "uninstall_crash_hooks", "dump_dir",
]

define_flag(
    "flight_recorder", True,
    "Always-on black-box event journal (observability.flight): a "
    "fixed-capacity ring of structured events (fusion flushes, host "
    "syncs, collectives, checkpoint/elastic/serving lifecycle) dumped "
    "as crash forensics on unhandled exceptions, watchdog timeouts, "
    "signals or flight.dump(). 0 disables recording (dump() still "
    "writes whatever the ring holds)")
define_flag(
    "flight_recorder_capacity", 4096,
    "Event capacity of the flight-recorder ring; the oldest events are "
    "evicted first (a dump carries the LAST N events)")
define_flag(
    "flight_dump_dir", "",
    "Directory flight-recorder dumps are written to; empty (default) "
    "uses the system temp dir")

_flag = _flag_registry["flight_recorder"]
_cap_flag = _flag_registry["flight_recorder_capacity"]
_dir_flag = _flag_registry["flight_dump_dir"]


def _make_lock():
    from ..analysis.locks import make_lock
    return make_lock("observability.flight")


_lock = _make_lock()

_M_dumps = _metrics.counter(
    "observability.dumps_total",
    "Flight-recorder dumps written, by trigger "
    "(explicit/exception/signal/watchdog)")


def _capacity() -> int:
    try:
        return max(int(_cap_flag.value), 16)
    except (TypeError, ValueError):
        return 4096


# event tuples: (ts_us, category, name, thread_ident, trace_id, attrs)
_ring: deque = deque(maxlen=_capacity())
_appended_n = 0
_dump_seq = 0
_last_dump: Optional[str] = None


def enabled() -> bool:
    """FLAGS_flight_recorder via the cached flag-info object — the same
    one-attribute-read kill switch the metrics plane uses."""
    return bool(_flag.value)


def _rebuild_ring() -> deque:
    """Capacity flag changed: rebuild the ring keeping the newest tail.
    Cold path (only on a flag transition)."""
    global _ring
    cap = _capacity()
    with _lock:
        if _ring.maxlen != cap:
            _ring = deque(_ring, maxlen=cap)
        return _ring


def record(category: str, name: str, trace_id: Optional[str] = None,
           **attrs) -> None:
    """Append one event to the ring. Hot-path contract: one cached flag
    read, one clock read, one tuple, one GIL-atomic deque append — no
    lock, no allocation beyond the event itself (losing an event to a
    racing capacity rebuild is acceptable; a black box is best-effort
    by definition)."""
    if not _flag.value:
        return
    global _appended_n
    ring = _ring
    if ring.maxlen != _cap_flag.value and ring.maxlen != _capacity():
        ring = _rebuild_ring()
    ring.append((_now_us(), category, name, threading.get_ident(),
                 trace_id, attrs or None))
    _appended_n += 1


def appended() -> int:
    """Events recorded since process start (including evicted ones)."""
    return _appended_n


def dropped() -> int:
    """Events evicted from the ring so far."""
    return max(0, _appended_n - len(_ring))


def clear() -> None:
    """Empty the ring and reset the appended tally (test/bench hook)."""
    global _appended_n
    with _lock:
        _ring.clear()
        _appended_n = 0


def _discard_events(pred) -> int:
    """Remove ring events matching ``pred(event_tuple)`` — internal,
    used by the analysis self-check to take its SYNTHETIC crash events
    back out of the production black box without dropping the real
    events recorded around them. An append racing the rebuild may be
    lost (the ring is best-effort by contract). Returns the count
    removed."""
    global _ring
    with _lock:
        kept = [ev for ev in _ring if not pred(ev)]
        removed = len(_ring) - len(kept)
        if removed:
            _ring = deque(kept, maxlen=_ring.maxlen)
    return removed


def _thread_names() -> Dict[int, str]:
    return {t.ident: t.name for t in threading.enumerate()
            if t.ident is not None}


def _to_dict(ev: Tuple, names: Optional[Dict[int, str]] = None
             ) -> Dict[str, Any]:
    ts, cat, name, tid, trace_id, attrs = ev
    d: Dict[str, Any] = {"ts_us": round(float(ts), 1), "cat": cat,
                         "name": name, "tid": tid}
    if names:
        thread = names.get(tid)
        if thread is not None:
            d["thread"] = thread
    if trace_id is not None:
        d["trace_id"] = trace_id
    if attrs:
        d["attrs"] = attrs
    return d


def events(n: Optional[int] = None, category: Optional[str] = None,
           trace_id: Optional[str] = None) -> List[Dict[str, Any]]:
    """Snapshot of the ring (oldest → newest) as dicts, optionally
    filtered by category and/or trace_id, truncated to the last ``n``."""
    with _lock:
        items = list(_ring)
    names = _thread_names()
    out = [_to_dict(ev, names) for ev in items
           if (category is None or ev[1] == category)
           and (trace_id is None or ev[4] == trace_id)]
    if n is not None:
        out = out[-int(n):]
    return out


def chrome_events() -> List[Dict[str, Any]]:
    """The ring as chrome-trace instant events ("ph": "i") —
    ``profiler.export_chrome_tracing`` merges these beside the host
    spans and step-timeline counters so one trace file carries all
    three planes."""
    with _lock:
        items = list(_ring)
    pid = os.getpid()
    out = []
    for ts, cat, name, tid, trace_id, attrs in items:
        args = dict(attrs) if attrs else {}
        if trace_id is not None:
            args["trace_id"] = trace_id
        out.append({"name": f"{cat}.{name}", "ph": "i", "s": "t",
                    "cat": cat, "pid": pid, "tid": tid, "ts": ts,
                    "args": args})
    return out


# ---------------------------------------------------------------------------
# dumps
# ---------------------------------------------------------------------------

def dump_dir() -> str:
    """Directory dumps land in: FLAGS_flight_dump_dir, or the system
    temp dir when unset."""
    d = str(_dir_flag.value or "").strip()
    return d or tempfile.gettempdir()


def dump(path: Optional[str] = None, trigger: str = "explicit",
         note: str = "") -> str:
    """Freeze the ring as a JSONL dump (header line + one event per
    line) and best-effort write the merged chrome trace beside it.
    Works regardless of FLAGS_flight_recorder — an operator asking for
    forensics gets whatever the ring holds. Returns the dump path."""
    global _dump_seq, _last_dump
    with _lock:
        items = list(_ring)
        _dump_seq += 1
        seq = _dump_seq
    names = _thread_names()
    if path is None:
        d = dump_dir()
        os.makedirs(d, exist_ok=True)
        path = os.path.join(
            d, f"flight-{os.getpid()}-{seq:03d}-{trigger}.jsonl")
    header = {
        "kind": "flight_header", "version": 1, "pid": os.getpid(),
        "trigger": trigger, "note": note,
        "time_unix": round(time.time(), 3), "host_now_us": _now_us(),
        "events": len(items), "dropped": dropped(),
        "capacity": _ring.maxlen, "thread_names":
            {str(k): v for k, v in names.items()},
    }
    chrome_path: Optional[str] = None
    try:
        from ..profiler import export_chrome_tracing
        chrome_path = export_chrome_tracing(path + ".trace.json")
        header["chrome_trace"] = os.path.basename(chrome_path)
    except Exception:  # noqa: BLE001 — no native tracer / no such dir
        chrome_path = None
    with open(path, "w") as f:
        f.write(json.dumps(header, default=str) + "\n")
        for ev in items:
            f.write(json.dumps(_to_dict(ev, names), default=str) + "\n")
        f.flush()
        try:
            os.fsync(f.fileno())
        except OSError:
            pass
    _M_dumps.inc(trigger=trigger)
    _last_dump = path
    return path


def last_dump_path() -> Optional[str]:
    return _last_dump


def find_dumps(directory: Optional[str] = None) -> List[str]:
    """Flight dumps in ``directory`` (default: :func:`dump_dir`),
    newest first."""
    d = directory or dump_dir()
    try:
        names = [n for n in os.listdir(d)
                 if n.startswith("flight-") and n.endswith(".jsonl")]
    except OSError:
        return []
    paths = [os.path.join(d, n) for n in names]
    paths.sort(key=lambda p: (os.path.getmtime(p), p), reverse=True)
    return paths


def load_dump(path: str) -> Tuple[Dict[str, Any], List[Dict[str, Any]]]:
    """(header, events) from a JSONL dump written by :func:`dump`."""
    header: Dict[str, Any] = {}
    evs: List[Dict[str, Any]] = []
    with open(path) as f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            obj = json.loads(line)
            if i == 0 and obj.get("kind") == "flight_header":
                header = obj
            else:
                evs.append(obj)
    return header, evs


def render_events(evs: List[Dict[str, Any]],
                  header: Optional[Dict[str, Any]] = None) -> str:
    """Human-readable trail: relative-ms timestamps, category.name,
    thread, trace id, attrs — the crash-forensics reading view."""
    lines: List[str] = []
    if header:
        lines.append(
            f"flight dump: trigger={header.get('trigger', '?')} "
            f"pid={header.get('pid', '?')} "
            f"events={header.get('events', len(evs))} "
            f"dropped={header.get('dropped', 0)} "
            f"capacity={header.get('capacity', '?')}"
            + (f" note={header['note']}" if header.get("note") else ""))
    if not evs:
        lines.append("<no events>")
        return "\n".join(lines)
    t0 = evs[0].get("ts_us", 0.0)
    for e in evs:
        rel_ms = (e.get("ts_us", t0) - t0) / 1e3
        who = e.get("thread") or e.get("tid", "?")
        tr = f" [{e['trace_id']}]" if "trace_id" in e else ""
        attrs = e.get("attrs") or {}
        astr = " ".join(f"{k}={v}" for k, v in attrs.items())
        lines.append(f"{rel_ms:+12.3f}ms  "
                     f"{e.get('cat', '?')}.{e.get('name', '?'):<24}"
                     f" ({who}){tr}{('  ' + astr) if astr else ''}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# crash-dump triggers: unhandled exceptions + signals
# ---------------------------------------------------------------------------

_prev_sys_hook = None
_prev_thread_hook = None
_prev_signals: Dict[int, Any] = {}
_hooks_installed = False


def _safe_dump(trigger: str, note: str = "") -> Optional[str]:
    try:
        return dump(trigger=trigger, note=note)
    except Exception:  # noqa: BLE001 — forensics must never re-crash
        return None


def install_crash_hooks(signals: Tuple[int, ...] = ()) -> None:
    """Install the crash-forensics triggers: wrap ``sys.excepthook`` and
    ``threading.excepthook`` so any unhandled exception records a
    ``crash`` event and writes a flight dump before chaining to the
    previous hook, and (optionally) bind the given signal numbers
    (e.g. ``signal.SIGUSR1``) to a live dump. Idempotent;
    :func:`uninstall_crash_hooks` restores everything."""
    global _prev_sys_hook, _prev_thread_hook, _hooks_installed
    if not _hooks_installed:
        _prev_sys_hook = sys.excepthook
        _prev_thread_hook = threading.excepthook

        def sys_hook(tp, val, tb):
            record("crash", "exception", error=tp.__name__,
                   message=str(val)[:200])
            _safe_dump("exception", f"{tp.__name__}: {val}"[:200])
            _prev_sys_hook(tp, val, tb)

        def thread_hook(args):
            tname = getattr(args.thread, "name", "?")
            record("crash", "thread_exception",
                   error=args.exc_type.__name__,
                   message=str(args.exc_value)[:200], thread=tname)
            _safe_dump("exception",
                       f"{args.exc_type.__name__} in thread {tname}: "
                       f"{args.exc_value}"[:200])
            _prev_thread_hook(args)

        sys.excepthook = sys_hook
        threading.excepthook = thread_hook
        _hooks_installed = True
    for signum in signals:
        if signum in _prev_signals:
            continue

        def handler(sig, frame, _n=signum):
            record("crash", "signal", signum=int(_n))
            _safe_dump("signal", f"signal {_n}")
            prev = _prev_signals.get(_n)
            if callable(prev):
                prev(sig, frame)

        try:
            _prev_signals[signum] = _signal.signal(signum, handler)
        except (ValueError, OSError):  # not main thread / unsupported
            pass


def uninstall_crash_hooks() -> None:
    """Restore the hooks/handlers :func:`install_crash_hooks` replaced."""
    global _hooks_installed
    with _lock:
        if _hooks_installed:
            sys.excepthook = _prev_sys_hook
            threading.excepthook = _prev_thread_hook
            _hooks_installed = False
        signums = list(_prev_signals)
        for signum in signums:
            prev = _prev_signals.pop(signum)
            try:
                _signal.signal(signum, prev)
            except (ValueError, OSError):
                pass
