"""Hot start: persistent executable cache + warm-bundle boot pre-warm.

Every compile cache in the framework — CapturedStep whole-step
programs, SOT segments, fusion-chain programs, fused optimizer steps,
the serving decode/prefill/spec executables — historically died with
the process, so a restarted trainer or a freshly rolled serving
replica paid full retrace+compile before its first useful step (the
~2.9ms vs ~305ms gap on the capture bench). This module closes that
gap in two layers:

- **Persistent executable cache**: wires JAX's persistent compilation
  cache under every ``jax.jit`` the framework issues, so compiled XLA
  artifacts live on DISK keyed by program content — a restarted
  process re-traces (cheap Python) but never re-compiles a program any
  earlier process already built. The directory is chosen by
  :func:`cache_dir`: ``JAX_COMPILATION_CACHE_DIR`` from the
  environment wins (JAX reads it itself; this module then never sets
  the directory), else ``FLAGS_executable_cache_dir``, else one fixed
  ``.jax_cache`` beside the package — never a temporary name, because
  the path is part of the cache key. :func:`ensure_executable_cache`
  is called from the compile-issuing seams (CapturedStep builds,
  ``capture_jit``, fusion programs, the fused optimizer step,
  ``jit.api`` builds, inference predictors) and from ``paddle_tpu``
  import; it lowers JAX's thresholds so every program is cached and
  installs the counters ``executable_cache.{hits,misses,writes}_total``.

- **Warm bundle + boot pre-warm** (``FLAGS_warmup_bundle``): the
  compile-issuing seams also :func:`note_program` the signature of
  every program a run actually built (the guard tuples CapturedStep
  computes, the serving engines' program geometry).
  :func:`export_bundle` writes them as a versioned JSON manifest
  beside the XLA cache dir; :func:`prewarm` replays a bundle at boot
  through the AOT seams (abstract args -> ``lower().compile()``), so
  a replica is 100%-cache-hit — disk reads, zero fresh XLA compiles —
  before it admits its first request. ``Model.prepare(warm_bundle=)``
  and ``inference.serve(warm_bundle=)`` both take a bundle (path or
  loaded dict); a truncated/corrupt bundle or an unreplayable entry
  degrades to cold compile with a counted
  ``warmup.failures_total{reason}`` — pre-warm failure is never a
  boot failure.

Fault-injection site: ``warmup.write`` (the bundle writer, same
truncated-write contract as ``checkpoint.write``).
"""
from __future__ import annotations

import json
import os
import stat as _stat
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from ..core.flags import (_registry as _flag_registry, define_flag,
                          flag_value)
from ..observability import flight as _flight
from ..observability import metrics as _om
from ..utils import fault_injection as _fi

__all__ = ["ensure_executable_cache", "cache_dir", "cache_stats",
           "note_program", "recorded", "clear_recorded", "export_bundle",
           "load_bundle", "prewarm", "gc_cache_dir", "BUNDLE_VERSION"]

define_flag(
    "executable_cache_dir", "",
    "Directory for JAX's persistent compilation cache: every jax.jit "
    "the framework issues (captured steps, SOT segments, fusion "
    "programs, fused optimizer steps, serving decode/prefill/spec "
    "executables) writes/reads disk-backed compiled artifacts there, "
    "so a restarted process re-traces but does not re-compile. "
    "JAX_COMPILATION_CACHE_DIR in the environment overrides it; empty "
    "(default) = the fixed .jax_cache directory beside the package")
define_flag(
    "warmup_bundle", "",
    "Default warm-bundle manifest path for boot pre-warm: consumers "
    "that take warm_bundle= (Model.prepare, inference.serve, "
    "warmup.prewarm) fall back to this path when none is passed. "
    "Empty (default) = no automatic pre-warm")
define_flag(
    "executable_cache_gc_days", 0,
    "Age-based GC of the persistent executable cache dir: entries "
    "whose last hit (atime, falling back to mtime) is older than "
    "this many days are evicted — counted "
    "executable_cache.evicted_total — opportunistically whenever "
    "ensure_executable_cache (re)configures the cache, or explicitly "
    "via warmup.gc_cache_dir(). 0 (default) = never evict")

_dir_flag = _flag_registry["executable_cache_dir"]
# <checkout>/.jax_cache: fixed by the package's own location (the path is
# part of the cache key, so it must not move between runs)
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_bundle_flag = _flag_registry["warmup_bundle"]

BUNDLE_VERSION = 1
_BUNDLE_KEY = "__paddle_tpu_warm_bundle__"
_MAX_RECORDED = 512

_M = _om.scope("executable_cache")
_M_hits = _M.counter(
    "hits_total",
    "Compiles served from the persistent executable cache (disk "
    "artifact reused; no XLA compile ran)")
_M_misses = _M.counter(
    "misses_total",
    "Compiles that missed the persistent executable cache (fresh XLA "
    "compile; corrupt/unreadable entries count here too)")
_M_writes = _M.counter(
    "writes_total",
    "Compiled executables written into the persistent cache dir")
_M_evicted = _M.counter(
    "evicted_total",
    "Persistent-cache entries evicted by last-hit age "
    "(FLAGS_executable_cache_gc_days / warmup.gc_cache_dir)")
_W = _om.scope("warmup")
_M_programs = _W.counter(
    "programs_total",
    "Programs successfully pre-warmed from a warm bundle at boot")
_M_failures = _W.counter(
    "failures_total",
    "Warm-bundle failures by reason (missing/corrupt/version/program) "
    "— every one degrades to cold compile, never a boot failure")

# enable-once state: the configured dir and whether the counting
# wrappers are installed (they stay installed)
_state: Dict[str, Any] = {"dir": None, "wrapped": False}


def _env_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()


def cache_dir() -> str:
    """Where compiled programs persist: ``JAX_COMPILATION_CACHE_DIR``
    if the environment sets it, else ``FLAGS_executable_cache_dir``,
    else the fixed ``.jax_cache`` beside the package."""
    return _env_dir() or str(_dir_flag.value or "").strip() or _DEFAULT_DIR


def ensure_executable_cache() -> str:
    """Configure JAX's persistent compilation cache at :func:`cache_dir`
    and return that directory. Called from every compile-issuing seam
    (and ``paddle_tpu`` import) — the steady-state path is one
    environment read + string compare. Flipping the flag at runtime
    reconfigures on the next compile. With ``JAX_COMPILATION_CACHE_DIR``
    set JAX reads the directory itself and this function never sets it;
    the thresholds and counters are installed either way."""
    d = cache_dir()
    if _state["dir"] == d:
        return d
    import jax
    from jax._src import compilation_cache as _cc
    if not _env_dir():
        os.makedirs(d, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", d)
    # cache EVERY program: the framework's small per-step/decode
    # executables are exactly what a restarted replica re-pays, and
    # jax's defaults (>=1s compile time) would skip all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _state["wrapped"]:
        _install_counters(_cc)
        _state["wrapped"] = True
    # clear the checked-once latch: a compile that ran BEFORE this call
    # must not pin the cache to the directory (or the off state) it saw
    _cc.reset_cache()
    _state["dir"] = d
    _flight.record("warmup", "cache_configured", dir=d)
    # opportunistic age GC: reconfiguration is the natural "a replica
    # just booted against this dir" moment, and it is cold-path
    try:
        gc_cache_dir(directory=d)
    except Exception:  # noqa: BLE001 — GC must never block boot
        pass
    return d


def gc_cache_dir(max_age_days: Optional[float] = None,
                 directory: Optional[str] = None) -> int:
    """Evict persistent-executable-cache entries by LAST-HIT age: a
    regular file in the cache dir whose newest of (atime, mtime) is
    older than ``max_age_days`` (default
    ``FLAGS_executable_cache_gc_days``; <= 0 disables) is removed and
    counted into ``executable_cache.evicted_total``. Warm-bundle
    manifests (``*.json``) and subdirectories are never touched — only
    the XLA cache's opaque artifact files age out. Returns the evicted
    count; all I/O errors degrade to keeping the entry."""
    if max_age_days is None:
        max_age_days = flag_value("executable_cache_gc_days")
    try:
        age = float(max_age_days)
    except (TypeError, ValueError):
        return 0
    d = directory or cache_dir()
    if age <= 0:
        return 0
    cutoff = time.time() - age * 86400.0
    try:
        names = os.listdir(d)
    except OSError:
        return 0
    removed = 0
    for name in names:
        if name.endswith(".json"):
            continue  # warm bundles are manifests, not cache entries
        path = os.path.join(d, name)
        try:
            st = os.stat(path)
            if not _stat.S_ISREG(st.st_mode):
                continue
            if max(st.st_atime, st.st_mtime) < cutoff:
                os.remove(path)
                removed += 1
        except OSError:
            continue  # raced/unreadable: keep it, try next boot
    if removed:
        _M_evicted.inc(removed)
        _flight.record("warmup", "cache_gc", dir=os.path.basename(d),
                       evicted=removed, max_age_days=age)
    return removed


def _install_counters(_cc) -> None:
    """Count hits/misses/writes precisely by wrapping the persistent
    cache's get/put seam (jax emits no write/miss monitoring events).
    A corrupt entry raising on read counts as a miss — jax's caller
    already degrades it to a fresh compile."""
    orig_get = _cc.get_executable_and_time
    orig_put = _cc.put_executable_and_time

    def counted_get(*a, **k):
        try:
            executable, t = orig_get(*a, **k)
        except Exception:
            _M_misses.inc()
            raise
        (_M_hits if executable is not None else _M_misses).inc()
        return executable, t

    def counted_put(*a, **k):
        out = orig_put(*a, **k)
        _M_writes.inc()
        return out

    _cc.get_executable_and_time = counted_get
    _cc.put_executable_and_time = counted_put


def cache_stats() -> Dict[str, int]:
    """{hits, misses, writes} of the persistent executable cache."""
    return {"hits": int(_M_hits.value()),
            "misses": int(_M_misses.value()),
            "writes": int(_M_writes.value())}


# ---------------------------------------------------------------------------
# signature <-> JSON: CapturedStep signatures are nested tuples of
# hashable scalars; JSON round-trips them as nested lists, so a deep
# list->tuple conversion restores the exact tuple
# ---------------------------------------------------------------------------

def sig_to_json(sig):
    if isinstance(sig, tuple):
        return [sig_to_json(v) for v in sig]
    return sig


def sig_from_json(obj):
    if isinstance(obj, list):
        return tuple(sig_from_json(v) for v in obj)
    return obj


# ---------------------------------------------------------------------------
# recording: what did this run actually compile?
# ---------------------------------------------------------------------------

# insertion-ordered, key = canonical JSON of the entry (dedup), bounded;
# compile seams on worker threads (serving loops) record concurrently
_recorded: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()


def _recorded_lock():
    from ..analysis.locks import make_lock
    return make_lock("jit.warmup.recorded")


_rec_lock = _recorded_lock()


def note_program(kind: str, name: str, entry: Dict[str, Any]) -> None:
    """Record one compiled program's replayable signature (called from
    the compile seams — compile events are rare and slow, so this is
    never hot-path cost). Non-JSON-serializable entries drop their
    ``sig`` first, then are skipped entirely — recording is
    best-effort, the disk cache alone already guarantees no fresh
    compiles on restart."""
    entry = dict(entry)
    entry["kind"] = kind
    entry["name"] = name
    try:
        key = json.dumps(entry, sort_keys=True)
    except (TypeError, ValueError):
        entry.pop("sig", None)
        try:
            key = json.dumps(entry, sort_keys=True)
        except (TypeError, ValueError):
            return
    with _rec_lock:
        if key in _recorded:
            return
        _recorded[key] = entry
        while len(_recorded) > _MAX_RECORDED:
            _recorded.popitem(last=False)


def recorded() -> List[Dict[str, Any]]:
    with _rec_lock:
        return [dict(e) for e in _recorded.values()]


def clear_recorded() -> None:
    with _rec_lock:
        _recorded.clear()


# ---------------------------------------------------------------------------
# bundle export / load
# ---------------------------------------------------------------------------

def _default_bundle_path() -> Optional[str]:
    p = str(_bundle_flag.value or "").strip()
    if p:
        return p
    d = str(_dir_flag.value or "").strip()
    if d:
        return os.path.join(d, "warm_bundle.json")
    return None


def export_bundle(path: Optional[str] = None) -> str:
    """Write the recorded program signatures as a versioned JSON
    manifest (default: ``<FLAGS_executable_cache_dir>/warm_bundle.json``
    — beside the XLA cache dir it indexes). Atomic write-then-rename
    through the ``warmup.write`` fault-injection site; a kill/truncate
    mid-write leaves no (partial) bundle behind."""
    import jax
    path = path or _default_bundle_path()
    if not path:
        raise ValueError(
            "export_bundle needs a path (or FLAGS_executable_cache_dir/"
            "FLAGS_warmup_bundle to derive one)")
    bundle = {_BUNDLE_KEY: BUNDLE_VERSION,
              "jax": jax.__version__,
              "entries": recorded()}
    blob = json.dumps(bundle, sort_keys=True, indent=1).encode()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            _fi.write_bytes("warmup.write", f, blob)
            f.flush()
        os.replace(tmp, path)
    except Exception:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    _flight.record("warmup", "bundle_exported", path=os.path.basename(path),
                   entries=len(bundle["entries"]))
    return path


def _fail(reason: str, **attrs) -> None:
    _M_failures.inc(reason=reason)
    _flight.record("warmup", "bundle_failed", reason=reason, **attrs)


def load_bundle(path: Optional[str] = None) -> Optional[Dict[str, Any]]:
    """Load a warm-bundle manifest; ``None`` (with a counted
    ``warmup.failures_total{reason}``) for anything unusable —
    missing, truncated, corrupt, or a version this build does not
    understand. The cold path is the fallback, never a crash."""
    path = path or _default_bundle_path()
    if not path:
        return None
    base = os.path.basename(path)
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        _fail("missing", path=base)
        return None
    try:
        bundle = json.loads(raw.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        _fail("corrupt", path=base)
        return None
    if not isinstance(bundle, dict) or \
            not isinstance(bundle.get("entries"), list):
        _fail("corrupt", path=base)
        return None
    version = bundle.get(_BUNDLE_KEY)
    if not isinstance(version, int) or version > BUNDLE_VERSION:
        _fail("version", path=base, version=str(version))
        return None
    return bundle


# ---------------------------------------------------------------------------
# boot pre-warm
# ---------------------------------------------------------------------------

def prewarm(bundle=None, captured=None, engine=None) -> Dict[str, int]:
    """Replay a warm bundle's recorded programs at boot through the AOT
    seams (abstract args -> ``lower().compile()``), so the process is
    100%-persistent-cache-hit before its first real step/request.

    ``bundle``: a loaded bundle dict, a manifest path, or None (the
    ``FLAGS_warmup_bundle`` / cache-dir default). ``captured``: a
    ``CapturedStep`` (or ``jit.TrainStep``) to replay
    ``captured_step`` entries into. ``engine``: a serving decode
    engine to replay ``serving`` entries into. Entries without a
    matching target are skipped; every per-entry failure is counted
    (``warmup.failures_total{reason=program}``) and pre-warm
    continues — this function never raises for bundle content."""
    if bundle is None or isinstance(bundle, str):
        bundle = load_bundle(bundle)
    out = {"programs": 0, "failures": 0, "skipped": 0}
    if not bundle:
        return out
    ensure_executable_cache()
    step_target = getattr(captured, "_step", captured)
    for entry in bundle.get("entries", []):
        if not isinstance(entry, dict):
            out["skipped"] += 1
            continue
        kind = entry.get("kind")
        try:
            if kind == "captured_step" and step_target is not None:
                step_target.prewarm(entry)
                out["programs"] += 1
            elif kind == "serving" and engine is not None:
                res = engine._prewarm_entry(entry)
                if res == "stale":
                    # bundle written by a DIFFERENTLY-configured
                    # replica (slots/blocks/buckets/spec_k): replaying
                    # would compile fresh programs at boot while
                    # claiming warmth — degrade instead, counted
                    out["failures"] += 1
                    _M_failures.inc(reason="stale")
                    _flight.record("warmup", "bundle_failed",
                                   reason="stale",
                                   fn=str(entry.get("name", "")))
                elif res:
                    out["programs"] += 1
                else:
                    out["skipped"] += 1
            else:
                out["skipped"] += 1
        except Exception as e:  # noqa: BLE001 — degrade to cold compile
            out["failures"] += 1
            _M_failures.inc(reason="program")
            _flight.record("warmup", "program_failed",
                           fn=str(entry.get("name", "")),
                           error=type(e).__name__)
    if out["programs"]:
        _M_programs.inc(out["programs"])
    _flight.record("warmup", "prewarm", **out)
    return out
