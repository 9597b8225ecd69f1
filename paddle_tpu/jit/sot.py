"""SOT-style dy2static: guarded compiled subgraphs with graph breaks.

The reference compiles arbitrary user Python with a CPython-bytecode
tracer (ref: python/paddle/jit/sot/opcode_translator/executor/
opcode_executor.py — guard-based cache, graph-break fallback) plus an AST
transpiler (python/paddle/jit/dy2static/). A bytecode interpreter is the
wrong tool on TPU, where every tensor op already flows through ONE
dispatch point (core.autograd.apply_op). This tracer therefore works at
the op-dispatch level:

- **Record**: run the function EAGERLY (so it is always correct, any
  Python allowed) while logging each apply_op into the current *segment*.
  When Python forces a host value out of a tensor (``bool()``/``item()``/
  ``.numpy()`` — i.e. data-dependent control flow), the segment is closed
  and the extracted value becomes a **guard** (the analog of the
  reference's graph break + guard).
- **Replay**: later calls with the same input signature execute the
  recorded segments as jit-compiled programs. Guards validate
  SPECULATIVELY: every segment of the recorded path dispatches without
  waiting, the guard tensors are packed into one uint8 array in-jit,
  and a single host fetch checks the whole path — N graph breaks cost
  one device round-trip, not N serialized ones. Matching paths run
  fully compiled; a mismatch discards the speculated tail (segments are
  pure programs; side-effectful recordings never replay) and re-records
  that branch (the trace tree grows one path per taken branch, e.g. one
  per while-loop trip count).
- **Fallback**: recordings that consumed RNG (dropout) or mutated
  buffers in place (BN train-mode running stats) are marked non-
  replayable — those calls simply stay eager, which is the reference's
  graph-break fallback contract with correctness guaranteed.

Dynamic shapes: the compile cache is keyed on input signatures and
LRU-bounded (FLAGS_sot_cache_size). Axes declared dynamic via
``BucketPolicy`` are padded up to the next bucket so varlen batches
reuse a bounded set of entries instead of compiling per length.
"""
from __future__ import annotations

import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as random_mod
from ..core import tensor as tensor_mod
from ..core import autograd as autograd_mod
from ..core.flags import define_flag, flag_value
from ..core.flags import _registry as _flag_registry
from ..core.tensor import Tensor
from ..observability import flight as _flight
from ..observability import metrics as _om
from ..profiler import RecordEvent

__all__ = ["sot_compile", "SOTFunction", "BucketPolicy", "capture",
           "CapturedStep", "capture_jit"]

define_flag("sot_cache_size", 64,
            "Max (signature, guard-path) entries in a SOTFunction's "
            "compile cache (LRU eviction)")
define_flag("sot_capture", True,
            "Whole-step program capture (jit/sot.py): SOTFunction "
            "replays recorded paths as compiled segments and "
            "hapi.Model.train_batch/eval_batch + jit.TrainStep run as "
            "ONE cached, buffer-donated executable. 0 is the kill "
            "switch: every consumer falls back to today's per-chain "
            "eager fusion, bit-for-bit")
define_flag("sot_capture_cache", 8,
            "Max captured whole-step executables per CapturedStep "
            "(LRU eviction; one entry per input signature x "
            "train/eval-mode x trainable-set x optimizer config)")
define_flag("sot_guard_budget", 512,
            "Max TOTAL guard bytes a recorded SOT path may validate "
            "per replay (per-guard values are capped at 256B "
            "separately); an over-budget recording stays eager with a "
            "counted fallback reason")

_capture_flag = _flag_registry["sot_capture"]
_capture_cache_flag = _flag_registry["sot_capture_cache"]
_guard_budget_flag = _flag_registry["sot_guard_budget"]

# -- telemetry: the production counters a guard-miss storm is diagnosed
# from (plus sot.* flight-recorder events for the black-box trail)
_M = _om.scope("sot")
_M_flag = _om.flag_info()
_M_captured = _M.counter(
    "captured_steps_total",
    "Step executions served by a captured program — a successful "
    "SOTFunction whole-path replay or one CapturedStep/capture_jit "
    "donated executable call")
_M_guard_miss = _M.counter(
    "guard_misses_total",
    "Replay guard validations that missed: the speculated tail was "
    "discarded (side-effect-free) and the next candidate path or a "
    "re-record served the call")
_M_retraces = _M.counter(
    "retraces_total",
    "Calls where every cached candidate path missed its guards and "
    "the branch was re-recorded (the trace tree grew)")
_M_fallbacks = _M.counter(
    "fallbacks_total",
    "Recordings that stayed eager (per-chain fusion), by reason "
    "(rng / mutation / backward / oversized_guard / guard_budget / "
    "gate reasons from CapturedStep)")
_M_seg_compiles = _M.counter(
    "segment_compiles_total",
    "SOT path segments jit-compiled (compile-on-second-replay; the "
    "first replay of a path runs its segments un-jitted)")
_M_step_compiles = _M.counter(
    "captured_compiles_total",
    "Whole-step captured programs built (CapturedStep signatures + "
    "capture_jit first executions)")
_M_hits = _M.counter(
    "cache_hits_total",
    "CapturedStep executions served by an already-built executable")


def _fallback_category(why: str) -> str:
    """Bounded-cardinality label for fallbacks_total: why_not strings
    can embed per-call values (byte sizes), counters must not."""
    if "RNG" in why:
        return "rng"
    if "mutation" in why:
        return "mutation"
    if "backward" in why:
        return "backward"
    if "guard budget" in why:
        return "guard_budget"
    if "guard limit" in why or "materialized" in why:
        return "oversized_guard"
    return "other"


def _count_fallback(reason: str, name: str = "") -> None:
    _M_fallbacks.inc(reason=reason)
    _flight.record("sot", "fallback", reason=reason, fn=name)


class BucketPolicy:
    """Pad dynamic axes up to bucket sizes so varlen inputs share compiled
    entries. ``axes`` maps arg index -> {axis: buckets}; ``buckets`` is a
    sorted list of sizes, or "pow2" for powers of two.

    Padding uses ``pad_value`` — choose it so the padded region is
    numerically inert for your model (e.g. the loss ignore_index for
    token ids, 0 for already-masked activations). This is an explicit
    policy, not silent magic: bucketing changes tensor shapes the
    function sees.
    """

    def __init__(self, axes: Dict[int, Dict[int, Any]], pad_value=0):
        self.axes = axes
        self.pad_value = pad_value

    def bucket_of(self, size: int, buckets) -> int:
        if buckets == "pow2":
            b = 1
            while b < size:
                b *= 2
            return b
        for b in buckets:
            if b >= size:
                return int(b)
        return int(buckets[-1])  # larger than every bucket: use max

    def apply(self, args: tuple):
        out = list(args)
        for idx, ax_map in self.axes.items():
            if idx >= len(out) or not isinstance(out[idx], Tensor):
                continue
            arr = out[idx]._data
            pads = [(0, 0)] * arr.ndim
            changed = False
            for axis, buckets in ax_map.items():
                size = arr.shape[axis]
                tgt = self.bucket_of(size, buckets)
                if tgt > size:
                    pads[axis] = (0, tgt - size)
                    changed = True
            if changed:
                arr = jnp.pad(arr, pads, constant_values=self.pad_value)
                out[idx] = Tensor(arr, stop_gradient=out[idx].stop_gradient)
        return tuple(out)


# ---------------------------------------------------------------------------
# recording structures
# ---------------------------------------------------------------------------

class _Op:
    __slots__ = ("fn", "arg_refs", "kwargs", "out_ids", "multi", "name")

    def __init__(self, fn, arg_refs, kwargs, out_ids, multi, name=""):
        self.fn = fn            # pure jax fn captured at record time
        self.arg_refs = arg_refs  # list of ("id", sot_id) | ("ext", Tensor) | ("lit", value)
        self.kwargs = kwargs
        self.out_ids = out_ids
        self.multi = multi
        self.name = name        # dispatch op name (capture-plan metadata)


class _Segment:
    __slots__ = ("ops", "jitted", "pure", "input_ids", "ext_tensors",
                 "output_ids")

    def __init__(self):
        self.ops: List[_Op] = []
        self.jitted = None   # built lazily: compile-on-second-replay
        self.pure = None     # the un-jitted segment function
        self.input_ids: List[int] = []
        self.ext_tensors: List[Tensor] = []
        self.output_ids: List[int] = []


class _Guard:
    __slots__ = ("tensor_id", "kind", "value")

    def __init__(self, tensor_id, kind, value):
        self.tensor_id = tensor_id
        self.kind = kind        # "item" | "numpy"
        self.value = value      # python scalar or small-ndarray bytes


class _Recording:
    """One straight-line trace: segments alternating with guards, plus the
    provenance of the final return value."""

    __slots__ = ("segments", "guards", "ext_guards", "result_spec",
                 "replayable", "why_not")

    def __init__(self):
        self.segments: List[_Segment] = []
        self.guards: List[_Guard] = []
        # (Tensor ref, bytes): captured tensors whose host value steered
        # Python during recording — re-checked up front at every replay
        self.ext_guards: List[Tuple[Tensor, bytes]] = []
        self.result_spec = None
        self.replayable = True
        self.why_not = ""


_MAX_GUARD_BYTES = 256

# content-digest memo for raw-array cache keys: keyed by object id with a
# weakref keeping the entry honest (a dead id can be reused by a new array)
_digest_memo: Dict[int, Tuple[Any, tuple]] = {}


def _content_digest(a):
    import hashlib
    import weakref
    # memoize ONLY for jax.Array: device buffers are immutable, so the
    # digest stays valid for the object's lifetime. Mutable host arrays
    # (np.ndarray) are re-hashed every call — host sha1 is cheap and a
    # stale digest would silently replay old constants.
    memoizable = isinstance(a, jax.Array)
    key = id(a)
    if memoizable:
        hit = _digest_memo.get(key)
        if hit is not None and hit[0]() is a:
            return hit[1]
    arr = np.asarray(a)
    dig = (arr.shape, str(arr.dtype),
           hashlib.sha1(arr.tobytes()).hexdigest())
    if memoizable:
        try:
            _digest_memo[key] = (weakref.ref(
                a, lambda _: _digest_memo.pop(key, None)), dig)
        except TypeError:
            pass
    return dig


class _Recorder:
    """Installs the apply_op / materialize / mutation / rng hooks for the
    duration of one eagerly-executed call."""

    def __init__(self):
        self.rec = _Recording()
        self.cur = _Segment()
        self.next_id = 0
        self.tensor_ids: Dict[int, int] = {}   # id(Tensor) -> sot id
        self.keepalive: List[Tensor] = []      # pin tensors so ids stay valid
        self.produced_in_cur: set = set()
        self.guard_values: List[Any] = []

    # -- id helpers --------------------------------------------------------
    def tag(self, t: Tensor) -> int:
        sid = self.next_id
        self.next_id += 1
        self.tensor_ids[id(t)] = sid
        self.keepalive.append(t)
        return sid

    def ref_of(self, t: Tensor):
        sid = self.tensor_ids.get(id(t))
        if sid is None:
            return ("ext", t)      # parameter / captured tensor
        return ("id", sid)

    # -- hooks -------------------------------------------------------------
    def on_op(self, fn, args, kwargs, outs, name):
        arg_refs = []
        for a in args:
            if isinstance(a, Tensor):
                arg_refs.append(self.ref_of(a))
            else:
                arg_refs.append(("lit", a))
        out_ids = []
        for o in outs:
            sid = self.tag(o)
            out_ids.append(sid)
            self.produced_in_cur.add(sid)
        self.cur.ops.append(
            _Op(fn, arg_refs, dict(kwargs), out_ids, len(outs) > 1,
                name))

    def on_materialize(self, t: Tensor, kind: str):
        sid = self.tensor_ids.get(id(t))
        arr = np.asarray(t._data)
        if arr.nbytes > _MAX_GUARD_BYTES:
            self.rec.replayable = False
            self.rec.why_not = (
                f"materialized a {arr.nbytes}-byte tensor into Python "
                f"(> {_MAX_GUARD_BYTES}B guard limit)")
            return
        value = arr.tobytes()
        if sid is None:
            # a tensor from outside the trace (captured param/const)
            # steered Python: guard on its value directly
            self.rec.ext_guards.append((t, value))
            return
        self._break(sid, kind, value)

    def on_mutation(self, t: Tensor):
        self.rec.replayable = False
        self.rec.why_not = "in-place tensor mutation during trace"

    def on_rng(self):
        self.rec.replayable = False
        self.rec.why_not = "RNG consumed during trace (e.g. dropout)"

    def on_backward(self):
        self.rec.replayable = False
        self.rec.why_not = "autograd backward ran during trace"

    def _break(self, sid: int, kind: str, value):
        # only tensors produced in the CURRENT segment need exporting from
        # it; guards on inputs or earlier-segment outputs read the replay
        # env directly
        extra = [sid] if sid in self.produced_in_cur else []
        self._close_segment(extra_outputs=extra)
        self.rec.guards.append(_Guard(sid, kind, value))

    def _close_segment(self, extra_outputs=()):
        seg = self.cur
        for sid in extra_outputs:
            if sid not in seg.output_ids:
                seg.output_ids.append(sid)
        self.rec.segments.append(seg)
        self.cur = _Segment()
        self.produced_in_cur = set()

    # -- finalize ----------------------------------------------------------
    def finish(self, result):
        # mark every id consumed by later segments / the result as a
        # segment output, and compute each segment's inputs
        def result_refs(r):
            if isinstance(r, Tensor):
                return self.ref_of(r)
            if isinstance(r, (list, tuple)):
                return (type(r).__name__,
                        [result_refs(v) for v in r])
            if isinstance(r, dict):
                return ("dict", {k: result_refs(v) for k, v in r.items()})
            return ("lit", r)

        self._close_segment()
        self.rec.result_spec = result_refs(result)

        produced_by = {}
        for si, seg in enumerate(self.rec.segments):
            for op in seg.ops:
                for oid in op.out_ids:
                    produced_by[oid] = si

        needed_after: Dict[int, set] = {}

        def note_need(sid, at_seg):
            src = produced_by.get(sid)
            if src is not None and src != at_seg:
                needed_after.setdefault(src, set()).add(sid)

        for si, seg in enumerate(self.rec.segments):
            for op in seg.ops:
                for kind, v in op.arg_refs:
                    if kind == "id":
                        note_need(v, si)

        def walk_result(spec):
            kind = spec[0]
            if kind == "id":
                note_need(spec[1], -1)
            elif kind in ("list", "tuple"):
                for v in spec[1]:
                    walk_result(v)
            elif kind == "dict":
                for v in spec[1].values():
                    walk_result(v)

        walk_result(self.rec.result_spec)
        # a guard read after later segments still needs its producer to
        # export it
        for g in self.rec.guards:
            note_need(g.tensor_id, -1)

        for si, seg in enumerate(self.rec.segments):
            outs = set(seg.output_ids) | needed_after.get(si, set())
            seg.output_ids = sorted(outs)
            ins = []
            exts = []
            seen_ext = set()
            local = {oid for op in seg.ops for oid in op.out_ids}
            for op in seg.ops:
                for kind, v in op.arg_refs:
                    if kind == "id" and v not in local and v not in ins:
                        ins.append(v)
                    elif kind == "ext" and id(v) not in seen_ext:
                        seen_ext.add(id(v))
                        exts.append(v)
            seg.input_ids = ins
            seg.ext_tensors = exts
        return self.rec


class _RecorderSession:
    def __init__(self, recorder: _Recorder):
        self.recorder = recorder

    def __enter__(self):
        r = self.recorder
        if autograd_mod._op_recorder is not None:
            raise RuntimeError(
                "SOT recording cannot nest with static-graph recording")
        autograd_mod._op_recorder = \
            lambda fn, args, kwargs, outs, name: r.on_op(
                fn, args, kwargs, outs, name)
        tensor_mod._materialize_hook = r.on_materialize
        tensor_mod._mutation_hook = r.on_mutation
        random_mod._key_observer = r.on_rng
        autograd_mod._backward_observer = r.on_backward
        return r

    def __exit__(self, *exc):
        autograd_mod._op_recorder = None
        tensor_mod._materialize_hook = None
        tensor_mod._mutation_hook = None
        random_mod._key_observer = None
        autograd_mod._backward_observer = None
        return False


# ---------------------------------------------------------------------------
# replay
# ---------------------------------------------------------------------------

def _segment_fn(seg: _Segment):
    """Build one PURE callable: (ext_arrays, input_arrays) -> outputs.
    Jitting is the caller's policy (compile-on-second-replay, like the
    fusion plane's second-sighting rule)."""
    ops = seg.ops
    input_ids = list(seg.input_ids)
    output_ids = list(seg.output_ids)

    def seg_fn(ext_vals, in_vals):
        env: Dict[int, Any] = dict(zip(input_ids, in_vals))
        ext_map = {id(t): v for t, v in zip(seg.ext_tensors, ext_vals)}
        for op in ops:
            call = []
            for kind, v in op.arg_refs:
                if kind == "id":
                    call.append(env[v])
                elif kind == "ext":
                    call.append(ext_map[id(v)])
                else:
                    call.append(v)
            res = op.fn(*call, **op.kwargs)
            res = tuple(res) if op.multi else (res,)
            for oid, r in zip(op.out_ids, res):
                env[oid] = r
        return [env[o] for o in output_ids]

    return seg_fn


@jax.jit
def _pack_bytes(vals):
    """Concatenate arbitrary fixed-size-dtype arrays into ONE uint8
    array (little-endian element bytes == numpy tobytes order)."""
    parts = []
    for v in vals:
        v = jnp.asarray(v)
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.uint8)
        flat = v.reshape(-1)
        if flat.dtype.itemsize > 1:
            flat = jax.lax.bitcast_convert_type(
                flat, jnp.uint8).reshape(-1)
        parts.append(flat)
    if not parts:
        return jnp.zeros((0,), jnp.uint8)
    return jnp.concatenate(parts)


class _CompiledPath:
    """One guard path of one signature: recorded segments + guards.
    Segments compile LAZILY — the first replay runs them un-jitted
    (one-off paths never pay XLA), the second replay jits each segment
    once (``sot.segment_compiles_total`` + a flight event), and later
    replays are fully compiled."""

    def __init__(self, rec: _Recording, input_ids: List[int],
                 name: str = ""):
        self.rec = rec
        self.input_ids = input_ids
        self.name = name
        self.replays = 0  # successful whole-path replays
        for seg in rec.segments:
            seg.pure = _segment_fn(seg)
        # tail guard values (guard 0 is checked early, on its own),
        # concatenated once for the packed single-fetch validation
        self._tail_guard_bytes = b"".join(
            g.value for g in rec.guards[1:])

    def _runner(self, seg: _Segment):
        if self.replays < 1:
            return seg.pure
        if seg.jitted is None:
            from .warmup import ensure_executable_cache
            ensure_executable_cache()
            seg.jitted = jax.jit(seg.pure)
            _M_seg_compiles.inc()
            _flight.record("sot", "segment_compile", fn=self.name,
                           ops=len(seg.ops))
        return seg.jitted

    def replay(self, input_tensors: List[Tensor]):
        """Returns (ok, result). ok=False on a guard miss.

        Each segment executes through apply_op, so replayed outputs carry
        tape nodes: loss.backward() after a replayed call differentiates
        THROUGH the compiled segments into the inputs and the captured
        parameters (apply_op takes jax.vjp of the jitted segment — the
        jit boundary is kept as a call primitive, so it stays compiled).

        Guard handling is SPECULATIVE (the lax.cond-flavored answer to
        the reference's per-break host sync, SURVEY §3.1): the FIRST
        guard is checked after the first segment (so a wrong candidate
        path — MRU probing tries siblings — costs ~one segment, as the
        per-guard scheme did), then every remaining segment dispatches
        without waiting and the rest of the guard tensors are packed
        into one uint8 array in-jit and validated with ONE further
        fetch — N graph breaks cost ~2 device round-trips instead of N
        serialized ones (device-resident ext guards share one more
        packed fetch). Segments are pure compiled programs
        (RNG/mutating recordings never replay), so a wrong-path tail is
        discarded without side effects; any exception while speculating
        (e.g. a NaN check tripping on wrong-path garbage) also falls
        back to re-recording, and NaN flags the discarded tail enqueued
        are rolled back.
        """
        from ..core import autograd as autograd_mod
        from ..core.autograd import apply_op
        rec = self.rec
        # ext guards: host values compare directly; device-resident ones
        # share one packed fetch
        dev_guards = []
        for t, val in rec.ext_guards:
            if isinstance(t._data, jax.Array):
                dev_guards.append((t._data, val))
            elif np.asarray(t._data).tobytes() != val:
                self._note_miss("ext")
                return False, None
        if dev_guards:
            got = np.asarray(_pack_bytes(
                [d for d, _ in dev_guards])).tobytes()
            if got != b"".join(v for _, v in dev_guards):
                self._note_miss("ext")
                return False, None
        env: Dict[int, Tensor] = dict(zip(self.input_ids, input_tensors))
        guard_vals = []
        # NaN-flag isolation: flush whatever earlier eager ops enqueued
        # FIRST (outside the try — a genuine pre-existing NaN raises
        # here with its real attribution), then give the speculation its
        # own queue. On success the speculation's flags merge back (they
        # belong to real outputs); on a miss they are discarded with the
        # garbage they describe. A mid-speculation stride flush only
        # ever sees speculation-owned flags, so a trip there is caught
        # below and simply falls back to re-record.
        autograd_mod.flush_nan_checks()
        saved_pending = autograd_mod._nan_pending
        autograd_mod._nan_pending = []

        def miss():
            autograd_mod._nan_pending = saved_pending
            return False, None

        try:
            for si, seg in enumerate(rec.segments):
                n_ext = len(seg.ext_tensors)
                in_tensors = [env[i] for i in seg.input_ids]
                if seg.ops:
                    runner = self._runner(seg)

                    def run_seg(*flat, _j=runner, _n=n_ext):
                        return tuple(_j(list(flat[:_n]),
                                        list(flat[_n:])))

                    outs = apply_op(run_seg, *seg.ext_tensors,
                                    *in_tensors, op_name="sot_segment")
                    if not isinstance(outs, tuple):
                        outs = (outs,)
                    for oid, o in zip(seg.output_ids, outs):
                        env[oid] = o
                if si < len(rec.guards):
                    g = rec.guards[si]
                    if si == 0:
                        # early check: wrong sibling candidates bail
                        # after one segment instead of a full path
                        got = np.asarray(
                            env[g.tensor_id]._data).tobytes()
                        if got != g.value:
                            self._note_miss("early")
                            return miss()
                    else:
                        guard_vals.append(env[g.tensor_id]._data)
            if guard_vals:
                got = np.asarray(_pack_bytes(guard_vals)).tobytes()
                if got != self._tail_guard_bytes:
                    self._note_miss("tail")
                    return miss()  # miss somewhere on the tail
        except FloatingPointError:
            # wrong-path garbage legitimately trips the NaN check;
            # re-record eagerly — if the CORRECT path is non-finite, the
            # re-record reproduces the error with its real context
            return miss()
        except Exception as e:  # noqa: BLE001 — degrade, but loudly
            warnings.warn(
                f"SOT replay fell back to re-recording on an unexpected "
                f"{type(e).__name__}: {e} — speculation disabled for "
                f"this call", RuntimeWarning)
            return miss()
        autograd_mod._nan_pending = \
            saved_pending + autograd_mod._nan_pending
        self.replays += 1
        if _M_flag.value:
            _M_captured._v += 1  # inline fast cell: per-replay hot path
        return True, self._build_result(env)

    def _note_miss(self, where: str) -> None:
        _M_guard_miss.inc()
        _flight.record("sot", "guard_miss", fn=self.name, where=where)

    def _build_result(self, env):
        def build(spec):
            kind = spec[0]
            if kind == "id":
                return env[spec[1]]
            if kind == "ext":
                return spec[1]
            if kind in ("list", "tuple"):
                vals = [build(v) for v in spec[1]]
                return tuple(vals) if kind == "tuple" else vals
            if kind == "dict":
                return {k: build(v) for k, v in spec[1].items()}
            return spec[1]
        return build(self.rec.result_spec)


class SOTFunction:
    """paddle.jit.to_static with graph breaks (see module docstring)."""

    def __init__(self, fn: Callable, bucket_policy: Optional[BucketPolicy]
                 = None, name: Optional[str] = None, input_spec=None):
        self._fn = fn
        self._bucket = bucket_policy
        self.input_spec = input_spec  # kept for save/export tooling parity
        self._name = name or getattr(fn, "__name__", "fn")
        # (signature, guard-values-tuple) -> _CompiledPath; the eager
        # fallback marker lives under (signature, "eager") so it never
        # shadows compiled paths of OTHER branches of the same signature
        self._cache: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._warned = set()
        # why recordings stayed eager, by reason — the capture planner
        # reads this as dynamic PTC002-class evidence
        self._fallback_reasons: Dict[str, int] = {}
        # Layers whose .training flag steers the trace (dropout/BN modes):
        # the bound self plus any Layer captured in the fn's closure.
        # Their modes join the cache signature — the analog of the
        # reference SOT guarding attribute reads.
        from ..nn.layer import Layer
        self._layers = []

        def note(v):
            if isinstance(v, Layer) and v not in self._layers:
                self._layers.append(v)
            elif isinstance(v, (list, tuple)):
                for x in v:
                    if isinstance(x, Layer):
                        note(x)
            elif isinstance(v, dict):
                for x in v.values():
                    if isinstance(x, Layer):
                        note(x)

        note(getattr(fn, "__self__", None))
        for cell in getattr(fn, "__closure__", None) or ():
            try:
                note(cell.cell_contents)
            except ValueError:
                continue
        # module-global Layers the code actually references (co_names)
        code = getattr(fn, "__code__", None)
        gl = getattr(fn, "__globals__", None)
        if code is not None and gl is not None:
            for name in code.co_names:
                note(gl.get(name))

    # -- signature ---------------------------------------------------------
    @staticmethod
    def _arg_key(a):
        if isinstance(a, Tensor):
            return ("T", tuple(a._data.shape), str(a._data.dtype),
                    not a.stop_gradient)
        if isinstance(a, (np.ndarray, jax.Array)):
            # raw arrays are baked into the trace as constants, so the
            # key must cover their CONTENT (repr truncates large arrays);
            # the digest is memoized per array object so a reused buffer
            # isn't re-hashed (and re-fetched) every call
            return ("A", *_content_digest(a))
        return ("L", repr(a))

    def _signature(self, args, kwargs):
        parts = [self._arg_key(a) for a in args]
        for k in sorted(kwargs):
            parts.append((k, self._arg_key(kwargs[k])))
        # non-tensor state that steers traces: layer train/eval modes and
        # the AMP autocast regime (apply_op casts differently under it)
        from ..amp.auto_cast import amp_signature
        modes = tuple(
            sub.training for lyr in self._layers
            for sub in lyr.sublayers(include_self=True))
        parts.append(("mode", modes) + amp_signature())
        return tuple(parts)

    def _cache_put(self, key, value):
        self._cache[key] = value
        self._cache.move_to_end(key)
        limit = max(int(flag_value("sot_cache_size") or 64), 1)
        while len(self._cache) > limit:
            self._cache.popitem(last=False)

    def cache_size(self):
        return len(self._cache)

    def capture_metadata(self):
        """Segment/guard metadata for the capture planner
        (``analysis.capture_plan``): per recorded path, the compiled
        segments (op names, arity) and the guards between them — the
        ground-truth segmentation whole-step capture starts from — plus
        the reasons any recording stayed eager (dynamic PTC002-class
        evidence: RNG, in-place mutation, oversized guards)."""
        paths = []
        for key, val in self._cache.items():
            if val == "eager":
                paths.append({"kind": "eager"})
                continue
            rec = val.rec
            paths.append({
                "kind": "compiled",
                "segments": [
                    {"n_ops": len(seg.ops),
                     "ops": [op.name for op in seg.ops],
                     "inputs": len(seg.input_ids),
                     "ext_tensors": len(seg.ext_tensors),
                     "outputs": len(seg.output_ids)}
                    for seg in rec.segments],
                "guards": [{"kind": g.kind, "nbytes": len(g.value)}
                           for g in rec.guards],
                "ext_guards": len(rec.ext_guards),
            })
        return {"name": self._name,
                "cache_entries": len(self._cache),
                "paths": paths,
                "fallback_reasons": dict(self._fallback_reasons)}

    @staticmethod
    def _tensor_args(args, kwargs):
        return [a for a in args if isinstance(a, Tensor)] + \
            [kwargs[k] for k in sorted(kwargs)
             if isinstance(kwargs[k], Tensor)]

    # -- record ------------------------------------------------------------
    def _record(self, sig, args, kwargs):
        rec_obj = _Recorder()
        tensor_args = self._tensor_args(args, kwargs)
        input_ids = [rec_obj.tag(t) for t in tensor_args]
        with _RecorderSession(rec_obj):
            result = self._fn(*args, **kwargs)
        rec = rec_obj.finish(result)
        if rec.replayable:
            # per-path guard budget: every replay re-validates the whole
            # guard set, so a path with kilobytes of guards pays more in
            # validation than compiled replay saves
            budget = max(int(_guard_budget_flag.value or 0), 0)
            total = sum(len(g.value) for g in rec.guards) + \
                sum(len(v) for _, v in rec.ext_guards)
            if budget and total > budget:
                rec.replayable = False
                rec.why_not = (
                    f"guard budget exceeded ({total}B of guard values > "
                    f"FLAGS_sot_guard_budget={budget}B)")
        guard_path = tuple(g.value for g in rec.guards)
        if rec.replayable:
            path = _CompiledPath(rec, input_ids, self._name)
            self._cache_put((sig, guard_path), path)
        else:
            # marker key is distinct from every guard-path key, so a
            # non-replayable BRANCH never evicts compiled sibling paths
            self._cache_put((sig, "eager"), "eager")
            # bounded cardinality: why_not can embed per-call values
            # (guard byte sizes) — past the cap, collapse to <other>
            reason = rec.why_not
            _count_fallback(_fallback_category(reason), self._name)
            if reason not in self._fallback_reasons and \
                    len(self._fallback_reasons) >= 16:
                reason = "<other>"
            self._fallback_reasons[reason] = \
                self._fallback_reasons.get(reason, 0) + 1
            if self._name not in self._warned:
                self._warned.add(self._name)
                warnings.warn(
                    f"to_static({self._name}): trace is not replayable "
                    f"({rec.why_not}); running eagerly (graph-break "
                    f"fallback)", stacklevel=3)
        return result

    # -- call --------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        # nested under an active recording (outer SOTFunction or static
        # program tape): run the plain function so the OUTER recorder sees
        # every op — an inner replay would hide ops behind opaque ext refs
        if autograd_mod._op_recorder is not None:
            return self._fn(*args, **kwargs)
        if not _capture_flag.value:
            # kill switch: today's per-chain eager fusion, bit-for-bit
            return self._fn(*args, **kwargs)
        if self._bucket is not None:
            args = self._bucket.apply(args)
        sig = self._signature(args, kwargs)
        tensor_args = self._tensor_args(args, kwargs)
        # candidate paths for this signature, most-recently-used first.
        # Each replay re-checks its own guards, so trying candidates in
        # order is always correct; a taken-branch set of size k costs at
        # most k replay attempts before falling back to re-recording.
        candidates = [(k, v) for k, v in reversed(self._cache.items())
                      if k[0] == sig and v != "eager"]
        for key, path in candidates:
            ok, result = path.replay(tensor_args)
            if ok:
                self._cache.move_to_end(key)
                return result
        if candidates:
            # every cached path for this signature missed: the branch
            # re-records below (discard-and-retrace)
            _M_retraces.inc()
            _flight.record("sot", "retrace", fn=self._name,
                           candidates=len(candidates))
        if self._cache.get((sig, "eager")) == "eager":
            # a known non-replayable branch for this signature: plain
            # eager, skip the recording bookkeeping
            self._cache.move_to_end((sig, "eager"))
            return self._fn(*args, **kwargs)
        return self._record(sig, args, kwargs)


def sot_compile(fn=None, bucket_policy: Optional[BucketPolicy] = None):
    """Decorator form: @sot_compile or sot_compile(fn, bucket_policy=...)."""
    def deco(f):
        return SOTFunction(f, bucket_policy)
    if fn is not None:
        return deco(fn)
    return deco


def capture(fn=None, bucket_policy: Optional[BucketPolicy] = None,
            name: Optional[str] = None):
    """``@sot.capture`` — production whole-step capture for an arbitrary
    step callable: record once, replay as lazily-compiled segments with
    speculatively validated guards, fall back per-chain to eager fusion
    on unreplayable events (RNG/mutation/host I/O) with a counted
    reason. ``FLAGS_sot_capture=0`` restores plain eager execution.
    (For the known fwd+bwd+optimizer train-step shape, use
    :class:`CapturedStep` / ``jit.TrainStep`` — those run the whole step
    as ONE donated executable instead of per-segment replay.)"""
    def deco(f):
        return SOTFunction(f, bucket_policy, name=name)
    if fn is not None:
        return deco(fn)
    return deco


def jit_named(fn, name: str, **jit_kwargs):
    """``jax.jit`` of ``fn`` under ``name``: the XLA module, and with it
    the device trace's module line, reads ``jit_<name>`` (every character
    that is no letter, digit or ``_`` becomes ``_``) instead of the Python
    function's name, so two programs built from one function (a prefill
    per bucket) stay apart in a trace."""
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = "".join(
        c if c.isalnum() or c == "_" else "_" for c in name)
    named.__wrapped__ = fn
    return jax.jit(named, **jit_kwargs)


def capture_jit(fn, donate_argnums=(), name: Optional[str] = None,
                warm: Optional[Dict[str, Any]] = None):
    """Wrap an already-whole-step function (e.g. the serving decode
    body) as a captured executable: ``jax.jit`` + SOT capture
    accounting — the first (trace+compile) execution journals a
    ``sot.capture_compile`` flight event and every call counts into
    ``sot.captured_steps_total`` while ``FLAGS_sot_capture`` is on.
    Behavior is identical to ``jax.jit`` (the kill switch only mutes
    the accounting — the step was already a single executable).
    ``warm`` (a small JSON-able dict, e.g. the serving engines'
    program geometry) records the first compile into the warm-bundle
    manifest (``jit.warmup.note_program``) so a boot pre-warm can
    rebuild it AOT."""
    from .warmup import ensure_executable_cache, note_program
    ensure_executable_cache()
    nm = name or getattr(fn, "__name__", "fn")
    jf = jit_named(fn, nm, donate_argnums=donate_argnums)
    compiled = [False]
    called = [False]

    def call(*args, **kwargs):
        if called[0]:
            out = jf(*args, **kwargs)
        else:
            # the first call traces and compiles (or reads the
            # persistent cache): a span with the program's name, under
            # the iteration or step that paid for it
            with RecordEvent("jit.compile", name=nm, kind="capture_jit"):
                out = jf(*args, **kwargs)
            called[0] = True
        # accounting only (execution above is a bare jax.jit either
        # way); the kill switch mutes ALL of it, and the compile event
        # lands only after the first call actually succeeded
        if _capture_flag.value:
            if not compiled[0]:
                compiled[0] = True
                _M_step_compiles.inc()
                _flight.record("sot", "capture_compile", fn=nm)
                if warm is not None:
                    note_program("serving", nm, {"meta": dict(warm)})
            if _M_flag.value:
                _M_captured._v += 1  # inline fast cell: hot path
        return out

    call._jitted = jf
    call.__name__ = nm
    return call


# ---------------------------------------------------------------------------
# whole-step capture: fwd + bwd + optimizer as ONE donated executable
# ---------------------------------------------------------------------------

class CapturedStep:
    """Execute a train (or eval) step as ONE cached, buffer-donated
    jitted executable — the Fusion III engine behind
    ``hapi.Model.train_batch``/``eval_batch`` and ``jit.TrainStep``.

    The capture plan (``analysis.capture_plan``, PR 7) proved a llama
    ``Model.fit`` step segments CONSISTENT: every flush boundary is
    absorbed by capture, the loss fetch is HOISTABLE, and the donated
    optimizer step is the tail segment. This class executes that plan:

    * **One program** per *signature* — batch shapes/dtypes, layer
      train/eval modes, the trainable set, optimizer type + static
      hyperparameters + per-param weight-decay statics, clip spec. A
      signature change is the guard miss: the stale program stays
      cached (LRU, ``FLAGS_sot_capture_cache``) and the new signature
      retraces.
    * **Compile policy** (``strict`` mode): first sighting of a
      signature runs today's eager path (and warms optimizer state),
      the second builds + compiles the whole-step program, later calls
      hit the cache — the fusion plane's compile-on-second-sighting.
    * **Donation** — params, buffers, optimizer state and the
      device-resident RNG carry are donated; leaves aliased by a live
      ``detach()`` snapshot are copied first (the PR 5 alias-registry
      contract), and pending eager-fusion chains are flushed through
      ``fusion.capture_handoff()`` before anything is invalidated.
    * **Hoisted loss** — the returned loss is a LAZY device scalar
      (a ``Tensor``); nothing inside the captured region syncs to
      host. Fetch it at the logging boundary (``float(loss)``).
    * **AMP + GradScaler** capture too (the PR 10 ``amp`` residue,
      closed): the autocast regime joins the signature and the forward
      traces under the ambient thread-local; with ``step(...,
      scaler=)`` the whole iteration — loss scale, backward, unscale +
      finite check, device-masked skip, dynamic-scale bookkeeping —
      is the one donated executable, scaler counters riding as 0-d
      device carries.
    * **Fallbacks** are total and counted (``sot.fallbacks_total``
      {reason} + a flight event): debug flags
      (check_nan_inf / benchmark / retain-all), layer or tensor hooks,
      non-fusable optimizers, unknown clip objects, non-static
      hyperparams, aliased donation leaves, pre-accumulated grads,
      overridden scaler/optimizer steps (``scaler``) —
      each returns ``None`` and the caller runs today's eager path.
    """

    def __init__(self, network, loss_fn=None, optimizer=None,
                 mean_reduce: bool = False, cast_loss_f32: bool = False,
                 donate: bool = True, strict: bool = True,
                 bucket_policy: Optional[BucketPolicy] = None,
                 name: str = "step", build_kind: str = "sot_capture"):
        from .api import _Swap
        self.network = network
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._swap = _Swap(network)
        self._mean_reduce = mean_reduce
        self._cast_f32 = cast_loss_f32
        self._donate = donate
        self._strict = strict
        self._bucket = bucket_policy
        self._name = name
        self._build_kind = build_kind
        self._sublayers = list(network.sublayers(include_self=True))
        self._cache: "OrderedDict[tuple, Any]" = OrderedDict()
        # device-resident RNG carry: (root key, step counter), donated
        # through the program so dropout re-randomizes per step without
        # a per-step host->device key upload
        self._rng = None
        self._rng_epoch = None
        self._uncalled = None   # (program, kind) built and not yet called
        self.stats: Dict[str, Any] = {
            "captured_steps": 0, "compiles": 0, "cache_hits": 0,
            "eager_steps": 0, "fallbacks": {}}

    # -- gating ------------------------------------------------------------
    def _gate(self, train: bool, scaler=None) -> Optional[str]:
        """Capture preconditions. None = capturable; otherwise the
        fallback reason (the caller runs today's eager path). AMP
        autocast is NOT a gate anymore: the regime is part of the
        program signature and the forward traces under the ambient
        thread-local, so AMP (and GradScaler, via the ``scaler``
        carry) steps capture like plain ones."""
        if scaler is not None and \
                scaler.capture_statics(self.optimizer) is None:
            # an overridden scaler/optimizer step must run as written
            return "scaler"
        if _flag_registry["check_nan_inf"].value:
            return "nan_check"
        if _flag_registry["benchmark"].value:
            return "benchmark"
        if _flag_registry["retain_grad_for_all_tensor"].value:
            return "retain_grad"
        for lyr in self._sublayers:
            if lyr._forward_pre_hooks or lyr._forward_post_hooks:
                return "hooks"
        for p in self._swap.params.values():
            if p._hooks:
                return "hooks"
            if p._dist_attr is not None:
                return "dist"
            if isinstance(p._data, jax.core.Tracer):
                return "tracer"
        # a layer added/removed after this engine was built would be
        # invisible to the functionalized program — cheap count gate
        if sum(1 for _ in self.network.named_parameters()) != \
                len(self._swap.params):
            return "network_changed"
        if train:
            opt = self.optimizer
            if opt is None:
                return "no_optimizer"
            if getattr(opt, "_fusable_step", True) is False:
                return "optimizer"
            from ..utils.clip_grad import clip_spec
            if clip_spec(opt._grad_clip, exact=True) is None:
                return "grad_clip"
            from ..optimizer.fused_step import _hyper_key
            if _hyper_key(opt) is None:
                return "hyper"
            # the captured tail updates the NETWORK's trainables; the
            # eager step updates the OPTIMIZER's list — they must be
            # the same set or the semantics differ
            if {id(p) for p in opt._parameter_list
                if not p.stop_gradient} != \
                    {id(p) for p in self._swap.params.values()
                     if not p.stop_gradient}:
                return "param_set"
            if any(not p.stop_gradient and p.grad is not None
                   for p in self._swap.params.values()):
                # eager backward ACCUMULATES into primed grads; the
                # captured program starts from zero — not equivalent
                return "pending_grads"
        return None

    def _fallback(self, reason: str) -> None:
        self.stats["fallbacks"][reason] = \
            self.stats["fallbacks"].get(reason, 0) + 1
        _count_fallback(reason, self._name)

    # -- signature ---------------------------------------------------------
    def _tkeys(self):
        return [k for k in sorted(self._swap.params)
                if not self._swap.params[k].stop_gradient]

    def _signature(self, kind: str, arrays, n_ins: int, tkeys,
                   scaler_statics=None) -> Optional[tuple]:
        from ..amp.auto_cast import amp_signature
        modes = tuple(lyr.training for lyr in self._sublayers)
        # n_ins is part of the key: same shapes with a different
        # input/label split are DIFFERENT programs. The AMP regime is
        # a guard too: a program traced under autocast must never
        # serve a plain call (and vice versa).
        parts: List[Any] = [kind, n_ins, modes, tuple(tkeys),
                            amp_signature()]
        for a in arrays:
            parts.append((tuple(a.shape), str(a.dtype)))
        if kind in ("train", "train_scaled"):
            from ..optimizer.fused_step import _hyper_key, _param_statics
            from ..utils.clip_grad import clip_spec
            opt = self.optimizer
            statics = _param_statics(
                opt, [self._swap.params[k] for k in tkeys])
            if statics is None and self._strict:
                return None  # caller falls back (param_static)
            parts.append((type(opt).__qualname__, _hyper_key(opt),
                          statics,
                          clip_spec(opt._grad_clip,
                                    exact=self._strict)))
        if scaler_statics is not None:
            parts.append(("scaler",) + tuple(scaler_statics))
        return tuple(parts)

    # -- batch plumbing ----------------------------------------------------
    def _arrays(self, values) -> Optional[list]:
        """Raw device/host arrays for the batch; lazy fusion chains
        hand off at the capture boundary (flush reason sot_capture)."""
        from ..core import fusion
        out = []
        for v in values:
            if isinstance(v, Tensor):
                if v._lazy is not None:
                    fusion.materialize_tensor(v, "sot_capture")
                d = v._data
                if self._strict and isinstance(d, jax.core.Tracer):
                    return None  # under an outer trace: stay eager
                out.append(d)
            elif isinstance(v, jax.Array):
                out.append(v)
            elif hasattr(v, "aval"):  # raw tracer (nested jit)
                out.append(v)
            else:
                out.append(jnp.asarray(np.asarray(v)))
        return out

    # -- overridable build hooks (the distributed step specializes) --------
    def _value_and_grads(self, loss_of, train_p, buffers, batch, labels,
                         key):
        """Trace-time hook: loss + grads of the trainable tree for one
        step. ``loss_of(tp, bufs, mb, lbls, k_) -> (primal, (loss,
        new_buffers))`` — the primal is what backward differentiates
        (the SCALED loss under a GradScaler), the aux loss is what the
        caller sees. The distributed subclass overrides this with the
        gradient-merge scan."""
        (_, (loss, new_buffers)), grads = jax.value_and_grad(
            loss_of, has_aux=True)(train_p, buffers, batch, labels, key)
        return loss, grads, new_buffers

    def _sync_grads(self, grads, tkeys):
        """Trace-time hook between backward and the optimizer tail:
        the distributed subclass emits bucketed gradient collectives
        here (first-class DAG nodes that overlap remaining backward
        compute). Single-chip base: identity."""
        return grads

    # -- program build -----------------------------------------------------
    def _build(self, kind: str, n_ins: int, scaler_statics=None):
        from .api import _notify_build, _tree_unwrap
        from ..core.autograd import no_grad
        _notify_build(self._build_kind)
        network, loss_fn, opt = self.network, self.loss_fn, self.optimizer
        swap = self._swap
        mean_reduce, cast_f32 = self._mean_reduce, self._cast_f32

        def loss_value(out, lbls):
            with jax.named_scope("loss"):
                loss_t = loss_fn(out, *lbls) if loss_fn is not None \
                    else out
                ld = loss_t._data
                if mean_reduce and ld.ndim > 0:
                    ld = ld.mean()
                if cast_f32:
                    ld = ld.astype(jnp.float32)
            return ld

        if kind == "eval":
            def eval_fn(params, buffers, key, *batch):
                with no_grad(), random_mod.key_stream(key):
                    ins = tuple(Tensor(b) for b in batch[:n_ins])
                    lbls = tuple(Tensor(b) for b in batch[n_ins:])
                    out, new_buffers = swap.run(params, buffers,
                                                network.__call__, *ins)
                    ld = loss_value(out, lbls) if \
                        (loss_fn is not None and lbls) else None
                return _tree_unwrap(out), ld, new_buffers

            return jit_named(eval_fn, f"{self._name}_eval")

        scaled = kind == "train_scaled"
        tkeys = self._tkeys()
        trainable = set(tkeys)
        param_objs = [swap.params[k] for k in tkeys]
        from ..utils.clip_grad import clip_spec
        cspec = clip_spec(opt._grad_clip, exact=self._strict) or ()

        def run_step(params, buffers, states, lr, key, batch,
                     scale=None):
            """fwd + bwd + (unscale/check) + optimizer tail — shared
            by the plain and the GradScaler-scaled programs."""
            train_p = {k: v for k, v in params.items() if k in trainable}
            frozen_p = {k: v for k, v in params.items()
                        if k not in trainable}

            def loss_of(tp, bufs, mb, lbls, k_):
                full = {**tp, **frozen_p}
                with no_grad(), random_mod.key_stream(k_):
                    ins = tuple(Tensor(b) for b in mb)
                    lbl_t = tuple(Tensor(x) for x in lbls)
                    out, new_buffers = swap.run(full, bufs,
                                                network.__call__, *ins)
                    ld = loss_value(out, lbl_t)
                # the primal backward differentiates is the SCALED loss
                # (eager parity: scaler.scale(loss).backward()); the
                # scale is cast into the loss dtype exactly like
                # GradScaler.scale
                primal = ld if scale is None else \
                    ld * scale.astype(ld.dtype)
                return primal, (ld, new_buffers)

            loss, grads, new_buffers = self._value_and_grads(
                loss_of, train_p, buffers, tuple(batch[:n_ins]),
                tuple(batch[n_ins:]), key)
            grads = self._sync_grads(grads, tkeys)
            g_leaves = [grads[k] for k in tkeys]
            p_leaves = [params[k] for k in tkeys]
            found = None
            if scale is not None:
                # grad unscale + global finite check: the SAME numeric
                # definition as GradScaler.unscale_/try_step_scaled
                from ..optimizer.fused_step import _unscale_fn
                g_leaves, found = _unscale_fn(
                    g_leaves, jnp.float32(1.0) / scale)
            from ..optimizer.fused_step import apply_update_tail
            with jax.named_scope("optimizer"):
                new_ps, new_ss = apply_update_tail(
                    opt, param_objs, p_leaves, g_leaves, states, lr,
                    cspec)
            if found is not None:
                # conditional skip ON DEVICE (the fused scaled step's
                # mask): non-finite grads keep every param/state leaf
                new_ps = [jnp.where(found, p, q)
                          for p, q in zip(p_leaves, new_ps)]
                new_ss = [{k2: jnp.where(found, st[k2], v)
                           for k2, v in ns.items()}
                          for st, ns in zip(states, new_ss)]
            new_params = dict(params)
            for k, v in zip(tkeys, new_ps):
                new_params[k] = v
            return loss, new_params, new_buffers, new_ss, found

        if not scaled:
            def step_fn(params, buffers, states, lr, rng, *batch):
                root, count = rng
                key = jax.random.fold_in(root, count)
                loss, new_params, new_buffers, new_ss, _ = run_step(
                    params, buffers, states, lr, key, batch)
                return (loss, new_params, new_buffers, new_ss,
                        (root, count + jnp.uint32(1)))

            donate = (0, 1, 2, 4) if self._donate else ()
            return jit_named(step_fn, self._name, donate_argnums=donate)

        # train_scaled: the whole GradScaler iteration in ONE program —
        # scale, backward, unscale + finite check, masked update, and
        # the dynamic-loss-scale bookkeeping on donated 0-d carries
        from ..amp.grad_scaler import _scale_update
        dynamic, incr_ratio, decr_ratio, incr_every, decr_every = \
            scaler_statics

        def scaled_step_fn(params, buffers, states, lr, rng, carry,
                           *batch):
            root, count = rng
            key = jax.random.fold_in(root, count)
            scale, good, bad = carry
            loss, new_params, new_buffers, new_ss, found = run_step(
                params, buffers, states, lr, key, batch, scale=scale)
            if dynamic:
                new_scale, new_good, new_bad = _scale_update(
                    found, scale, good, bad,
                    jnp.float32(incr_ratio), jnp.float32(decr_ratio),
                    jnp.int32(incr_every), jnp.int32(decr_every))
            else:
                new_scale, new_good, new_bad = scale, good, bad
            return (loss, new_params, new_buffers, new_ss,
                    (root, count + jnp.uint32(1)),
                    (new_scale, new_good, new_bad), found)

        donate = (0, 1, 2, 4, 5) if self._donate else ()
        return jit_named(scaled_step_fn, f"{self._name}_scaled",
                         donate_argnums=donate)

    def _get_program(self, kind: str, sig, n_ins: int,
                     scaler_statics=None, arrays=None):
        """Compile-on-second-sighting (strict mode): returns the jitted
        program, or None when this signature should run eager this
        call."""
        entry = self._cache.get(sig)
        if entry is not None and entry is not _SEEN_STEP:
            self._cache.move_to_end(sig)
            self.stats["cache_hits"] += 1
            _M_hits.inc()
            return entry
        if entry is None and self._strict:
            self._cache[sig] = _SEEN_STEP
            self._trim()
            return None
        from .warmup import (ensure_executable_cache, note_program,
                             sig_to_json)
        ensure_executable_cache()
        jitted = self._build(kind, n_ins, scaler_statics)
        self._uncalled = (jitted, kind)
        self._cache[sig] = jitted
        self._trim()
        self.stats["compiles"] += 1
        _M_step_compiles.inc()
        _flight.record("sot", "capture_compile", fn=self._name,
                       kind=kind)
        # warm-bundle record: enough to rebuild this program AOT at a
        # future boot (prewarm), plus the exact signature so the warm
        # program pre-populates the in-memory cache too
        note_program("captured_step", self._name, {
            "build": kind, "n_ins": n_ins,
            "batch": [[list(a.shape), str(a.dtype)]
                      for a in (arrays or [])],
            "scaler": (list(scaler_statics) if scaler_statics
                       else None),
            "sig": sig_to_json(sig)})
        return jitted

    def _call(self, jitted, *args):
        """Call a program. The first call of one `_get_program` has just
        built traces and compiles it (or reads the persistent cache): a
        `jit.compile` span with the step's name, beside the
        `capture_compile` flight event."""
        if self._uncalled is None or self._uncalled[0] is not jitted:
            return jitted(*args)
        kind, self._uncalled = self._uncalled[1], None
        with RecordEvent("jit.compile", name=self._name, kind=kind):
            return jitted(*args)

    def _trim(self):
        cap = max(int(_capture_cache_flag.value or 8), 1)
        while len(self._cache) > cap:
            self._cache.popitem(last=False)

    # -- donation-safe leaf gathering --------------------------------------
    def _opt_state_for(self, p):
        """Optimizer slot state for one param (creation hook: the
        distributed subclass co-shards freshly created slots with the
        parameter's own placement — the ZeRO contract)."""
        return self.optimizer._state_for(p)

    @staticmethod
    def _safe_leaf(v):
        if isinstance(v, Tensor):
            v = v._data
        if not isinstance(v, jax.Array):
            v = jnp.asarray(v)
        if tensor_mod.buffer_has_alias(v):
            # a live detach() snapshot shares this buffer: donation
            # would delete it under the alias — donate a copy instead
            v = jnp.copy(v)
        return v

    def _gather(self, train: bool, tkeys=None):
        """(params, buffers, states) leaves for one call, alias-copied
        for donation. Two donated leaves sharing one buffer (tied
        storage — XLA rejects double donation): strict mode returns
        None (eager fallback); non-strict (TrainStep, no eager path)
        copies the duplicate and proceeds."""
        swap, opt = self._swap, self.optimizer
        params = {k: self._safe_leaf(t._data)
                  for k, t in swap.params.items()}
        buffers = {k: self._safe_leaf(t._data)
                   for k, t in swap.buffers.items()}
        states = []
        if train:
            for k in (self._tkeys() if tkeys is None else tkeys):
                st = self._opt_state_for(swap.params[k])
                states.append({kk: self._safe_leaf(vv)
                               for kk, vv in st.items()})
        if self._donate:
            seen = set()

            def dedup(leaf):
                if id(leaf) in seen:
                    return None if self._strict else jnp.copy(leaf)
                seen.add(id(leaf))
                return leaf

            for d in (params, buffers):
                for k, leaf in d.items():
                    leaf = dedup(leaf)
                    if leaf is None:
                        return None
                    d[k] = leaf
            for st in states:
                for k, leaf in st.items():
                    leaf = dedup(leaf)
                    if leaf is None:
                        return None
                    st[k] = leaf
        return params, buffers, states

    def _next_rng(self):
        if self._rng is None or \
                self._rng_epoch != random_mod.seed_epoch():
            self._rng = (random_mod.next_key(), jnp.uint32(0))
            self._rng_epoch = random_mod.seed_epoch()
        return self._rng

    # -- entry points ------------------------------------------------------
    def step(self, inputs, labels=(), scaler=None):
        """One captured train step over ``inputs``/``labels`` (lists of
        tensors/arrays). Returns the LAZY device loss ``Tensor``, or
        ``None`` when the caller must run today's eager path (kill
        switch, gate fallback, first sighting). In non-strict mode
        (``jit.TrainStep`` — an EXPLICIT whole-step API with no eager
        fallback) the kill switch and the gates do not apply.

        With ``scaler`` (an enabled ``amp.GradScaler``) the captured
        program is the WHOLE AMP iteration: loss scale, backward,
        grad unscale + finite check, device-masked update and the
        dynamic-loss-scale bookkeeping — the scaler's scale/counters
        ride as donated 0-d device carries and the skip decision
        never syncs to host.

        Two host spans split the call: ``train.step.guard`` (gates,
        signature, program lookup, leaf gathering) and
        ``train.step.enqueue`` (the jitted call until it returns,
        unblocked)."""
        with RecordEvent("train.step.guard"):
            ready = self._guard_step(inputs, labels, scaler)
        if ready is None:
            return None
        jitted, arrays, tkeys, scaler, (params, buffers, states) = ready
        from ..optimizer.fused_step import _lr_device
        opt, swap = self.optimizer, self._swap
        with RecordEvent("train.step.enqueue"):
            if scaler is None:
                (loss, new_params, new_buffers, new_ss,
                 self._rng) = self._call(
                    jitted, params, buffers, states, _lr_device(opt),
                    self._next_rng(), *arrays)
            else:
                # donated carries: a live handle on the scale buffer (a
                # held get_loss_scaling snapshot) copies before donation
                carry = tuple(self._safe_leaf(v)
                              for v in scaler.capture_carry())
                (loss, new_params, new_buffers, new_ss, self._rng,
                 new_carry, found) = self._call(
                    jitted, params, buffers, states, _lr_device(opt),
                    self._next_rng(), carry, *arrays)
                scaler.absorb_captured(new_carry, found)
        for k, t in swap.params.items():
            t._data = new_params[k]
        for k, t in swap.buffers.items():
            t._data = new_buffers[k]
        for k, ns in zip(tkeys, new_ss):
            opt._states[id(swap.params[k])] = ns
        opt._global_step += 1
        if self._strict:  # hapi semantics: step() + clear_grad()
            for p in opt._parameter_list:
                p.grad = None
        self.stats["captured_steps"] += 1
        if _M_flag.value:
            _M_captured._v += 1  # inline fast cell: per-step hot path
        return Tensor(loss)

    def _guard_step(self, inputs, labels, scaler):
        """Everything of ``step`` before the jitted call: the program
        and its donation-safe arguments, or None for the eager path."""
        if scaler is not None and not scaler.is_enable():
            scaler = None
        if self._strict:
            if not _capture_flag.value:
                return None
            if autograd_mod._op_recorder is not None:
                return None  # an outer recorder must see the real ops
            reason = self._gate(train=True, scaler=scaler)
            if reason is not None:
                self._fallback(reason)
                return None
        scaler_statics = None
        if scaler is not None:
            scaler_statics = scaler.capture_statics(self.optimizer)
            if scaler_statics is None:
                # non-strict callers have no eager path to fall back to
                raise RuntimeError(
                    "CapturedStep: this scaler/optimizer pairing "
                    "(overridden step()/unscale_()/update(), or a "
                    "pending manual unscale_) cannot run as a captured "
                    "program")
        if self._bucket is not None:
            inputs = list(self._bucket.apply(tuple(inputs)))
        arrays = self._arrays(list(inputs) + list(labels))
        if arrays is None:
            self._fallback("tracer")
            return None
        tkeys = self._tkeys()
        kind = "train" if scaler is None else "train_scaled"
        sig = self._signature(kind, arrays, len(inputs), tkeys,
                              scaler_statics)
        if sig is None:
            self._fallback("param_static")
            return None
        jitted = self._get_program(kind, sig, len(inputs),
                                   scaler_statics, arrays=arrays)
        if jitted is None:
            self.stats["eager_steps"] += 1
            return None
        gathered = self._gather(train=True, tkeys=tkeys)
        if gathered is None:
            self._fallback("aliased")
            return None
        from ..core import fusion
        fusion.capture_handoff()
        return jitted, arrays, tkeys, scaler, gathered

    def forward(self, inputs, labels=()):
        """One captured eval/inference forward. Returns ``(out, loss)``
        — ``out`` re-wrapped as Tensors, ``loss`` a lazy device scalar
        or None — or ``None`` for the eager path."""
        if not _capture_flag.value:
            return None
        if autograd_mod._op_recorder is not None:
            return None
        reason = self._gate(train=False)
        if reason is not None:
            self._fallback(reason)
            return None
        if self._bucket is not None:
            inputs = list(self._bucket.apply(tuple(inputs)))
        arrays = self._arrays(list(inputs) + list(labels))
        if arrays is None:
            self._fallback("tracer")
            return None
        sig = self._signature("eval", arrays, len(inputs),
                              self._tkeys())
        jitted = self._get_program("eval", sig, len(inputs),
                                   arrays=arrays)
        if jitted is None:
            self.stats["eager_steps"] += 1
            return None
        from ..core import fusion
        fusion.capture_handoff()
        swap = self._swap
        params = {k: t._data for k, t in swap.params.items()}
        buffers = {k: t._data for k, t in swap.buffers.items()}
        root, count = self._next_rng()
        key = jax.random.fold_in(root, count)
        self._rng = (root, count + jnp.uint32(1))
        out, loss, new_buffers = self._call(jitted, params, buffers, key,
                                            *arrays)
        for k, t in swap.buffers.items():
            t._data = new_buffers[k]
        from .api import _tree_wrap
        self.stats["captured_steps"] += 1
        if _M_flag.value:
            _M_captured._v += 1
        return _tree_wrap(out), (None if loss is None else Tensor(loss))

    def prewarm(self, entry) -> None:
        """Boot pre-warm from one warm-bundle ``captured_step`` entry:
        rebuild the recorded program and AOT-compile it over abstract
        batch args (``lower().compile()`` — with the persistent
        executable cache enabled this is a disk read, not an XLA
        compile), then pre-populate the in-memory program cache under
        the recorded signature so the first real step is a cache hit
        (strict mode's first-sighting eager run is skipped too). A
        signature that no longer matches this model/optimizer merely
        leaves an unused cache entry — the real call still compiles
        against the disk cache. Raises on unreplayable entries; the
        caller (``warmup.prewarm``) counts and continues."""
        kind = entry.get("build")
        if kind not in ("train", "eval", "train_scaled"):
            raise ValueError(f"unknown captured_step build {kind!r}")
        n_ins = int(entry.get("n_ins", 1))
        batch = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
                 for s, d in entry.get("batch", [])]
        scaler_statics = entry.get("scaler")
        if scaler_statics is not None:
            scaler_statics = tuple(scaler_statics)
        jitted = self._build(kind, n_ins, scaler_statics)
        swap = self._swap
        params = {k: t._data for k, t in swap.params.items()}
        buffers = {k: t._data for k, t in swap.buffers.items()}
        # helper args reuse the live step's own constructors
        # (next_key / the 0-d uint32 counter) or pure avals, so the
        # pre-warm never compiles a helper program the bundle's
        # writer didn't already write. The key draws are rolled back
        # after: pre-warm must not advance the seeded RNG stream, or a
        # warm boot's training randomness diverges from an identically
        # seeded cold boot.
        rng_state = random_mod.get_rng_state()
        try:
            if kind == "eval":
                jitted.lower(params, buffers, random_mod.next_key(),
                             *batch).compile()
            else:
                states = []
                for k in self._tkeys():
                    st = self._opt_state_for(swap.params[k])
                    states.append({kk: self._safe_leaf(vv)
                                   for kk, vv in st.items()})
                from ..optimizer.fused_step import _lr_device
                lr = _lr_device(self.optimizer)
                rng = (random_mod.next_key(), jnp.uint32(0))
                if kind == "train":
                    jitted.lower(params, buffers, states, lr, rng,
                                 *batch).compile()
                else:
                    carry = (jax.ShapeDtypeStruct((), jnp.float32),
                             jax.ShapeDtypeStruct((), jnp.int32),
                             jax.ShapeDtypeStruct((), jnp.int32))
                    jitted.lower(params, buffers, states, lr, rng,
                                 carry, *batch).compile()
        finally:
            random_mod.set_rng_state(rng_state)
        sig = entry.get("sig")
        if sig is not None:
            from .warmup import sig_from_json
            self._cache[sig_from_json(sig)] = jitted
            self._trim()
        _flight.record("warmup", "captured_step", fn=self._name,
                       kind=kind)

    def compile_stats(self, inputs, labels=()):
        """Compile the train step for these batch shapes without running
        it and return XLA's per-device memory analysis (TrainStep's
        compile_stats contract)."""
        arrays = self._arrays(list(inputs) + list(labels))
        jitted = self._build("train", len(inputs))
        gathered = self._gather(train=True)
        params, buffers, states = gathered
        from ..optimizer.fused_step import _lr_device
        probe_rng = (jax.random.key(0), jnp.uint32(0))
        return jitted.lower(
            params, buffers, states, _lr_device(self.optimizer),
            probe_rng, *arrays).compile().memory_analysis()


_SEEN_STEP = object()  # first-sighting marker: signature noted, ran eager
