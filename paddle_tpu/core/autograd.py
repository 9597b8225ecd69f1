"""Eager autograd engine.

The reference implements define-by-run autograd with generated C++ GradNodes
and a queue-based backward (ref: paddle/fluid/eager/grad_node_info.h:197,
paddle/fluid/eager/backward.cc:105 RunBackward). The TPU-native design keeps
the same user semantics (``stop_gradient``, ``.grad`` accumulation,
``loss.backward()``, hooks) but each op's gradient comes from ``jax.vjp`` of
its pure-JAX implementation taken at forward time — no per-op handwritten
grad kernels, and the residuals live in the vjp closure (the analog of the
reference's TensorWrapper saved-tensor scheme, ref: eager/tensor_wrapper.h).

Under ``jax.jit`` tracing (the performance path) this tape is bypassed
entirely: gradients come from ``jax.grad`` over the functionalized program.
"""
from __future__ import annotations

import os
import threading
import time as _time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import memory as _memory
from .flags import _registry as _flag_registry
from ..observability import metrics as _om

__all__ = [
    "no_grad", "enable_grad", "is_grad_enabled", "set_grad_enabled",
    "GradNode", "apply_op", "backward", "grad", "flush_nan_checks",
]


class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_state = _GradState()


def is_grad_enabled() -> bool:
    return _state.enabled


def set_grad_enabled(mode: bool):
    _state.enabled = bool(mode)


class _GradModeGuard:
    def __init__(self, mode: bool):
        self._mode = mode

    def __enter__(self):
        self._prev = _state.enabled
        _state.enabled = self._mode
        return self

    def __exit__(self, *exc):
        _state.enabled = self._prev
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with type(self)(self._mode):
                return fn(*args, **kwargs)

        return wrapper


def no_grad(func=None):
    """Context manager / decorator disabling tape recording.
    ref: python/paddle/base/dygraph/base.py no_grad_
    """
    guard = _GradModeGuard(False)
    if func is not None:
        return guard(func)
    return guard


def enable_grad(func=None):
    guard = _GradModeGuard(True)
    if func is not None:
        return guard(func)
    return guard


class GradNode:
    """One recorded op: holds the vjp closure and edges to input tensors.
    ref-analog: paddle/fluid/eager/grad_node_info.h GradNodeBase + Edge.
    """

    __slots__ = ("vjp_fn", "inputs", "out_avals", "name", "fn", "datas",
                 "kwargs", "diff_idx", "__weakref__")

    def __init__(self, vjp_fn, inputs, out_avals, name, fn=None, datas=None,
                 kwargs=None, diff_idx=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs          # tuple of differentiable input Tensors
        self.out_avals = out_avals    # (shape, dtype) aval per output
        self.name = name
        # Retained for create_graph=True: re-running the op's forward under
        # the tape makes the backward step differentiable w.r.t. primals too
        # (the vjp closure alone only captures the linear cotangent part).
        # ref-analog: eager/backward.cc:439 general_grad (grad-of-grad).
        self.fn = fn
        self.datas = datas            # full positional arg list (raw arrays)
        self.kwargs = kwargs
        self.diff_idx = diff_idx

    def __repr__(self):
        return f"GradNode({self.name})"


class _Aval:
    """Minimal (shape, dtype) aval for GradNode outputs — a
    jax.ShapeDtypeStruct here costs ~5µs/op of checked-__setattr__ on
    the eager hot path for two fields the backward ever reads."""
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = dtype


def _zeros_ct(aval):
    if jnp.issubdtype(aval.dtype, jnp.inexact):
        return jnp.zeros(aval.shape, aval.dtype)
    return np.zeros(aval.shape, jax.dtypes.float0)


_diff_dtype_cache: Dict[Any, bool] = {}


def _is_diff_dtype(x) -> bool:
    # dtype-keyed cache: jnp.result_type costs ~10µs/call on the eager
    # hot path; arrays expose .dtype directly and the distinct dtype
    # population is tiny
    dt = getattr(x, "dtype", None)
    if dt is not None:
        hit = _diff_dtype_cache.get(dt)
        if hit is None:
            hit = _diff_dtype_cache[dt] = bool(
                jnp.issubdtype(dt, jnp.inexact))
        return hit
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


# Pending device-side NaN flags: (op_name, out_index, 0-d bool jax.Array).
# Computing `any(~isfinite)` is an async device op; only the *fetch* blocks.
# Batching the fetch every FLAGS_check_nan_inf_stride ops turns N host
# round-trips into one (each fetch stalls the dispatch queue) while
# keeping exact (op, output) attribution on failure.
_nan_pending: List[Tuple[str, int, Any]] = []


def flush_nan_checks() -> None:
    """Fetch all pending NaN flags in one host sync; raise naming the first
    offending op. Called on stride overflow and at backward() boundaries."""
    global _nan_pending
    if not _nan_pending:
        return
    pending, _nan_pending = _nan_pending, []
    flags = np.asarray(jnp.stack([f for _, _, f in pending]))  # one fetch
    if flags.any():
        name, i, _ = pending[int(np.argmax(flags))]
        raise FloatingPointError(
            f"Operator {name} output {i} contains NaN or Inf "
            f"(FLAGS_check_nan_inf is set)")


_nan_flag = None     # resolved Flag objects (registry identity is
_stride_flag = None  # stable) — avoids per-op registry lookups

# FLAGS_benchmark: block on each op's outputs so wall time measures the
# device, not dispatch pipelining. Inline .value read per dispatch (the
# _M_flag idiom) — off costs one attribute load.
_bench_flag = _flag_registry["benchmark"]
# FLAGS_retain_grad_for_all_tensor: every differentiable interior
# tensor accumulates .grad during backward, as if retain_grads() had
# been called on it (ref: the reference's global retain switch)
_retain_all_flag = _flag_registry["retain_grad_for_all_tensor"]


def _benchmark_sync(outs) -> None:
    for o in outs:
        if isinstance(o, jax.Array) and not isinstance(o, jax.core.Tracer):
            o.block_until_ready()


def _maybe_check_nan_inf(name: str, outs) -> None:
    """FLAGS_check_nan_inf per-op scan (ref: eager/nan_inf_utils.h:38 —
    CheckTensorHasNanOrInf after each ad_func). Only active in eager mode
    (concrete arrays); tracing skips it, matching the reference's
    dygraph-only check."""
    global _nan_flag, _stride_flag
    if _nan_flag is None:
        from .flags import _registry
        _nan_flag = _registry["check_nan_inf"]
        _stride_flag = _registry["check_nan_inf_stride"]
    if not _nan_flag.value:
        return
    stride = max(int(_stride_flag.value or 1), 1)
    for i, o in enumerate(outs):
        if isinstance(o, jax.core.Tracer):
            return  # inside jit trace, skip (dygraph-only check)
        if isinstance(o, jax.Array) and jnp.issubdtype(o.dtype, jnp.inexact):
            flag = jnp.any(~jnp.isfinite(o))  # device op, no host sync
            if stride <= 1:
                if bool(flag):
                    raise FloatingPointError(
                        f"Operator {name} output {i} contains NaN or Inf "
                        f"(FLAGS_check_nan_inf is set)")
            else:
                _nan_pending.append((name, i, flag))
    if len(_nan_pending) >= stride:
        flush_nan_checks()


# Per-op dispatch gate backed by the native OpRegistry (the KernelFactory
# analog — ref: phi/core/kernel_factory.cc:267 SelectKernelOrThrowError):
# first dispatch of each op name looks up its descriptor (arity bounds,
# has_vjp) and validates the call; later dispatches are one dict hit.
# has_vjp=False ops (samplers) skip the tape entirely — their outputs are
# not differentiable by contract.
# name -> [has_vjp: bool, dispatch_count: int] (mutated in place)
_op_gate_cache: Dict[str, list] = {}

# -- telemetry (paddle_tpu.observability) ------------------------------------
# dispatch.ops_total is the one REAL hot-path instrument (a counter inc
# per dispatch, kill-switched by FLAGS_metrics). Per-op attribution rides free: the
# collector below reads the dispatch counts _op_gate already keeps, so
# ops_dispatched_total{op=...} costs the hot loop nothing.
_M_ops = _om.counter(
    "dispatch.ops_total", "Eager op dispatches through apply_op")
_M_flag = _om.flag_info()  # FLAGS_metrics, cached for the inline check
_M_pair_builds = _om.counter(
    "dispatch.jit_pair_builds_total",
    "Jitted (fwd, vjp) pair cache entries built for eager fast dispatch")
_M_pair_hits = _om.counter(
    "dispatch.jit_pair_hits_total",
    "Dispatches served by a cached jitted pair")
_M_pair_misses = _om.counter(
    "dispatch.jit_pair_misses_total",
    "Dispatches that found no cached pair (first sighting or build)")
_M_compile_s = _om.histogram(
    "dispatch.jit_compile_seconds",
    "First-call (trace+compile) seconds of a freshly built jit pair")
_M_nojit = _om.counter(
    "dispatch.nojit_demotions_total",
    "(fn, config) entries pinned to the plain eager path")


def _collect_dispatch():
    return {"dispatch.ops_dispatched_total":
            {name: cell[1] for name, cell in _op_gate_cache.items()}}


_om.register_collector("dispatch", _collect_dispatch)


def _op_gate(name: str, n_args: int) -> bool:
    """Returns has_vjp for the op; validates arity on first dispatch and
    counts dispatches (introspection via op_registry.dispatch_counts)."""
    if _M_flag.value:
        # inline unlabeled-counter bump (see Counter._v): the whole
        # per-dispatch telemetry cost
        _M_ops._v += 1
    hit = _op_gate_cache.get(name)
    if hit is not None:
        hit[1] += 1
        return hit[0]
    has_vjp = True
    try:
        from ..ops.op_registry import get_op_info
        info = get_op_info(name)
    except Exception:
        info = None
    if info:
        has_vjp = bool(info.get("has_vjp", True))
        # the descriptor's nargs caps the POSITIONAL surface; attrs may
        # also ride the kernel closure, so there is no lower bound here,
        # and variadic ops (one positional per tensor) have no cap
        hi = max(int(info.get("nargs", 1)), int(info.get("nin", 0)))
        if n_args > hi and not info.get("variadic", False):
            raise TypeError(
                f"op {name!r} dispatched with {n_args} positional args "
                f"but its registry descriptor allows at most {hi} "
                f"(ops.yaml contract)")
    _op_gate_cache[name] = [has_vjp, 1]
    return has_vjp


# -- eager dispatch fast path -------------------------------------------------
# The reference engineers its eager hot loop to sub-10µs/op (generated
# ad_funcs + cached kernel selection, ref: test/cpp/eager/performance_tests/
# benchmark_eager_cuda.cc, SURVEY §3.1). Here the dominant cost is
# jax.vjp's per-call retrace (~1.4 ms/op measured on v5e): this cache keys
# (fn identity, static args, kwargs) to a jitted forward and a jitted vjp
# program, so the steady-state recorded op is two C++-jit-cache dispatches.
# Engaged only for concrete (non-tracer) eager calls; anything unusual
# (unhashable statics, tracers, exotic cotangents) falls back to plain
# jax.vjp with identical semantics.

import weakref as _weakref

_pair_cache_weak: "_weakref.WeakKeyDictionary" = _weakref.WeakKeyDictionary()
_pair_cache_strong: Dict[Any, dict] = {}
_FAST_DISPATCH = os.environ.get(
    "PADDLE_TPU_DISABLE_FAST_DISPATCH", "0") != "1"


def _fn_pair_cache(fn):
    # id-keyed first: jnp ufunc objects define __hash__/__eq__ that cost
    # ~3µs per lookup on the hot path; ufuncs are module-level
    # singletons so identity is the right key (the entry holds fn,
    # keeping the id stable)
    hit = _pair_cache_strong.get(id(fn))
    if hit is not None:
        return hit[1]
    try:
        d = _pair_cache_weak.get(fn)
        if d is None:
            d = {}
            _pair_cache_weak[fn] = d
        elif "_seen" in d:
            # second+ dispatch of the same fn OBJECT: long-lived (a
            # module fn or ufunc) — promote to the id-keyed cache so
            # later dispatches skip fn.__hash__/__eq__ (jnp ufuncs
            # spend ~3µs there per lookup). Bounded by the 1024-clear.
            if len(_pair_cache_strong) > 1024:
                _pair_cache_strong.clear()
            _pair_cache_strong[id(fn)] = (fn, d)
        return d
    except TypeError:  # fn doesn't support weakrefs (e.g. jnp ufunc objs)
        if len(_pair_cache_strong) > 1024:
            _pair_cache_strong.clear()
        d = {}
        _pair_cache_strong[id(fn)] = (fn, d)
        return d


def _freeze(v):
    """Hashable cache-key form of a static value; TypeError if impossible."""
    if isinstance(v, (list, tuple)):
        return (type(v).__name__,) + tuple(_freeze(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    if isinstance(v, (jax.Array, np.ndarray)):
        raise TypeError("array is not a static value")
    hash(v)
    return v


def _build_pair(fn, kwargs, datas, dyn_idx, diff_idx):
    """(jitted fwd, jitted vjp, meta) for this op configuration. Static
    (non-array) positional args are baked in; dynamic args are passed, so
    jit's own aval-keyed cache handles shape/dtype polymorphism."""
    template = [None if i in dyn_idx else datas[i]
                for i in range(len(datas))]
    dyn_idx_t = tuple(dyn_idx)
    meta = {"multi": False}

    def _call(dyn_args, overrides=()):
        call = list(template)
        for p, i in zip(dyn_args, dyn_idx_t):
            call[i] = p
        for i, p in overrides:
            call[i] = p
        return fn(*call, **kwargs)

    @jax.jit
    def jfwd(*dyn_args):
        res = _call(dyn_args)
        multi = isinstance(res, (tuple, list))
        meta["multi"] = multi  # set at trace time, read after first call
        return tuple(res) if multi else (res,)

    @jax.jit
    def jbwd(dyn_args, cts):
        prims = [datas_i for i, datas_i in zip(dyn_idx_t, dyn_args)
                 if i in diff_idx]

        def g(*ps):
            res = _call(dyn_args, overrides=tuple(zip(diff_idx, ps)))
            return tuple(res) if isinstance(res, (tuple, list)) else (res,)

        return jax.vjp(g, *prims)[1](cts)

    return jfwd, jbwd, meta


_NOJIT = "nojit"  # sentinel: this (fn, config) must not run under jit


def _fast_pair(fn, kwargs, datas, diff_idx):
    """Cache lookup/build; None when this call can't take the fast path.

    Build policy: a pair is only built for an fn OBJECT seen on a second
    dispatch — per-call fresh closures (whose jit compile would cost
    hundreds of ms every call) die with their first sighting marker and
    never compile; module-level fns and ufuncs pay one deferred build.
    """
    if not _FAST_DISPATCH:
        return None
    dyn_idx, static_key = [], []
    try:
        for i, d in enumerate(datas):
            if isinstance(d, jax.core.Tracer):
                return None  # under an outer trace: plain path
            if isinstance(d, (jax.Array, np.ndarray)):
                dyn_idx.append(i)
            elif isinstance(d, (float, np.floating)):
                # python floats are numeric operands (scales, epsilons),
                # not structure: pass them as (weak-typed) jit arguments
                # so a host-varying scalar — `x * lr` in a loop — hits
                # the same compiled pair instead of compiling per value.
                # A fn that branches on the value fails the trace once
                # and is marked nojit (plain path) below.
                dyn_idx.append(i)
            else:
                static_key.append((i, _freeze(d)))
        key = (tuple(diff_idx), tuple(static_key),
               () if not kwargs else _freeze(kwargs))
    except TypeError:
        if _dispatch_observer is not None:
            _dispatch_observer("unhashable_static", fn)
        return None
    cache = _fn_pair_cache(fn)
    pair = cache.get(key)
    if pair is _NOJIT:
        return None
    if pair is not None:
        if _M_flag.value:
            _M_pair_hits._v += 1  # inline fast cell (see _M_ops)
        return pair, tuple(dyn_idx), cache, key
    if _M_flag.value:
        _M_pair_misses._v += 1
    if pair is None:
        if "_seen" not in cache:
            cache["_seen"] = True
            return None
        if len(cache) > 32:
            # static args that keep changing value (novel key per call)
            # would compile a fresh pair every time — stop building; the
            # existing entries keep serving their own keys
            return None
        pair = _build_pair(fn, kwargs, datas, set(dyn_idx), tuple(diff_idx))
        cache[key] = pair
        _M_pair_builds.inc()
        if _dispatch_observer is not None:
            _dispatch_observer("pair_build", fn)
    return pair, tuple(dyn_idx), cache, key


def _mark_nojit(cache, key, exc=None):
    """Pin (fn, config) to the plain eager path — but only for errors
    that prove the fn can't trace (host-side numpy, value-dependent
    control flow). A transient runtime failure (e.g. RESOURCE_EXHAUSTED
    during the one-time compile under memory pressure) must NOT
    permanently demote the op to the ~1.5ms eager path: evict the cache
    entry so the next dispatch retries the jit, bounded to a few
    attempts so a persistently failing config still settles to eager."""
    msg = "" if exc is None else str(exc)
    transient = ("RESOURCE_EXHAUSTED" in msg or "OUT_OF_MEMORY" in msg
                 or "out of memory" in msg)
    # retry counters live in ONE nested dict so bookkeeping can never
    # crowd the len(cache) gate that caps new pair builds in _fast_pair
    rc = cache.get("_retry_counts")
    if not transient:
        if rc:
            rc.pop(key, None)  # settled: drop the bookkeeping slot
        cache[key] = _NOJIT
        _M_nojit.inc()
        return
    if rc is None:
        rc = cache.setdefault("_retry_counts", {})
    retries = rc.get(key, 0)
    if retries >= 3:
        rc.pop(key, None)
        cache[key] = _NOJIT
        _M_nojit.inc()
        return
    rc[key] = retries + 1
    pair = cache.get(key)
    if isinstance(pair, tuple) and pair[2].get("ever_ok"):
        # the pair has executed successfully at least once — the
        # compile is fine, only this execution hit resource pressure.
        # Keep the compiled executable across the WHOLE retry budget
        # (re-tracing under the same pressure would cost hundreds of
        # ms for nothing); a later success re-confirms it (clearing
        # the counter via state), consecutive failures settle above.
        pair[2]["state"] = 0
        return
    cache.pop(key, None)  # failed during initial compile: rebuild


# When paddle_tpu.static is recording (enable_static / program_guard), this
# holds a callable(fn, args, kwargs, outs, name) appending to the Program
# tape; None in the (default) eager mode — one global check per op.
_op_recorder = None

# SOT hook: notified when a backward walk starts (a recorded trace that
# ran autograd internally cannot be replayed as pure forward segments).
_backward_observer = None

# Analysis-auditor hook (paddle_tpu.analysis.auditor): notified of
# dispatch-cache events that signal recompile risk — ("pair_build", fn)
# when a jitted pair compiles, ("unhashable_static", fn) when a call's
# static args can't enter the cache key (the call runs un-jitted every
# time). None outside an audit: one global read on the miss paths only.
_dispatch_observer = None


# resolved on first dispatch (tensor.py/amp import us — a module-level
# import would be circular; a per-call import costs ~1.5µs of the
# measured dispatch budget)
_Tensor = None
_amp_state = None
_maybe_cast_inputs = None
_fusion = None


def apply_op(fn: Callable, *args, op_name: Optional[str] = None,
             fuse_attrs: Optional[tuple] = None, **kwargs):
    """Run ``fn`` (a pure JAX function) on mixed Tensor/raw args, recording a
    GradNode when grad is enabled and any Tensor input requires grad.

    ``fuse_attrs`` marks a parametric fusable dispatch (reduction
    terminator / contraction epilogue): a hashable (key, value) tuple
    the caller guarantees re-expresses everything ``fn`` bakes in beyond
    its array args, so core/fusion.py can defer the op through its
    registered parametric impl (see fusion._PIMPLS) with the attrs
    folded into the program cache key. None (the default) means plain
    dispatch — elementwise fusion still gates on fn identity.

    Returns Tensor or tuple-of-Tensor mirroring fn's output structure.
    This is the analog of a generated ``*_ad_func`` forward
    (ref: fluid/eager/api/manual/eager_manual/forwards/multiply_fwd_func.cc:68).
    """
    global _Tensor, _amp_state, _maybe_cast_inputs, _fusion
    if _Tensor is None:
        from .tensor import Tensor as _T
        from ..amp.auto_cast import _state as _s, maybe_cast_inputs as _m
        from . import fusion as _f
        _Tensor, _amp_state, _maybe_cast_inputs, _fusion = _T, _s, _m, _f
    Tensor = _Tensor

    name = op_name or getattr(fn, "__name__", "op")

    # lazy-eager fusion: fusable ops — elementwise chains, reduction
    # terminators, matmul/linear epilogue hosts — defer into an
    # expression DAG and compile per-chain instead of per-op
    # (core/fusion.py). The _op_gate still runs so arity validation +
    # dispatch_counts see every dispatch; recorders (SOT/static), AMP,
    # and tracers take the plain path untouched.
    if (_op_recorder is None and not _amp_state.enabled
            and not _bench_flag.value and _fusion.enabled()):
        # FLAGS_benchmark disables deferral: "sync after each op" is
        # only meaningful when each op actually dispatches
        fused_out = _fusion.try_fuse(name, fn, args, kwargs, fuse_attrs)
        if fused_out is not None:
            _op_gate(name, len(args))
            return fused_out

    datas = []
    reason = None
    for a in args:
        if isinstance(a, Tensor):
            if a._lazy is not None:
                # a pending chain meets a non-fusable consumer: flush at
                # the op boundary (gather/reshape/...). The reason label
                # distinguishes reductions/contractions that WOULD have
                # deferred with FLAGS_eager_fusion_reduce/_epilogue on
                # (reduce_boundary / matmul_boundary) from plain
                # op_boundary flushes — the bisection taxonomy.
                if reason is None:
                    reason = _fusion.boundary_reason(name)
                _fusion.materialize_tensor(a, reason)
            datas.append(a._buf)
        else:
            datas.append(a)

    # AMP hook (the analog of the generated ad_func AMP block,
    # ref: multiply_fwd_func.cc:49-70)
    record_fn = fn
    if _amp_state.enabled:
        datas = _maybe_cast_inputs(name, datas)
        # recorders (SOT/static tape) must capture the cast too, so a
        # replayed program reproduces the same AMP numerics
        def record_fn(*a, _fn=fn, _name=name, **kw):
            return _fn(*_maybe_cast_inputs(_name, list(a)), **kw)

    has_vjp = _op_gate(name, len(args))
    # _buf, not the _data property: the unwrap loop above already
    # materialized every Tensor arg, so the lazy-flush branch is dead
    # weight on this measured hot path
    diff_idx = [
        i for i, a in enumerate(args)
        if isinstance(a, Tensor) and not a.stop_gradient
        and _is_diff_dtype(a._buf)
    ]
    record = _state.enabled and bool(diff_idx) and has_vjp

    if not record:
        outs = multi = None
        fast = _fast_pair(fn, kwargs, datas, ())
        if fast is not None:
            (jfwd, _, meta), dyn_idx, cache, ckey = fast
            # an unconfirmed pair's first call pays trace+compile: time
            # it into the registry (steady-state calls skip the clock)
            fresh = meta.get("state") != 1
            if fresh:
                t0 = _time.perf_counter()
            try:
                outs = jfwd(*(datas[i] for i in dyn_idx))
                multi = meta["multi"]
                if fresh:
                    # first success (or first after a transient retry):
                    # confirm the pair and clear the failure counter
                    meta["state"] = 1
                    meta["ever_ok"] = True
                    _M_compile_s.observe(_time.perf_counter() - t0)
                    rc = cache.get("_retry_counts")
                    if rc:
                        rc.pop(ckey, None)
            except FloatingPointError:
                raise
            except Exception as e:
                # fn isn't jittable here (host-side numpy, value-dependent
                # control flow): run it eagerly from now on — unless the
                # failure was transient (resource), which retries
                _mark_nojit(cache, ckey, e)
                outs = None
        if outs is None:
            out = fn(*datas, **kwargs)
            multi = isinstance(out, (tuple, list))
            outs = tuple(out) if multi else (out,)
        _maybe_check_nan_inf(name, outs)
        if _bench_flag.value:
            _benchmark_sync(outs)
        for o in outs:
            _memory.track(o)
        wrapped = tuple(Tensor(o, stop_gradient=True) for o in outs)
        if _op_recorder is not None:
            _op_recorder(record_fn, args, kwargs, wrapped, name)
        return wrapped if multi else wrapped[0]

    outs = None
    fast = _fast_pair(fn, kwargs, datas, diff_idx)
    if fast is not None:
        (jfwd, jbwd, meta), dyn_idx, cache, ckey = fast
        dyn_args = tuple(datas[i] for i in dyn_idx)
        fresh = meta.get("state") != 1
        if fresh:
            t0 = _time.perf_counter()
        try:
            outs = jfwd(*dyn_args)
            multi = meta["multi"]
            if fresh:
                meta["state"] = 1
                meta["ever_ok"] = True
                _M_compile_s.observe(_time.perf_counter() - t0)
                rc = cache.get("_retry_counts")
                if rc:
                    rc.pop(ckey, None)
        except FloatingPointError:
            raise
        except Exception as e:
            _mark_nojit(cache, ckey, e)
            outs = None
        else:
            def vjp_fn(cts, _dyn=dyn_args, _jb=jbwd):
                try:
                    return _jb(_dyn, cts)
                except FloatingPointError:
                    raise
                except Exception:
                    # exotic cotangent (float0/sparse) the jitted vjp
                    # can't take as an argument: one plain retrace
                    def f2(*primals):
                        call = list(datas)
                        for i, p in zip(diff_idx, primals):
                            call[i] = p
                        res = fn(*call, **kwargs)
                        return (tuple(res)
                                if isinstance(res, (tuple, list))
                                else (res,))
                    return jax.vjp(
                        f2, *[datas[i] for i in diff_idx])[1](cts)
    if outs is None:
        struct = {"multi": False}

        def f(*primals):
            call = list(datas)
            for i, p in zip(diff_idx, primals):
                call[i] = p
            res = fn(*call, **kwargs)
            struct["multi"] = isinstance(res, (tuple, list))
            return tuple(res) if struct["multi"] else (res,)

        primals = [datas[i] for i in diff_idx]
        outs, vjp_fn = jax.vjp(f, *primals)
        multi = struct["multi"]
    _maybe_check_nan_inf(name, outs)
    if _bench_flag.value:
        _benchmark_sync(outs)
    for o in outs:
        _memory.track(o)

    out_avals = tuple(_Aval(o.shape, o.dtype) for o in outs)
    node = GradNode(vjp_fn, tuple(args[i] for i in diff_idx), out_avals, name,
                    fn=fn, datas=datas, kwargs=kwargs, diff_idx=diff_idx)

    wrapped = tuple(
        Tensor(o, stop_gradient=False, node=node, out_index=k)
        for k, o in enumerate(outs))
    if _op_recorder is not None:
        _op_recorder(record_fn, args, kwargs, wrapped, name)
    if not multi:
        return wrapped[0]
    return wrapped


def _ensure_jnp(g, aval):
    if g is None:
        return _zeros_ct(aval)
    from .tensor import Tensor
    if isinstance(g, Tensor):
        g = g._data
    if not isinstance(g, (jax.Array, np.ndarray, int, float)):
        return g  # structured cotangent (e.g. sparse BCOO): pass through
    return jnp.asarray(g, aval.dtype) if jnp.issubdtype(
        aval.dtype, jnp.inexact) else g


def _topo_order(root_node: GradNode) -> List[GradNode]:
    """Reverse postorder over the node DAG: every consumer precedes its
    producers, so cotangents are fully accumulated before a node runs."""
    order: List[GradNode] = []
    visited = set()
    stack: List[Tuple[GradNode, int]] = [(root_node, 0)]
    # iterative DFS with explicit postorder
    while stack:
        node, phase = stack.pop()
        if phase == 0:
            if id(node) in visited:
                continue
            visited.add(id(node))
            stack.append((node, 1))
            for t in node.inputs:
                child = t._node
                if child is not None and id(child) not in visited:
                    stack.append((child, 0))
        else:
            order.append(node)
    order.reverse()
    return order


def _node_backward_taped(node: GradNode, ct_tensors):
    """Run one node's backward step *through the tape* so the produced grads
    are themselves differentiable (w.r.t. both the node's primal inputs and
    the incoming cotangents). Used by create_graph=True.
    ref-analog: eager/backward.cc:439 general_grad."""
    if node.datas is None:
        raise RuntimeError(
            f"create_graph backward through {node.name}: the node's "
            f"forward inputs were already freed by a previous "
            f"backward(); pass retain_graph=True to the first backward "
            f"if you need grad-of-grad afterwards")
    nprim = len(node.diff_idx)

    def node_grad_fn(*flat):
        primals, cts = flat[:nprim], flat[nprim:]

        def f(*ps):
            call = list(node.datas)
            for i, p in zip(node.diff_idx, ps):
                call[i] = p
            res = node.fn(*call, **node.kwargs)
            return tuple(res) if isinstance(res, (tuple, list)) else (res,)

        _, vjp = jax.vjp(f, *primals)
        return tuple(vjp(tuple(cts)))

    out = apply_op(node_grad_fn, *node.inputs, *ct_tensors,
                   op_name=node.name + "_grad")
    return out if isinstance(out, tuple) else (out,)


def _run_backward(roots, root_grads, accumulate_into_grad: bool,
                  wanted: Optional[Sequence] = None,
                  create_graph: bool = False,
                  retain_graph: bool = False):
    """Core backward walk shared by Tensor.backward() and paddle.grad().

    ref-analog: eager/backward.cc RunBackward — queue-based topological walk
    routing grads along edges into GradTensorHolder accumulators.

    With ``create_graph=True`` cotangents travel as Tensors and every
    backward step is recorded via apply_op, so returned grads compose for
    grad-of-grad.
    """
    from .tensor import Tensor
    if _backward_observer is not None:
        _backward_observer()

    def _add(a, b):
        if create_graph and (isinstance(a, Tensor) or isinstance(b, Tensor)):
            return apply_op(lambda x, y: x + y, _as_t(a), _as_t(b),
                            op_name="grad_add")
        return a + b

    def _as_t(g):
        return g if isinstance(g, Tensor) else Tensor(g, stop_gradient=True)

    node_cts: Dict[int, List[Any]] = {}
    node_by_id: Dict[int, GradNode] = {}
    results: Dict[int, Any] = {}
    wanted_ids = {id(t) for t in wanted} if wanted is not None else None

    def seed(node, idx, g):
        node_by_id[id(node)] = node
        cts = node_cts.setdefault(id(node), [None] * len(node.out_avals))
        cts[idx] = g if cts[idx] is None else _add(cts[idx], g)

    order: List[GradNode] = []
    seen = set()
    for t, g in zip(roots, root_grads):
        if t._node is None:
            # a leaf root: its grad is just the seed
            _accumulate_leaf(t, g, accumulate_into_grad, results, wanted_ids)
            continue
        seed(t._node, t._out_index, g)
        # a retained non-leaf ROOT gets its seed as .grad (ref parity:
        # loss.grad == ones after backward under retain_grads / the
        # retain-all flag) — the interior loop below can't see roots
        if t._retain_grads or _retain_all_flag.value \
                or (wanted_ids and id(t) in wanted_ids):
            _accumulate_leaf(t, g, accumulate_into_grad, results,
                             wanted_ids, force=True, add=_add)
        for n in _topo_order(t._node):
            if id(n) not in seen:
                seen.add(id(n))
                order.append(n)

    # In multi-root cases, a merged order must still satisfy consumer-before-
    # producer; re-sort by a global DFS from a virtual root.
    if len([t for t in roots if t._node is not None]) > 1:
        virt = GradNode(None, tuple(t for t in roots if t._node is not None),
                        (), "virtual_root")
        order = [n for n in _topo_order(virt) if n is not virt]

    for node in order:
        cts = node_cts.get(id(node))
        if cts is None:
            continue  # unreachable from seeds
        if create_graph:
            full = tuple(
                _as_t(_zeros_ct(a)) if c is None else _as_t(c)
                for c, a in zip(cts, node.out_avals))
            in_grads = _node_backward_taped(node, full)
        else:
            full = tuple(
                _ensure_jnp(c, a) for c, a in zip(cts, node.out_avals))
            in_grads = node.vjp_fn(full)
            if not retain_graph:
                # release the retained forward inputs (kept for potential
                # create_graph re-differentiation) once the node is
                # consumed — the eager-training memory profile then
                # matches the plain vjp-residual tape
                node.fn = node.datas = node.kwargs = None
        for t, g in zip(node.inputs, in_grads):
            if not create_graph:
                if isinstance(g, np.ndarray) and g.dtype == jax.dtypes.float0:
                    continue
                g = _apply_hooks(t, g)
            elif t._hooks:
                # hooks receive the live taped Tensor so a hook built from
                # paddle ops stays differentiable for grad-of-grad
                for hook in list(t._hooks.values()):
                    r = hook(g)
                    if r is not None:
                        g = r if isinstance(r, Tensor) else _as_t(r)
            if t._node is not None:
                seed(t._node, t._out_index, g)
                if t._retain_grads or _retain_all_flag.value \
                        or (wanted_ids and id(t) in wanted_ids):
                    _accumulate_leaf(t, g, accumulate_into_grad, results,
                                     wanted_ids, force=True, add=_add)
            else:
                _accumulate_leaf(t, g, accumulate_into_grad, results,
                                 wanted_ids, add=_add)
        # free residuals as we go unless the caller wants to re-run
        node_cts.pop(id(node), None)
    return results


def _apply_hooks(t, g):
    from .tensor import Tensor
    if t._hooks:
        tg = Tensor(g, stop_gradient=True)
        for hook in list(t._hooks.values()):
            r = hook(tg)
            if r is not None:
                tg = r if isinstance(r, Tensor) else Tensor(r, stop_gradient=True)
        g = tg._data
    return g


def _accumulate_leaf(t, g, accumulate_into_grad, results, wanted_ids,
                     force=False, add=None):
    from .tensor import Tensor
    is_wanted = wanted_ids is not None and id(t) in wanted_ids
    if wanted_ids is not None and not is_wanted and not force:
        return
    if is_wanted or force:
        prev = results.get(id(t))
        if prev is None:
            results[id(t)] = g
        else:
            results[id(t)] = add(prev, g) if add is not None else prev + g
    if accumulate_into_grad and not t.stop_gradient:
        # ref-analog: GradNodeAccumulation writing param.grad
        gd = g._data if isinstance(g, Tensor) else g
        if t.grad is None:
            t.grad = Tensor(gd, stop_gradient=True)
        else:
            t.grad = Tensor(t.grad._data + gd, stop_gradient=True)


def backward(tensors, grad_tensors=None, retain_graph=False):
    """paddle.autograd.backward. ref: python/paddle/autograd/autograd.py"""
    from .tensor import Tensor
    flush_nan_checks()  # drain forward-pass flags before walking the tape
    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if _fusion is not None:
        for t in tensors:
            if t._lazy is not None:  # flush pending chains: the walk
                _fusion.materialize_tensor(t, "backward")  # needs nodes
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]
    seeds = []
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad must be provided for non-scalar backward root")
            g = jnp.ones(t.shape, t.dtype)
        else:
            g = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        seeds.append(g)
    _run_backward(tensors, seeds, accumulate_into_grad=True,
                  retain_graph=retain_graph)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """Functional gradient API. ref: python/paddle/base/dygraph/base.py grad

    With ``create_graph=True`` the backward pass is itself recorded on the
    tape (each grad step re-runs the op's forward under jax.vjp via
    apply_op), so the returned grads compose for grad-of-grad.
    ref: paddle/fluid/eager/backward.cc:439 general_grad.
    """
    from .tensor import Tensor
    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if _fusion is not None:
        for t in list(outputs) + list(inputs):
            if t._lazy is not None:
                _fusion.materialize_tensor(t, "backward")
    if grad_outputs is None:
        grad_outputs = [None] * len(outputs)
    elif isinstance(grad_outputs, Tensor):
        grad_outputs = [grad_outputs]
    seeds = []
    for t, g in zip(outputs, grad_outputs):
        if g is None:
            g = jnp.ones(t.shape, t.dtype)
        elif isinstance(g, Tensor):
            g = g if create_graph else g._data
        else:
            g = jnp.asarray(g)
        seeds.append(g)
    results = _run_backward(outputs, seeds, accumulate_into_grad=False,
                            wanted=inputs, create_graph=create_graph,
                            retain_graph=bool(retain_graph) or create_graph)
    out = []
    for t in inputs:
        g = results.get(id(t))
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears unused; "
                    "pass allow_unused=True to return None for it")
            out.append(None)
        elif isinstance(g, Tensor):
            out.append(g)
        else:
            out.append(Tensor(g, stop_gradient=create_graph is False))
    return out
