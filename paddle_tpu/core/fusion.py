"""Lazy-eager fusion runtime: elementwise chains, reduction terminators,
matmul epilogues.

The eager hot path dispatches one jitted pair per op (core/autograd
apply_op), so an N-op elementwise chain costs N host dispatches and N
HBM round-trips — the locality problem operator-fusion compilers
(Neptune, FlashFuser; the reference's CINN pass) attack at the graph
level. Here the same win is taken WITHOUT leaving eager semantics:

* Ops flagged ``fusable: true`` in ``ops/ops.yaml`` do not execute at
  dispatch. ``apply_op`` routes them here; each builds a ``LazyExpr``
  node over its inputs and returns a real ``Tensor`` whose ``_data``
  materializes on demand (the handle is indistinguishable to user code).
* Ops flagged ``fusable: reduce`` (sum/mean/max/min/prod/logsumexp/...)
  are NOT flush boundaries either: they join the DAG as reduction
  terminator nodes, with their attrs (axis/keepdim/dtype) folded into
  the structural cache key — ``mean((x*y+z)**2)`` compiles and runs as
  ONE executable with no intermediate materialization. Fusable consumers
  may keep chaining past a terminator (softmax-style
  ``exp(x - max(x)) / sum(exp(x - max(x)))`` fuses whole).
* Ops flagged ``fusable: epilogue`` (matmul/linear) defer the same way
  as contraction nodes, so a following bias-add + activation (+ cast)
  chain compiles INTO the dot's program and executes as an XLA epilogue
  of the contraction instead of a second full-tensor pass. A held
  requires-grad matmul handle stays a real tape edge (the chain cuts
  there, exactly like any live fused intermediate), so the epilogue only
  captures contractions with no other live grad consumers.
* The expression DAG flushes at materialization points — a host read
  (``.numpy()``/``item``/``__array__``), a non-fusable op consuming the
  tensor (gather/reshape/...), ``backward()``, an in-place mutation,
  a gradient hook, or the chain-length cap — by compiling the WHOLE
  reachable chain as ONE jitted executable.
* Compiled programs live in an LRU cache keyed by (DAG structure + node
  attrs, input shapes/dtypes/weak-types, diff pattern, live outputs), so
  steady-state loops hit the cache and dispatch once per chain.
* Gradients: the flush records ONE GradNode against the fused program's
  VJP (``jax.vjp`` of the generated pure function), with per-edge
  ``stop_gradient`` inserts reproducing exactly the dispatch-time
  stop_gradient/no_grad semantics the per-op tape would have had.

Kill switch: ``FLAGS_eager_fusion=0`` (or env ``PADDLE_TPU_EAGER_FUSION=0``)
restores the exact pre-fusion dispatch path; ``FLAGS_eager_fusion_reduce``
and ``FLAGS_eager_fusion_epilogue`` turn off just the reduction-terminator
or matmul-epilogue capture for bisection. Observability: ``fusion.stats()``
— chains built, cache hits/misses, flush reasons (incl. the granular
``reduce_boundary``/``matmul_boundary`` labels the kill switches re-create),
reductions/epilogues fused, ops-per-chain histogram.
"""
from __future__ import annotations

import math as _math
import threading
import time as _time
import weakref
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd as _ag
from . import memory as _memory
from .flags import _registry as _flag_registry
from ..observability import flight as _flight
from ..observability import metrics as _om

__all__ = ["stats", "reset_stats", "clear_cache", "register_impl",
           "register_param_impl", "enabled", "materialize_tensor",
           "boundary_reason", "infer_output_aval", "capture_handoff"]

_INT32_MIN, _INT32_MAX = -(2 ** 31), 2 ** 31

# python scalar -> weak-typed device array, interned so a recurring
# literal (the `0.25` in a loop's `add(t, 0.25)`) is the SAME jax.Array
# every dispatch: the fused executable then takes only committed arrays
# (pjit's C++ fast path; a raw python scalar argument re-uploads a fresh
# scalar buffer per call) and identity-dedup collapses repeats to one
# program slot. jnp.asarray keeps python scalars weak-typed, so
# promotion semantics match the eager `jnp.add(x, 0.25)` exactly.
_scalar_cache: Dict[tuple, Any] = {}

# Live handles of pending (unflushed) chains. Buffer DONATION sites
# (the fused optimizer step, the AMP batched unscale) must flush these
# first: a pending chain captured its input buffers at dispatch time,
# and donating one to XLA deletes it under the chain's feet. Keyed by
# id() — a WeakSet would route bucket collisions through Tensor's
# elementwise __eq__ and die on bool(array).
_pending_tensors = weakref.WeakValueDictionary()

# -- telemetry: the registry IS the storage; fusion.stats() below is a
# view reconstructing the legacy dict shape from these instruments
_M_flag = _om.flag_info()
_M = _om.scope("fusion")
_M_deferred = _M.counter("ops_deferred_total",
                         "Fusable dispatches deferred into expression DAGs")
_M_chains = _M.counter("chains_flushed_total", "Fused programs executed")
_M_ops_fused = _M.counter("ops_fused_total",
                          "Ops executed through fused programs")
_M_hits = _M.counter("cache_hits_total",
                     "Flushes served by a cached executable")
_M_misses = _M.counter("cache_misses_total",
                       "Flushes that compiled a new program")
_M_uncompiled = _M.counter("uncompiled_runs_total",
                           "First-sighting flushes run un-jitted")
_M_fallbacks = _M.counter("jit_fallbacks_total",
                          "Flushes that fell back to un-jitted eval")
_M_flushes = _M.counter("flushes_total", "Chain flushes by reason")
_M_chain_len = _M.counter("chain_length", "Ops-per-chain distribution")
_M_reduce_fused = _M.counter(
    "reductions_fused_total",
    "Reduction terminator nodes flushed WITH their producer chain "
    "(the input edge was an interior node of the same fused program)")
_M_epi_fused = _M.counter(
    "epilogues_fused_total",
    "Contraction (matmul/linear) nodes flushed with at least one "
    "consumer in the same fused program — the epilogue actually fused")
_M_compile_s = _M.histogram(
    "compile_seconds", "First execution (trace+compile) of a freshly "
    "built fused program, labeled by program kind "
    "(elementwise/reduce/epilogue)")
_M_flush_sites = _M.counter(
    "flush_sites_total",
    "Chain flushes by (reason, origin call site) — the Fusion III "
    "planning input: which code locations break whole-step capture, "
    "not just why. Populated when FLAGS_fusion_flush_origin=1 (stack "
    "attribution costs ~µs/flush) or during an analysis audit")
_om.default_registry().gauge(
    "fusion.cache_size",
    "Live fused-program cache entries").set_function(
        lambda: len(_cache))


def _intern_scalar(v):
    key = (type(v), v)
    if v == 0 and isinstance(v, float):
        # 0.0 == -0.0 hash-collide but differ for sign-sensitive ops
        # (copysign/atan2/1/x): key the sign in explicitly
        key = (type(v), v, _math.copysign(1.0, v))
    hit = _scalar_cache.get(key)  # lock-free hit: dict get is atomic
    if hit is None:
        # miss path under the fusion lock: an unguarded check-then-clear
        # could drop a scalar another thread JUST interned (and whose
        # identity a pending chain already captured), and two concurrent
        # misses on one value would intern two distinct arrays — either
        # breaks the committed-array identity dedup. Evict oldest
        # entries instead of clearing so live recent literals survive.
        with _cache_lock:
            hit = _scalar_cache.get(key)
            if hit is None:
                while len(_scalar_cache) > 4096:
                    _scalar_cache.pop(next(iter(_scalar_cache)))
                hit = _scalar_cache[key] = jnp.asarray(v)
    return hit

# op name -> canonical pure-JAX implementation. Registration (from
# ops/math.py, ops/extra_math.py) pins a STRONG reference, so the fn's
# identity is stable for the lifetime of the process: a dispatch fuses
# only when its fn IS the registered object, which makes the structural
# cache key (op names) a faithful key for the generated program.
_IMPLS: Dict[str, Any] = {}

# op name -> canonical PARAMETRIC implementation ``fn(*arrays, **attrs)``
# for reduction terminators and contraction/epilogue ops: the dispatch
# wrapper bakes its attrs (axis/keepdim/dtype, transpose flags) into a
# per-call closure for the eager path, so fn identity can't gate fusion
# here — instead the wrapper passes the SAME attrs explicitly
# (apply_op's fuse_attrs) and codegen rebuilds the node from this
# registry + the attrs folded into the structural signature. Contract
# (held by the in-tree call sites): fn(*arrays, **dict(attrs)) is
# semantically identical to the eager closure it rides along with.
_PIMPLS: Dict[str, Any] = {}

# name -> False | True ("elementwise") | "reduce" | "epilogue": ops.yaml
# `fusable` class gate (resolved lazily; ops.yaml loads after the op
# modules that register impls)
_YAML_OK: Dict[str, Any] = {}

_flag = _flag_registry["eager_fusion"]
_reduce_flag = _flag_registry["eager_fusion_reduce"]
_epilogue_flag = _flag_registry["eager_fusion_epilogue"]
_max_chain = _flag_registry["eager_fusion_max_chain"]
_cache_cap = _flag_registry["eager_fusion_cache"]
_nan_flag = _flag_registry["check_nan_inf"]
_origin_flag = _flag_registry["fusion_flush_origin"]

# Analysis-auditor hooks (paddle_tpu.analysis). _flush_observer, when
# set, receives (reason, nops, pkind, origin) after every chain flush;
# _program_observer receives (sig, event) with event in
# "hit"/"compile"/"first" from the program cache. Both are None outside
# an audit — the hot path pays one global read.
_flush_observer = None
_program_observer = None

# frames skipped when attributing a flush to its origin call site: the
# fusion/dispatch machinery itself can never be the planning-relevant
# location
_ORIGIN_SKIP = ("core/fusion.py", "core/tensor.py", "core/autograd.py",
                "analysis/auditor.py", "analysis/locks.py")


def _flush_origin() -> str:
    """``pkg/file.py:line`` of the nearest stack frame outside the
    fusion machinery — the call site whose host read / op boundary
    forced this flush."""
    import sys
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename.replace("\\", "/")
        if not fn.endswith(_ORIGIN_SKIP):
            parts = fn.split("/")
            short = "/".join(parts[-2:]) if len(parts) > 1 else fn
            return f"{short}:{f.f_lineno}"
        f = f.f_back
    return "<unknown>"

# cardinality cap for flush_sites_total's site label: a long-lived
# process under FLAGS_fusion_flush_origin must not grow one counter
# cell per distinct call site forever — the long tail collapses into
# "<other>" (audits are unaffected; they record raw events)
_MAX_FLUSH_SITES = 128
_seen_flush_sites: set = set()

_Tensor = None  # resolved on first dispatch (core.tensor imports us)

# hot-path type handles: jax.Array/jax.core.Tracer lookups go through
# module __getattr__ shims, and jax.Array isinstance is an ABC walk —
# cache the names once and the concrete ArrayImpl type for a one-check
# fast path (it covers every committed eager buffer). _ArrayImpl is
# resolved on FIRST DISPATCH, not at import: `type(jnp.zeros(()))` here
# would initialize the JAX backend when `import paddle_tpu` runs —
# grabbing the exclusive TPU from every subprocess and pinning the
# platform before user code can override it.
_Tracer = jax.core.Tracer
_JaxArray = jax.Array
_ArrayImpl = None


def register_impl(name: str, fn) -> None:
    """Declare ``fn`` the canonical implementation of op ``name`` for
    fusion codegen. First registration wins (e.g. math.tanh vs the
    nn.functional wrapper): later dispatches of a DIFFERENT fn object
    under the same name simply fall back to the eager path."""
    _IMPLS.setdefault(name, fn)


def register_param_impl(name: str, fn) -> None:
    """Declare ``fn(*arrays, **attrs)`` the canonical parametric
    implementation of reduction/contraction op ``name`` (see _PIMPLS).
    First registration wins."""
    _PIMPLS.setdefault(name, fn)


def enabled() -> bool:
    # check_nan_inf wants per-op NaN attribution — a debug mode where
    # chain-level deferral would blur the blame; turn fusion off with it
    return bool(_flag.value) and not _nan_flag.value


def _yaml_class(name: str):
    """ops.yaml fusable class for ``name``: False, True (elementwise),
    "reduce", or "epilogue" (contraction)."""
    ok = _YAML_OK.get(name)
    if ok is None:
        try:
            from ..ops.op_registry import OP_TABLE
            info = OP_TABLE.get(name)
            ok = False
            if info and info.get("has_vjp", True):
                f = info.get("fusable")
                if f in (True, "reduce", "epilogue"):
                    ok = f
        except Exception:
            ok = False
        _YAML_OK[name] = ok
    return ok


# op name -> flush-reason label for apply_op's non-fusable-consumer
# branch: a pending chain flushed by a reduction/contraction consumer
# that DIDN'T defer (granular flag off, impl unregistered, odd call
# shape) is labeled reduce_boundary/matmul_boundary so stats() shows
# exactly the flushes the fusion flags would have avoided.
_BOUNDARY_REASON: Dict[str, str] = {}


def boundary_reason(name: str) -> str:
    r = _BOUNDARY_REASON.get(name)
    if r is None:
        cls = _yaml_class(name)
        r = ("reduce_boundary" if cls == "reduce" else
             "matmul_boundary" if cls == "epilogue" else "op_boundary")
        _BOUNDARY_REASON[name] = r
    return r


# ---------------------------------------------------------------------------
# expression DAG
# ---------------------------------------------------------------------------

class LazyExpr:
    """One deferred fusable op.

    ``args`` entries are LazyExpr (unmaterialized producer), Tensor
    (concrete leaf, strong ref — the GradNode-input analog), raw array,
    or a python scalar. ``adiff[i]`` captures, at dispatch time, whether
    gradient flows through edge i (grad mode on AND the input was
    differentiable then) — the fused program inserts
    ``lax.stop_gradient`` on every adiff=False edge, reproducing the
    per-op tape's stop_gradient semantics edge-exactly.
    """

    __slots__ = ("op", "args", "bufs", "adiff", "shape", "dtype", "weak",
                 "rg", "nops", "val", "anchor", "tref", "attrs", "kind")

    def __init__(self, op, args, bufs, adiff, shape, dtype, weak, nops,
                 attrs=None, kind="e"):
        self.op = op
        self.args = args
        # per-arg buffer captured AT DISPATCH for Tensor leaves (None for
        # expr children / raw arrays): jax arrays are immutable, so an
        # in-place mutation of the leaf later (set_value/zero_/[...]=)
        # only REBINDS t._buf — the flush must compute from the
        # dispatch-time value, exactly as the eager op would have
        self.bufs = bufs
        self.adiff = adiff
        self.shape = shape
        self.dtype = dtype
        self.weak = weak
        self.rg = any(adiff)
        self.nops = nops
        # parametric node state: attrs is the hashable (key, value) tuple
        # folded into the structural cache key (axis/keepdim/dtype for
        # reductions, transpose flags for contractions); None marks a
        # plain elementwise node. kind: "e" elementwise / "r" reduction
        # terminator / "c" contraction (epilogue host).
        self.attrs = attrs
        self.kind = kind
        self.val = None      # set at flush for live outputs
        self.anchor = None   # strong Tensor ref after flush (grad chaining)
        self.tref = None     # weakref to the owning Tensor


# (op, input descriptors) -> (shape, dtype, weak_type); jax.eval_shape
# costs ~100µs, a dict hit ~100ns — steady-state chains never re-infer
_aval_cache: Dict[tuple, tuple] = {}


def _infer_aval(name, fn, descs, entries, attrs=None):
    key = ((name, attrs) if attrs is not None else (name,)) + descs
    hit = _aval_cache.get(key)
    if hit is not None:
        return hit
    if len(_aval_cache) > 8192:  # bound it like the other fusion caches
        # a lock would guard nothing: get/insert run lock-free and a
        # racing insert lost to the eviction just re-infers
        _aval_cache.clear()  # lint-allow: PTL003 GIL-atomic memo eviction
    if attrs is not None:
        # infer through the registered parametric impl + attrs — exactly
        # what codegen will run — not through the per-call eager closure
        fn = _param_fn(name, attrs)
    try:
        eval_args = []
        for d, e in zip(descs, entries):
            if d[0] == "a":
                eval_args.append(
                    jax.ShapeDtypeStruct(d[1], d[2], weak_type=d[3]))
            else:
                eval_args.append(e)  # python scalar, passed verbatim
        out = jax.eval_shape(fn, *eval_args)
        if isinstance(out, (tuple, list)):
            return None  # fusable ops are single-output by contract
        aval = (tuple(out.shape), np.dtype(out.dtype),
                bool(getattr(out, "weak_type", False)))
    except Exception:
        return None
    _aval_cache[key] = aval
    return aval


def infer_output_aval(name, avals, attrs=None):
    """Live-impl ground truth for the analysis plane's shape/dtype
    abstract interpreter (analysis/shapes.py): the output
    ``(shape, dtype, weak_type)`` of fusable op ``name`` applied to
    abstract inputs ``avals`` (an iterable of ``(shape, dtype)`` or
    ``(shape, dtype, weak)`` tuples), computed by ``jax.eval_shape`` of
    the REGISTERED fusion impl through the same ``_aval_cache`` memo the
    flush path uses — so spec validation grades against exactly what
    codegen will run. ``attrs`` is the hashable attr tuple for
    parametric ops (reductions/contractions/cast). Returns None when no
    impl is registered or the impl rejects the avals."""
    if attrs is None:
        if _IMPLS.get(name) is None:
            return None
    elif name not in _PIMPLS:
        return None
    descs = tuple(
        ("a", tuple(a[0]), np.dtype(a[1]),
         bool(a[2]) if len(a) > 2 else False)
        for a in avals)
    # entries are only consulted for non-"a" descs (python scalars) —
    # every abstract input is an array desc here
    return _infer_aval(name, _IMPLS.get(name), descs,
                       (None,) * len(descs), attrs)


def _param_fn(op, attrs):
    """Evaluation callable for a parametric node: the registered impl
    with the node's attrs baked in (identity for attr-less nodes, e.g.
    bias-less linear or squared_l2_norm)."""
    base = _PIMPLS[op]
    if not attrs:
        return base
    kw = dict(attrs)

    def call(*vals):
        return base(*vals, **kw)

    return call


def _new_lazy_tensor(expr: LazyExpr):
    t = _Tensor.__new__(_Tensor)
    t._buf = None
    t._lazy = expr
    t.stop_gradient = not expr.rg
    t.grad = None
    t._node = None
    t._out_index = 0
    t._retain_grads = False
    t._hooks = {}
    t._hook_counter = 0
    t.name = ""
    t.trainable = False
    t._dist_attr = None
    expr.tref = weakref.ref(t)
    _pending_tensors[id(t)] = t
    return t


def try_fuse(name: str, fn, args, kwargs, attrs=None):
    """Defer one fusable dispatch; returns the handle Tensor, or None to
    take the normal eager path. Hot path: isinstance dispatch is ordered
    Tensor -> exact scalar types -> arrays, and input descriptors are
    built inline so nothing is touched twice.

    ``attrs`` is None for plain elementwise ops (fn identity gates the
    fuse) and a hashable (key, value) tuple for parametric dispatches
    (reductions / contractions) — then the op's ops.yaml class plus its
    registered parametric impl gate instead, and kwargs (which the eager
    ``fn`` may still need, e.g. matmul's transpose flags) are trusted to
    be exactly re-expressed by ``attrs`` (the in-tree wrapper contract,
    see _PIMPLS)."""
    global _Tensor, _ArrayImpl
    if attrs is None:
        if kwargs or _IMPLS.get(name) is not fn or \
                _yaml_class(name) is not True:
            return None
        kind = "e"
    else:
        cls = _yaml_class(name)
        if cls == "reduce":
            if not _reduce_flag.value:
                return None
            kind = "r"
        elif cls == "epilogue":
            if not _epilogue_flag.value:
                return None
            kind = "c"
        elif cls is True:
            # parametric elementwise (gelu's approximate, cast's dtype):
            # attrs ride the structural key like any other node attrs
            kind = "e"
        else:
            return None
        if name not in _PIMPLS:
            return None
        try:
            hash(attrs)  # attrs enter the structural cache key
        except TypeError:
            return None
    if _Tensor is None:
        from .tensor import Tensor as _T
        _Tensor = _T
        _ArrayImpl = type(jnp.zeros(()))
    grad_on = _ag._state.enabled
    entries: List[Any] = []
    bufs: List[Any] = []
    adiff: List[bool] = []
    descs: List[tuple] = []
    nops = 1
    for a in args:
        if isinstance(a, _Tensor):
            lz = a._lazy
            if lz is not None and lz.val is None:
                d = grad_on and not a.stop_gradient \
                    and _ag._is_diff_dtype(lz)
                if not (d and not lz.rg):
                    entries.append(lz)
                    bufs.append(None)
                    adiff.append(d)
                    descs.append(("a", lz.shape, lz.dtype, lz.weak))
                    nops += lz.nops
                    continue
                # stop_gradient was flipped to False on a chain built
                # under no_grad: eager semantics make this tensor a grad
                # LEAF (grads accumulate here, not through its history) —
                # flush it so it enters the new chain as a concrete leaf
                materialize_tensor(a, "grad_leaf")
            buf = a._buf
            if type(buf) is _ArrayImpl:
                weak = buf.weak_type
            elif isinstance(buf, np.ndarray):
                weak = False
            elif isinstance(buf, _JaxArray) and \
                    not isinstance(buf, _Tracer):
                weak = bool(getattr(buf, "weak_type", False))
            else:
                return None
            entries.append(a)
            bufs.append(buf)  # dispatch-time snapshot (mutation safety)
            adiff.append(grad_on and not a.stop_gradient
                         and _ag._is_diff_dtype(buf))
            descs.append(("a", buf.shape, buf.dtype, weak))
        else:
            ta = type(a)
            if ta is float or ta is int or ta is bool:
                # huge python ints overflow the weak-int32 coercion;
                # bail to the eager path rather than fail at trace time
                if ta is int and not (_INT32_MIN <= a < _INT32_MAX):
                    return None
                s = _intern_scalar(a)
                entries.append(s)
                bufs.append(None)
                adiff.append(False)
                descs.append(("a", (), s.dtype, True))
            elif isinstance(a, (_JaxArray, np.ndarray)):
                if isinstance(a, _Tracer):
                    return None
                entries.append(a)
                bufs.append(None)
                adiff.append(False)
                descs.append(("a", tuple(a.shape), a.dtype,
                              bool(getattr(a, "weak_type", False))))
            elif isinstance(a, (bool, int, float)):  # np scalar subclasses
                s = _intern_scalar(a)
                entries.append(s)
                bufs.append(None)
                adiff.append(False)
                descs.append(("a", (), s.dtype, bool(s.weak_type)))
            else:
                return None
    aval = _infer_aval(name, fn, tuple(descs), entries, attrs)
    if aval is None:
        return None
    expr = LazyExpr(name, tuple(entries), tuple(bufs), tuple(adiff),
                    aval[0], aval[1], aval[2], nops, attrs, kind)
    t = _new_lazy_tensor(expr)
    if _M_flag.value:
        _M_deferred._v += 1  # inline fast cell: per-deferral hot path
    if nops >= max(int(_max_chain.value or 32), 2):
        _flush(expr, "cap")
    return t


# ---------------------------------------------------------------------------
# program cache + codegen
# ---------------------------------------------------------------------------

_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
_cache_lock = threading.Lock()


def _build_pure(sig):
    """Decode a structural signature into the pure fused function. It is
    rebuilt from the signature alone — the impl registries map op names
    (+ node attrs for reduction/contraction nodes) back to their
    canonical jnp callables — so one program serves every flush with the
    same structure."""
    nodes, leaf_descs, out_idx, diff_idx = sig
    impls = tuple(_IMPLS[op] if attrs is None else _param_fn(op, attrs)
                  for op, _, attrs in nodes)

    def fused(*leaf_vals):
        env: List[Any] = []
        for (op, children, _attrs), impl in zip(nodes, impls):
            vals = []
            for kind, j, ad in children:
                v = env[j] if kind == "n" else leaf_vals[j]
                if not ad:
                    v = jax.lax.stop_gradient(v)
                vals.append(v)
            env.append(impl(*vals))
        return tuple(env[i] for i in out_idx)

    return fused


def _build_program(sig):
    """(pure fn, jitted fwd, jitted vjp) for a chain structure."""
    from ..jit.warmup import ensure_executable_cache
    ensure_executable_cache()  # fusion programs persist across boots too
    diff_idx = sig[3]
    fused = _build_pure(sig)
    jfwd = jax.jit(fused)

    def bwd(leaf_vals, cts):
        prims = [leaf_vals[i] for i in diff_idx]

        def g(*ps):
            call = list(leaf_vals)
            for i, p in zip(diff_idx, ps):
                call[i] = p
            return fused(*call)

        return jax.vjp(g, *prims)[1](cts)

    jbwd = jax.jit(bwd)
    return fused, jfwd, jbwd


_SEEN = object()  # first-sighting marker: structure noted, not compiled


def _trace_compile_span(pkind: str, dt: float) -> None:
    """Land the first-call (trace+compile) window in the host tracer as
    a ``fusion_compile[kind]`` span when the native tracer is live, so
    ``export_chrome_tracing`` step traces attribute the first-call spike
    to fusion compilation instead of an anonymous gap. Lazy module
    lookup only — never triggers the native build."""
    import sys
    mod = sys.modules.get("paddle_tpu._native")
    lib = getattr(mod, "lib", None)
    if lib is None:
        return
    try:
        if lib.tracer_enabled():
            now = lib.tracer_now()
            lib.tracer_record(f"fusion_compile[{pkind}]",
                              now - dt * 1e6, now)
    except Exception:
        pass


def _timed_first_call(jf, pkind):
    """Wrap a freshly built jitted forward so its FIRST execution (the
    one that traces+compiles) lands in fusion.compile_seconds — labeled
    by program kind (elementwise/reduce/epilogue) — and, when the host
    tracer is recording, as a chrome-trace span; later calls pay one
    flag check."""
    done = [False]

    def wrapper(*a):
        if done[0]:
            return jf(*a)
        t0 = _time.perf_counter()
        out = jf(*a)
        done[0] = True
        dt = _time.perf_counter() - t0
        _M_compile_s.observe(dt, kind=pkind)
        _trace_compile_span(pkind, dt)
        return out

    return wrapper


def _get_program(sig, pkind):
    """Compile policy mirrors autograd's pair cache: a chain structure
    only compiles on its SECOND sighting. One-off chains (test suites,
    cold paths) run un-jitted — op-by-op jnp cost, no XLA compile — and
    steady-state loops compile once on iteration two and hit the cache
    thereafter. Returns (pure fn, jfwd|None, jbwd|None)."""
    with _cache_lock:
        entry = _cache.get(sig)
        if entry is not None and entry is not _SEEN:
            _cache.move_to_end(sig)
            _M_hits.inc()
            if _program_observer is not None:
                _program_observer(sig, "hit")
            return entry
    if entry is _SEEN:
        _M_misses.inc()
        _flight.record("fusion", "compile", kind=pkind)
        if _program_observer is not None:
            _program_observer(sig, "compile")
        built = _build_program(sig)
        built = (built[0], _timed_first_call(built[1], pkind), built[2])
        with _cache_lock:
            _cache[sig] = built
            cap = max(int(_cache_cap.value or 256), 8)
            while len(_cache) > cap:
                _cache.popitem(last=False)
        return built
    _M_uncompiled.inc()
    if _program_observer is not None:
        _program_observer(sig, "first")
    with _cache_lock:
        _cache[sig] = _SEEN
        cap = max(int(_cache_cap.value or 256), 8)
        while len(_cache) > cap:
            _cache.popitem(last=False)
    return _build_pure(sig), None, None


# ---------------------------------------------------------------------------
# flush
# ---------------------------------------------------------------------------

def has_pending() -> bool:
    """Any live unflushed chains? Cheap gate for donation sites."""
    return len(_pending_tensors) > 0


def flush_pending(reason: str = "donation") -> int:
    """Flush EVERY pending chain. Called by buffer-donation sites
    (fused optimizer step, AMP batched unscale) so no deferred program
    can later read a buffer XLA just invalidated. Returns the number
    of chains flushed."""
    n = 0
    for t in list(_pending_tensors.values()):
        _pending_tensors.pop(id(t), None)
        if t._lazy is not None:
            materialize_tensor(t, reason)
            n += 1
    return n


def capture_handoff() -> int:
    """Whole-step capture boundary (jit/sot.py): flush every pending
    eager chain with reason ``sot_capture`` before a captured
    executable donates its inputs — a deferred chain may have snapshot
    buffers the donation is about to invalidate. These flushes are the
    segment handoff INTO the captured program, so the capture planner
    classifies the ``sot_capture`` reason capture-compatible (it is the
    capture boundary, not a break). Returns the number of chains
    flushed; a steady-state captured step flushes zero."""
    if not _pending_tensors:
        return 0
    return flush_pending("sot_capture")


def materialize_tensor(t, reason: str = "host_read") -> None:
    """Flush the chain the lazy tensor ``t`` heads (no-op if concrete)."""
    lz = t._lazy
    if lz is None:
        return
    if lz.val is not None:  # flushed via a shared DAG; just bind
        t._lazy = None
        if t._buf is None:
            t._buf = lz.val
        return
    _flush(lz, reason)


def _flush(root: LazyExpr, reason: str) -> None:
    # -- collect the reachable unmaterialized DAG (postorder) ------------
    order: List[LazyExpr] = []
    node_index: Dict[int, int] = {}
    leaf_vals: List[Any] = []
    leaf_tensors: List[Optional[Any]] = []
    leaf_descs: List[tuple] = []
    leaf_index: Dict[int, int] = {}
    sig_nodes: List[tuple] = []

    def leaf_slot(a, buf):
        # scalars were interned to arrays at dispatch, so every leaf is
        # LazyExpr (materialized earlier) / Tensor / raw array
        if type(a) is LazyExpr:
            key, val, tens = id(a), a.val, a.anchor
        elif buf is not None:
            # Tensor leaf: use the dispatch snapshot. Key by BOTH the
            # buffer and the tensor: same tensor mutated between
            # dispatches -> distinct slots (different bufs), while two
            # tensors SHARING one buffer (x and x.detach()) also stay
            # distinct — merging them would let the first-seen tensor's
            # grad identity swallow the other's cotangent
            key, val, tens = (id(buf), id(a)), buf, a
        else:
            key, val, tens = id(a), a, None
        idx = leaf_index.get(key)
        if idx is None:
            idx = leaf_index[key] = len(leaf_vals)
            leaf_vals.append(val)
            leaf_tensors.append(tens)
            leaf_descs.append(("a", val.shape, val.dtype,
                               bool(getattr(val, "weak_type", False))))
        return idx

    seen = set()
    stack: List[Tuple[LazyExpr, int]] = [(root, 0)]
    while stack:
        e, phase = stack.pop()
        if phase == 0:
            if id(e) in seen:
                continue
            seen.add(id(e))
            stack.append((e, 1))
            for a in e.args:
                if isinstance(a, LazyExpr) and a.val is None and \
                        id(a) not in seen:
                    stack.append((a, 0))
        else:
            children = []
            for a, buf, ad in zip(e.args, e.bufs, e.adiff):
                if isinstance(a, LazyExpr) and a.val is None:
                    children.append(("n", node_index[id(a)], ad))
                else:
                    children.append(("l", leaf_slot(a, buf), ad))
            node_index[id(e)] = len(order)
            order.append(e)
            sig_nodes.append((e.op, tuple(children), e.attrs))

    # -- outputs: every node whose Tensor handle is still alive ----------
    out_idx = []
    out_tensors = []
    for i, e in enumerate(order):
        t = e.tref() if e.tref is not None else None
        # the handle must still OWN this expr: a direct `t._data = ...`
        # rebind discarded the chain for t, and binding here would
        # silently revert the user's buffer to the stale fused value.
        # (The expr itself stays valid for OTHER pending consumers,
        # which by eager semantics see the dispatch-time value.)
        if t is not None and t._lazy is e:
            out_idx.append(i)
            out_tensors.append(t)

    # Live requires-grad INTERIOR tensors must sit on real tape edges —
    # eager users inspect them later (paddle.grad(loss, [y]), post-hoc
    # retain_grads()/register_hook()), and a single fused GradNode only
    # exposes the chain's leaves. Cut the chain there: flush each such
    # producer first (its own GradNode, producers-before-consumers via
    # the postorder), then re-walk — the cut points re-enter as concrete
    # anchored leaves. Hot loops never hit this: their intermediates are
    # dead by flush time.
    root_i = node_index[id(root)]
    cuts = [order[i] for i in out_idx if i != root_i and order[i].rg]
    if cuts:
        for e in cuts:
            if e.val is None:
                _flush(e, reason)
        _flush(root, reason)
        return

    if not out_idx:  # root's handle died mid-flush; nothing observes it
        out_idx = [root_i]
        out_tensors = [None]

    diff_set = set()
    for op, children, _attrs in sig_nodes:
        for kind, j, ad in children:
            if kind == "l" and ad:
                diff_set.add(j)
    diff_idx = tuple(sorted(diff_set))

    # program kind for compile-seconds attribution: a contraction makes
    # it an epilogue program, else a terminator makes it a reduce one
    pkind = "elementwise"
    for e in order:
        if e.kind == "c":
            pkind = "epilogue"
            break
        if e.kind == "r":
            pkind = "reduce"

    sig = (tuple(sig_nodes), tuple(leaf_descs), tuple(out_idx), diff_idx)
    fused, jfwd, jbwd = _get_program(sig, pkind)

    if jfwd is None:  # first sighting of this structure: run un-jitted
        outs = fused(*leaf_vals)
    else:
        try:
            outs = jfwd(*leaf_vals)
        except FloatingPointError:
            raise
        except Exception:
            # jit-specific failure (e.g. resource pressure during the
            # compile): the un-jitted trace has identical semantics
            _M_fallbacks.inc()
            outs = fused(*leaf_vals)

    # -- grad wiring: ONE GradNode over the fused program ----------------
    node = None
    if diff_idx and any(order[i].rg for i in out_idx):
        diff_tensors = tuple(leaf_tensors[i] for i in diff_idx)
        out_avals = tuple(_ag._Aval(o.shape, o.dtype) for o in outs)
        datas = list(leaf_vals)

        def vjp_fn(cts, _lv=tuple(leaf_vals), _jb=jbwd):
            if _jb is not None:
                try:
                    return _jb(_lv, cts)
                except FloatingPointError:
                    raise
                except Exception:
                    pass  # exotic cotangent (float0/sparse): retrace
            # un-compiled first sighting, or jitted-vjp bail: one plain
            # jax.vjp retrace with identical semantics
            prims = [_lv[i] for i in diff_idx]

            def g(*ps):
                call = list(_lv)
                for i, p in zip(diff_idx, ps):
                    call[i] = p
                return fused(*call)

            return jax.vjp(g, *prims)[1](cts)

        node = _ag.GradNode(vjp_fn, diff_tensors, out_avals, "fused_chain",
                            fn=fused, datas=datas, kwargs={},
                            diff_idx=list(diff_idx))

    _ag._maybe_check_nan_inf("fused_chain", outs)

    # -- bind results back into the live handles -------------------------
    for k, (i, t) in enumerate(zip(out_idx, out_tensors)):
        if t is None:
            continue  # dead handle: value unobservable, keep expr interior
        e = order[i]
        o = outs[k]
        _memory.track(o)
        e.val = o
        e.anchor = t  # strong: later chains grad-link through this Tensor
        t._buf = o
        t._lazy = None
        if node is not None and e.rg:
            t._node = node
            t._out_index = k

    _M_chains.inc()
    _M_ops_fused.inc(len(order))
    _M_flushes.inc(reason=reason)
    _M_chain_len.inc(**{"len": len(order)})
    _flight.record("fusion", "flush", reason=reason, nops=len(order))
    obs = _flush_observer
    if obs is not None or _origin_flag.value:
        # stack-origin attribution: WHERE capture broke, not just why —
        # the fusion-III planning input. Off the hot path unless the
        # flag or an origin-consuming observer asks for it (the lock
        # checker's chained observer sets needs_origin=False, so pure
        # lock instrumentation skips the walk).
        want = _origin_flag.value or (
            obs is not None and getattr(obs, "needs_origin", True))
        origin = _flush_origin() if want else "<unattributed>"
        if _origin_flag.value:
            site = origin
            if site not in _seen_flush_sites:
                if len(_seen_flush_sites) >= _MAX_FLUSH_SITES:
                    site = "<other>"
                else:
                    _seen_flush_sites.add(site)
            _M_flush_sites.inc(reason=reason, site=site)
        if obs is not None:
            obs(reason, len(order), pkind, origin)
    if pkind != "elementwise":
        # a reduction "fused" when its input chain flushed WITH it (the
        # input edge is an interior node); a contraction's epilogue fused
        # when some node in this program consumes the dot's output
        consumed = set()
        for _op, children, _attrs in sig_nodes:
            for k, j, _ad in children:
                if k == "n":
                    consumed.add(j)
        for i, e in enumerate(order):
            if e.kind == "r":
                if any(k == "n" for k, _j, _ad in sig_nodes[i][1]):
                    _M_reduce_fused.inc()
            elif e.kind == "c" and i in consumed:
                _M_epi_fused.inc()


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------

def stats() -> Dict[str, Any]:
    """Counter snapshot: chains built, cache hits/misses, flush reasons,
    ops-per-chain histogram, live cache size.

    Since the telemetry unification this is a VIEW over the process
    registry (``observability.snapshot()['fusion']`` carries the same
    counters); with ``FLAGS_metrics=0`` the counters freeze."""
    chains = _M_chains.value()
    ops_fused = _M_ops_fused.value()
    snap = {
        "ops_deferred": _M_deferred.value(),
        "chains_flushed": chains,
        "ops_fused": ops_fused,
        "cache_hits": _M_hits.value(),
        "cache_misses": _M_misses.value(),
        "uncompiled_runs": _M_uncompiled.value(),
        "jit_fallbacks": _M_fallbacks.value(),
        "reductions_fused": _M_reduce_fused.value(),
        "epilogues_fused": _M_epi_fused.value(),
        # labeled registry cells back to the legacy dict shapes (label
        # values keep their Python type, so chain lengths come back int)
        "flush_reasons": {k[0][1]: v
                          for k, v in _M_flushes.series().items() if k},
        "chain_length_hist": {k[0][1]: v
                              for k, v in _M_chain_len.series().items()
                              if k},
        "cache_size": len(_cache),
        "avg_ops_per_chain": ops_fused / chains if chains else 0.0,
    }
    return snap


def reset_stats() -> None:
    for m in (_M_deferred, _M_chains, _M_ops_fused, _M_hits, _M_misses,
              _M_uncompiled, _M_fallbacks, _M_flushes, _M_chain_len,
              _M_reduce_fused, _M_epi_fused):
        m.reset()


def clear_cache() -> None:
    with _cache_lock:
        _cache.clear()
        _scalar_cache.clear()
        _aval_cache.clear()
