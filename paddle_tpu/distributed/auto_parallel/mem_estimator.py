"""Static peak-memory estimator over a traced program (jaxpr liveness).

The reference's static auto-parallel prices recompute candidates against
a memory model over its IR (ref: python/paddle/distributed/passes/
auto_parallel_recompute.py + auto_parallel/static/cost/), not against a
compiled binary. This is the jaxpr analog: a linear liveness scan —
every value is born at its producer and dies after its last consumer;
the peak is the largest concurrently-live byte count. Call-like
equations (pjit, checkpoint/remat, cond branches) are handled
recursively: a region's internals are transient, so only its boundary
values stay live outside — which is exactly how ``jax.checkpoint``
saves memory, and why this estimator sees remat savings that XLA CPU's
schedule-agnostic ``temp_size_in_bytes`` does not.

This is a MODEL, not ground truth: XLA fusion/scheduling moves the real
number (the TPU compiled ``memory_analysis()`` is the deployment
truth); the model's job is backend-neutral, compile-free RANKING of
program variants — e.g. with/without recompute segments.
"""
from __future__ import annotations

from collections import defaultdict

from jax.extend import core as jcore

__all__ = ["estimate_peak_bytes"]


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    size = 1
    for d in shape:
        if not isinstance(d, int):
            return 0  # symbolic dim: unpriceable, skip
        size *= d
    return size * dtype.itemsize


def _inner_jaxprs(eqn):
    out = []
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else [val]
        for v in vals:
            if isinstance(v, jcore.ClosedJaxpr):
                out.append(v.jaxpr)
            elif isinstance(v, jcore.Jaxpr):
                out.append(v)
    return out


def _peak(jaxpr) -> int:
    boundary = sum(_aval_bytes(v)
                   for v in (*jaxpr.invars, *jaxpr.constvars))
    last_use: dict = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last_use[v] = i
    n = len(jaxpr.eqns)
    for v in jaxpr.outvars:
        if isinstance(v, jcore.Var):
            last_use[v] = n
    deaths = defaultdict(list)
    for v, i in last_use.items():
        deaths[i].append(v)

    inputs = set(v for v in (*jaxpr.invars, *jaxpr.constvars)
                 if isinstance(v, jcore.Var))
    current = boundary  # inputs counted live throughout (constant term)
    peak = current
    for i, eqn in enumerate(jaxpr.eqns):
        out_b = sum(_aval_bytes(v) for v in eqn.outvars)
        io_b = out_b + sum(_aval_bytes(v) for v in eqn.invars
                           if isinstance(v, jcore.Var))
        current += out_b
        # a region's internal peak beyond its boundary values is
        # transient extra memory at this program point
        internal_extra = 0
        for inner in _inner_jaxprs(eqn):
            internal_extra = max(internal_extra,
                                 _peak(inner) - io_b)
        peak = max(peak, current + max(internal_extra, 0))
        for v in deaths.get(i, []):
            if v not in inputs:
                current -= _aval_bytes(v)
        # outputs with no consumer (DropVars, dead outvars) die here
        # too — without this they'd inflate `current` forever
        for v in eqn.outvars:
            if v not in last_use:
                current -= _aval_bytes(v)
    return peak


def estimate_peak_bytes(traced_or_jaxpr) -> int:
    """Estimated peak live bytes of a traced program.

    Accepts a ``jax.stages.Traced`` (``jitted.trace(*args)``), a
    ``ClosedJaxpr`` (``jax.make_jaxpr(f)(*args)``), or a raw Jaxpr.
    """
    obj = traced_or_jaxpr
    if hasattr(obj, "jaxpr"):
        obj = obj.jaxpr
    if isinstance(obj, jcore.ClosedJaxpr):
        obj = obj.jaxpr
    return _peak(obj)
