"""Degree-2 power retention (Manifest AI, "Symmetric Power Transformers" and
"Scaling Context Requires Rethinking Attention"), Pallas-on-TPU: the two
kernels behind a serving layer whose memory is a fixed-size STATE a request.

Query head ``i`` reads KV head ``h = i // r``; with ``G`` the cumulative log
decay of the head (``gamma <= 0`` a token)::

    o_t = sum_{s<=t} e^{G_t - G_s} (q_t.k_s)^2 v_s
          / (sum_{s<=t} e^{G_t - G_s} (q_t.k_s)^2 + eps)

The state form: ``phi(x)`` holds ``x_a x_b`` for ``a <= b`` (``sqrt 2`` where
``a != b``), so that ``phi(q).phi(k) = (q.k)^2``; its width is ``D = d (d +
1) / 2`` (8,256 at ``d`` 128). A KV head keeps ``S [D, dv]`` and ``z [D]``
(float32)::

    S_t = e^{gamma_t} S_{t-1} + phi(k_t) v_t^T;   z_t = e^{gamma_t} z_{t-1} + phi(k_t)
    o_t = S_t^T phi(q_t) / (z_t . phi(q_t) + eps)

**The layout of ``phi``** (the state's rows): ``d / 2`` blocks of ``d`` rows
and one of ``d / 2``. Row ``a`` of block ``j < d / 2`` is the pair ``(a, (a +
j) mod d)``; row ``a`` of the last block is ``(a, a + d / 2)``. Each
unordered pair is there once. A block of ``phi(x)`` is
``x * roll(x, -j)``: one vector product and one rotation, so both kernels form
it in VMEM from ``q`` and ``k`` and no array of the expanded width ever
reaches HBM.

- :func:`retention_step` (decode, one token a slot): for every ACTIVE slot
  and KV head the kernel reads ``S`` block by block once, decays it, adds
  ``phi(k) v^T``, writes it back in place and accumulates the ``r`` query
  heads' numerators on the way; ``z`` likewise. A slot that is not active is
  neither read nor written: the grid visits the active slots first and then
  stays on the last block it wrote. The rows of ``phi`` are formed as columns
  (a rotation of ``q``, ``k`` along sublanes) and scale whole rows of ``S`` on
  the VPU: exact float32, and memory-bound (8.5 MB a KV head at ``d`` 128).
- :func:`retention_chunk` (prefill, one slot's chunk of ``T`` rows, a grid
  step a KV head): the intra-chunk quadratic form under the decay mask in row
  tiles of ``ROWS``, ``phi(Q) S_0`` and ``phi(Q) . z_0`` block by block on the
  MXU at ``HIGHEST``, and the state carried to the chunk's end, ``e^{G_T} S_0
  + phi(K)^T (e^{G_T - G} V)``. The state is read once and written once a
  chunk; a request's first chunk reads it as zeros. **A decay is only ever
  taken as a difference of cumulative logs that is <= 0.**

Both have a jnp form with the same contract (the CPU path, widths Mosaic
refuses, and the numerics oracle); the seams count which one ran
(``pallas.path_selected_total{kernel="retention_step"|"retention_chunk"}``).
:func:`retention_recurrence` is the token-by-token rule and
:func:`retention_quadratic` the quadratic form, for tests and the model's own
forward.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import count_path

__all__ = ["retention_step", "retention_chunk", "retention_recurrence",
           "retention_quadratic", "feature_dim", "phi",
           "kernel_available", "EPS", "ROWS"]

EPS = 1e-6           # under the normaliser
ROWS = 128           # the chunk kernel's row tile (the spans count in it)
_SQRT2 = math.sqrt(2.0)
_HI = jax.lax.Precision.HIGHEST
_STEP_VMEM = 40 << 20
_CHUNK_VMEM = 96 << 20


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def decay(x):
    """``e^x`` for ``x <= 0``. Near 0 it is the Taylor sum (its degree-7 term
    is under 1e-10 of it), not ``jnp.exp``: on the v5e a slot's state decayed
    once a token by ``jnp.exp`` drifted 5e-4 from the closed form in 512
    decode steps (the slow heads' decay, about 1 - 1e-4, a few 1e-6 off each
    time); by this sum, 2e-6."""
    x = jnp.asarray(x, jnp.float32)
    small = x > -0.125
    t = jnp.where(small, x, 0.0)
    series = 1.0 + t * (1.0 + t / 2 * (1.0 + t / 3 * (1.0 + t / 4 * (
        1.0 + t / 5 * (1.0 + t / 6)))))
    return jnp.where(small, series, jnp.exp(jnp.where(small, -1.0, x)))


def feature_dim(d: int) -> int:
    return d * (d + 1) // 2


def phi(x):
    """``[..., d]`` -> ``[..., D]`` float32 in the state's layout (jnp form):
    products of rotations, as the kernels form it, and no gather (XLA:TPU may
    take a gather of few rows as a matmul at the default precision)."""
    d = x.shape[-1]
    half = d // 2
    x = x.astype(jnp.float32)
    return jnp.concatenate(
        [x * x] + [_SQRT2 * x * jnp.roll(x, -j, -1) for j in range(1, half)]
        + [_SQRT2 * x[..., :half] * x[..., half:]], axis=-1)


def kernel_available(d: int, dv: int, group: int) -> bool:
    """Mosaic takes whole 128-lane rows; a step's packed rows (the group's
    queries, k, v and the decay) are one tile of 8."""
    return d == 128 and dv == 128 and group + 3 <= 8


def _use_kernel(use_kernel, interpret, d, dv, group, dtype) -> bool:
    if dtype != jnp.float32:
        return False
    if interpret:       # the interpreter takes any even width
        return d == dv and d % 2 == 0 and group + 3 <= 8
    if use_kernel is None:
        use_kernel = _on_tpu()
    return bool(use_kernel) and kernel_available(d, dv, group)


# ---------------------------------------------------------------------------
# the rule itself (tests, the model's forward, and the oracle of both forms)
# ---------------------------------------------------------------------------

def retention_recurrence(S, z, q, k, v, gamma, eps: float = EPS):
    """A token at a time. ``S [Hk, D, dv]``, ``z [Hk, D]``; ``q [T, Hq, d]``,
    ``k [T, Hk, d]``, ``v [T, Hk, dv]``, ``gamma [T, Hk]``. Returns ``(o [T,
    Hq, dv], S, z)`` after the ``T`` tokens, all float32."""
    Hk = S.shape[0]
    r = q.shape[1] // Hk

    def one(carry, x):
        S, z = carry
        q, k, v, g = x
        a = decay(g)
        fk = phi(k)
        S = a[:, None, None] * S + fk[..., None] * v[:, None, :]
        z = a[:, None] * z + fk
        fq = phi(q).reshape(Hk, r, -1)
        num = jnp.einsum("hrD,hDv->hrv", fq, S, precision=_HI)
        den = jnp.einsum("hrD,hD->hr", fq, z, precision=_HI)
        return (S, z), (num / (den + eps)[..., None]).reshape(Hk * r, -1)
    f32 = jnp.float32
    (S, z), o = jax.lax.scan(one, (S.astype(f32), z.astype(f32)),
                             tuple(x.astype(f32) for x in (q, k, v, gamma)))
    return o, S, z


def retention_quadratic(q, k, v, gamma, eps: float = EPS):
    """The quadratic form over whole sequences from a zero state: ``q [..., T,
    Hq, d]``, ``k [..., T, Hk, d]``, ``v [..., T, Hk, dv]``, ``gamma [..., T,
    Hk]`` -> ``o [..., T, Hq, dv]`` float32."""
    f32 = jnp.float32
    *lead, T, Hq, d = q.shape
    Hk = k.shape[-2]
    q = q.astype(f32).reshape(*lead, T, Hk, Hq // Hk, d)
    G = jnp.cumsum(gamma.astype(f32), axis=-2)                # [.., T, Hk]
    s = jnp.einsum("...thrd,...shd->...hrts", q, k.astype(f32),
                   precision=_HI)
    Gh = jnp.swapaxes(G, -1, -2)                              # [.., Hk, T]
    diff = Gh[..., :, None] - Gh[..., None, :]                # G_t - G_s
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    dec = jnp.where(causal, decay(jnp.minimum(diff, 0.0)), 0.0)
    A = s * s * dec[..., None, :, :]
    num = jnp.einsum("...hrts,...shv->...thrv", A, v.astype(f32),
                     precision=_HI)
    den = jnp.moveaxis(A.sum(-1), -1, -3)                     # [.., T, Hk, r]
    return (num / (den + eps)[..., None]).reshape(*lead, T, Hq, -1)


# ---------------------------------------------------------------------------
# step (decode)
# ---------------------------------------------------------------------------

def _step_reference(S, z, q, k, v, gamma, act, eps):
    NS, Hk, D, dv = S.shape
    a = decay(gamma)
    fk = phi(k)                                               # [NS, Hk, D]
    S1 = a[..., None, None] * S + fk[..., None] * v[:, :, None, :]
    z1 = a[..., None] * z + fk
    fq = phi(q).reshape(NS, Hk, -1, D)
    num = jnp.einsum("nhrD,nhDv->nhrv", fq, S1, precision=_HI)
    den = jnp.einsum("nhrD,nhD->nhr", fq, z1, precision=_HI)
    o = (num / (den + eps)[..., None]).reshape(NS, -1, dv)
    live = act[:, None, None]
    return (jnp.where(live, o, 0.0), jnp.where(live[..., None], S1, S),
            jnp.where(live, z1, z))


def _step_kernel(ids_ref, n_ref, s_ref, z_ref, x_ref, so_ref, zo_ref, o_ref,
                 *, r):
    h = pl.program_id(1)
    P, d = x_ref.shape[2:]
    half = d // 2

    @pl.when(pl.program_id(0) < n_ref[0])
    def _update():
        X = x_ref[0, 0]              # [P, d]: r queries, k, v, the decay
        a = X[r + 2:r + 3, :]         # the decay across the lanes
        v = X[r + 1:r + 2, :]
        Xc = X.T                     # the same as columns
        mine = jax.lax.broadcasted_iota(
            jnp.int32, (zo_ref.shape[1], d), 0) == h

        def block(Pc, Pr, off, rows, c, carry):
            """Rows ``off .. off + rows`` of the state: ``Pc`` the block of
            phi as columns, ``Pr`` as rows (lanes)."""
            nums, den = carry
            S = a * s_ref[0, 0, pl.ds(off, rows), :] \
                + (c * Pc[:rows, r:r + 1]) * v
            so_ref[0, 0, pl.ds(off, rows), :] = S
            nums = tuple(acc + jnp.sum(S * (c * Pc[:rows, i:i + 1]), axis=0,
                                       keepdims=True)
                         for i, acc in enumerate(nums))
            # z of this head is one row of the slot's [Hk, D] block
            sel = mine[:, :rows]
            zj = a[:, :rows] * jnp.sum(jnp.where(
                sel, z_ref[0, :, pl.ds(off, rows)], 0.0), axis=0, keepdims=True) \
                + c * Pr[r:r + 1, :rows]
            zo_ref[0, :, pl.ds(off, rows)] = jnp.where(
                sel, zj, zo_ref[0, :, pl.ds(off, rows)])
            den = den + jnp.sum(c * Pr[:, :rows] * zj, axis=1, keepdims=True)
            return nums, den

        carry = (tuple(jnp.zeros((1, v.shape[1]), jnp.float32)
                       for _ in range(r)),
                 jnp.zeros((P, 1), jnp.float32))
        carry = block(Xc * Xc, X * X, 0, d, 1.0, carry)

        def body(j, carry):
            return block(Xc * pltpu.roll(Xc, d - j, 0),
                         X * pltpu.roll(X, d - j, 1),
                         pl.multiple_of(j * d, d), d, _SQRT2, carry)
        carry = jax.lax.fori_loop(1, half, body, carry)
        nums, den = block(Xc * pltpu.roll(Xc, half, 0),
                          X * pltpu.roll(X, half, 1), half * d, half, _SQRT2,
                          carry)
        dv = v.shape[1]
        o_ref[0, 0, 0:P, :] = jnp.concatenate(
            list(nums) + [jnp.zeros((P - r, dv), jnp.float32)], axis=0)
        o_ref[0, 0, P:2 * P, :] = jnp.broadcast_to(den, (P, dv))

    # no slot is active: every step is the first slot's last block, which is
    # written back once, as it came
    @pl.when(n_ref[0] == 0)
    def _keep():
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("r", "interpret"))
def _retention_step_call(S, z, X, ids, n, *, r, interpret=False):
    """S [NS, Hk, D, dv] and z [NS, Hk, D] (the kernel's inputs and outputs:
    updated in place where the caller donates them), X [NS, Hk, P, d] (rows:
    the group's r queries, k, v, the decay e^gamma), ids [NS] the active
    slots first then the last of them again, n [1]. Returns (S, z, out [NS,
    Hk, 2 P, dv]): numerators in rows 0 .. r-1, each denominator across row
    P + i."""
    NS, Hk, D, dv = S.shape
    P, d = X.shape[2:]

    def at(i, h, ids, n):
        # past the active slots: stay on the last block written
        return ids[i], jnp.where(i < n[0], h, Hk - 1)

    state = pl.BlockSpec((1, 1, D, dv), lambda i, h, ids, n:
                         (*at(i, h, ids, n), 0, 0))
    zs = pl.BlockSpec((1, Hk, D), lambda i, h, ids, n: (ids[i], 0, 0))
    rows = lambda width: pl.BlockSpec(
        (1, 1, width, d), lambda i, h, ids, n: (*at(i, h, ids, n), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(NS, Hk),
        in_specs=[state, zs, rows(P)], out_specs=[state, zs, rows(2 * P)])
    return pl.pallas_call(
        functools.partial(_step_kernel, r=r), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((NS, Hk, 2 * P, dv), jnp.float32)],
        # operands 2 and 3 (after the two prefetched scalars): the pools
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STEP_VMEM),
        interpret=interpret,
    )(ids, n, S, z, X)


def retention_step(S, z, q, k, v, gamma, act, use_kernel: Optional[bool] = None,
                   interpret=False, eps: float = EPS):
    """One token for every slot of the pool. ``S [NS, Hk, D, dv]`` and ``z
    [NS, Hk, D]`` float32 (every slot's state), ``q [NS, Hq, d]``, ``k [NS,
    Hk, d]``, ``v [NS, Hk, dv]``, ``gamma [NS, Hk]`` (log decay), ``act [NS]``
    bool. Returns ``(o [NS, Hq, dv] float32, S, z)``: the active slots'
    states updated, the others' bit for bit as they were (and their ``o``
    zero)."""
    NS, Hk, D, dv = S.shape
    d = k.shape[-1]
    r = q.shape[1] // Hk
    f32 = jnp.float32
    q, k, v, gamma = (x.astype(f32) for x in (q, k, v, gamma))
    if not _use_kernel(use_kernel, interpret, d, dv, r, S.dtype):
        count_path("retention_step", "reference")
        o, S2, z2 = _step_reference(S.astype(f32), z.astype(f32), q, k, v,
                                    gamma, act, eps)
        return o, S2.astype(S.dtype), z2.astype(z.dtype)
    count_path("retention_step", "pallas")
    P = 8
    X = jnp.concatenate([
        q.reshape(NS, Hk, r, d), k[:, :, None], v[:, :, None],
        jnp.broadcast_to(decay(gamma)[..., None, None], (NS, Hk, 1, d)),
        jnp.zeros((NS, Hk, P - r - 3, d), f32)], axis=2)
    order = jnp.argsort(~act, stable=True).astype(jnp.int32)
    n = jnp.sum(act).astype(jnp.int32)
    ids = jnp.where(jnp.arange(NS) < n, order, order[jnp.maximum(n - 1, 0)])
    S, z, out = _retention_step_call(S, z, X, ids, n[None], r=r,
                                     interpret=interpret)
    num = out[:, :, :r, :]
    den = out[:, :, P:P + r, :1]
    o = (num / (den + eps)).reshape(NS, Hk * r, dv)
    return jnp.where(act[:, None, None], o, 0.0), S, z


# ---------------------------------------------------------------------------
# chunk (prefill)
# ---------------------------------------------------------------------------

def _chunk_reference(S, z, slot, fresh, q, k, v, gamma, eps):
    NS, Hk, D, dv = S.shape
    T = q.shape[0]
    S0 = jax.lax.dynamic_index_in_dim(S, slot, 0, keepdims=False)
    z0 = jax.lax.dynamic_index_in_dim(z, slot, 0, keepdims=False)
    S0 = jnp.where(fresh, 0.0, S0.astype(jnp.float32))
    z0 = jnp.where(fresh, 0.0, z0.astype(jnp.float32))
    G = jnp.cumsum(gamma, axis=0)                             # [T, Hk]
    Gl = G[-1]
    qg = q.reshape(T, Hk, -1, q.shape[-1])
    s = jnp.einsum("thrd,shd->hrts", qg, k, precision=_HI)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    dec = jnp.where(causal, decay(jnp.minimum(
        G.T[:, :, None] - G.T[:, None, :], 0.0)), 0.0)        # [Hk, T, T]
    A = s * s * dec[:, None]
    fq = phi(qg)                                              # [T, Hk, r, D]
    eG = decay(G)[:, :, None]
    num = jnp.einsum("hrts,shv->thrv", A, v, precision=_HI) \
        + eG[..., None] * jnp.einsum("thrD,hDv->thrv", fq, S0, precision=_HI)
    den = jnp.moveaxis(A.sum(-1), -1, 0) \
        + eG * jnp.einsum("thrD,hD->thr", fq, z0, precision=_HI)
    w = decay(Gl[None] - G)                                   # [T, Hk]
    fk = phi(k) * w[..., None]
    S1 = decay(Gl)[:, None, None] * S0 \
        + jnp.einsum("thD,thv->hDv", fk, v, precision=_HI)
    z1 = decay(Gl)[:, None] * z0 + fk.sum(0)
    S = jax.lax.dynamic_update_index_in_dim(S, S1.astype(S.dtype), slot, 0)
    z = jax.lax.dynamic_update_index_in_dim(z, z1.astype(z.dtype), slot, 0)
    return (num / (den + eps)[..., None]).reshape(T, -1, dv), S, z


def _chunk_kernel(meta_ref, s_ref, z_ref, q_ref, k_ref, v_ref, e_ref, w_ref,
                  dec_ref, so_ref, zo_ref, o_ref, num_scr, den_scr, *, eps):
    h = pl.program_id(0)
    _, r, T, d = q_ref.shape
    half = d // 2
    R = min(ROWS, T)
    fresh = meta_ref[1] != 0

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims, precision=_HI,
                                   preferred_element_type=jnp.float32)

    eG = e_ref[0]           # [T, d] across the lanes: e^{G_t}
    end = eG[T - 1:T, :]    # e^{G_T}, the chunk's whole decay
    w = w_ref[0]            # e^{G_T - G_s}
    K = k_ref[0]
    V = v_ref[0]
    Vw = V * w
    Q = q_ref[0].reshape(r * T, d)
    mine = jax.lax.broadcasted_iota(jnp.int32, (zo_ref.shape[1], d), 0) == h
    num_scr[...] = jnp.zeros_like(num_scr)
    den_scr[...] = jnp.zeros_like(den_scr)

    def block(PQ, PK, off, rows):
        """Rows ``off .. off + rows`` of the state against the chunk: ``PQ``,
        ``PK`` the block of phi of its queries and keys (lanes)."""
        S0 = jnp.where(fresh, 0.0, s_ref[0, 0, pl.ds(off, rows), :])
        sel = mine[:, :rows]
        z0 = jnp.where(fresh, 0.0, jnp.sum(jnp.where(
            sel, z_ref[0, :, pl.ds(off, rows)], 0.0), axis=0, keepdims=True))
        PQ, PK = PQ[:, :rows], PK[:, :rows]
        num_scr[...] += dot(PQ, S0)
        so_ref[0, 0, pl.ds(off, rows), :] = end[:, :S0.shape[1]] * S0 \
            + dot(PK, Vw, (((0,), (0,)), ((), ())))
        zo_ref[0, :, pl.ds(off, rows)] = jnp.where(
            sel, end[:, :rows] * z0 + jnp.sum(PK * w[:, :rows], axis=0,
                                              keepdims=True),
            zo_ref[0, :, pl.ds(off, rows)])
        return PQ * z0

    den_scr[...] += block(Q * Q, K * K, 0, d)

    def body(j, _):
        den_scr[...] += block(
            _SQRT2 * Q * pltpu.roll(Q, d - j, 1),
            _SQRT2 * K * pltpu.roll(K, d - j, 1), pl.multiple_of(j * d, d), d)
        return 0
    jax.lax.fori_loop(1, half, body, 0)
    den_scr[:, 0:1] += jnp.sum(block(
        _SQRT2 * Q * pltpu.roll(Q, half, 1),
        _SQRT2 * K * pltpu.roll(K, half, 1), half * d, half),
        axis=1, keepdims=True)

    # the quadratic form within the chunk, a row tile at a time against the
    # keys up to its last row; then both parts under one normaliser
    for m in range(T // R):
        t0, t1 = m * R, (m + 1) * R
        dec = dec_ref[0, t0:t1, :t1]
        et = eG[t0:t1, 0:1]
        for i in range(r):
            s = dot(q_ref[0, i, t0:t1, :], K[:t1], (((1,), (1,)), ((), ())))
            A = s * s * dec
            lo = i * T + t0
            num = dot(A, V[:t1]) + et * num_scr[lo:lo + R, :]
            den = jnp.sum(A, axis=1, keepdims=True) \
                + et * jnp.sum(den_scr[lo:lo + R, :], axis=1, keepdims=True)
            o_ref[0, i, t0:t1, :] = num / (den + eps)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _retention_chunk_call(S, z, qg, k, v, e, w, dec, meta, *, eps,
                          interpret=False):
    """S [NS, Hk, D, dv], z [NS, Hk, D] (inputs and outputs: slot
    ``meta[0]``'s state updated in place, read as zeros where ``meta[1]``),
    qg [Hk, r, T, d], k [Hk, T, d], v [Hk, T, dv], the decays across the
    lanes e = e^{G_t} and w = e^{G_T - G_t} [Hk, T, d], and within the chunk
    dec [Hk, T, T] (e^{G_t - G_s} where s <= t, else 0) -> (S, z, o [Hk, r, T,
    dv])."""
    NS, Hk, D, dv = S.shape
    _, r, T, d = qg.shape
    state = pl.BlockSpec((1, 1, D, dv), lambda h, m: (m[0], h, 0, 0))
    zs = pl.BlockSpec((1, Hk, D), lambda h, m: (m[0], 0, 0))
    head = lambda *shape: pl.BlockSpec(
        (1,) + shape, lambda h, m: (h,) + (0,) * len(shape))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(Hk,),
        in_specs=[state, zs, head(r, T, d), head(T, d), head(T, dv),
                  head(T, d), head(T, d), head(T, T)],
        out_specs=[state, zs, head(r, T, dv)],
        scratch_shapes=[pltpu.VMEM((r * T, dv), jnp.float32),
                        pltpu.VMEM((r * T, d), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_chunk_kernel, eps=eps), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((Hk, r, T, dv), jnp.float32)],
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_CHUNK_VMEM),
        interpret=interpret,
    )(meta, S, z, qg, k, v, e, w, dec)


def retention_chunk(S, z, slot, fresh, q, k, v, gamma,
                    use_kernel: Optional[bool] = None, interpret=False,
                    eps: float = EPS):
    """One slot's chunk of rows. ``S [NS, Hk, D, dv]``, ``z [NS, Hk, D]``
    float32 (the pools), ``slot`` an int32 scalar, ``fresh`` a bool scalar
    (the chunk is a request's first: its state starts at zero whatever the
    pool holds), ``q [T, Hq, d]``, ``k [T, Hk, d]``, ``v [T, Hk, dv]``,
    ``gamma [T, Hk]``; a padding row after the last valid one has ``k = v =
    0`` and ``gamma = 0`` and leaves the state as it was. Returns ``(o [T, Hq,
    dv] float32, S, z)`` with the slot's state after the ``T`` rows."""
    NS, Hk, D, dv = S.shape
    T, Hq, d = q.shape
    r = Hq // Hk
    f32 = jnp.float32
    q, k, v, gamma = (x.astype(f32) for x in (q, k, v, gamma))
    slot = jnp.asarray(slot, jnp.int32)
    if not _use_kernel(use_kernel, interpret, d, dv, r, S.dtype) or T % 8:
        count_path("retention_chunk", "reference")
        return _chunk_reference(S, z, slot, fresh, q, k, v, gamma, eps)
    count_path("retention_chunk", "pallas")
    # the decays in XLA, the kernel only multiplies by them
    G = jnp.cumsum(gamma, axis=0).T                           # [Hk, T]
    lanes = lambda x: jnp.broadcast_to(x[:, :, None], (Hk, T, d))
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    dec = jnp.where(causal, decay(jnp.minimum(
        G[:, :, None] - G[:, None, :], 0.0)), 0.0)
    qg = q.reshape(T, Hk, r, d).transpose(1, 2, 0, 3)
    meta = jnp.stack([slot, jnp.asarray(fresh, jnp.int32)])
    S, z, o = _retention_chunk_call(
        S, z, qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        lanes(decay(G)), lanes(decay(G[:, -1:] - G)), dec, meta, eps=eps,
        interpret=interpret)
    return o.reshape(Hq, T, dv).transpose(1, 0, 2), S, z
