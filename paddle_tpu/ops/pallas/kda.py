"""Gated delta-rule linear attention with a decay a channel (Kimi Delta
Attention, arXiv 2510.26692), Pallas-on-TPU: the two kernels behind a
serving layer whose memory is a fixed-size STATE a request, not a row a
token.

A head keeps ``S [dk, dv]`` (float32). A token with query ``q``, key ``k``
(both ``[dk]``), value ``v [dv]``, decay ``a = exp(g) in (0, 1]^dk`` and
write strength ``beta`` does::

    S' = Diag(a) S;   S = S' + beta k (v - S'^T k)^T;   o = S^T q

- :func:`kda_step` (decode, one token a slot): every ACTIVE slot's heads
  read their state once, apply the rank-one update and write it back, in
  place (the pool is the kernel's input and its output). A slot that is not
  active is neither read nor written: the grid visits the active slots first
  and then stays on the last block it wrote. The kernel is memory-bound:
  128 KB a head a token against about 200 vector operations.
- :func:`kda_chunk` (prefill, one slot's chunk of rows): sub-chunks of 64
  rows in WY form. Within a sub-chunk ``U = T (beta V - beta K~ S_0)`` with
  ``T = (I + Diag(beta) A)^-1``, ``A[r, i] = sum_c k_r k_i exp(G_r - G_i)``
  (``i < r``, ``G`` the cumulative log decay), ``O = Q~ S_0 + B U``, ``S_C =
  Diag(exp(G_C)) S_0 + K^^T U``. **The decay is only ever taken as a
  difference of cumulative logs that is <= 0**: never ``exp(-G)``, which
  overflows float32 after 45 tokens at a decay of 2 a token. Two rows go
  through the END of the first half of the smallest power-of-two group of
  rows that holds both, ``exp(G_r - G_e) exp(G_e - G_i)``, both factors <=
  1: one batched matmul a level of halving, from 32 rows down to 8; pairs
  in one group of 8 are computed directly (``exp(G_r - G_i)``, masked above
  the diagonal before the exponential). (A direct form over blocks of 16
  with one matmul a block column read 3.76 ms a layer a 512-row chunk on
  the v5e, this one 0.74: PERF.md, PR 35.) What does not
  depend on the state (``A``, ``B``, the triangular solve, the decayed
  copies of ``q`` and ``k``) is XLA's, batched over heads and sub-chunks;
  the KERNEL is the part that is sequential: a grid over (head group,
  sub-chunk) that carries the state in VMEM across a group's sub-chunks,
  reads it from the slot's pool once and writes it once a chunk (zeros in
  place of the read where the chunk is a request's first).

Both have a jnp form with the same contract (the CPU path, head sizes
Mosaic refuses, and the numerics oracle); the seams count which one ran
(``pallas.path_selected_total{kernel="kda_step"|"kda_chunk"}``).
:func:`kda_recurrence` is the token-by-token rule itself, for tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import count_path

__all__ = ["kda_step", "kda_chunk", "kda_recurrence", "kernel_available",
           "SUBCHUNK"]

SUBCHUNK = 64        # rows of one WY sub-chunk
_BLOCK = 8           # rows of a group whose pairs are computed directly
_STEP_HEADS = 16     # heads a grid step of the step kernel takes
_CHUNK_HEADS = 8     # heads a grid step of the chunk kernel takes
_HI = jax.lax.Precision.HIGHEST


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_available(heads: int, dk: int, dv: int) -> bool:
    """Mosaic takes whole 128-lane rows and whole head groups only."""
    return (dk == 128 and dv == 128 and heads % _STEP_HEADS == 0
            and heads % _CHUNK_HEADS == 0)


def _use_kernel(use_kernel, interpret, heads, dk, dv) -> bool:
    if use_kernel is None:
        use_kernel = _on_tpu()
    return bool(use_kernel or interpret) and kernel_available(heads, dk, dv)


# ---------------------------------------------------------------------------
# the rule itself, a token at a time (tests, and the oracle of both forms)
# ---------------------------------------------------------------------------

def kda_recurrence(S, q, k, v, g, beta):
    """``S [H, dk, dv]``; ``q, k, g [T, H, dk]``, ``v [T, H, dv]``, ``beta
    [T, H]``, all float32. Returns ``(o [T, H, dv], S after the T tokens)``."""
    def one(S, x):
        q, k, v, g, beta = x
        S1 = S * jnp.exp(g)[..., None]
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S1, k,
                                            precision=_HI))
        S2 = S1 + k[..., None] * u[:, None, :]
        return S2, jnp.einsum("hkv,hk->hv", S2, q, precision=_HI)
    S, o = jax.lax.scan(one, S, (q, k, v, g, beta))
    return o, S


# ---------------------------------------------------------------------------
# step (decode)
# ---------------------------------------------------------------------------

def _step_reference(S, q, k, v, a, beta, act):
    S1 = S * a[..., None]
    u = beta[..., None] * (v - jnp.einsum("shkv,shk->shv", S1, k,
                                          precision=_HI))
    S2 = S1 + k[..., None] * u[:, :, None, :]
    o = jnp.einsum("shkv,shk->shv", S2, q, precision=_HI)
    live = act[:, None, None, None]
    return jnp.where(live[..., 0], o, 0.0), jnp.where(live, S2, S)


def _step_kernel(ids_ref, n_ref, s_ref, pk_ref, bv_ref, so_ref, o_ref, *,
                 hb):
    i = pl.program_id(0)

    @pl.when(i < n_ref[0])
    def _update():
        cols = pk_ref[0, 0]                       # [dk, 4 hb | zeros]
        for j in range(hb):
            S1 = s_ref[0, j] * cols[:, 2 * hb + j:2 * hb + j + 1]
            r = jnp.sum(S1 * cols[:, 3 * hb + j:3 * hb + j + 1], axis=0,
                        keepdims=True)
            u = bv_ref[0, j:j + 1, :] - r                     # [1, dv]
            S2 = S1 + cols[:, j:j + 1] * u
            so_ref[0, j] = S2
            o_ref[0, j:j + 1, :] = jnp.sum(
                S2 * cols[:, hb + j:hb + j + 1], axis=0, keepdims=True)

    # no slot is active: every step is the first slot's last block, which
    # is written back once, as it came
    @pl.when(n_ref[0] == 0)
    def _keep():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_step_call(S, packed, bv, ids, n, *, interpret=False):
    """S [NS, H, dk, dv] (the kernel's input and output: updated in place
    where the caller donates it), packed [NS, G, dk,
    128] (columns: k, q, a, beta k of the group's heads), bv [NS, H, dv],
    ids [NS] the active slots first then the last of them again, n [1]."""
    NS, H, dk, dv = S.shape
    hb = _STEP_HEADS
    G = H // hb

    def at(i, g, ids, n):
        # past the active slots: stay on the last block written
        return ids[i], jnp.where(i < n[0], g, G - 1)

    state = pl.BlockSpec((1, hb, dk, dv), lambda i, g, ids, n:
                         (*at(i, g, ids, n), 0, 0))
    rows = pl.BlockSpec((1, hb, dv), lambda i, g, ids, n:
                        (*at(i, g, ids, n), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(NS, G),
        in_specs=[state,
                  pl.BlockSpec((1, 1, dk, 128), lambda i, g, ids, n:
                               (*at(i, g, ids, n), 0, 0)),
                  rows],
        out_specs=[state, rows])
    return pl.pallas_call(
        functools.partial(_step_kernel, hb=hb), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((NS, H, dv), jnp.float32)],
        # operand 2 (after the two prefetched scalars) is the pool
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(ids, n, S, packed, bv)


def kda_step(S, q, k, v, g, beta, act, use_kernel: Optional[bool] = None,
             interpret=False):
    """One token for every slot of the pool. ``S [NS, H, dk, dv]`` float32
    (every slot's state), ``q, k, g [NS, H, dk]``, ``v [NS, H, dv]``, ``beta
    [NS, H]``, ``act [NS]`` bool. Returns ``(o [NS, H, dv] float32, S)``: the
    active slots' states updated, the others' bit for bit as they were (and
    their ``o`` zero)."""
    NS, H, dk, dv = S.shape
    f32 = jnp.float32
    q, k, v, beta = (x.astype(f32) for x in (q, k, v, beta))
    a = jnp.exp(g.astype(f32))
    if not _use_kernel(use_kernel, interpret, H, dk, dv) \
            or S.dtype != jnp.float32:
        count_path("kda_step", "reference")
        o, S2 = _step_reference(S.astype(f32), q, k, v, a, beta, act)
        return o, S2.astype(S.dtype)
    count_path("kda_step", "pallas")
    hb = _STEP_HEADS
    # the vectors that scale ROWS of S, as columns: [NS, G, dk, 4 hb]
    cols = jnp.stack([k, q, a, beta[..., None] * k], axis=1)    # [NS,4,H,dk]
    cols = cols.reshape(NS, 4, H // hb, hb, dk).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(NS, H // hb, dk, 4 * hb)
    packed = jnp.pad(cols, ((0, 0),) * 3 + ((0, 128 - 4 * hb),))
    order = jnp.argsort(~act, stable=True).astype(jnp.int32)
    n = jnp.sum(act).astype(jnp.int32)
    ids = jnp.where(jnp.arange(NS) < n, order, order[jnp.maximum(n - 1, 0)])
    S, o = _kda_step_call(S, packed, beta[..., None] * v, ids, n[None],
                          interpret=interpret)
    return jnp.where(act[:, None, None], o, 0.0), S


# ---------------------------------------------------------------------------
# chunk (prefill)
# ---------------------------------------------------------------------------

def _pairs(q, k, kb, G):
    """``A'[r, i] = sum_c kb_r k_i exp(G_r - G_i)`` below the diagonal and
    ``B[r, i] = sum_c q_r k_i exp(G_r - G_i)`` on and below it, for
    sub-chunks ``[N, H, C, d]``; every exponent taken is <= 0. By halving:
    the rows of a group of ``2 m`` against the columns of its first half go
    through that half's END (``exp(G_r - G_e) exp(G_e - G_i)``, one batched
    matmul a level), for ``m = C / 2`` down to ``_BLOCK``; a group of
    ``_BLOCK`` rows is computed pair by pair."""
    N, H, C, d = k.shape
    A = jnp.zeros((N, H, C, C), jnp.float32)
    B = jnp.zeros((N, H, C, C), jnp.float32)
    b = min(_BLOCK, C)
    m = C // 2
    while m >= b:
        n = C // (2 * m)
        grp = lambda x: x.reshape(N, H, n, 2, m, d)
        Gg = grp(G)
        Ge = Gg[:, :, :, 0, m - 1:m, :]
        right = grp(k)[:, :, :, 0] * jnp.exp(Ge - Gg[:, :, :, 0])
        down = jnp.exp(Gg[:, :, :, 1] - Ge)
        eye = jnp.eye(n, dtype=jnp.float32)

        def placed(x):
            """The level's blocks, each at (second half, first half) of its
            group on the diagonal of groups."""
            blk = jnp.einsum("nhjrd,nhjid->nhjri", grp(x)[:, :, :, 1] * down,
                             right, precision=_HI)
            full = jnp.einsum("nhjri,jl->nhjrli", blk, eye)
            return jnp.zeros((N, H, n, 2, m, n, 2, m), jnp.float32).at[
                :, :, :, 1, :, :, 0, :].set(full).reshape(N, H, C, C)
        A, B = A + placed(kb), B + placed(q)
        m //= 2
    nb = C // b
    blk = lambda x: x.reshape(N, H, nb, b, d)
    Gd = blk(G)
    r, i = jnp.arange(b)[:, None], jnp.arange(b)[None, :]
    diff = Gd[..., :, None, :] - Gd[..., None, :, :]          # G_r - G_i
    w = jnp.exp(jnp.where((i <= r)[..., None], diff, -jnp.inf)) \
        * blk(k)[..., None, :, :]
    eye = jnp.eye(nb, dtype=jnp.float32)
    on_diagonal = lambda x: jnp.einsum("nhjri,jl->nhjrli", x, eye).reshape(
        N, H, C, C)
    A = A + on_diagonal(jnp.where(
        i < r, jnp.sum(w * blk(kb)[..., :, None, :], -1), 0.0))
    B = B + on_diagonal(jnp.sum(w * blk(q)[..., :, None, :], -1))
    return A, B


def _chunk_prepare(q, k, v, g, beta, C):
    """What of the WY form does not depend on the state, for rows ``[T, H,
    d]`` in sub-chunks of ``C``: ``W, Y, Qt, Kh [N, H, C, d]``, ``B [N, H, C,
    C]``, ``gc [N, H, dk]`` with ``U = W - Y S_0``, ``O = Qt S_0 + B U``,
    ``S_C = gc S_0 + Kh^T U``."""
    T, H, dk = k.shape
    N = T // C
    sub = lambda x: x.reshape(N, C, H, -1).transpose(0, 2, 1, 3)
    q, k, v, g = sub(q), sub(k), sub(v), sub(g)
    beta = sub(beta[..., None])
    G = jnp.cumsum(g, axis=2)
    kb, dec = k * beta, jnp.exp(G)
    A, B = _pairs(q, k, kb, G)
    rhs = jnp.concatenate([v * beta, dec * kb], axis=-1)
    sol = jax.lax.linalg.triangular_solve(
        A + jnp.eye(C, dtype=jnp.float32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    last = G[:, :, -1:, :]
    return (sol[..., :v.shape[-1]], sol[..., v.shape[-1]:], dec * q, B,
            jnp.exp(last - G) * k, jnp.exp(last[:, :, 0]))


def _chunk_scan(S0, W, Y, Qt, B, Kh, gc):
    """The sub-chunks in turn, the state carried (jnp form)."""
    def one(S, x):
        W, Y, Qt, B, Kh, gc = x
        U = W - jnp.einsum("hck,hkv->hcv", Y, S, precision=_HI)
        O = jnp.einsum("hck,hkv->hcv", Qt, S, precision=_HI) \
            + jnp.einsum("hcr,hrv->hcv", B, U, precision=_HI)
        S = gc[..., None] * S + jnp.einsum("hck,hcv->hkv", Kh, U,
                                           precision=_HI)
        return S, O
    return jax.lax.scan(one, S0, (W, Y, Qt, B, Kh, gc))


def _chunk_kernel(meta_ref, s_ref, w_ref, y_ref, qt_ref, b_ref, kh_ref,
                  gct_ref, so_ref, o_ref, S_scr, *, hb, n_sub):
    j = pl.program_id(1)

    @pl.when((j == 0) & (meta_ref[1] != 0))
    def _fresh():                       # a request's first chunk: no read
        S_scr[...] = jnp.zeros_like(S_scr)

    @pl.when((j == 0) & (meta_ref[1] == 0))
    def _carry_on():
        S_scr[...] = s_ref[0]

    def dot(a, b, dims=(((1,), (0,)), ((), ()))):
        return jax.lax.dot_general(a, b, dims, precision=_HI,
                                   preferred_element_type=jnp.float32)

    gct = gct_ref[0, 0]                                   # [dk, hb]
    for h in range(hb):
        S = S_scr[h]
        U = w_ref[0, h] - dot(y_ref[0, h], S)
        o_ref[0, h] = dot(qt_ref[0, h], S) + dot(b_ref[0, h], U)
        S_scr[h] = gct[:, h:h + 1] * S \
            + dot(kh_ref[0, h], U, (((0,), (0,)), ((), ())))

    @pl.when(j == n_sub - 1)
    def _write():
        so_ref[0] = S_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_chunk_call(S, W, Y, Qt, B, Kh, gct, meta, *, interpret=False):
    """S [NS, H, dk, dv] (input and output: slot ``meta[0]``'s state updated in
    place, read as zeros where ``meta[1]``), W [N, H, C, dv], Y, Qt, Kh [N,
    H, C, dk], B [N, H, C, C], gct [N, H / hb, dk, hb] -> (S, O [N, H, C,
    dv])."""
    NS, H, dk, dv = S.shape
    N, _, C, _ = W.shape
    hb = _CHUNK_HEADS
    state = pl.BlockSpec((1, hb, dk, dv), lambda g, j, m: (m[0], g, 0, 0))

    def rows(width):
        return pl.BlockSpec((1, hb, C, width), lambda g, j, m: (j, g, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(H // hb, N),
        in_specs=[state, rows(dv), rows(dk), rows(dk), rows(C), rows(dk),
                  pl.BlockSpec((1, 1, dk, hb), lambda g, j, m: (j, g, 0, 0))],
        out_specs=[state, rows(dv)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_chunk_kernel, hb=hb, n_sub=N),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((N, H, C, dv), jnp.float32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(meta, S, W, Y, Qt, B, Kh, gct)


def kda_chunk(S, slot, fresh, q, k, v, g, beta,
              use_kernel: Optional[bool] = None, interpret=False):
    """One slot's chunk of rows. ``S [NS, H, dk, dv]`` float32 (the pool),
    ``slot`` an int32 scalar, ``fresh`` a bool scalar (the chunk is a
    request's first: its state starts at zero whatever the pool holds), ``q,
    k, g [T, H, dk]``, ``v [T, H, dv]``, ``beta [T, H]``; a padding row has
    ``g = 0`` and ``beta = 0`` and leaves the state as it was. Returns ``(o
    [T, H, dv] float32, S)`` with the slot's state after the ``T`` rows."""
    NS, H, dk, dv = S.shape
    T = q.shape[0]
    f32 = jnp.float32
    C = SUBCHUNK
    pad = -T % C
    q, k, v, g, beta = (jnp.pad(x.astype(f32), ((0, pad),) + ((0, 0),) * (
        x.ndim - 1)) for x in (q, k, v, g, beta))
    W, Y, Qt, B, Kh, gc = _chunk_prepare(q, k, v, g, beta, C)
    slot = jnp.asarray(slot, jnp.int32)
    if _use_kernel(use_kernel, interpret, H, dk, dv) \
            and S.dtype == jnp.float32:
        count_path("kda_chunk", "pallas")
        hb = _CHUNK_HEADS
        gct = gc.reshape(-1, H // hb, hb, dk).transpose(0, 1, 3, 2)
        meta = jnp.stack([slot, jnp.asarray(fresh, jnp.int32)])
        S, O = _kda_chunk_call(S, W, Y, Qt, B, Kh, gct, meta,
                               interpret=interpret)
    else:
        count_path("kda_chunk", "reference")
        S0 = jax.lax.dynamic_index_in_dim(S, slot, 0, keepdims=False)
        S0 = jnp.where(fresh, 0.0, S0.astype(f32))
        S1, O = _chunk_scan(S0, W, Y, Qt, B, Kh, gc)
        S = jax.lax.dynamic_update_index_in_dim(S, S1.astype(S.dtype), slot,
                                                0)
    return O.transpose(0, 2, 1, 3).reshape(T + pad, H, dv)[:T], S
