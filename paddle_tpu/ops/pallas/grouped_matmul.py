"""Grouped (ragged) matmul, Pallas-on-TPU — the MoE expert-FFN kernel.

TPU-native replacement for the reference's CUTLASS grouped GEMM
(ref: paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu) used by its
MoE layer (python/paddle/incubate/distributed/models/moe/moe_layer.py).

Contract (megablocks-style): tokens are pre-sorted by expert and each
expert's group is padded to a multiple of the token tile, so every token
tile belongs to exactly ONE expert. The expert id per tile rides in as a
scalar-prefetch operand; the BlockSpec index_map uses it to stream just
that expert's weight tile into VMEM — each tile is one dense MXU matmul,
no wasted FLOPs on other experts (the dense-dispatch fallback pays
O(E) per token instead).

grouped_matmul(lhs [T, K], rhs [E, K, N], group_sizes [E]) -> [T, N],
with rows of group e computed against rhs[e]. Rows beyond sum(group_sizes)
(padding) produce zeros.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import count_path

__all__ = ["grouped_matmul", "grouped_matmul_reference",
           "tile_expert_ids", "expert_rows_matmul"]


def grouped_matmul_reference(lhs, rhs, group_sizes):
    """Dense oracle: per-row expert id via cumsum, one-hot contraction.
    O(T*E*K*N) — correctness baseline only."""
    t = lhs.shape[0]
    e = rhs.shape[0]
    bounds = jnp.cumsum(group_sizes)
    row_expert = jnp.searchsorted(bounds, jnp.arange(t), side="right")
    valid = jnp.arange(t) < bounds[-1]
    oh = jax.nn.one_hot(row_expert, e, dtype=lhs.dtype)       # [T, E]
    out = jnp.einsum("tk,te,ekn->tn", lhs, oh, rhs)
    return out * valid[:, None].astype(lhs.dtype)


def tile_expert_ids(group_sizes, block_t: int, num_tiles: int):
    """Expert id per token tile, given tile-aligned group sizes
    (every group size must be a multiple of block_t)."""
    bounds = jnp.cumsum(group_sizes)
    starts = jnp.arange(num_tiles) * block_t
    return jnp.searchsorted(bounds, starts, side="right").astype(jnp.int32)


def _dot_precision(dtype):
    """Explicit contraction precision per operand dtype. Pinning matters
    twice over: (a) bf16 operands + an ambient fp32/HIGHEST matmul
    precision produce a tpu.matmul Mosaic rejects ("Bad lhs type") —
    bf16 runs the native single-pass MXU path with fp32 accumulation
    from preferred_element_type (measured 44 -> 24 ms on the MoE bench);
    (b) fp32 operands keep HIGHEST so true-fp32 callers don't silently
    drop to bf16 passes under an ambient DEFAULT."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _gmm_kernel(ids_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                n_k_tiles):
    # one token tile x one (prefetch-selected) expert weight tile: plain
    # MXU dot in the operands' own dtype with fp32 accumulation in VMEM
    # scratch across the K tiles (K is tiled so block_t can be large —
    # big token tiles amortize the expert-weight streaming that
    # otherwise makes the kernel HBM-bound: measured 1.74 -> 0.91 ms
    # fwd at t=16K,k=1024,n=4096 going block_t 128 -> 512). Precision
    # keys on the PROMOTED dtype: a bf16 x fp32 call promotes to fp32,
    # which must not silently run single-pass bf16 multiplies.
    kk = pl.program_id(2)
    prec = _dot_precision(
        jnp.promote_types(lhs_ref.dtype, rhs_ref.dtype))
    contrib = jnp.dot(lhs_ref[...], rhs_ref[0], precision=prec,
                      preferred_element_type=jnp.float32)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = contrib

    @pl.when(kk > 0)
    def _acc():
        acc_ref[...] += contrib

    @pl.when(kk == n_k_tiles - 1)
    def _emit():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm_drhs_kernel(ids_ref, lhs_ref, g_ref, out_ref):
    """drhs[e] = sum over e's token tiles of lhs_tileᵀ @ g_tile. The grid
    is (k_tile, n_tile, token_tile MINOR) so for fixed (k, n) tiles every
    token tile of one expert is consecutive — the output block stays
    resident in VMEM across those steps and accumulates. K tiling keeps
    the [block_t, block_k] lhs tile inside VMEM at large block_t."""
    i = pl.program_id(2)  # token tile (minor/fastest)
    is_first = (i == 0) | (ids_ref[i] != ids_ref[jnp.maximum(i - 1, 0)])
    # dot_general contracting on lhs axis 0 == lhsᵀ @ g without a
    # materialized in-kernel transpose (a bf16 tile transpose trips the
    # Mosaic compiler; contraction-dim choice is free on the MXU)
    contrib = jax.lax.dot_general(
        lhs_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        precision=_dot_precision(
            jnp.promote_types(lhs_ref.dtype, g_ref.dtype)),
        preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(is_first)
    def _init():
        out_ref[0] = contrib

    @pl.when(jnp.logical_not(is_first))
    def _acc():
        out_ref[0] += contrib


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm_pallas(lhs, rhs, tile_ids, block_t):
    return _gmm_fwd_impl(lhs, rhs, tile_ids, block_t)


# empirical VMEM model (validated against the compiler's scoped-stack
# accounting at K=4096): ~3x the naive tile sum covers double buffering
# of every ref plus in-kernel f32 temporaries
_VMEM_WORDS = int(13.5 * 1024 * 1024) // 4  # fp32 words under the 16MB cap


def _pick_blocks(k: int, n: int, block_t: int):
    """(block_n, block_k) for the fwd kernel's working set — the
    [block_k, block_n] weight tile, [block_t, block_k] lhs tile,
    [block_t, block_n] out tile and the f32 accumulator — under the
    scoped VMEM limit. Prefers fat N tiles, then fat K tiles (fewer
    accumulation rounds)."""
    for bn in (512, 256, 128):
        if n % bn:
            continue
        for bk in (2048, 1024, 512, 256, 128):
            if k % bk:
                continue
            words = 3 * (bk * bn + block_t * bk + block_t * bn) \
                + block_t * bn
            if words <= _VMEM_WORDS:
                return bn, bk
    return (128 if n % 128 == 0 else n), (128 if k % 128 == 0 else k)


@functools.partial(jax.jit, static_argnames=("block_t",))
def _gmm_fwd_impl(lhs, rhs, tile_ids, block_t):
    t, k = lhs.shape
    e, _, n = rhs.shape
    block_n, block_k = _pick_blocks(k, n, block_t)
    n_k_tiles = k // block_k
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # K minor: the f32 scratch accumulates across the K tiles of one
        # (token, n) output block before it is emitted
        grid=(t // block_t, n // block_n, n_k_tiles),
        in_specs=[
            pl.BlockSpec((block_t, block_k),
                         lambda i, j, kk, ids: (i, kk)),
            pl.BlockSpec((1, block_k, block_n),
                         lambda i, j, kk, ids: (ids[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n),
                               lambda i, j, kk, ids: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_t, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_k_tiles=n_k_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, n), lhs.dtype),
    )(tile_ids, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("e", "block_t"))
def _gmm_drhs_impl(lhs, g, tile_ids, e, block_t):
    t, k = lhs.shape
    n = g.shape[1]
    block_n, block_k = _pick_blocks(k, n, block_t)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        # token tiles MINOR: see kernel docstring (VMEM-resident
        # accumulation over each expert's consecutive token tiles)
        grid=(k // block_k, n // block_n, t // block_t),
        in_specs=[
            pl.BlockSpec((block_t, block_k),
                         lambda kk, j, i, ids: (i, kk)),
            pl.BlockSpec((block_t, block_n),
                         lambda kk, j, i, ids: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, block_k, block_n),
                               lambda kk, j, i, ids: (ids[i], kk, j)),
    )
    out = pl.pallas_call(
        _gmm_drhs_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((e, k, n), jnp.float32),
    )(tile_ids, lhs, g)
    # experts with no tiles never get written: mask whatever VMEM held
    present = jnp.zeros((e,), bool).at[tile_ids].set(True)
    return jnp.where(present[:, None, None], out, 0.0)


def _gmm_vjp_fwd(lhs, rhs, tile_ids, block_t):
    return _gmm_fwd_impl(lhs, rhs, tile_ids, block_t), (lhs, rhs, tile_ids)


def _gmm_vjp_bwd(block_t, res, g):
    lhs, rhs, tile_ids = res
    dlhs = _gmm_fwd_impl(g, jnp.swapaxes(rhs, 1, 2), tile_ids, block_t)
    drhs = _gmm_drhs_impl(lhs, g, tile_ids, rhs.shape[0], block_t)
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None


_gmm_pallas.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def _use_pallas(t, k, n, block_t) -> bool:
    return (jax.default_backend() == "tpu"
            and t % block_t == 0 and k % 128 == 0 and n % 128 == 0)


def grouped_matmul(lhs, rhs, group_sizes, block_t: int = 128,
                   tile_ids: Optional[jax.Array] = None):
    """Ragged matmul over tile-aligned groups (see module docstring).

    Off the TPU, or when shapes or group sizes are not tile-aligned, the
    dense reference runs instead (correct, slower); the choice is counted
    (``pallas.path_selected_total``). ``tile_ids`` may be
    passed when the caller already knows the per-tile expert map (e.g. the
    fixed-capacity MoE layout where every group is exactly C rows).
    ``tile_ids`` MUST be non-decreasing: the dRHS backward accumulates
    into one resident VMEM block per expert and decides init-vs-accumulate
    by comparing adjacent ids, so a non-sorted map would silently produce
    wrong weight gradients (forward would still be right).
    """
    t, k = lhs.shape
    e, k2, n = rhs.shape
    if k2 != k:
        raise ValueError(f"lhs K {k} != rhs K {k2}")
    if tile_ids is not None and not isinstance(tile_ids, jax.core.Tracer):
        ids_np = np.asarray(tile_ids)
        if (np.diff(ids_np) < 0).any():
            raise ValueError(
                "grouped_matmul tile_ids must be non-decreasing (tokens "
                "pre-sorted by expert): the dRHS backward accumulates "
                "per-expert output tiles in VMEM and a scattered map "
                "yields wrong weight grads. Sort tokens by expert or use "
                "grouped_matmul_reference.")

    def reference(sizes):
        count_path("grouped_matmul", "reference")
        return grouped_matmul_reference(lhs, rhs, sizes)

    if not _use_pallas(t, k, n, block_t):
        return reference(group_sizes)
    if tile_ids is None:
        # group sizes must be tile-aligned (and concrete) for the
        # one-expert-per-tile contract; otherwise use the dense fallback
        if isinstance(group_sizes, jax.core.Tracer):
            return reference(group_sizes)
        sizes = np.asarray(group_sizes)
        if (sizes % block_t != 0).any():
            return reference(jnp.asarray(sizes))
    count_path("grouped_matmul", "pallas")
    if tile_ids is None:
        tile_ids = tile_expert_ids(jnp.asarray(sizes), block_t,
                                   t // block_t)
        total = int(sizes.sum())
        if total < t:
            # padding tiles get expert id E (clamped to the last expert by
            # the BlockSpec index_map) — zero them to honor the contract
            out = _gmm_pallas(lhs, rhs, jnp.minimum(tile_ids, e - 1),
                              block_t)
            valid = (jnp.arange(t) < total)[:, None]
            return out * valid.astype(out.dtype)
    return _gmm_pallas(lhs, rhs, tile_ids, block_t)


# ---------------------------------------------------------------------------
# forward-only rows-by-expert matmul for serving: ragged groups, static
# shapes, empty tiles skipped
# ---------------------------------------------------------------------------

def _rows_kernel(ids_ref, nv_ref, lhs_ref, rhs_ref, out_ref):
    """One row tile x one column tile of its expert's weight. Tiles past
    the live ones (``nv_ref[0]``) fetch nothing new (their block indices
    repeat the last live tile's) and write zeros."""
    del ids_ref
    live = pl.program_id(1) < nv_ref[0]

    @pl.when(live)
    def _dot():
        out_ref[...] = jnp.dot(
            lhs_ref[...], rhs_ref[0],
            precision=_dot_precision(
                jnp.promote_types(lhs_ref.dtype, rhs_ref.dtype)),
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)


def _rows_block_n(k: int, n: int, itemsize: int) -> int:
    """Columns of the weight tile: the widest of 512/256/128 that divides
    ``n`` and keeps one ``[k, block_n]`` tile at 4 MiB or less (two are
    in flight)."""
    for bn in (512, 256, 128):
        if n % bn == 0 and k * bn * itemsize <= 4 * 1024 * 1024:
            return bn
    return 128


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def _expert_rows_matmul_call(lhs, rhs, tile_ids, n_live, *, block_t,
                             interpret=False):
    m, k = lhs.shape
    e, _, n = rhs.shape
    bn = _rows_block_n(k, n, jnp.dtype(rhs.dtype).itemsize)
    n_tiles = m // block_t

    def last_live(nv):
        return jnp.maximum(nv[0] - 1, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        # column tiles outer, row tiles inner: consecutive row tiles of
        # one expert reuse its weight tile, so each expert that has rows
        # is streamed once, and the dead tiles at the end repeat the
        # last live tile's blocks, which Pallas does not fetch again
        grid=(n // bn, n_tiles),
        in_specs=[
            pl.BlockSpec((block_t, k), lambda j, i, ids, nv: (
                jnp.minimum(i, last_live(nv)), 0)),
            pl.BlockSpec((1, k, bn), lambda j, i, ids, nv: (
                ids[jnp.minimum(i, last_live(nv))], 0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, bn), lambda j, i, ids, nv: (i, j)),
    )
    need = 2 * (k * bn + block_t * k + block_t * bn) \
        * jnp.dtype(rhs.dtype).itemsize + 2 * block_t * bn * 4
    return pl.pallas_call(
        _rows_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(32 * 1024 * 1024, need * 3 // 2)),
        interpret=interpret,
    )(tile_ids.astype(jnp.int32), jnp.reshape(n_live, (1,)).astype(jnp.int32),
      lhs, rhs)


def expert_rows_matmul(lhs, rhs, tile_ids, n_live, block_t: int,
                       use_kernel: Optional[bool] = None,
                       interpret: bool = False):
    """Rows sorted by expert times their expert's matrix, forward only,
    for serving: ``lhs [M, K]`` holds groups of rows, each group padded
    to whole tiles of ``block_t`` rows (padding rows zero); ``tile_ids
    [M // block_t]`` names the expert of each tile (non-decreasing over
    the first ``n_live`` tiles; what follows is ignored); ``rhs [E, K,
    N]``. Returns ``[M, N]``; rows of tiles past ``n_live`` are zero.

    Unlike :func:`grouped_matmul` the group sizes are traced values and
    ``M`` is the static worst case, so most tiles are dead in most
    calls: the Pallas path skips their weight fetch and their dot. Off
    the TPU (or for shapes the kernel does not tile) the reference runs:
    every row against its tile's expert by a one-hot contraction. The
    choice is counted (``pallas.path_selected_total{kernel=
    "expert_rows_matmul"}``)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    use_kernel = bool(use_kernel or interpret) and m % block_t == 0 \
        and block_t % 8 == 0 and k % 128 == 0 and n % 128 == 0
    count_path("expert_rows_matmul", "pallas" if use_kernel else "reference")
    if use_kernel:
        return _expert_rows_matmul_call(lhs, rhs, tile_ids, n_live,
                                        block_t=int(block_t),
                                        interpret=bool(interpret))
    tiles = m // block_t
    live = (jnp.arange(tiles) < n_live)
    oh = jax.nn.one_hot(tile_ids, rhs.shape[0], dtype=jnp.float32) \
        * live[:, None]
    out = jnp.einsum("tbk,te,ekn->tbn", lhs.reshape(tiles, block_t, k),
                     oh.astype(lhs.dtype), rhs,
                     preferred_element_type=jnp.float32)
    return out.reshape(m, n).astype(lhs.dtype)
