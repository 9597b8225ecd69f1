"""Learned sparse attention over a latent cache, Pallas-on-TPU: the two
dense kernels behind ``serving_cache.paged_index_scores`` and
``serving_cache.paged_latent_attention``.

A model of this kind (latent attention with a learned indexer) keeps one
row ``[c_kv ; k_rope]`` a token a layer and, on its indexer layers, one
index key a token. A query row scores every visible position with the
indexer, keeps the ``k`` largest and attends those rows only, in absorbed
form (the up-projection folded into the query and the output, so the
rows are read as they are stored).

- :func:`index_scores`: ``I(t, c) = sum_j w[t, j] * relu(q[t, j] . k[c])``
  for query rows ``t`` (``j`` over the index heads) against a slot's keys
  ``k [N, D]`` laid out by position. One grid step is a tile of rows
  against a tile of keys: the (row, head) pairs are the rows of one dot
  (bf16 operands, float32 accumulation), relu and the weighted head sum
  run on the float32 tile, and only ``[rows, keys]`` scores leave VMEM.
  Key tiles past a row tile's last visible position are neither fetched
  (their block index repeats the last live one) nor computed: zeros.
- :func:`latent_attention`: a slot's query rows against the slot's OWN
  blocks of the latent pool, walked through its block table as
  ``paged_attention``'s kernel walks K and V (one grid step a slot, a
  ``fori_loop`` over groups of its blocks, each group's blocks copied
  into the other half of a two-slot VMEM buffer while this one is
  computed on), under a MASK of the selected positions: a position that
  is not selected contributes exactly zero. All heads share a row
  (``W = rank + rope`` columns, padded to whole 128-lane rows): its
  scores use all ``W`` columns, its weighted sum the first ``rank``.

**Why a masked walk and not a gather of the selected rows** (numbers in
PERF.md, PR 33): XLA's row gather on the v5e costs about 14 ns a ROW
whatever its width (262,144 rows of 1,280 B in 4.0 ms), so gathering 2048
rows a query costs a 512-row chunk 14 ms a layer and a 32-slot decode step
0.9 ms a layer before any arithmetic; the walk reads a slot's blocks at
HBM speed (82 KB a copy) and feeds the MXU whole tiles, which is less for
every context this engine serves at its slot count.

Both have a jnp form with the same contract (the CPU path and the
numerics oracle); the seams count which one ran
(``pallas.path_selected_total{kernel="index_scores"|"latent_attention"}``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import count_path
from .grouped_matmul import _dot_precision

__all__ = ["index_scores", "latent_attention", "KEY_TILE"]

_NEG_INF = -1e30
KEY_TILE = 2048          # keys a grid step of the index kernel scores
_ROW_TILE = 8            # query rows a grid step of the index kernel takes


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# index scores
# ---------------------------------------------------------------------------

def _index_kernel(last_ref, q_ref, w_ref, k_ref, o_ref, *, rows, heads,
                  n_row_tiles):
    s_, t_, n_ = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = n_ <= last_ref[s_ * n_row_tiles + t_]

    @pl.when(live)
    def _score():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            precision=_dot_precision(k_ref.dtype),
            preferred_element_type=jnp.float32)          # [rows*heads, TN]
        s = jnp.maximum(s, 0.0) * w_ref[0]
        for r in range(rows):       # the heads of a row are `heads` sublanes
            o_ref[0, r:r + 1, :] = jnp.sum(
                s[r * heads:(r + 1) * heads], axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("T", "interpret"))
def _index_score_call(q, w, keys, last, *, T, interpret=False):
    """q [S, T*J, D] ((row, head) pairs, row-major), w [S, T*J, 1] f32,
    keys [S, N, D], last [S * T/rows] int32 (last live key tile of each
    row tile) -> [S, T, N] f32."""
    S, tj, D = q.shape
    N = keys.shape[1]
    J = tj // T
    rows = min(T, _ROW_TILE)
    n_row_tiles = T // rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S, n_row_tiles, N // KEY_TILE),
        in_specs=[
            pl.BlockSpec((1, rows * J, D), lambda s, t, n, last: (s, t, 0)),
            pl.BlockSpec((1, rows * J, 1), lambda s, t, n, last: (s, t, 0)),
            pl.BlockSpec((1, KEY_TILE, D), lambda s, t, n, last: (
                s, jnp.minimum(n, last[s * n_row_tiles + t]), 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, KEY_TILE),
                               lambda s, t, n, last: (s, t, n)),
    )
    return pl.pallas_call(
        functools.partial(_index_kernel, rows=rows, heads=J,
                          n_row_tiles=n_row_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
    )(last, q, w, keys)


def index_scores(q, w, keys, positions, use_kernel: Optional[bool] = None,
                 interpret: bool = False):
    """Index scores of query rows against a slot's keys.

    ``q [S, T, J, D]`` (``J`` index heads), ``w [S, T, J]`` float32 head
    weights, ``keys [S, N, D]`` the slot's index keys by position,
    ``positions [S, T]`` each row's own position. Returns ``[S, T, N]``
    float32: ``sum_j w * relu(q_j . k_c)`` for every ``c`` up to the key
    tile that holds the row tile's last position; what lies past it reads
    0 there and anything in the jnp form: the caller masks ``c >
    positions``. Operands in ``keys``' dtype, float32 accumulation."""
    S, T, J, D = q.shape
    N = keys.shape[1]
    rows = min(T, _ROW_TILE)
    if use_kernel is None:
        use_kernel = _on_tpu()
    use_kernel = bool(use_kernel or interpret) and N % KEY_TILE == 0 \
        and T % rows == 0 and D % 128 == 0 and (rows * J) % 8 == 0
    count_path("index_scores", "pallas" if use_kernel else "reference")
    q = q.astype(keys.dtype)
    if use_kernel:
        last = jnp.max(positions.reshape(S, T // rows, rows), axis=-1) \
            // KEY_TILE
        last = jnp.clip(last, 0, N // KEY_TILE - 1).astype(jnp.int32)
        return _index_score_call(
            q.reshape(S, T * J, D), w.astype(jnp.float32).reshape(S, T * J, 1),
            keys, last.reshape(-1), T=T, interpret=bool(interpret))
    s = jnp.einsum("stjd,snd->stjn", q, keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("stjn,stj->stn", jnp.maximum(s, 0.0),
                      w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


# ---------------------------------------------------------------------------
# attention over a slot's latent blocks, under the selection's mask
# ---------------------------------------------------------------------------

_MASK_ROWS = 8           # rows the mask holds for a slot of fewer (decode)
_ROW_BUDGET = 1024       # (row, head) pairs a slot's program takes at once


def _latent_kernel(tables_ref, nblk_ref, q_ref, mask_hbm, pool_hbm, o_ref,
                   kv_buf, mask_buf, sems, m_s, l_s, acc_s, *, C, block_size,
                   rows, heads, rank, scale):
    """One slot's program: ``rows`` query rows x ``heads`` (the rows of one
    dot, row-major) against the slot's first ``nblk`` blocks, ``C`` at a
    time. Scalar prefetch: the block table and each slot's live block
    count. ``mask_hbm [S, max(rows, 8), N]`` int32 and ``pool_hbm [NB, bs,
    W]`` stay in HBM; scratch: the two-slot buffers the copies land in,
    their semaphores [stream, half], and the float32 online-softmax state."""
    s = pl.program_id(0)
    G = C * block_size
    nb = nblk_ref[s]
    n_groups = (nb + C - 1) // C
    cdtype = kv_buf.dtype

    def copies(g, half, act):
        """Start, or wait for, the copies of group ``g``: its live blocks
        (blocks past the slot's count are never fetched) and the mask's
        columns over it."""
        def one(j, carry):
            blk = jnp.maximum(tables_ref[s, g * C + j], 0)
            getattr(pltpu.make_async_copy(
                pool_hbm.at[blk], kv_buf.at[half, j], sems.at[0, half]),
                act)()
            return carry
        jax.lax.fori_loop(0, jnp.minimum(C, nb - g * C), one, 0)
        getattr(pltpu.make_async_copy(
            mask_hbm.at[s, :, pl.ds(pl.multiple_of(g * G, G), G)],
            mask_buf.at[half], sems.at[1, half]), act)()

    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(n_groups > 0)
    def _first():
        copies(0, 0, "start")

    prec = _dot_precision(cdtype)

    def group(g, carry):
        half = g % 2

        @pl.when(g + 1 < n_groups)
        def _prefetch():
            copies(g + 1, 1 - half, "start")

        copies(g, half, "wait")
        kv = kv_buf[half].reshape(G, kv_buf.shape[-1])
        # a position that is not selected must contribute EXACTLY zero
        # whatever a recycled or never-fetched block holds: its score is
        # replaced, and the values' non-finite numbers go to 0 (compared
        # in float32: the v5e's VPU has no bf16 compare)
        v = kv[:, :rank].astype(jnp.float32)
        v = jnp.where(jnp.abs(v) <= float(jnp.finfo(cdtype).max), v,
                      0.0).astype(cdtype)
        picked = mask_buf[half] > 0                       # [mask rows, G]
        if rows == 1:
            ok = picked[0:1]
        else:       # each row's mask for its `heads` rows of the dot
            ok = jnp.concatenate(
                [jnp.broadcast_to(picked[t:t + 1], (heads, G))
                 for t in range(rows)], axis=0)
        sc = jax.lax.dot_general(
            q_ref[0], kv, (((1,), (1,)), ((), ())), precision=prec,
            preferred_element_type=jnp.float32) * scale    # [rows*heads, G]
        sc = jnp.where(ok, sc, _NEG_INF)
        m_old = m_s[...]
        m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1, keepdims=True))
        # a fully-masked row has sc == m_new == -1e30: exp gives 1, so p
        # is masked again
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        corr = jnp.exp(m_old - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jnp.dot(
            p.astype(cdtype), v, precision=prec,
            preferred_element_type=jnp.float32)
        m_s[...] = m_new
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def _group_tokens(block_size: int, n: int) -> int:
    """Tokens a loop step of the kernel covers: the most of 512 / 256 / 128
    that is whole blocks and divides the mask's ``n`` columns (0: none)."""
    return next((g for g in (512, 256, 128)
                 if g % block_size == 0 and n % g == 0), 0)


@functools.partial(jax.jit, static_argnames=("rows", "block_size", "rank",
                                             "scale", "interpret"))
def _latent_attention_call(q, mask, pool, tables, nblk, *, rows, block_size,
                           rank, scale, interpret=False):
    """q [S, rows*H, W] ((row, head) pairs, row-major), mask [S, max(rows,
    8), N] int32, pool [NB, bs, W], tables [S, MB], nblk [S] -> [S, rows*H,
    rank]."""
    S, rh, W = q.shape
    n_mask, N = mask.shape[1:]
    G = _group_tokens(block_size, N)
    C = G // block_size
    heads = rh // rows
    row_spec = pl.BlockSpec((1, rh, W), lambda s, *_: (s, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[row_spec, hbm, hbm],
        out_specs=pl.BlockSpec((1, rh, rank), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, C, block_size, W), pool.dtype),
            pltpu.VMEM((2, n_mask, G), jnp.int32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((rh, 1), jnp.float32),
            pltpu.VMEM((rh, 1), jnp.float32),
            pltpu.VMEM((rh, rank), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_latent_kernel, C=C, block_size=block_size,
                          rows=rows, heads=heads, rank=rank, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, rh, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(tables.astype(jnp.int32), nblk.astype(jnp.int32), q, mask, pool)


def latent_attention(q, pool, tables, mask, positions, *, block_size: int,
                     rank: int, scale: float,
                     use_kernel: Optional[bool] = None,
                     interpret: bool = False):
    """Absorbed latent attention of each slot's query rows over the SELECTED
    positions of its paged latent pool.

    ``q [S, T, H, W]`` (per head: the query folded through the key
    up-projection, ``rank`` columns, then its rope part, zero-padded to
    ``W``), ``pool [num_blocks, block_size, W]`` rows ``[c_kv ; k_rope ;
    0]``, ``tables [S, max_blocks]``, ``mask [S, T, N]`` (``N >= max_blocks
    * block_size``; true where row ``(s, t)`` attends position ``c``, never
    past ``positions[s, t]``). Returns ``[S, T, H, rank]``: ``softmax over
    the selected c of (q . row_c * scale)`` times ``row_c[:rank]``; a row
    with nothing selected gives zeros. Float32 softmax, operands in the
    pool's dtype. The kernel takes a slot's rows ``_ROW_BUDGET / H`` at a
    time, each such tile a slot of its own over the same table row."""
    S, T, H, W = q.shape
    MB = tables.shape[1]
    N = mask.shape[-1]
    if use_kernel is None:
        use_kernel = _on_tpu()
    tile = next((t for t in range(min(T, max(_ROW_BUDGET // H, 1)), 0, -1)
                 if T % t == 0), 1)
    use_kernel = bool(use_kernel or interpret) and W % 128 == 0 \
        and rank % 128 == 0 and H % 8 == 0 and _group_tokens(block_size, N) > 0
    count_path("latent_attention", "pallas" if use_kernel else "reference")
    q = q.astype(pool.dtype)
    if use_kernel:
        nt = T // tile
        nblk = jnp.minimum(
            jnp.max(positions.reshape(S * nt, tile), axis=-1) // block_size + 1,
            MB)
        m = mask.reshape(S * nt, tile, N).astype(jnp.int32)
        if tile < _MASK_ROWS:       # a copy takes whole sublane tiles
            m = jnp.pad(m, ((0, 0), (0, _MASK_ROWS - tile), (0, 0)))
        out = _latent_attention_call(
            q.reshape(S * nt, tile * H, W), m, pool,
            tables if nt == 1 else jnp.repeat(tables, nt, axis=0), nblk,
            rows=tile, block_size=int(block_size), rank=int(rank),
            scale=float(scale), interpret=bool(interpret))
        return out.reshape(S, T, H, rank)
    rows = pool[jnp.maximum(tables, 0)].reshape(S, MB * block_size, W)
    rows = jnp.pad(rows, ((0, 0), (0, N - MB * block_size), (0, 0)))
    # what a masked column holds must not matter (a recycled block's NaN)
    rows = jnp.where(jnp.any(mask, axis=1)[..., None], rows, 0)
    sc = jnp.einsum("sthw,snw->sthn", q, rows,
                    preferred_element_type=jnp.float32) * scale
    ok = mask[:, :, None, :]
    sc = jnp.where(ok, sc, _NEG_INF)
    p = jnp.where(ok, jnp.exp(sc - jnp.max(sc, -1, keepdims=True)), 0.0)
    o = jnp.einsum("sthn,snc->sthc", p.astype(pool.dtype), rows[..., :rank],
                   preferred_element_type=jnp.float32)
    return (o / jnp.maximum(jnp.sum(p, -1), 1e-30)[..., None]).astype(q.dtype)
