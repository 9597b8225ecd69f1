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
  for query rows ``t`` (``j`` over the index heads) against the keys of
  the slot's paged index pool, read through its block table. One grid
  step is a tile of rows against a tile of ``KEY_TILE`` keys: the (row,
  head) pairs are the rows of one dot (bf16 operands, float32
  accumulation), relu and the weighted head sum run on the float32 tile,
  and only ``[rows, keys]`` scores leave VMEM. A key tile's blocks are
  copied from HBM, one copy a block, into one half of a two-half buffer
  while the tile before is scored, once for all of a slot's row tiles;
  blocks past the slot's last live row and unmapped entries are never
  copied, and key tiles past a row tile's last position are not
  computed: zeros. (A gather of the keys into position order first
  writes and reads every slot's keys to the table's end, 419 MB a layer
  a 32-slot decode step, for a kernel that reads only the live tiles.)
  The starts are a loop of their own, not straight-line code among the
  arithmetic as in ``paged_attention``: that form traced and lowered
  five times as long, and GLM-5.2's cell paid it in every program of
  every process (``setup_s`` +12%, PERF.md section 6, PR 36) to save
  about 0.1 ms a decode step.
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

def _index_kernel(tables_ref, last_ref, tiles_ref, q_ref, w_ref, pool_hbm,
                  o_ref, buf, sems, half_ref, *, rows, heads, per, n_slots,
                  n_key_tiles, n_row_tiles):
    """Grid step ``(s, n, t)``: row tile ``t`` of slot ``s`` against key
    tile ``n``, the row tiles innermost, so that a key tile is copied once
    for all the row tiles of a slot. Scalar prefetch: the block table
    (entries past a slot's last live block are -1), each row tile's last
    live key tile, and each key tile's count of copies (-1: no row tile
    reads it; the tiles that some row tile reads are a slot's first ones).
    ``pool_hbm [NB, bs, D]`` stays in HBM; scratch: a two-half buffer of
    one key tile's blocks, a semaphore a half, and the half that the
    current key tile lands in."""
    s, n, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    NT = n_key_tiles
    copies = tiles_ref[s * NT + n]
    live = n <= last_ref[s * n_row_tiles + t]
    # the next key tile that some row tile reads: the slot's next one, else
    # the next slot's first
    same = (n + 1 < NT) & (tiles_ref[s * NT + jnp.minimum(n + 1, NT - 1)] >= 0)
    more = same | (s + 1 < n_slots)
    ns = jnp.where(same, s, jnp.minimum(s + 1, n_slots - 1))
    nn = jnp.where(same, n + 1, 0)

    def start_mapped(slot, kt, half):
        """A loop over the tile's blocks, copying the mapped ones."""
        def one(j, carry):
            blk = tables_ref[slot, kt * per + j]

            @pl.when(blk >= 0)
            def _start():
                pltpu.make_async_copy(pool_hbm.at[blk], buf.at[half, j],
                                      sems.at[half]).start()
            return carry
        jax.lax.fori_loop(0, per, one, 0)

    def wait(count, half):
        """A semaphore counts bytes: a whole tile is waited for at once,
        the blocks of a ragged one each."""
        def one(j, carry):
            pltpu.make_async_copy(buf.at[half, 0], buf.at[half, 0],
                                  sems.at[half]).wait()
            return carry

        @pl.when(count == per)
        def _whole():
            pltpu.make_async_copy(buf.at[half], buf.at[half],
                                  sems.at[half]).wait()

        @pl.when(count < per)
        def _ragged():
            jax.lax.fori_loop(0, count, one, 0)

    def score(half):
        """The row tile against the key tile in ``half``."""
        k = buf[half].reshape(per * buf.shape[2], buf.shape[3])
        sc = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            precision=_dot_precision(buf.dtype),
            preferred_element_type=jnp.float32)          # [rows*heads, TN]
        sc = jnp.maximum(sc, 0.0) * w_ref[0]
        for r in range(rows):       # the heads of a row are `heads` sublanes
            o_ref[0, r:r + 1, :] = jnp.sum(
                sc[r * heads:(r + 1) * heads], axis=0, keepdims=True)

    first = (s == 0) & (n == 0) & (t == 0)

    @pl.when(first)
    def _first():
        half_ref[0] = 0

    half = half_ref[0]
    # the first grid step that reads this key tile: the next tile's copies
    # are started (and, on the grid's first step, this one's before them),
    # this one's waited for
    fresh = (t == 0) & (copies >= 0)

    @pl.when(fresh & (first | more))
    def _start():
        def tile(i, carry):
            own = first & (i == 0)
            start_mapped(jnp.where(own, s, ns), jnp.where(own, n, nn),
                         jnp.where(own, half, 1 - half))
            return carry
        jax.lax.fori_loop(0, first.astype(jnp.int32) + more.astype(jnp.int32),
                          tile, 0)

    @pl.when(fresh)
    def _wait():
        wait(copies, half)

    @pl.when(live)
    def _score():
        score(half)

    @pl.when(jnp.logical_not(live))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((t == n_row_tiles - 1) & (copies >= 0))
    def _flip():
        half_ref[0] = 1 - half


@functools.partial(jax.jit, static_argnames=("T", "interpret"))
def _index_score_call(q, w, pool, tables, last, tiles, *, T, interpret=False):
    """q [S, T*J, D] ((row, head) pairs, row-major), w [S, T*J, 1] f32,
    pool [NB, bs, D], tables [S, N/bs] int32 (-1 past a slot's live
    blocks), last [S * T/rows] int32 (last live key tile of each row
    tile), tiles [S * N/KEY_TILE] int32 (copies of each key tile, -1 where
    no row tile reads it) -> [S, T, N] f32."""
    S, tj, D = q.shape
    _, bs, _ = pool.shape
    per = KEY_TILE // bs
    N = tables.shape[1] * bs
    J = tj // T
    rows = min(T, _ROW_TILE)
    n_row_tiles = T // rows
    n_key_tiles = N // KEY_TILE
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, n_key_tiles, n_row_tiles),
        in_specs=[
            pl.BlockSpec((1, rows * J, D), lambda s, n, t, *_: (s, t, 0)),
            pl.BlockSpec((1, rows * J, 1), lambda s, n, t, *_: (s, t, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows, KEY_TILE),
                               lambda s, n, t, *_: (s, t, n)),
        scratch_shapes=[pltpu.VMEM((2, per, bs, D), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_index_kernel, rows=rows, heads=J, per=per,
                          n_slots=S, n_key_tiles=n_key_tiles,
                          n_row_tiles=n_row_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        interpret=interpret,
    )(tables, last, tiles, q, w, pool)


def index_scores(q, w, pool, tables, positions, *, block_size: int,
                 use_kernel: Optional[bool] = None, interpret=False):
    """Index scores of query rows against their slot's paged keys.

    ``q [S, T, J, D]`` (``J`` index heads), ``w [S, T, J]`` float32 head
    weights, ``pool [num_blocks, block_size, D]`` the index keys,
    ``tables [S, max_blocks]``, ``positions [S, T]`` each row's own
    position. Returns ``[S, T, N]`` float32 with ``N`` the table's
    positions rounded up to whole key tiles: ``sum_j w * relu(q_j . k_c)``
    for every ``c`` up to the key tile that holds the row tile's last
    position, where ``c`` lies in a mapped block up to the slot's last
    row; what lies elsewhere reads 0 past that tile and anything before
    it: the caller masks ``c > positions`` and unmapped blocks. Operands
    in the pool's dtype, float32 accumulation. The kernel copies a slot's
    blocks through its table row, those up to its last row; the jnp form
    gathers every block."""
    S, T, J, D = q.shape
    bs = int(block_size)
    per = KEY_TILE // bs if KEY_TILE % bs == 0 else 1
    tab = jnp.pad(tables.astype(jnp.int32),
                  ((0, 0), (0, -tables.shape[1] % per)), constant_values=-1)
    N = tab.shape[1] * bs
    rows = min(T, _ROW_TILE)
    if use_kernel is None:
        use_kernel = _on_tpu()
    use_kernel = bool(use_kernel or interpret) and KEY_TILE % bs == 0 \
        and T % rows == 0 and D % 128 == 0 and (rows * J) % 8 == 0
    count_path("index_scores", "pallas" if use_kernel else "reference")
    q = q.astype(pool.dtype)
    if use_kernel:
        NT = N // KEY_TILE
        top = jnp.max(positions, axis=1)
        tab = jnp.where(jnp.arange(tab.shape[1])[None, :]
                        <= (top // bs)[:, None], tab, -1)
        last = jnp.clip(jnp.max(positions.reshape(S, T // rows, rows),
                                axis=-1) // KEY_TILE, 0, NT - 1)
        copies = jnp.sum(tab.reshape(S, NT, per) >= 0, axis=-1,
                         dtype=jnp.int32)
        tiles = jnp.where(jnp.arange(NT)[None, :]
                          <= jnp.max(last, axis=1, keepdims=True), copies, -1)
        return _index_score_call(
            q.reshape(S, T * J, D), w.astype(jnp.float32).reshape(S, T * J, 1),
            pool, tab, last.reshape(-1).astype(jnp.int32),
            tiles.reshape(-1), T=T, interpret=interpret)
    keys = pool[jnp.maximum(tab, 0)].reshape(S, N, D)
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
