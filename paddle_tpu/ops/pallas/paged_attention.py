"""Block-table paged attention, Pallas-on-TPU.

TPU kernel behind the ``serving_cache.paged_attention`` seam: the
pure-jnp tiled walk (the CPU/tier-1 numerics oracle) streams each
slot's mapped KV blocks through XLA gathers. This kernel keeps the
identical flat ``(q, pools, tables, positions)`` signature and the same
online softmax, and walks only what is live:

- the grid is one step a slot. The block table and each slot's own
  block count (``max_t positions[s, t] // block_size + 1``, capped by
  the caller's ``n_tiles``) are scalar-prefetched to SMEM; the pools
  stay in HBM (``memory_space=pl.ANY``);
- a slot's blocks are walked a group of ``C`` at a time
  (``group_blocks``: from static shapes, aiming at ``_GROUP_TOKENS``
  tokens): the async copies of a group (one a block, K and V, ids read
  from the table: one block is one contiguous ``[bs, KVH*D]`` slab, so
  one copy brings all KV heads) land in one half of a two-half VMEM
  buffer and are computed on as one ``[C*bs, KVH*D]`` tile;
- the halves alternate over (slot, group) across grid steps (scratch
  and semaphores outlive a step), so the copies of a slot's first
  group are in flight while the slot before it is computed;
- what a copy costs this kernel is its START (a block is 16 KB a
  stream: the scalar core spends longer describing a group's copies
  than the DMA engines moving them). Where a slot is narrow (``T *
  n_rep`` query rows a KV head up to ``_NARROW_ROWS``: a decode call,
  whose arithmetic a group is about as long as the starts of its copies)
  half a group's starts are straight-line code inside the arithmetic
  of the group before, the rest a loop of four a pass, and a group is
  waited for with six waits at most, not one a block. A wide slot (a
  prompt chunk, whose arithmetic dwarfs all this) keeps the plain
  loops: every line here is traced and lowered again by every serving
  process, once a program;
- a slot's last group is ragged: blocks past the slot's count are not
  fetched, their columns are masked. A slot with no block at all
  (``n_tiles`` 0) starts no copy of its own and computes nothing;
- with ``lower`` (a window layer: each row's first visible position)
  the walk starts at the slot's first live block, ``min_t lower[s, t]
  // block_size``, and masks the ragged head of it. Without it nothing
  of this is in the program: a full-attention call compiles as before.

A pool is ``[num_blocks, block_size, KVH*D]`` from its allocation on,
the layout the copies above read, and reaches the kernel as it is
stored: compiled for the v5e a bf16 ``[..., KVH, 128]`` array is tiled
``T(4,128)(2,1)`` and the kernel's slab ``T(8,128)(2,1)``, so a
four-dimensional pool was copied whole, K and V, a layer a launch.

Contract (shared with the jnp walk, parity-pinned in
tests/test_serving_spec.py):

- row ``(s, t)`` attends every column ``lower[s, t] <= c <=
  positions[s, t]`` (``lower`` 0 when not given) of the first
  ``n_tiles`` blocks; unmapped (-1) table entries read block 0;
- GQA runs against the UNEXPANDED pools: a KV head is a 128-lane slice
  of the tile, its ``n_rep`` query heads are the rows of one dot;
- operands go to the MXU in the pool's dtype with float32
  accumulation; m, l, acc and the softmax are float32;
- ``k_scale``/``v_scale`` switch the tile load to int8-dequant mode;
- a masked column contributes exactly zero whatever a recycled or
  never-fetched block holds (NaN/inf from a previous request): its
  score is replaced before the softmax and V's non-finite values are
  zeroed before the PV dot.

``interpret=True`` runs the same kernel through the Pallas interpreter
— how the CPU parity test asserts same-numerics without a TPU. That
interpreter copies at a DMA's start and takes every wait as done;
``interpret=pltpu.InterpretParams()`` counts a semaphore's bytes as the
chip does (a wait for more than was started blocks, one for less leaves
the data behind), which is how tier 1 holds the starts and waits above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .grouped_matmul import _dot_precision

__all__ = ["paged_attention_kernel", "kernel_available"]

_NEG_INF = -1e30
_LANES = 128
# what a group of blocks aims at and may cost (see group_blocks)
_GROUP_TOKENS = 512
_KV_VMEM_BUDGET = 4 * 1024 * 1024
_SCORE_VMEM_BUDGET = 1024 * 1024
# query rows a KV head (T * n_rep) up to which a slot is narrow: one packed
# register tile of bf16 rows, which is a decode call (8 rows at Yi's heads,
# 16 at Command A+'s). There a group's arithmetic is about as long as the
# starts of its copies (1.06 against 1.3 us at 8 rows and 32 blocks of 16
# tokens on the v5e) and the starts are worth more code. Every line of the
# kernel is traced and lowered again by every process that serves, once a
# program (the cells' set-up time shows it: a second and more a narrow
# program), so the prompt chunks' programs keep the plain loops
_NARROW_ROWS = 16
# blocks a pass of the loop that starts a narrow slot's copies takes
_START_UNROLL = 4


def kernel_available(head_dim: int, interpret: bool = False) -> bool:
    """True when the Pallas paged-attention kernel can run these shapes:
    on a TPU backend with ``head_dim`` a multiple of the 128 lanes (or
    anywhere through the interpreter, for CPU parity tests).

    The shape rule is Mosaic's, found by compiling against a v5e
    topology (jax 0.9.0, libtpu 0.0.34): the kernel takes a KV head as
    the lane slice ``[h*D, (h+1)*D)`` of a K/V tile ``[tokens, KVH*D]``
    and a block as one DMA of ``[bs, KVH*D]``, so D has to fill whole
    128-lane registers. head_dim 128 and 256 compile for every (KVH,
    n_rep, T, block_size, pool dtype) tried: KVH 1..32, n_rep 1..8, T
    1..256, block_size 2..128, bf16/f32/int8 pools; a block thinner
    than one 32-bit sublane row (block_size 1 in bf16, 1-2 in int8) is
    refused ("slice shape must be aligned to tiling") and the seam
    sends it to the jnp walk. The old kernel's lane-splitting reshape
    was refused below head_dim 128; this one's slices compile at
    head_dim 64 and 32 too where KVH*D is a multiple of 128, but have
    not run on a chip, so those widths stay with the walk.
    tests/test_serving_spec.py pins the rule."""
    if interpret:
        return True
    return jax.default_backend() == "tpu" and head_dim % 128 == 0


def group_blocks(block_size: int, kv_width: int, pool_dtype, T: int,
                 n_rep: int, max_blocks: int, dequant: bool = False) -> int:
    """Blocks the kernel fetches and computes on at once (``C``), from
    static shapes only. A group of ``C * block_size`` tokens aims at
    ``_GROUP_TOKENS``, less where the double-buffered K and V tiles
    (``4 * tokens * kv_width`` pool elements, plus the scales of an
    int8 pool) or the float32 score tile (``T * n_rep`` rows) would
    pass their VMEM budgets, and never more than the table holds. One
    block a group where blocks do not stack into one tile for free
    (``block_size`` short of the sublane packing of what the tile is
    computed in: 8 rows for 4-byte, 16 for 2-byte, 32 for 1-byte
    elements)."""
    itemsize = jnp.dtype(pool_dtype).itemsize
    packing = 8 if dequant else 32 // itemsize
    if block_size % packing:
        return 1
    per_token = 4 * kv_width * itemsize + (
        4 * _LANES * 4 if dequant else 0)
    tokens = min(_GROUP_TOKENS, _KV_VMEM_BUDGET // per_token,
                 _SCORE_VMEM_BUDGET // (4 * T * n_rep))
    return int(max(1, min(tokens // block_size, max_blocks)))


def _vmem_limit(TR, K, D, G, q_dtype, pool_dtype, dequant) -> int:
    """Scoped-VMEM limit for one slot's program, from its shapes: the
    double-buffered q/out/position blocks and K/V group buffers, the
    lane-padded m/l and acc state, and the live float32 score tiles;
    half as much again for what Mosaic keeps, and never under the
    compiler's own default."""
    rows = K * TR
    need = (4 * rows * D * jnp.dtype(q_dtype).itemsize
            + 2 * TR * _LANES * 4
            + 4 * G * K * D * jnp.dtype(pool_dtype).itemsize
            + (4 * G * _LANES * 4 + 2 * G * D * 4 if dequant else 0)
            + 2 * rows * _LANES * 4 + rows * D * 4
            + 6 * TR * max(G, _LANES) * 4)
    return max(16 * 1024 * 1024, need * 3 // 2)


def group_tokens(block_size: int, *shapes) -> int:
    """Tokens a loop step of the kernel covers (``group_blocks``'
    arguments): what a slot's walk is rounded up to, and what
    ``serving.decode``'s ``walk_tokens`` counts with."""
    return block_size * group_blocks(block_size, *shapes)


def _kernel(tables_ref, nblk_ref, *refs, block_size, C, narrow, kvh,
            head_dim, dequant, cdtype, bounded):
    """One slot's program. Scalar-prefetch refs (SMEM): the block table
    and each slot's live block count (and, ``bounded``, its first live
    block), with a zero past the last slot. Tensor refs: q [1, K, T*R, D]
    and row positions [1, T*R, 1] (and, ``bounded``, the rows' first
    visible positions, same shape) in VMEM | K and V pools [NB, bs, K*D],
    the layout they are allocated in (one block is one contiguous slab,
    so one copy brings all KV heads), and their scales [NB, bs, K], left
    in HBM | out [1, K, T*R, D]. Scratch (it outlives a grid step): the
    two-half group buffers [2, C, bs, .] the DMAs land in, their
    semaphores [stream, half], the float32 online-softmax state m/l
    [K, T*R, 1], acc [K, T*R, D], and in SMEM the half that the next
    group to be computed lands in.

    The halves alternate over (slot, group) in the order the groups are
    computed, across grid steps: under a group's arithmetic runs the
    copy of the slot's next group or, under its last group, of the NEXT
    slot's first one. Only the call's first slot starts its own first
    group. A slot with no block (a count of 0) starts no copy of its own
    and computes nothing; it hands the start of the next slot's first
    group on. ``narrow`` (static) chooses how a group's copies are
    started and waited for: see the module's text."""
    if bounded:
        first_ref, refs = refs[0], refs[1:]
    n = 4 if dequant else 2                # K, V (and their scales)
    q_ref, pos_ref = refs[0], refs[1]
    if bounded:
        lo_ref, refs = refs[2], refs[:2] + refs[3:]
    o_ref = refs[2 + n]
    bufs = refs[3 + n:3 + 2 * n]
    streams = tuple(zip(refs[2:2 + n], bufs))
    sems, m_s, l_s, acc_s, half_ref = refs[3 + 2 * n:]
    s = pl.program_id(0)
    D, G = head_dim, C * block_size
    # blocks of a group whose copies are started from inside the
    # arithmetic of the group before it
    NI = C // 2 if narrow else 0

    def walk(s):
        """Slot ``s``'s own blocks [b0, nb) and their groups (a window
        layer's walk starts at its first live block)."""
        nb = nblk_ref[s]
        b0 = jnp.minimum(first_ref[s], nb) if bounded else 0
        return nb, b0, (nb - b0 + C - 1) // C

    def start_block(s, b0, g, half, j):
        # unmapped (-1) entries clamp to block 0, as the jnp walk's
        # max(tables, 0): the mask is by position only
        blk = jnp.maximum(tables_ref[s, b0 + g * C + j], 0)
        for i, (hbm, buf) in enumerate(streams):
            pltpu.make_async_copy(
                hbm.at[blk], buf.at[half, j], sems.at[i, half]).start()

    def live(nb, b0, g):
        """Blocks of a slot's group ``g`` that are fetched (those past
        the slot's count never are: none where it has no group ``g``)."""
        return jnp.clip(nb - b0 - g * C, 0, C)

    def start(s, nb, b0, g, half, frm=0, unroll=1):
        """Start the copies of slot ``s``'s group ``g`` into buffer
        ``half``, from its block ``frm`` on, ``unroll`` blocks a pass
        of the loop (a copy's start is some twenty scalar instructions,
        and a pass of one block spends as long on the loop as on the
        start)."""
        cnt = live(nb, b0, g)

        def many(jj, carry):
            return jax.lax.fori_loop(
                0, unroll, lambda u, c: start_block(
                    s, b0, g, half, frm + jj * unroll + u) or c,
                carry, unroll=True)

        def one(j, carry):
            start_block(s, b0, g, half, j)
            return carry
        if unroll > 1:
            n_many = jnp.maximum(cnt - frm, 0) // unroll
            jax.lax.fori_loop(0, n_many, many, 0)
            frm = frm + n_many * unroll
        jax.lax.fori_loop(frm, cnt, one, 0)

    def wait(nb, b0, g, half):
        """Wait for every copy of a group into buffer ``half``. A
        stream's copies signal one semaphore and a semaphore counts
        bytes, so a narrow slot waits for a count of ``n`` blocks as its
        binary digits: 2^k blocks at a time, six waits at most where a
        wait a block was up to 32."""
        cnt = live(nb, b0, g)
        if not narrow:
            def one(j, carry):
                for i, (hbm, buf) in enumerate(streams):
                    pltpu.make_async_copy(
                        hbm.at[0], buf.at[half, j], sems.at[i, half]).wait()
                return carry
            jax.lax.fori_loop(0, cnt, one, 0)
            return
        for bit in reversed(range(C.bit_length())):
            @pl.when((cnt >> bit) & 1 == 1)
            def _wait_some(n=1 << bit):
                for i, (_, buf) in enumerate(streams):
                    # (only the size of what is named is read)
                    part = buf.at[half, pl.ds(0, n)]
                    pltpu.make_async_copy(
                        part, part, sems.at[i, half]).wait()

    nb, b0, n_groups = walk(s)
    nb1, b01, _ = walk(s + 1)

    @pl.when(s == 0)
    def _first():
        half_ref[0] = 0
        start(0, nb, b0, 0, 0)

    # where this slot's first group lands
    half0 = half_ref[0]

    @pl.when(n_groups == 0)
    def _hand_on():
        start(s + 1, nb1, b01, 0, half0)

    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)
    posv = pos_ref[0]                                  # [T*R, 1]
    scale = 1.0 / float(np.sqrt(D))
    # operands go to the MXU in the pool's dtype, accumulation is float32
    prec = _dot_precision(jnp.promote_types(q_ref.dtype, cdtype))

    def attend(g, half, under=None):
        """Fold group ``g``, in buffer ``half``, into the slot's online
        softmax. ``under`` names a (slot, first block, group, half)
        whose first ``NI`` copies are started from inside this
        arithmetic, a share a KV head: straight-line code beside the
        dots, which the scheduler packs into the same instructions,
        where a loop before them runs alone."""
        cols = (b0 * block_size + g * G) + jax.lax.broadcasted_iota(
            jnp.int32, (posv.shape[0], G), 1)
        # a column is attended up to the row's position, and only where
        # the walk reaches (the caller's n_tiles may stop it short)
        ok = (cols <= posv) & (cols < nb * block_size)  # [T*R, G]
        if bounded:
            # the ragged head of the first block, and every row's own
            # bound where the rows of a chunk differ
            ok = ok & (cols >= lo_ref[0])
        for h in range(kvh):
            lanes = slice(h * D, (h + 1) * D)
            k_t = bufs[0][half, :, :, lanes]           # [C, bs, D]
            v_t = bufs[1][half, :, :, lanes]
            if dequant:
                k_t = k_t.astype(jnp.float32) * bufs[2][half, :, :, h:h + 1]
                v_t = v_t.astype(jnp.float32) * bufs[3][half, :, :, h:h + 1]
            # a masked column must contribute EXACTLY zero whatever a
            # recycled or never-fetched block holds. K needs no care:
            # its scores are replaced below, never multiplied. V does
            # (0 * NaN = NaN in the PV dot): non-finite values go to 0,
            # compared in float32 (the v5e's VPU has no bf16 compare)
            v_t = v_t.astype(jnp.float32)
            v_t = jnp.where(jnp.abs(v_t) <= float(jnp.finfo(cdtype).max),
                            v_t, 0.0)
            k_t = k_t.reshape(G, D).astype(cdtype)
            v_t = v_t.reshape(G, D).astype(cdtype)
            sc = jax.lax.dot_general(
                q_ref[0, h], k_t, (((1,), (1,)), ((), ())),
                precision=prec,
                preferred_element_type=jnp.float32) * scale  # [T*R, G]
            sc = jnp.where(ok, sc, _NEG_INF)
            m_old = m_s[h]
            m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1,
                                               keepdims=True))
            # a fully-masked row has sc == m_new == -1e30: exp gives 1,
            # re-mask p so its contribution is exactly zero
            p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
            corr = jnp.exp(m_old - m_new)
            l_s[h] = l_s[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_s[h] = acc_s[h] * corr + jax.lax.dot_general(
                p.astype(cdtype), v_t, (((1,), (0,)), ((), ())),
                precision=prec,
                preferred_element_type=jnp.float32)    # [T*R, D]
            m_s[h] = m_new
            if under is not None:
                # (unrolled where it is lowered: traced once, no loop)
                jax.lax.fori_loop(
                    h * NI // kvh, (h + 1) * NI // kvh,
                    lambda jj, c: start_block(*under, jj) or c, 0,
                    unroll=True)

    def group(g, carry):
        half = (half0 + g) % 2
        # in flight while this group is computed: the slot's next group
        # or, after its last, the next slot's first
        last = g + 1 == n_groups
        ns, nnb = jnp.where(last, s + 1, s), jnp.where(last, nb1, nb)
        nb0, ng = jnp.where(last, b01, b0), jnp.where(last, 0, g + 1)
        if not NI:
            start(ns, nnb, nb0, ng, 1 - half)
            wait(nb, b0, g, half)
            attend(g, half)
            return carry
        # a next group of NI blocks or more has its first NI started
        # under this group's arithmetic, the rest before it
        under = live(nnb, nb0, ng) >= NI
        start(ns, nnb, nb0, ng, 1 - half, jnp.where(under, NI, 0),
              unroll=_START_UNROLL)
        wait(nb, b0, g, half)

        @pl.when(under)
        def _attend_and_start():
            attend(g, half, under=(ns, nb0, ng, 1 - half))

        @pl.when(jnp.logical_not(under))
        def _attend():
            attend(g, half)
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)
    o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)).astype(
        o_ref.dtype)
    half_ref[0] = (half0 + n_groups) % 2


@functools.partial(
    jax.jit, static_argnames=("block_size", "n_rep", "interpret"))
def _paged_attention_call(q, k_pool, v_pool, tables, positions,
                          n_tiles, k_scale, v_scale, lower, *, block_size,
                          n_rep, interpret):
    S, T, H, D = q.shape
    bounded = lower is not None
    K = H // n_rep
    MB = tables.shape[1]
    R, TR = n_rep, T * n_rep
    dequant = k_scale is not None
    C = group_blocks(block_size, K * D, k_pool.dtype, T, R, MB, dequant)
    # int8 pools dequantise to the queries' dtype, as the jnp walk does
    cdtype = q.dtype if dequant else k_pool.dtype
    kernel = functools.partial(
        _kernel, block_size=block_size, C=C, narrow=TR <= _NARROW_ROWS,
        kvh=K, head_dim=D, dequant=dequant, cdtype=cdtype, bounded=bounded)
    positions = positions.astype(jnp.int32)
    # each slot walks its own blocks only: its longest row's, capped
    # by the caller's n_tiles and by the table. A zero past the last
    # slot: the slot after it, whose first group nobody has to start
    pad = (0, 1)
    nblk = jnp.pad(jnp.minimum(
        jnp.max(positions, axis=1) // block_size + 1,
        jnp.minimum(jnp.asarray(n_tiles, jnp.int32), MB)), pad)
    # rows of one KV head together, (t, r)-major: q is laid out once
    # here, not once a tile in the kernel
    q_rows = q.reshape(S, T, K, R, D).transpose(0, 2, 1, 3, 4).reshape(
        S, K, TR, D)
    pos_rows = jnp.repeat(positions, R, axis=1)[..., None]
    row_spec = pl.BlockSpec((1, K, TR, D), lambda s, *_: (s, 0, 0, 0))
    pos_spec = pl.BlockSpec((1, TR, 1), lambda s, *_: (s, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [row_spec, pos_spec]
    args = [q_rows, pos_rows]
    prefetch = [tables.astype(jnp.int32), nblk]
    if bounded:
        lower = jnp.maximum(lower.astype(jnp.int32), 0)
        prefetch.append(jnp.pad(jnp.min(lower, axis=1) // block_size, pad))
        in_specs.append(pos_spec)
        args.append(jnp.repeat(lower, R, axis=1)[..., None])
    n_rows = len(args)
    # the pools go in as they are stored: no relayout of a pool a launch
    in_specs += [hbm, hbm]
    args += [k_pool, v_pool]
    scratch = [pltpu.VMEM((2, C, block_size, K * D), k_pool.dtype),
               pltpu.VMEM((2, C, block_size, K * D), v_pool.dtype)]
    if dequant:
        # Mosaic copies whole 128-lane rows only: the [NB, bs, K] scales
        # (lane-padded in HBM as they are) get their lanes made explicit
        lanes = ((0, 0), (0, 0), (0, -K % _LANES))
        in_specs += [hbm, hbm]
        args += [jnp.pad(k_scale, lanes), jnp.pad(v_scale, lanes)]
        scratch += [pltpu.VMEM((2, C) + args[-1].shape[1:], sc.dtype)
                    for sc in (k_scale, v_scale)]
    scratch += [pltpu.SemaphoreType.DMA((len(args) - n_rows, 2)),
                pltpu.VMEM((K, TR, 1), jnp.float32),
                pltpu.VMEM((K, TR, 1), jnp.float32),
                pltpu.VMEM((K, TR, D), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, K, TR, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_limit(
                TR, K, D, C * block_size, q.dtype, k_pool.dtype, dequant)),
        interpret=interpret,
    )(*prefetch, *args)
    return out.reshape(S, K, T, R, D).transpose(0, 2, 1, 3, 4).reshape(
        S, T, H, D)


def paged_attention_kernel(q, k_pool, v_pool, tables, positions, *,
                           block_size: int, n_rep: int, n_tiles=None,
                           k_scale=None, v_scale=None, lower=None,
                           interpret: bool = False):
    """Flat-signature drop-in for ``serving_cache.paged_attention``
    (q [S, T, H, D], pools [num_blocks, bs, KVH*D], tables
    [S, max_blocks], positions [S, T]); ``n_tiles`` may be traced —
    it caps every slot's own block count. ``lower [S, T]`` is each
    row's first visible position (a window layer's ``pos - W + 1``).
    """
    if n_tiles is None:
        n_tiles = tables.shape[1]
    return _paged_attention_call(
        q, k_pool, v_pool, tables, positions, n_tiles, k_scale,
        v_scale, lower, block_size=int(block_size), n_rep=int(n_rep),
        interpret=interpret if isinstance(
            interpret, pltpu.InterpretParams) else bool(interpret))
