"""Block-table paged attention, Pallas-on-TPU.

TPU kernel behind the ``serving_cache.paged_attention`` seam: the
pure-jnp tiled walk (the CPU/tier-1 numerics oracle) streams each
slot's mapped KV blocks through XLA gathers; on TPU that per-tile
gather loop is the remaining decode roofline gap (ROADMAP item 1b).
This kernel keeps the identical flat ``(q, pools, tables, positions)``
signature and the identical online-softmax tiling, but lets the Mosaic
pipeline move blocks HBM->VMEM via **scalar-prefetched block-table
indexing** (the vLLM-style recipe): the grid walks (slot, tile) and
each tile's BlockSpec index_map reads ``tables[s, t]`` — prefetched to
SMEM before the body runs — so the next physical block's DMA overlaps
the current tile's MXU work instead of round-tripping a gather.

Contract (shared with the jnp walk, parity-pinned in
tests/test_serving_spec.py):

- row ``(s, t)`` attends every column ``c <= positions[s, t]``;
- GQA runs against the UNEXPANDED pools (``n_rep`` query heads per KV
  head, grouped batched dots — never a repeated pool);
- ``k_scale``/``v_scale`` switch the tile load to int8-dequant mode;
- recycled-block garbage (NaN/inf from a previous request) is
  sanitized per tile, so masked columns contribute exactly zero;
- tiles at or past ``n_tiles`` are skipped (``@pl.when``), so short
  histories pay only their own compute (their DMAs land on the
  clamped block and are overlapped anyway).

``interpret=True`` runs the same kernel through the Pallas interpreter
— how the CPU parity test asserts same-numerics without a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_kernel", "kernel_available"]

_NEG_INF = -1e30


def kernel_available(head_dim: int, interpret: bool = False) -> bool:
    """True when the Pallas paged-attention kernel can run these shapes:
    on a TPU backend with ``head_dim`` a multiple of the 128 lanes (or
    anywhere through the interpreter, for CPU parity tests).

    The shape rule is Mosaic's, found by compiling against a v5e
    topology (jax 0.9.0, libtpu 0.0.34): the kernel views a K/V tile
    ``[bs, KVH*D]`` as ``[bs, KVH, D]``, which splits the lane dimension
    and is refused (``infer-vector-layout: unsupported shape cast``)
    unless D fills whole 128-lane registers. head_dim 128 and 256
    compile for every (KVH, n_rep, T, block_size, pool dtype) tried —
    KVH 1..32, n_rep 1..8, T 1..64, block_size 1..128, bf16/f32/int8
    pools; head_dim 16, 32 and 64 are refused (64 compiles only in the
    degenerate KVH = n_rep = 1 f32 case). tests/test_serving_spec.py
    pins the rule."""
    if interpret:
        return True
    return jax.default_backend() == "tpu" and head_dim % 128 == 0


def _kernel(tables_ref, pos_ref, nt_ref, *refs, block_size, n_rep, T,
            kvh, head_dim, dequant):
    """One (slot, tile) program. Scalar-prefetch refs: the flat block
    table (drives the BlockSpec index maps — see the pallas_call),
    per-row positions, and the live tile count. Tensor refs:
    q [1, T, H*D] | k/v tile [1, bs, K*D] | (k/v scale [1, bs, K]) |
    out [1, T, H*D]; scratch: m/l [K, T*R] + acc [K, T*R, D] carries
    that live across the sequential tile dimension of the grid."""
    if dequant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s = refs
        ks_ref = vs_ref = None
    s = pl.program_id(0)
    t = pl.program_id(1)
    R, D = n_rep, head_dim

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(t < nt_ref[0])
    def _tile():
        k_t = k_ref[0].reshape(block_size, kvh, D)
        v_t = v_ref[0].reshape(block_size, kvh, D)
        if dequant:
            k_t = k_t.astype(jnp.float32) * ks_ref[0][..., None]
            v_t = v_t.astype(jnp.float32) * vs_ref[0][..., None]
        # recycled blocks may hold non-finite garbage from a previous
        # request — same sanitization as the jnp walk, masked columns
        # must contribute EXACTLY zero (0 * NaN = NaN in the PV dot)
        k_t = jnp.nan_to_num(k_t.astype(jnp.float32))
        v_t = jnp.nan_to_num(v_t.astype(jnp.float32))
        # grouped GQA: [K, T*R, D] x [K, bs, D] batched over KV heads,
        # never expanding the pools n_rep-fold
        q = q_ref[0].reshape(T, kvh, R, D).transpose(1, 0, 2, 3)
        q = q.reshape(kvh, T * R, D).astype(jnp.float32)
        kt = k_t.transpose(1, 0, 2)                    # [K, bs, D]
        vt = v_t.transpose(1, 0, 2)
        scores = jax.lax.dot_general(
            q, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [K, T*R, bs]
        scores = scores * (1.0 / float(np.sqrt(D)))
        cols = t * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (T, block_size), 1)
        posv = jnp.stack([pos_ref[s, i] for i in range(T)])
        ok = cols <= posv[:, None]                     # [T, bs]
        okr = jnp.repeat(ok, R, axis=0)                # rows t*R + r
        scores = jnp.where(okr[None], scores, _NEG_INF)
        m_new = jnp.maximum(m_s[...], jnp.max(scores, axis=-1))
        # a fully-masked row has scores == m_new == -1e30: exp gives 1,
        # re-mask p so its contribution is exactly zero (jnp-walk rule)
        p = jnp.where(okr[None], jnp.exp(scores - m_new[..., None]),
                      0.0)
        corr = jnp.exp(m_s[...] - m_new)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p, vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [K, T*R, D]
        acc_s[...] = acc_s[...] * corr[..., None] + pv
        m_s[...] = m_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _done():
        out = acc_s[...] / jnp.maximum(l_s[...], 1e-30)[..., None]
        out = out.reshape(kvh, T, R, D).transpose(1, 0, 2, 3)
        o_ref[0] = out.reshape(T, kvh * R * D).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_size", "n_rep", "interpret"))
def _paged_attention_call(q, k_pool, v_pool, tables, positions,
                          n_tiles, k_scale, v_scale, *, block_size,
                          n_rep, interpret):
    S, T, H, D = q.shape
    K = k_pool.shape[2]
    MB = tables.shape[1]
    dequant = k_scale is not None
    kernel = functools.partial(
        _kernel, block_size=block_size, n_rep=n_rep, T=T, kvh=K,
        head_dim=D, dequant=dequant)

    def _phys(s, t, tables_ref, pos_ref, nt_ref):
        # unmapped (-1) and beyond-n_tiles entries clamp to block 0:
        # the DMA still lands somewhere valid, @pl.when skips/masks
        # the compute exactly like the jnp walk's max(tables, 0)
        return jnp.maximum(tables_ref[s, t], 0)

    q_spec = pl.BlockSpec(
        (1, T, H * D), lambda s, t, tr, pr, nr: (s, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, block_size, K * D),
        lambda s, t, tr, pr, nr: (_phys(s, t, tr, pr, nr), 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q.reshape(S, T, H * D),
            k_pool.reshape(k_pool.shape[0], block_size, K * D),
            v_pool.reshape(v_pool.shape[0], block_size, K * D)]
    if dequant:
        sc_spec = pl.BlockSpec(
            (1, block_size, K),
            lambda s, t, tr, pr, nr: (_phys(s, t, tr, pr, nr), 0, 0))
        in_specs += [sc_spec, sc_spec]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, T, H * D), lambda s, t, tr, pr, nr: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, T * n_rep), jnp.float32),
            pltpu.VMEM((K, T * n_rep), jnp.float32),
            pltpu.VMEM((K, T * n_rep, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, T, H * D), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), positions.astype(jnp.int32),
      jnp.asarray(n_tiles, jnp.int32).reshape(1), *args)
    return out.reshape(S, T, H, D)


def paged_attention_kernel(q, k_pool, v_pool, tables, positions, *,
                           block_size: int, n_rep: int, n_tiles=None,
                           k_scale=None, v_scale=None,
                           interpret: bool = False):
    """Flat-signature drop-in for ``serving_cache.paged_attention``
    (q [S, T, H, D], pools [num_blocks, bs, KVH, D], tables
    [S, max_blocks], positions [S, T]); ``n_tiles`` may be traced —
    it rides in as a scalar-prefetch operand bounding the live tiles.
    """
    if n_tiles is None:
        n_tiles = tables.shape[1]
    return _paged_attention_call(
        q, k_pool, v_pool, tables, positions, n_tiles, k_scale,
        v_scale, block_size=int(block_size), n_rep=int(n_rep),
        interpret=bool(interpret))
