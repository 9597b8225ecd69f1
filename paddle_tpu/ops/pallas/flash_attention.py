"""Flash attention, Pallas-on-TPU — forward AND backward kernels.

TPU-native replacement for the reference's flash-attention wrapper
(ref: paddle/phi/kernels/gpu/flash_attn_kernel.cu fwd +
flash_attn_grad_kernel.cu bwd, which call the vendored third_party/flashattn
CUDA lib). Design: online-softmax tiling over the KV sequence so logits
never materialize in HBM, with block sizes aligned to the MXU (128).

Forward emits the per-row logsumexp; backward uses the standard two-kernel
flash recipe — a dq kernel tiled over Q blocks and a dk/dv kernel tiled
over KV blocks, both re-computing P from (q, k, lse) so memory stays
O(L·D) instead of O(L²). Off the TPU (CPU mesh tests) or where shapes
don't tile, the XLA oracle and its recompute-based VJP run instead; the
choice is counted (``pallas.path_selected_total``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import count_path

__all__ = ["flash_attention_fwd", "flash_attention",
           "flash_attention_segmented"]

_NEG_INF = -1e30


def _sdpa_xla(q, k, v, causal=False, scale=None, mask=None,
              dropout_p=0.0, seed=None, dropout_key=None):
    """Numeric oracle, layout [B, L, H, D]. `mask` is additive, broadcast
    against [B, H, Lq, Lk] logits. Handles Lq < Lk (KV-cache decode) by
    offsetting the causal diagonal. Dropout is deterministic given
    ``seed`` (or an explicit ``dropout_key``) so the VJP fallback can
    replay the identical mask. This is THE reference oracle —
    nn.functional's _sdpa_reference delegates here."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    if dropout_p >= 1.0:
        # everything dropped: zeros with zero (not NaN) gradients — the
        # 1/(1-p) rescale below would divide by zero
        return jnp.zeros_like(q)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt).astype(jnp.float32) * s
    if causal:
        ql, kl = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((ql, kl), bool), k=kl - ql)
        logits = jnp.where(cm, logits, _NEG_INF)
    if mask is not None:
        logits = logits + mask
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if dropout_p > 0.0:
        if dropout_key is None and seed is not None:
            dropout_key = jax.random.PRNGKey(jnp.asarray(seed).reshape(()))
        if dropout_key is not None:
            keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p,
                                        probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p),
                              0.0).astype(probs.dtype)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)



def _keep_mask(seed_ref, b, qi, ki, block_q, block_k, seq_len, dropout_p):
    """Deterministic per-tile dropout keep-mask. Seeding with the
    (seed, batch-head, q-tile, k-tile) tuple makes the mask a pure
    function of absolute tile position, so forward and both backward
    kernels regenerate identical bits regardless of their grid order
    (ref: the flash_attn CUDA kernels thread a philox offset the same
    way, paddle/phi/kernels/gpu/flash_attn_kernel.cu seed/offset args).
    Mosaic caps prng_seed at 2 values, so the tile coordinate folds into
    one int32 — injective because qi < L/block_q and ki < L/block_k."""
    nq = seq_len // block_q
    nk = seq_len // block_k
    tile = (b * nq + qi) * nk + ki
    pltpu.prng_seed(seed_ref[0], tile)
    bits = pltpu.prng_random_bits((block_q, block_k))
    bits = jax.lax.bitcast_convert_type(bits, jnp.uint32)
    thresh = jnp.uint32(min(int(dropout_p * (2 ** 32)), 2 ** 32 - 1))
    return bits >= thresh


# ---------------------------------------------------------------------------
# forward kernel: one (batch*head, q-block) program; inner loop tiles KV
# with online softmax; also emits logsumexp for the backward pass
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, block_q, block_k, seq_len, causal, scale,
                segmented=False, dropout_p=0.0, fold_bh=False):
    if dropout_p > 0.0:
        seed_ref, *refs = refs
    else:
        seed_ref = None
    q_ref, k_ref, v_ref, *rest = refs
    if segmented:
        seg_ref, o_ref, lse_ref = rest
    else:
        seg_ref = None
        o_ref, lse_ref = rest
    if fold_bh:
        # layout-native path: grid (b, h, i) over [B, L, H*D] arrays;
        # (b, h) folds into one id so the dropout tile seed stays unique
        # across heads. Data blocks look identical to the [BH, L, D]
        # path; only lse rides in [B, H, L, 1].
        b = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
        qi = pl.program_id(2)
    else:
        b = pl.program_id(0)
        qi = pl.program_id(1)
    # operands stay in their native dtype (bf16 on the bench path) for
    # every MXU dot, with f32 accumulation via preferred_element_type —
    # f32 multiplies run the MXU at a fraction of bf16 rate (measured
    # on v5e at the BERT d=64 geometry: fwd kernel 1.12 -> 0.64 ms,
    # bwd pair 2.9 -> 1.5 ms per layer); softmax statistics stay f32
    q = q_ref[0]  # [block_q, d]

    m = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_offset = qi * block_q
    num_k_blocks = seq_len // block_k
    if causal:
        num_k_blocks_eff = (q_offset + block_q + block_k - 1) // block_k
    else:
        num_k_blocks_eff = num_k_blocks
    if segmented:
        seg_q = seg_ref[0, pl.ds(q_offset, block_q), :]  # [block_q, 1]

    def body(ki, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :]
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            precision=_prec(q, k_blk),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_ids = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            logits = jnp.where(q_ids >= k_ids, logits, _NEG_INF)
        if segmented:
            # varlen packing: tokens attend within their segment only
            seg_k = seg_ref[0, pl.ds(ki * block_k, block_k), :]
            logits = jnp.where(seg_q == seg_k.reshape(1, block_k),
                               logits, _NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        # softmax statistics (l, lse) use the UNdropped probabilities;
        # dropout zeroes entries of the numerator only — dividing by the
        # full l afterwards is exactly dropout(softmax(s)) since the
        # normalization is linear
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        if dropout_p > 0.0:
            keep = _keep_mask(seed_ref, b, qi, ki, block_q, block_k,
                              seq_len, dropout_p)
            p = jnp.where(keep, p, 0.0)
        acc_new = alpha * acc + jax.lax.dot(
            p.astype(v_blk.dtype), v_blk, precision=_prec(v_blk),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, num_k_blocks_eff, body, (m, l, acc))
    if dropout_p > 0.0:
        acc = acc * (1.0 / (1.0 - dropout_p))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_val = m + jnp.log(jnp.maximum(l, 1e-30))
    if fold_bh:
        lse_ref[0, 0] = lse_val  # [B, H, L, 1] block (1, 1, block_q, 1)
    else:
        lse_ref[0] = lse_val


def _prec(*operands):
    """Explicit contraction precision: bf16 operands must run DEFAULT
    (the native single-pass MXU path — an ambient fp32/HIGHEST precision
    produces a tpu.matmul Mosaic rejects with 'Bad lhs type'), f32
    operands keep HIGHEST. One rule for every Pallas kernel: shared
    with grouped_matmul."""
    from .grouped_matmul import _dot_precision
    dt = operands[0].dtype
    for o in operands[1:]:
        dt = jnp.promote_types(dt, o.dtype)
    return _dot_precision(dt)


# ---------------------------------------------------------------------------
# backward kernels (standard flash bwd algebra):
#   P  = exp(scale·QKᵀ − lse)          (recomputed per tile)
#   dV = Pᵀ @ dO
#   dS = P ∘ (dO @ Vᵀ − Δ) · scale     with Δ = rowsum(dO ∘ O)
#   dQ = dS @ K ;  dK = dSᵀ @ Q
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, block_q, block_k, seq_len, causal, scale,
                   segmented=False, dropout_p=0.0, fold_bh=False):
    if dropout_p > 0.0:
        seed_ref, *refs = refs
    else:
        seed_ref = None
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
    if segmented:
        seg_ref, dq_ref = rest
    else:
        seg_ref = None
        (dq_ref,) = rest
    if fold_bh:
        b = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
        qi = pl.program_id(2)
        lse = lse_ref[0, 0]      # [block_q, 1]
        delta = delta_ref[0, 0]  # [block_q, 1]
    else:
        b = pl.program_id(0)
        qi = pl.program_id(1)
        lse = lse_ref[0]      # [block_q, 1]
        delta = delta_ref[0]  # [block_q, 1]
    q = q_ref[0]   # native dtype: MXU dots run bf16 with f32 acc
    do = do_ref[0]
    q_offset = qi * block_q
    if causal:
        num_k_blocks_eff = (q_offset + block_q + block_k - 1) // block_k
    else:
        num_k_blocks_eff = seq_len // block_k
    if segmented:
        seg_q = seg_ref[0, pl.ds(q_offset, block_q), :]

    def body(ki, dq):
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :]
        s = scale * jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            precision=_prec(q, k_blk),
            preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse)
        if causal:
            q_ids = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(q_ids >= k_ids, p, 0.0)
        if segmented:
            seg_k = seg_ref[0, pl.ds(ki * block_k, block_k), :]
            p = jnp.where(seg_q == seg_k.reshape(1, block_k), p, 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            precision=_prec(do, v_blk),
            preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            # dS = P ∘ (M∘dP_d/(1−p) − Δ): Δ = rowsum(dO∘O) already
            # equals Σ_k P_d·dP_d, so only the dp term needs the mask
            keep = _keep_mask(seed_ref, b, qi, ki, block_q, block_k,
                              seq_len, dropout_p)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout_p))
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot(
            ds.astype(k_blk.dtype), k_blk, precision=_prec(k_blk),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        0, num_k_blocks_eff, body,
        jnp.zeros((block_q, q.shape[-1]), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, block_q, block_k, seq_len, causal,
                    scale, segmented=False, dropout_p=0.0,
                    fold_bh=False):
    if dropout_p > 0.0:
        seed_ref, *refs = refs
    else:
        seed_ref = None
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest = refs
    if segmented:
        seg_ref, dk_ref, dv_ref = rest
    else:
        seg_ref = None
        dk_ref, dv_ref = rest
    if fold_bh:
        b = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
        ki = pl.program_id(2)
    else:
        b = pl.program_id(0)
        ki = pl.program_id(1)
    k_blk = k_ref[0]      # [block_k, d] native dtype (bf16 MXU dots)
    v_blk = v_ref[0]
    k_offset = ki * block_k
    num_q_blocks = seq_len // block_q
    # causal: only q blocks at or after this kv block contribute
    q_start = k_offset // block_q if causal else 0
    if segmented:
        seg_k = seg_ref[0, pl.ds(k_offset, block_k), :]

    def body(qi, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qi * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qi * block_q, block_q), :]
        if fold_bh:
            lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), :]
            delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q), :]
        else:
            lse = lse_ref[0, pl.ds(qi * block_q, block_q), :]
            delta = delta_ref[0, pl.ds(qi * block_q, block_q), :]
        s = scale * jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            precision=_prec(q_blk, k_blk),
            preferred_element_type=jnp.float32)  # [block_q, block_k]
        p = jnp.exp(s - lse)
        if causal:
            q_ids = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_ids = k_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(q_ids >= k_ids, p, 0.0)
        if segmented:
            seg_q = seg_ref[0, pl.ds(qi * block_q, block_q), :]
            p = jnp.where(seg_q == seg_k.reshape(1, block_k), p, 0.0)
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            precision=_prec(do_blk, v_blk),
            preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            # same (seed, b, qi, ki) tuple as fwd/dq — identical mask
            # despite this kernel's transposed grid order
            keep = _keep_mask(seed_ref, b, qi, ki, block_q, block_k,
                              seq_len, dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            p_d = jnp.where(keep, p, 0.0) * inv   # dropped P for dV
            dp = jnp.where(keep, dp, 0.0) * inv
        else:
            p_d = p
        # contracting dim 0 == transposed-operand dot without an
        # in-kernel transpose (free on the MXU)
        dv_new = dv + jax.lax.dot_general(
            p_d.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            precision=_prec(do_blk),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            precision=_prec(q_blk),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        q_start, num_q_blocks, body,
        (jnp.zeros((block_k, k_blk.shape[-1]), jnp.float32),
         jnp.zeros((block_k, v_blk.shape[-1]), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "dropout_p"))
def _flash_fwd_pallas(q, k, v, causal, scale, block_q=256, block_k=256,
                      dropout_p=0.0, seed=None):
    """q,k,v: [BH, L, D] -> (out [BH, L, D], lse [BH, L]).
    ``seed``: (1,) int32 SMEM scalar, required when dropout_p > 0 —
    dropout masks are regenerated from it in the backward kernels."""
    bh, seq_len, d = q.shape
    grid = (bh, seq_len // block_q)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, seq_len=seq_len,
        causal=causal, scale=scale, dropout_p=dropout_p)
    seed_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  if dropout_p > 0.0 else [])
    seed_args = (seed,) if dropout_p > 0.0 else ()
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_len, 1), jnp.float32),
        ],
    )(*seed_args, q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "dropout_p"))
def _flash_bwd_pallas(q, k, v, out, lse, do, causal, scale, block_q=256,
                      block_k=256, dropout_p=0.0, seed=None):
    """[BH, L, D] residuals + dO -> (dq, dk, dv)."""
    bh, seq_len, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, L, 1]
    seed_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  if dropout_p > 0.0 else [])
    seed_args = (seed,) if dropout_p > 0.0 else ()

    dq_kernel = functools.partial(
        _bwd_dq_kernel, block_q=block_q, block_k=block_k, seq_len=seq_len,
        causal=causal, scale=scale, dropout_p=dropout_p)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, seq_len // block_q),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_len, d), q.dtype),
    )(*seed_args, q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, block_q=block_q, block_k=block_k, seq_len=seq_len,
        causal=causal, scale=scale, dropout_p=dropout_p)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, seq_len // block_k),
        in_specs=seed_specs + [
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_len, d), v.dtype),
        ],
    )(*seed_args, q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "dropout_p"))
def _flash_fwd_pallas_blhd(q, k, v, causal, scale, block_q=256,
                           block_k=256, dropout_p=0.0, seed=None):
    """[B, L, H, D] layout-native forward: arrays are viewed as
    [B, L, H*D] (a free minor-dim reshape) and the grid walks (batch,
    head, q-block) with the head selecting a d-wide block of the last
    dim — the kernel consumes the model's own activation layout, so the
    physical [B,H,L,D] transpose copies disappear (measured ~10 ms/step
    of pure copy time at the 1.17B Llama bench geometry). Requires
    d % 128 == 0 (Mosaic block constraint); lse comes back [B, H, L, 1].
    """
    b, seq_len, h, d = q.shape
    qf, kf, vf = (x.reshape(b, seq_len, h * d) for x in (q, k, v))
    grid = (b, h, seq_len // block_q)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, seq_len=seq_len,
        causal=causal, scale=scale, dropout_p=dropout_p, fold_bh=True)
    seed_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  if dropout_p > 0.0 else [])
    seed_args = (seed,) if dropout_p > 0.0 else ()
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((1, seq_len, d), lambda b, h, i: (b, 0, h)),
            pl.BlockSpec((1, seq_len, d), lambda b, h, i: (b, 0, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, h, i: (b, i, h)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, seq_len, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, h, seq_len, 1), jnp.float32),
        ],
    )(*seed_args, qf, kf, vf)
    return out.reshape(b, seq_len, h, d), lse


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "dropout_p"))
def _flash_bwd_pallas_blhd(q, k, v, out, lse, do, causal, scale,
                           block_q=256, block_k=256, dropout_p=0.0,
                           seed=None):
    """[B, L, H, D] residuals + dO -> (dq, dk, dv) in [B, L, H, D];
    lse/delta ride in [B, H, L, 1] (tiny, cheap to transpose)."""
    b, seq_len, h, d = q.shape
    delta = jnp.transpose(
        jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                axis=-1),
        (0, 2, 1))[..., None]  # [B, H, L, 1]
    qf, kf, vf, dof = (x.reshape(b, seq_len, h * d)
                       for x in (q, k, v, do))
    seed_specs = ([pl.BlockSpec(memory_space=pltpu.SMEM)]
                  if dropout_p > 0.0 else [])
    seed_args = (seed,) if dropout_p > 0.0 else ()

    q_blk_spec = pl.BlockSpec((1, block_q, d), lambda b, h, i: (b, i, h))
    q_seq_spec = pl.BlockSpec((1, seq_len, d), lambda b, h, i: (b, 0, h))
    r_blk_spec = pl.BlockSpec((1, 1, block_q, 1),
                              lambda b, h, i: (b, h, i, 0))
    r_seq_spec = pl.BlockSpec((1, 1, seq_len, 1),
                              lambda b, h, i: (b, h, 0, 0))
    k_blk_spec = pl.BlockSpec((1, block_k, d), lambda b, h, i: (b, i, h))

    dq_kernel = functools.partial(
        _bwd_dq_kernel, block_q=block_q, block_k=block_k,
        seq_len=seq_len, causal=causal, scale=scale, dropout_p=dropout_p,
        fold_bh=True)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, seq_len // block_q),
        in_specs=seed_specs + [q_blk_spec, q_seq_spec, q_seq_spec,
                               q_blk_spec, r_blk_spec, r_blk_spec],
        out_specs=q_blk_spec,
        out_shape=jax.ShapeDtypeStruct((b, seq_len, h * d), q.dtype),
    )(*seed_args, qf, kf, vf, dof, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, block_q=block_q, block_k=block_k,
        seq_len=seq_len, causal=causal, scale=scale, dropout_p=dropout_p,
        fold_bh=True)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, seq_len // block_k),
        in_specs=seed_specs + [q_seq_spec, k_blk_spec, k_blk_spec,
                               q_seq_spec, r_seq_spec, r_seq_spec],
        out_specs=[k_blk_spec, k_blk_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, seq_len, h * d), k.dtype),
            jax.ShapeDtypeStruct((b, seq_len, h * d), v.dtype),
        ],
    )(*seed_args, qf, kf, vf, dof, lse, delta)
    return (dq.reshape(b, seq_len, h, d), dk.reshape(b, seq_len, h, d),
            dv.reshape(b, seq_len, h, d))


def _tiles_ok(seq_len, d, block_q, block_k) -> bool:
    # d=64 (BERT-class heads) runs natively: Mosaic lays a [*, 64] tile
    # across half the 128 lanes; measured on v5e the native kernel beats
    # pad-to-128 at the BERT bench geometry (no pad/slice HBM traffic)
    return (seq_len % block_q == 0 and seq_len % block_k == 0
            and d % 64 == 0 and seq_len >= block_q)


_block_tune_cache: dict = {}


def _pick_block(seq_len: int, d: int = 128, sample=None) -> int:
    """Block-size choice. Default: the ladder measured on v5e (1.17B
    Llama, seq 2048, whole train step): 512 tiles ~7% faster than 256,
    256 ~15% faster than 128; 1024 exceeds VMEM.

    FLAGS_pallas_autotune=1 switches to a runtime tuner (the analog of
    the reference's kernels/autotune/cache.h): the first call per
    (seq_len, d) times each candidate on the live arrays and caches the
    winner for the process."""
    from ...core.flags import flag_value
    candidates = [b for b in (512, 256, 128) if seq_len % b == 0]
    if not candidates:
        return 128
    key = ("flash", seq_len, d)
    hit = _block_tune_cache.get(key)
    if hit is not None:
        return hit  # backward reuses the forward's tuned choice
    if sample is None or not flag_value("pallas_autotune"):
        return candidates[0]
    q, k, v = sample
    if isinstance(q, jax.core.Tracer):
        # inside a jit trace there is nothing to measure; do NOT cache —
        # a later eager call can still tune this shape
        return candidates[0]
    import time as _time
    fwd = _flash_fwd_pallas_blhd if q.ndim == 4 else _flash_fwd_pallas
    best, best_t = None, float("inf")
    for blk in candidates:
        try:
            out, _ = fwd(q, k, v, False, 1.0 / math.sqrt(d),
                         block_q=blk, block_k=blk)
            float(jnp.sum(out))  # warm; value fetch = the real barrier
            t0 = _time.perf_counter()
            for _ in range(3):
                out, _ = fwd(q, k, v, False, 1.0 / math.sqrt(d),
                             block_q=blk, block_k=blk)
            float(jnp.sum(out))
            dt = _time.perf_counter() - t0
        except Exception:
            continue
        if dt < best_t:
            best, best_t = blk, dt
    if best is None:
        return candidates[0]  # nothing measured: stay untuned, uncached
    _block_tune_cache[key] = best
    return best


def _use_pallas(l, d) -> bool:
    return jax.default_backend() == "tpu" and _tiles_ok(l, d, 128, 128)


def _to_bhld(x):
    b, l, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, l, d)


def _from_bhld(x, b, h):
    bh, l, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, l, d), 1, 2)


def _as_seed(seed):
    """Normalize to the (1,) int32 SMEM scalar the kernels expect."""
    return jnp.asarray(seed, jnp.int32).reshape(1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=False, scale=None, dropout_p=0.0,
                    seed=None):
    """[B, L, H, D] in/out (paddle flash-attention layout).

    ``dropout_p``/``seed`` give fused attention-probability dropout
    (ref: flash_attn_kernel.cu p_dropout + philox seed/offset): the keep
    mask is generated inside the kernel from (seed, tile position) and
    regenerated identically in the backward kernels, so dropped
    probabilities never touch HBM. ``seed`` may be a python int or a
    traced int scalar (changes per step under one compiled program)."""
    out, _ = _flash_fwd_res(q, k, v, causal, scale, dropout_p, seed)
    return out


def _flash_fwd_res(q, k, v, causal, scale, dropout_p=0.0, seed=None):
    b, l, h, d = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    if dropout_p > 0.0 and seed is None:
        raise ValueError("flash_attention dropout needs a seed")
    if dropout_p >= 1.0:
        raise ValueError(
            "flash_attention dropout_p must be < 1 (p=1 zeroes the "
            "output — handle it at the dropout call site)")
    use_kernel = _use_pallas(l, d)
    count_path("flash_attention", "pallas" if use_kernel else "xla")
    if use_kernel:
        if d % 128 == 0:
            # layout-native kernels: q/k/v/out stay [B, L, H, D] end to
            # end (viewed [B, L, H*D]) — no transpose copies between
            # the projections and the kernel
            blk = _pick_block(l, d, sample=(q, k, v))
            out, lse = _flash_fwd_pallas_blhd(
                q, k, v, causal, s, block_q=blk, block_k=blk,
                dropout_p=float(dropout_p),
                seed=_as_seed(seed) if dropout_p > 0.0 else None)
            return out, (out, lse)
        # d=64 (BERT-class): Mosaic needs the minor block dim % 128, so
        # this path keeps the [B*H, L, D] layout with transposes.
        # Zero-padding d to 128 to ride the layout-native path was
        # measured and LOST (BERT-base MLM 113.0K -> 106.4K tok/s): the
        # pad/slice pairs move 2x the bytes the transposes do, more
        # than the half-lane kernel inefficiency costs.
        qb, kb, vb = _to_bhld(q), _to_bhld(k), _to_bhld(v)
        blk = _pick_block(l, d, sample=(qb, kb, vb))
        out_bhld, lse = _flash_fwd_pallas(
            qb, kb, vb, causal, s, block_q=blk, block_k=blk,
            dropout_p=float(dropout_p),
            seed=_as_seed(seed) if dropout_p > 0.0 else None)
        out = _from_bhld(out_bhld, b, h)
        return out, (out, lse)
    return _sdpa_xla(q, k, v, causal=causal, scale=s,
                     dropout_p=dropout_p, seed=seed), None


def _flash_vjp_fwd(q, k, v, causal, scale, dropout_p, seed):
    out, res = _flash_fwd_res(q, k, v, causal, scale, dropout_p, seed)
    return out, (q, k, v, seed, res)


def _flash_vjp_bwd(causal, scale, dropout_p, residuals, g):
    q, k, v, seed, res = residuals
    b, l, h, d = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    if res is not None:  # pallas path: res = (out [B,L,H,D], lse)
        out, lse = res
        blk = _pick_block(l, d)
        if d % 128 == 0:
            dq, dk, dv = _flash_bwd_pallas_blhd(
                q, k, v, out, lse, g, causal, s, block_q=blk,
                block_k=blk, dropout_p=float(dropout_p),
                seed=_as_seed(seed) if dropout_p > 0.0 else None)
            return dq, dk, dv, None
        dq, dk, dv = _flash_bwd_pallas(
            _to_bhld(q), _to_bhld(k), _to_bhld(v), _to_bhld(out), lse,
            _to_bhld(g), causal, s, block_q=blk, block_k=blk,
            dropout_p=float(dropout_p),
            seed=_as_seed(seed) if dropout_p > 0.0 else None)
        return (_from_bhld(dq, b, h), _from_bhld(dk, b, h),
                _from_bhld(dv, b, h), None)
    # fallback: recompute-based XLA VJP (same seed -> identical mask)
    _, vjp = jax.vjp(
        lambda a, b_, c: _sdpa_xla(a, b_, c, causal=causal, scale=s,
                                   dropout_p=dropout_p, seed=seed),
        q, k, v)
    return vjp(g) + (None,)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention_fwd(q, k, v, causal=False, scale=None, dropout_p=0.0,
                        seed=None):
    """Entry used by nn.functional.attention."""
    return flash_attention(q, k, v, causal, scale, dropout_p, seed)


# ---------------------------------------------------------------------------
# segmented (varlen-packed) flash attention: cu_seqlens -> per-token segment
# ids; kernel tiles mask cross-segment pairs. This is the packing path the
# reference exposes as flash_attn_varlen_qkvpacked
# (ref: python/paddle/nn/functional/flash_attention.py:792).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k"))
def _flash_fwd_pallas_seg(q, k, v, seg, causal, scale, block_q=256,
                          block_k=256):
    """q,k,v: [BH, L, D]; seg: [BH, L, 1] int32 segment ids."""
    bh, seq_len, d = q.shape
    grid = (bh, seq_len // block_q)
    kernel = functools.partial(
        _fwd_kernel, block_q=block_q, block_k=block_k, seq_len=seq_len,
        causal=causal, scale=scale, segmented=True)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_len, 1), jnp.float32),
        ],
    )(q, k, v, seg)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k"))
def _flash_bwd_pallas_seg(q, k, v, out, lse, do, seg, causal, scale,
                          block_q=256, block_k=256):
    bh, seq_len, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, block_q=block_q, block_k=block_k,
            seq_len=seq_len, causal=causal, scale=scale, segmented=True),
        grid=(bh, seq_len // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_len, d), q.dtype),
    )(q, k, v, do, lse, delta, seg)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            seq_len=seq_len, causal=causal, scale=scale, segmented=True),
        grid=(bh, seq_len // block_k),
        in_specs=[
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_len, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_len, 1), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_len, d), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_len, d), v.dtype),
        ],
    )(q, k, v, do, lse, delta, seg)
    return dq, dk, dv


def _sdpa_xla_seg(q, k, v, seg, causal, scale):
    """XLA oracle for segmented attention; seg: [B, L] int32."""
    same = (seg[:, :, None] == seg[:, None, :])  # [B, Lq, Lk]
    mask = jnp.where(same[:, None, :, :], 0.0, _NEG_INF)
    return _sdpa_xla(q, k, v, causal=causal, scale=scale, mask=mask)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def flash_attention_segmented(q, k, v, seg, causal=False, scale=None):
    """[B, L, H, D] + seg [B, L] int32 — attention restricted to equal
    segment ids (varlen packing), composable with causal."""
    out, _ = _flash_seg_fwd_res(q, k, v, seg, causal, scale)
    return out


def _flash_seg_fwd_res(q, k, v, seg, causal, scale):
    b, l, h, d = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    use_kernel = _use_pallas(l, d)
    count_path("flash_attention_segmented",
               "pallas" if use_kernel else "xla")
    if use_kernel:
        blk = _pick_block(l, d)
        seg3 = jnp.repeat(seg[:, None, :], h, axis=1).reshape(b * h, l, 1)
        seg3 = seg3.astype(jnp.int32)
        out_bhld, lse = _flash_fwd_pallas_seg(
            _to_bhld(q), _to_bhld(k), _to_bhld(v), seg3, causal, s,
            block_q=blk, block_k=blk)
        return _from_bhld(out_bhld, b, h), (out_bhld, lse, seg3)
    return _sdpa_xla_seg(q, k, v, seg, causal, s), None


def _flash_seg_vjp_fwd(q, k, v, seg, causal, scale):
    out, res = _flash_seg_fwd_res(q, k, v, seg, causal, scale)
    return out, (q, k, v, seg, res)


def _flash_seg_vjp_bwd(causal, scale, residuals, g):
    q, k, v, seg, res = residuals
    b, l, h, d = q.shape
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    if res is not None:
        out_bhld, lse, seg3 = res
        blk = _pick_block(l, d)
        dq, dk, dv = _flash_bwd_pallas_seg(
            _to_bhld(q), _to_bhld(k), _to_bhld(v), out_bhld, lse,
            _to_bhld(g), seg3, causal, s, block_q=blk, block_k=blk)
        return (_from_bhld(dq, b, h), _from_bhld(dk, b, h),
                _from_bhld(dv, b, h), None)
    _, vjp = jax.vjp(
        lambda a, b_, c: _sdpa_xla_seg(a, b_, c, seg, causal, s), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


flash_attention_segmented.defvjp(_flash_seg_vjp_fwd, _flash_seg_vjp_bwd)


# analysis-plane aval registration (ops.yaml `fusable: attention` +
# `shape: attention`): the eager fusion DAG never defers attention —
# try_fuse returns None for the class — but the capture planner's
# abstract interpreter grades its `shape:` spec against these REAL
# entry points via jax.eval_shape (core.fusion.infer_output_aval), so
# the declared arithmetic can't drift from what actually runs.
def _register_aval_impls() -> None:
    from ...core.fusion import register_param_impl
    register_param_impl("flash_attention", flash_attention)
    register_param_impl("flash_attention_segmented",
                        flash_attention_segmented)


_register_aval_impls()
