"""Attention functionals.

ref: python/paddle/nn/functional/flash_attention.py (flash_attention,
scaled_dot_product_attention). On TPU the fused path is the Pallas flash
kernel (paddle_tpu.ops.pallas.flash_attention); the reference implementation
here is plain jnp, used on CPU and as the numeric oracle in tests.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ...core.autograd import apply_op
from ...core.tensor import Tensor
from ...core import random as random_mod


def _sdpa_reference(q, k, v, mask=None, causal=False, scale=None,
                    dropout_p=0.0, dropout_key=None):
    """Thin delegate to the single sdpa oracle in ops.pallas.flash_attention
    (one copy of the softmax+dropout algebra to keep in sync)."""
    from ...ops.pallas.flash_attention import _sdpa_xla
    m = mask.astype(jnp.float32) if mask is not None else None
    return _sdpa_xla(q, k, v, causal=causal, scale=scale, mask=m,
                     dropout_p=dropout_p, dropout_key=dropout_key)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Layout [batch, seq, heads, head_dim], matching the reference API."""
    md = attn_mask._data if isinstance(attn_mask, Tensor) else attn_mask
    drop = dropout_p if training else 0.0

    if _should_use_flash(query) and md is None and drop < 1.0:
        from ...ops.pallas.flash_attention import flash_attention_fwd
        if drop > 0.0:
            # the key rides as a marked arg (same contract as F.dropout)
            # so static Program replay refills a FRESH key per run — a
            # closure-captured seed would freeze the mask across runs.
            # Under jit the key is traced off the step key per step.
            from .common import _rng_key_tensor
            key_t = _rng_key_tensor()

            def f(q, k, v, rng_key):
                return flash_attention_fwd(
                    q, k, v, causal=is_causal, dropout_p=float(drop),
                    seed=random_mod.derive_seed(rng_key))
            return apply_op(f, query, key, value, key_t,
                            op_name="flash_attention")
        return apply_op(
            lambda q, k, v: flash_attention_fwd(q, k, v, causal=is_causal),
            query, key, value, op_name="flash_attention")

    if drop > 0.0:
        # same marked-arg contract as the flash path: a closure-captured
        # key would freeze the dropout mask across compiled steps and
        # static replays
        from .common import _rng_key_tensor
        key_t = _rng_key_tensor()

        def f_drop(q, k, v, rng_key):
            return _sdpa_reference(q, k, v, mask=md, causal=is_causal,
                                   dropout_p=drop, dropout_key=rng_key)
        return apply_op(f_drop, query, key, value, key_t, op_name="sdpa")

    def f(q, k, v):
        return _sdpa_reference(q, k, v, mask=md, causal=is_causal)
    return apply_op(f, query, key, value, op_name="sdpa")


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """ref: nn/functional/flash_attention.py flash_attention — same
    signature; returns (out, softmax-or-None) tuple for parity."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return out, None


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, fixed_seed_offset=None,
                         rng_name="", training=True, name=None):
    """ref: nn/functional/flash_attention.py flash_attn_qkvpacked —
    qkv [B, L, 3, H, D]."""
    def f(p):
        return p[:, :, 0], p[:, :, 1], p[:, :, 2]
    q, k, v = apply_op(f, qkv, op_name="qkv_unpack")
    out, sm = flash_attention(q, k, v, dropout=dropout, causal=causal,
                              return_softmax=return_softmax,
                              training=training)
    return out, sm


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q, max_seqlen_k, scale,
                                dropout=0.0, causal=False,
                                return_softmax=False,
                                fixed_seed_offset=None, rng_name="",
                                varlen_padded=True, training=True,
                                name=None):
    """Varlen packed attention: sequences packed along dim 0, delimited by
    cu_seqlens; attention never crosses a sequence boundary.

    ref: python/paddle/nn/functional/flash_attention.py:792. TPU-native:
    cu_seqlens become per-token segment ids fed to the segment-masked
    Pallas flash kernel (paddle_tpu.ops.pallas.flash_attention,
    flash_attention_segmented) — tiles where seg_q != seg_k contribute
    nothing, so packing costs no extra FLOPs materialization.
    qkv: [total_tokens, 3, H, D]; returns [total_tokens, H, D].

    For packed qkv the q and k boundaries coincide, so segment ids derive
    from cu_seqlens_q alone; max_seqlen_q/k and varlen_padded are accepted
    for signature parity but unused (the segment mask makes them moot).
    A cu_seqlens_k that differs from cu_seqlens_q is rejected — silently
    masking with q boundaries would be wrong for that caller.
    """
    from ...ops.pallas.flash_attention import flash_attention_segmented

    if cu_seqlens_k is not None and cu_seqlens_k is not cu_seqlens_q:
        import jax as _jax
        import numpy as _np
        cq = (cu_seqlens_q._data if hasattr(cu_seqlens_q, "_data")
              else cu_seqlens_q)
        ck = (cu_seqlens_k._data if hasattr(cu_seqlens_k, "_data")
              else cu_seqlens_k)
        # traced values can't be compared on the host — trust the caller
        # under jit (eager use, the common path, is still validated)
        if not (isinstance(cq, _jax.core.Tracer)
                or isinstance(ck, _jax.core.Tracer)):
            cq, ck = _np.asarray(cq), _np.asarray(ck)
            if cq.shape != ck.shape or (cq != ck).any():
                raise ValueError(
                    "flash_attn_varlen_qkvpacked: cu_seqlens_k differs "
                    "from cu_seqlens_q, but packed qkv shares one set of "
                    "sequence boundaries — masking would be wrong. Use "
                    "the unpacked varlen API for cross-attention layouts.")

    def f(p, cu_arr):
        total = p.shape[0]
        # segment id per token: number of boundaries at or before it
        seg = jnp.searchsorted(cu_arr[1:], jnp.arange(total), side="right")
        q, k, v = p[:, 0], p[:, 1], p[:, 2]     # [total, H, D]
        out = flash_attention_segmented(
            q[None], k[None], v[None], seg[None].astype(jnp.int32),
            causal, scale)
        return out[0]

    out = apply_op(f, qkv, cu_seqlens_q, op_name="flash_attn_varlen")
    return out, None


def flashmask_attention(query, key, value, startend_row_indices,
                        dropout=0.0, causal=False, window_size=None,
                        return_softmax_lse=False, return_seed_offset=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """FlashMask: column-wise sparse mask representation.

    ref: python/paddle/nn/functional/flash_attention.py:1098
    flashmask_attention. startend_row_indices [B, H|1, Lk, C]:
      C=1 (causal): rows >= LTS masked;
      C=2 (causal): rows in [LTS, LTE) masked;
      C=2 (non-causal): rows >= LTS and rows < UTE masked;
      C=4: rows in [LTS, LTE) or [UTS, UTE) masked.
    TPU-native fallback expands the column encoding to an additive mask
    under jit (XLA fuses it into the attention); the Pallas tile-skip
    path is future work tracked with the sparse-attention kernel.
    """
    def f(q, k, v, se):
        lq, lk = q.shape[1], k.shape[1]
        rows = jnp.arange(lq).reshape(1, 1, lq, 1)   # i (query/row)
        cols = jnp.arange(lk).reshape(1, 1, 1, lk)   # j (key/col)
        se = se.astype(jnp.int32)                     # [B, H1, Lk, C]
        c = se.shape[-1]
        lts = se[..., 0][:, :, None, :]               # [B, H1, 1, Lk]
        if causal:
            if c == 1:
                masked = rows >= lts
            elif c == 2:
                lte = se[..., 1][:, :, None, :]
                masked = (rows >= lts) & (rows < lte)
            else:
                raise ValueError(
                    f"causal flashmask expects 1 or 2 columns, got {c}")
        else:
            if c == 2:
                ute = se[..., 1][:, :, None, :]
                masked = (rows >= lts) | (rows < ute)
            elif c == 4:
                lte = se[..., 1][:, :, None, :]
                uts = se[..., 2][:, :, None, :]
                ute = se[..., 3][:, :, None, :]
                masked = ((rows >= lts) & (rows < lte)) | \
                         ((rows >= uts) & (rows < ute))
            else:
                raise ValueError(
                    f"non-causal flashmask expects 2 or 4 columns, got {c}")
        if window_size is not None:
            # sliding window (left, right): only keys within
            # [i - left, i + right] may attend
            left, right = (window_size if isinstance(window_size,
                                                     (tuple, list))
                           else (window_size, window_size))
            masked = masked | (cols < rows - int(left)) | \
                (cols > rows + int(right))
        mask = jnp.where(masked, -1e30, 0.0).astype(jnp.float32)
        return _sdpa_reference(q, k, v, mask=mask, causal=causal)

    out = apply_op(f, query, key, value, startend_row_indices,
                   op_name="flashmask_attention")
    if return_softmax_lse or return_seed_offset:
        extras = [None] * (int(return_softmax_lse) +
                           int(return_seed_offset))
        return (out, *extras)
    return out


def _should_use_flash(q) -> bool:
    """True when the attention should route to the Pallas flash kernel.
    Traced values (inside jit/TrainStep) carry no devices — fall back to
    the default backend, NOT False: a compiled step on TPU must still
    take the fused path (this was exactly the BERT slow-path bug)."""
    import jax as _jax
    data = q._data if isinstance(q, Tensor) else q
    try:
        plats = {d.platform for d in data.devices()}
    except Exception:
        plats = set()
    if not plats:
        plats = {_jax.default_backend()}
    return "tpu" in plats
