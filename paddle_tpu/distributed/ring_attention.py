"""Ring attention: context parallelism over the ICI ring.

ABSENT in the reference (SURVEY.md §2.2 flags no ring/Ulysses/blockwise CP
in the snapshot — its long-context story stops at flash attention + Megatron
SP). This is the TPU-native fill: sequence-sharded Q/K/V, with K/V blocks
rotated around the mesh axis via jax.lax.ppermute while each device
accumulates its queries' online softmax — compute and ICI transfer overlap,
memory per chip stays O(L/n), total sequence scales with the ring size.

Layout [B, L, H, D], L sharded on the `axis` mesh dim. Causality is
enforced with global position ids, so the result is bit-for-bit the same
math as full causal attention over the unsharded sequence.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ring_self_attention"]

_NEG = -1e30


def _ring_attn_local(q, k, v, axis: str, scale: float, causal: bool):
    """Runs inside shard_map: q/k/v are the local sequence shards."""
    n = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    b, lq, h, d = q.shape
    lk = k.shape[1]

    qf = q.astype(jnp.float32) * scale
    q_pos = idx * lq + jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        # the kv block this device holds at step i originated on rank idx-i
        src = (idx - i) % n
        logits = jnp.einsum("blhd,bkhd->bhlk", qf,
                            k_cur.astype(jnp.float32))
        if causal:
            k_pos = src * lk + jax.lax.broadcasted_iota(
                jnp.int32, (lq, lk), 1)
            keep = (q_pos >= k_pos)[None, None]
            logits = jnp.where(keep, logits, _NEG)
        m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
        # guard: a fully-masked block must contribute zero probability even
        # when m_new is still the -inf sentinel
        p = jnp.where(logits > _NEG / 2, jnp.exp(logits - m_new), 0.0)
        alpha = jnp.exp(jnp.maximum(m, _NEG) -
                        jnp.maximum(m_new, _NEG))
        alpha = jnp.where(m > _NEG / 2, alpha, 0.0)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_new = alpha * acc + jnp.einsum(
            "bhlk,bkhd->bhld", p, v_cur.astype(jnp.float32))
        # rotate kv one hop around the ring for the next step
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis, perm)
        return k_nxt, v_nxt, m_new, l_new, acc_new

    m0 = jnp.full((b, h, lq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, lq, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, lq, d), jnp.float32)
    _, _, m, l, acc = jax.lax.fori_loop(
        0, n, step, (k, v, m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)  # [B, Lq, H, D]


def ring_attention(q, k, v, mesh, axis: str = "sp", causal: bool = True,
                   scale: Optional[float] = None):
    """q/k/v: [B, L, H, D] jax arrays (or already seq-sharded on `axis`).
    Returns attention output with the same sharding. Other mesh axes may
    shard batch/heads; they pass through untouched.
    """
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    jmesh = mesh.to_jax_mesh() if hasattr(mesh, "to_jax_mesh") else mesh
    # full-manual shard_map: map the other mesh axes onto their
    # conventional dims (data axes -> batch, model axes -> heads) so dp/tp
    # shardings ride through instead of being all-gathered per device
    from jax import shard_map

    from ._mesh_axes import classify_axes
    batch_axes, head_axes = classify_axes(jmesh, axis)
    spec = P(batch_axes or None, axis, head_axes or None, None)
    fn = shard_map(
        functools.partial(_ring_attn_local, axis=axis, scale=s,
                          causal=causal),
        mesh=jmesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ring_self_attention(q, k, v, mesh, axis: str = "sp", causal: bool = True,
                        scale: Optional[float] = None):
    """Tensor-level wrapper recording one autograd node (eager API)."""
    from ..core.autograd import apply_op
    return apply_op(
        lambda a, b, c: ring_attention(a, b, c, mesh, axis, causal, scale),
        q, k, v, op_name="ring_attention")


# analysis-plane aval registration (the flash_attention pattern, see
# ops/pallas/flash_attention.py): ring attention computes EXACT causal
# attention — the ring is a memory/comm schedule, not a different
# function — so its aval reference is the sdpa oracle cast back to the
# query dtype, exactly what the sharded entry point returns.
def _ring_attention_aval_ref(q, k, v):
    from ..ops.pallas.flash_attention import _sdpa_xla
    return _sdpa_xla(q, k, v, causal=True).astype(q.dtype)


def _register_aval_impls() -> None:
    from ..core.fusion import register_param_impl
    register_param_impl("ring_attention", _ring_attention_aval_ref)


_register_aval_impls()
