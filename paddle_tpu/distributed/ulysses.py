"""Ulysses (DeepSpeed-style) all-to-all sequence-parallel attention.

ABSENT in the reference (SURVEY.md §2.2: no Ulysses all-to-all attention
in the snapshot) — the second TPU-native context-parallel fill alongside
ring_attention. Instead of rotating K/V around the ring, ONE all-to-all
re-shards activations from sequence-sharded [B, L/n, H, D] to
head-sharded [B, L, H/n, D]; each device then runs ordinary (flash)
attention over the FULL sequence for its head subset; a second all-to-all
restores sequence sharding. Two collectives per layer, so it wins over
ring attention when heads >> mesh axis and per-hop latency dominates.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ulysses_attention", "ulysses_self_attention"]


def _ulysses_local(q, k, v, axis: str, scale: float, causal: bool):
    """Runs inside shard_map with seq-sharded inputs [B, l=L/n, H, D]."""
    from ..ops.pallas.flash_attention import flash_attention

    def seq2head(x):
        # [B, l, H, D] -> [B, L, H/n, D]: scatter head chunks across the
        # axis, gather the sequence shards (rank order = sequence order)
        return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head2seq(x):
        # [B, L, H/n, D] -> [B, l, H, D]: the inverse all-to-all
        return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq2head(q), seq2head(k), seq2head(v)
    # flash path: Pallas kernel on TPU, XLA sdpa fallback elsewhere — the
    # full-sequence O(L) memory profile is the point of Ulysses
    out = flash_attention(qh, kh, vh, causal=causal, scale=scale)
    return head2seq(out)


def ulysses_attention(q, k, v, mesh, axis: str = "sp",
                      causal: bool = True,
                      scale: Optional[float] = None):
    """q/k/v: [B, L, H, D] (global view), L sharded on `axis`; H must be
    divisible by the axis size. Same contract as ring_attention."""
    d = q.shape[-1]
    h = q.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    jmesh = mesh.to_jax_mesh() if hasattr(mesh, "to_jax_mesh") else mesh
    sizes = dict(zip(jmesh.axis_names, jmesh.devices.shape))
    n = sizes[axis]
    from jax import shard_map

    from ._mesh_axes import classify_axes
    batch_axes, head_axes = classify_axes(jmesh, axis)
    mp = 1
    for a in head_axes:
        mp *= sizes[a]
    if (h // mp) % n != 0:
        raise ValueError(
            f"the '{axis}' axis size {n} must divide the per-shard head "
            f"count {h}//{mp}={h // mp} (Ulysses scatters heads across "
            f"the sequence axis during attention)")
    spec = P(batch_axes or None, axis, head_axes or None, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis=axis, scale=s,
                          causal=causal),
        mesh=jmesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ulysses_self_attention(q, k, v, mesh, axis: str = "sp",
                           causal: bool = True,
                           scale: Optional[float] = None):
    """Tensor-level wrapper recording one autograd node (eager API)."""
    from ..core.autograd import apply_op
    return apply_op(
        lambda a, b, c: ulysses_attention(a, b, c, mesh, axis, causal,
                                          scale),
        q, k, v, op_name="ulysses_attention")
