"""An expert layer that holds a SHARE of its experts: the serving-side
mixture of experts of a model whose layer is divided over several chips
(expert parallelism), as one of those chips runs it.

The layer is told which experts ``[lo, hi)`` of ``n`` it holds. It routes
every row over all ``n`` (the router keeps its published width and its
experts per token), computes its own experts' part of the result for the
rows routed to them, and returns that partial sum: what the absent
experts would add is added on the chips that hold them. There is no
exchange here and nothing stands in for the other chips.

Routing is dropless: there is no capacity. Shapes are static all the
same: the ``T x k`` (row, expert) pairs are sorted by held expert into a
buffer sized for the worst case (every pair on a held expert), each
expert's group padded to whole row tiles; pairs routed to absent experts
are left out. The expert matmuls go through
``ops.pallas.grouped_matmul.expert_rows_matmul``, which skips the dead
tiles; the result is gathered back per pair and weighted.

ref: the reference's MoE layer dispatches with global_scatter/global_gather
and a CUTLASS grouped GEMM (fused_moe_kernel.cu); ``incubate/moe.py`` and
``moe_dispatch.py`` are its capacity-bounded (token-dropping) training
form with softmax gates.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.grouped_matmul import expert_rows_matmul

__all__ = ["sigmoid_topk_route", "row_tile", "held_experts_forward"]

_HI = jax.lax.Precision.HIGHEST


def sigmoid_topk_route(x, w_router, top_k: int, normalize: bool = True,
                       bias=None, scale: float = 1.0):
    """Float32 sigmoid router over ALL experts. ``x [T, H]``, ``w_router
    [n_experts, H]``. Returns ``(idx [T, k] int32, weight [T, k]
    float32)``: the ``k`` largest scores of each row and, ``normalize``,
    each over the sum of the row's ``k``. With ``bias [n_experts]`` (a
    score-correction bias) the choice is by ``score + bias`` and the
    weight still the score itself; ``scale`` multiplies the weights last
    (a routed scaling factor). Float32 at the highest matmul precision
    whatever the activations' dtype: a top-k choice near a tie must not
    turn on bfloat16 rounding."""
    scores = jax.nn.sigmoid(jnp.einsum(
        "th,eh->te", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=_HI, preferred_element_type=jnp.float32))
    if bias is None:
        weight, idx = jax.lax.top_k(scores, int(top_k))
    else:
        _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), int(top_k))
        weight = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if scale != 1.0:
        weight = weight * scale
    return idx.astype(jnp.int32), weight


def row_tile(rows: int, top_k: int, n_experts: int) -> int:
    """Rows of one tile of the sorted buffer for a launch of ``rows``
    rows: twice what an expert expects (``rows * top_k / n_experts``),
    as a power of two from 16 (a bfloat16 sublane tile) to 128 (the
    MXU's side), so that an expert's rows mostly make one tile."""
    want = max(2 * rows * top_k // max(n_experts, 1), 1)
    tile = 16
    while tile < want and tile < 128:
        tile *= 2
    return tile


def held_experts_forward(x, idx, weight, gate_up, down,
                         held: Tuple[int, int], block_t: int,
                         use_kernel=None, interpret: bool = False):
    """The held experts' part of the routed sum.

    ``x [T, H]`` rows, ``idx``/``weight [T, k]`` from the router (over all
    experts), ``gate_up [E_held, H, 2 I]`` (gate columns then up columns)
    and ``down [E_held, I, H]`` the held experts ``[lo, hi) = held``.
    Returns ``(y [T, H], counts int32 [3])``: ``y[t] = sum over the k
    choices of row t that are held of weight * E_e(x[t])``, ``E(x) =
    (silu(x W_g) * (x W_u)) W_d``; counts = (pairs that landed on held
    experts, held experts with at least one row, rows of the fullest)."""
    lo, hi = int(held[0]), int(held[1])
    n_held = hi - lo
    t, k = idx.shape
    pairs = t * k
    inter = down.shape[1]
    local = idx.reshape(pairs) - lo
    is_held = (local >= 0) & (local < n_held)
    local = jnp.where(is_held, local, n_held)          # absent: past the end
    onehot = (local[:, None] == jnp.arange(n_held)[None, :])     # [P, E]
    rank = jnp.cumsum(onehot, axis=0) - 1              # order within a group
    counts = jnp.sum(onehot, axis=0).astype(jnp.int32)            # [E]
    padded = -(-counts // block_t) * block_t
    ends = jnp.cumsum(padded)
    starts = ends - padded
    # worst case: every pair on a held expert, every group's tail padded
    n_tiles = -(-t * min(k, n_held) // block_t) + n_held
    m = n_tiles * block_t
    my_rank = jnp.sum(jnp.where(onehot, rank, 0), axis=1)
    my_start = jnp.sum(jnp.where(onehot, starts[None, :], 0), axis=1)
    dest = jnp.where(is_held, my_start + my_rank, m)   # absent: dropped
    token = jnp.arange(pairs, dtype=jnp.int32) // k
    src = jnp.full((m,), t, jnp.int32).at[dest].set(token, mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    rows = x_pad[src]                                   # [M, H]; padding 0
    n_live = (ends[-1] // block_t).astype(jnp.int32)
    tile_ids = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(n_tiles) * block_t, side="right"),
        n_held - 1).astype(jnp.int32)
    gu = expert_rows_matmul(rows, gate_up, tile_ids, n_live, block_t,
                            use_kernel=use_kernel, interpret=interpret)
    act = (jax.nn.silu(gu[:, :inter].astype(jnp.float32))
           * gu[:, inter:].astype(jnp.float32)).astype(x.dtype)
    out = expert_rows_matmul(act, down, tile_ids, n_live, block_t,
                             use_kernel=use_kernel, interpret=interpret)
    picked = out[jnp.minimum(dest, m - 1)].reshape(t, k, -1)
    w = jnp.where(is_held.reshape(t, k), weight, 0.0)
    y = jnp.einsum("tkh,tk->th", picked.astype(jnp.float32), w,
                   precision=_HI)
    stats = jnp.stack([jnp.sum(counts), jnp.sum(counts > 0),
                       jnp.max(counts)]).astype(jnp.int32)
    return y.astype(x.dtype), stats
