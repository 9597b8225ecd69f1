"""Seeded weights of the GLM-MoE-DSA decoder, made on the device, a leaf at a
time: leaf `i` of `leaf_specs` is drawn from `fold_in(key(seed), i)` exactly as
`lib.weights` draws a dense decoder's (the same integer bell, the same two
scalings), so the program under test and the reference (which regenerates a
layer at a time and imports nothing of the program) hold the same bits.

Leaves are laid out as the published checkpoints store them: a projection
`[out, in]`, the routed experts stacked `[experts held, H, 2 I]` (gate columns,
then up columns) and `[experts held, I, H]`. Only the experts this chip holds
are made (`n_routed_experts` here, `n_routed_experts_published` the router's
width). A layer has the leaves its kinds ask for: the indexer's five on a
`full` layer of `indexer_types`, a dense SwiGLU or the router, its score bias,
the held experts and the shared expert by `mlp_layer_types`.

The indexer's LayerNorm BIAS is a gain's draw less one: centred on 0 with
standard deviation 0.0496, so that a dropped bias shows.

The router's `e_score_correction_bias` is drawn so that EVERY SEED GIVES THIS
CHIP THE SAME WORK. A bias of 0.05 moves an expert's share of the rows by a
factor of three (the 8th of 256 sigmoid scores lies where a score's density is
thin), so 256 independent draws gave the 16 held experts 0.34 to 0.86 of a row's
8 choices a layer and 5.8 to 10.8 experts hit a decode step by the seed alone,
and the cell's tokens/s followed (PERF.md, PR 33). So every group of
`n_routed_experts` experts (one chip's share) gets the same values, the bell's
mid-quantiles (`router_bias_grid`: 16 values, standard deviation 0.048), and the
seed draws their ORDER within each group: which expert is favoured changes with
the seed, how many rows the held share draws does not. The bias still decides
choices (the 8th and 9th of 256 scores lie a few thousandths apart).
"""
from __future__ import annotations

from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import (_BYTES_STD, NORM_SHIFT, NORM_STEP, _key,
                                   _leaf, seed_u32)  # noqa: F401

ATTN = ("in_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o",
        "post_norm")
INDEX = ("index_q_b", "index_k", "index_k_norm", "index_k_bias", "index_w")
DENSE = ("gate", "up", "down")
SPARSE = ("router", "router_bias", "experts_gate_up", "experts_down",
          "shared_gate", "shared_up", "shared_down")


def experts_held(cfg) -> tuple:
    lo = int(cfg.get("experts_held_from", 0))
    return lo, lo + int(cfg["n_routed_experts"])


def layer_leaves(cfg, i: int) -> tuple:
    return (ATTN + (INDEX if cfg["indexer_types"][i] == "full" else ())
            + (DENSE if cfg["mlp_layer_types"][i] == "dense" else SPARSE))


def layer_shapes(cfg, i: int) -> dict:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv, rope = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    j, d = cfg["index_n_heads"], cfg["index_head_dim"]
    dense, inter = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, s = cfg["n_routed_experts"], cfg["n_shared_experts"] * inter
    every = {"in_norm": (h,), "q_a": (rq, h), "q_a_norm": (rq,),
             "q_b": (nh * (nope + rope), rq), "kv_a": (rkv + rope, h),
             "kv_a_norm": (rkv,), "kv_b": (nh * (nope + vd), rkv),
             "o": (h, nh * vd), "post_norm": (h,),
             "index_q_b": (j * d, rq), "index_k": (d, h), "index_k_norm": (d,),
             "index_k_bias": (d,), "index_w": (j, h),
             "gate": (dense, h), "up": (dense, h), "down": (h, dense),
             "router": (cfg["n_routed_experts_published"], h),
             "router_bias": (cfg["n_routed_experts_published"],),
             "experts_gate_up": (held, h, 2 * inter),
             "experts_down": (held, inter, h),
             "shared_gate": (s, h), "shared_up": (s, h), "shared_down": (h, s)}
    return {n: every[n] for n in layer_leaves(cfg, i)}


def leaf_specs(cfg) -> list:
    """[(name, shape)] of every leaf, in the order of their indices."""
    out = [("embed", (cfg["vocab_size"], cfg["hidden_size"]))]
    for i in range(cfg["num_hidden_layers"]):
        ls = layer_shapes(cfg, i)
        out += [(f"layers.{i}.{n}", ls[n]) for n in layer_leaves(cfg, i)]
    return out + [("final_norm", (cfg["hidden_size"],)),
                  ("head", (cfg["vocab_size"], cfg["hidden_size"]))]


def router_bias_grid(group: int) -> np.ndarray:
    """The `group` values a chip's share of the router's score bias takes, in
    the integer steps of a gain's draw: the mid-quantiles of that draw's bell
    (host arithmetic, so every program holds the same integers)."""
    bell = NormalDist(0.0, _BYTES_STD)
    return np.asarray([round(bell.inv_cdf((i + 0.5) / group))
                       for i in range(group)], np.int32)


def _router_bias(key, index, n: int, group: int, dtype):
    """`n` score biases: `router_bias_grid(group)` in every group of `group`
    experts, in an order drawn from the seed (integers until one exact scaling,
    as `_leaf`)."""
    bits = jax.random.bits(jax.random.fold_in(key, index), (n // group, group),
                           jnp.uint32)
    s = jnp.asarray(router_bias_grid(group))[jnp.argsort(bits, axis=-1)]
    return ((NORM_STEP * s).astype(jnp.float32).reshape(n)
            * 2.0 ** -NORM_SHIFT).astype(dtype)


def make_leaf(cfg, dtype):
    """(seed, leaf index) -> that leaf, one jitted program a shape and kind."""
    specs = leaf_specs(cfg)
    group = int(cfg["n_routed_experts"])
    if cfg["n_routed_experts_published"] % group:
        raise ValueError("the router's width is not a whole number of shares")
    programs = {}

    def f(seed, index: int):
        name, shape = specs[index]
        kind = name.rsplit(".", 1)[-1] if name.endswith("bias") else ""
        if (shape, kind) not in programs:
            def draw(s, i, shape=shape, kind=kind):
                if kind == "router_bias":
                    return _router_bias(_key(s), i, shape[0], group, dtype)
                leaf = _leaf(_key(s), i, shape, dtype)
                return (leaf - jnp.ones((), dtype)).astype(dtype) if kind else leaf
            programs[shape, kind] = jax.jit(draw)
        return programs[shape, kind](seed, index)
    return f


def make_layer(cfg, dtype):
    """(seed, layer index) -> {leaf: array} of one layer."""
    leaf = make_leaf(cfg, dtype)
    first = [1]
    for i in range(cfg["num_hidden_layers"]):
        first.append(first[-1] + len(layer_leaves(cfg, i)))

    def f(seed, layer: int):
        return {n: leaf(seed, first[layer] + j)
                for j, n in enumerate(layer_leaves(cfg, int(layer)))}
    return f


def make_ends(cfg, dtype):
    """seed -> (embed, final_norm, head)."""
    leaf = make_leaf(cfg, dtype)
    n = len(leaf_specs(cfg))
    return lambda seed: (leaf(seed, 0), leaf(seed, n - 2), leaf(seed, n - 1))
