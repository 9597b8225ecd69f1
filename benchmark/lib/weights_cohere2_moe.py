"""Seeded weights of the Cohere2-MoE decoder, made on the device, a leaf at a
time: leaf `i` of `leaf_specs` is drawn from `fold_in(key(seed), i)` exactly as
`lib.weights` draws a dense decoder's (the same integer bell, the same two
scalings), so the program under test and the reference (which regenerates a
layer at a time and imports nothing of the program) hold the same bits.

Leaves are laid out as the published checkpoints store them: a projection
`[out, in]`, the routed experts stacked `[experts held, H, 2 I]` (gate columns,
then up columns) and `[experts held, I, H]`, the shared experts side by side
`[S I, H]`, `[S I, H]`, `[H, S I]`. Only the experts this chip holds are made:
the share is part of the configuration (`num_experts` here, `num_experts_published`
the router's width).
"""
from __future__ import annotations

import jax

from benchmark.lib.weights import _key, _leaf, seed_u32  # noqa: F401

LAYER_LEAVES = ("norm", "q", "k", "v", "o", "router", "experts_gate_up",
                "experts_down", "shared_gate", "shared_up", "shared_down")


def experts_held(cfg) -> tuple:
    lo = int(cfg.get("experts_held_from", 0))
    return lo, lo + int(cfg["num_experts"])


def layer_shapes(cfg) -> dict:
    h, d, i = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s, held = cfg["num_shared_experts"], cfg["num_experts"]
    return {"norm": (h,), "q": (nh * d, h), "k": (kvh * d, h), "v": (kvh * d, h),
            "o": (h, nh * d), "router": (cfg["num_experts_published"], h),
            "experts_gate_up": (held, h, 2 * i), "experts_down": (held, i, h),
            "shared_gate": (s * i, h), "shared_up": (s * i, h),
            "shared_down": (h, s * i)}


def leaf_specs(cfg) -> list:
    """[(name, shape)] of every leaf, in the order of their indices."""
    ls = layer_shapes(cfg)
    out = [("embed", (cfg["vocab_size"], cfg["hidden_size"]))]
    for i in range(cfg["num_hidden_layers"]):
        out += [(f"layers.{i}.{n}", ls[n]) for n in LAYER_LEAVES]
    return out + [("final_norm", (cfg["hidden_size"],))]


def make_leaf(cfg, dtype):
    """(seed, leaf index) -> that leaf, one jitted program a shape."""
    specs = leaf_specs(cfg)
    by_shape = {}

    def f(seed, index: int):
        shape = specs[index][1]
        if shape not in by_shape:
            by_shape[shape] = jax.jit(
                lambda s, i, shape=shape: _leaf(_key(s), i, shape, dtype))
        return by_shape[shape](seed, index)
    return f


def make_layer(cfg, dtype):
    """(seed, layer index) -> {leaf: array} of one layer."""
    leaf = make_leaf(cfg, dtype)

    def f(seed, layer: int):
        base = 1 + int(layer) * len(LAYER_LEAVES)
        return {n: leaf(seed, base + j) for j, n in enumerate(LAYER_LEAVES)}
    return f


def make_ends(cfg, dtype):
    """seed -> (embed, final_norm)."""
    leaf = make_leaf(cfg, dtype)
    last = 1 + cfg["num_hidden_layers"] * len(LAYER_LEAVES)
    return lambda seed: (leaf(seed, 0), leaf(seed, last))
