"""Seeded weights of the Brumby decoder, made on the device, a leaf at a time:
leaf `i` of `leaf_specs` is drawn from `fold_in(key(seed), i)` exactly as
`lib.weights` draws a dense decoder's (the same integer bell, the same two
scalings), so the program under test and the reference (which regenerates a
layer at a time and imports nothing of the program) hold the same bits.

Leaves are laid out as checkpoints store them: a projection `[out, in]`. Two
kinds of leaf are not the bell:

- the gate `W_g` (hidden -> KV heads) is the bell times 2**-16 (standard
  deviation 0.0023): the part of the log decay that follows the token spreads
  by about 0.16 in the logit;
- its bias `b_g` holds the logits of `1 - 1 / L` for eight spans `L` spread
  log-evenly over 10 .. 10,000 tokens (the mid-quantiles), one a KV head, in an
  ORDER the seed draws (host arithmetic, so every program holds the same bits):
  a token's decay `sigmoid(W_g x + b_g)` then lies between 0.92 and 0.9999, the
  range a trained model's takes over a 32k context, and every seed gives the
  same multiset of decays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import MATRIX_SHIFT, _key, _leaf, seed_u32  # noqa: F401
from benchmark.lib.weights_solar_open2 import _permuted

LAYER = ("in_norm", "q", "k", "v", "o", "q_norm", "k_norm", "g", "g_bias",
         "post_norm", "gate", "up", "down")
GATE_SHIFT = 16         # W_g: s * 2**-16


def layer_shapes(cfg) -> dict:
    h, inter, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"in_norm": (h,), "q": (nh * d, h), "k": (kvh * d, h),
            "v": (kvh * d, h), "o": (h, nh * d), "q_norm": (d,),
            "k_norm": (d,), "g": (kvh, h), "g_bias": (kvh,),
            "post_norm": (h,), "gate": (inter, h), "up": (inter, h),
            "down": (h, inter)}


def leaf_specs(cfg) -> list:
    """[(name, shape)] of every leaf, in the order of their indices."""
    ls = layer_shapes(cfg)
    out = [("embed", (cfg["vocab_size"], cfg["hidden_size"]))]
    for i in range(cfg["num_hidden_layers"]):
        out += [(f"layers.{i}.{n}", ls[n]) for n in LAYER]
    return out + [("final_norm", (cfg["hidden_size"],)),
                  ("head", (cfg["vocab_size"], cfg["hidden_size"]))]


def gate_bias_grid(n: int) -> np.ndarray:
    """The `n` values a `g_bias` leaf takes (float64, host): the logits of
    `1 - 1 / L` for spans `L` at the mid-quantiles of log-uniform 10 .. 1e4."""
    span = 10.0 ** (1.0 + 3.0 * (np.arange(n) + 0.5) / n)
    return np.log(span - 1.0)


def make_leaf(cfg, dtype):
    """(seed, leaf index) -> that leaf, one jitted program a shape and kind."""
    specs = leaf_specs(cfg)
    programs = {}

    def f(seed, index: int):
        name, shape = specs[index]
        kind = name.rsplit(".", 1)[-1] if name.endswith((".g", ".g_bias")) \
            else ""
        if (shape, kind) not in programs:
            def draw(s, i, shape=shape, kind=kind):
                if kind == "g_bias":
                    return _permuted(_key(s), i, gate_bias_grid(shape[0]),
                                     dtype)
                leaf = _leaf(_key(s), i, shape, jnp.float32)
                if kind == "g":
                    leaf = leaf * 2.0 ** (MATRIX_SHIFT - GATE_SHIFT)
                return leaf.astype(dtype)
            programs[shape, kind] = jax.jit(draw)
        return programs[shape, kind](seed, index)
    return f


def make_layer(cfg, dtype):
    """(seed, layer index) -> {leaf: array} of one layer."""
    leaf = make_leaf(cfg, dtype)

    def f(seed, layer: int):
        base = 1 + int(layer) * len(LAYER)
        return {n: leaf(seed, base + j) for j, n in enumerate(LAYER)}
    return f


def make_ends(cfg, dtype):
    """seed -> (embed, final_norm, head)."""
    leaf = make_leaf(cfg, dtype)
    n = len(leaf_specs(cfg))
    return lambda seed: (leaf(seed, 0), leaf(seed, n - 2), leaf(seed, n - 1))
