"""Seeded weights of the Solar Open 2 decoder, made on the device, a leaf at a
time: leaf `i` of `leaf_specs` is drawn from `fold_in(key(seed), i)` exactly as
`lib.weights` draws a dense decoder's (the same integer bell, the same two
scalings), so the program under test and the reference (which regenerates a
layer at a time and imports nothing of the program) hold the same bits.

Leaves are laid out as checkpoints store them: a projection `[out, in]`, a
depthwise convolution `[channels, taps]` (the last tap on the token itself),
the routed experts stacked `[experts held, H, 2 I]` (gate columns, then up
columns) and `[experts held, I, H]`. Only the experts this chip holds are made
(`n_routed_experts` here, `n_routed_experts_published` the router's width). A
layer has the leaves of its kind: `gqa_layers` are softmax layers with an
output gate, the others gated delta-rule (KDA) layers.

Four kinds of leaf are not the bell:

- a convolution's taps are the bell times 2**-8 (standard deviation 0.58, not
  0.018): four taps of unit-variance rows then reach the curved part of SiLU;
- `A_log` holds the logs of the 64 mid-quantiles of U(1, 16) and `dt_bias` the
  inverse softplus of the mid-quantiles of U(1e-3, 0.1), one value a channel,
  each in an ORDER the seed draws (host arithmetic, so every program holds the
  same bits): a token's decay `exp(-exp(A_log) softplus(. + dt_bias))` then lies
  between 0.2 and 0.999, the range a trained model's takes, and every seed gives
  the same multiset of decays;
- the router's `e_score_correction_bias` holds the same `n_routed_experts`
  values in every group of that many experts (one chip's share), in an order the
  seed draws, so that every seed sends the held experts the same share of the
  rows (`lib/weights_glm_moe_dsa.py`, PERF.md section 6, PR 33 (5)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.weights import (MATRIX_SHIFT, _key, _leaf,
                                   seed_u32)  # noqa: F401
from benchmark.lib.weights_glm_moe_dsa import _router_bias

KDA = ("in_norm", "q", "k", "v", "o", "q_conv", "k_conv", "v_conv", "f_a",
       "f_b", "g_a", "g_b", "b", "A_log", "dt_bias", "o_norm")
GQA = ("in_norm", "q", "k", "v", "g", "o")
BLOCK = ("post_norm", "router", "router_bias", "experts_gate_up",
         "experts_down", "shared_gate", "shared_up", "shared_down")
CONV_SHIFT = 8          # taps: s * 2**-8


def experts_held(cfg) -> tuple:
    lo = int(cfg.get("experts_held_from", 0))
    return lo, lo + int(cfg["n_routed_experts"])


def layer_kind(cfg, i: int) -> str:
    return "gqa" if i in cfg["gqa_layers"] else "kda"


def layer_leaves(cfg, i: int) -> tuple:
    return (GQA if layer_kind(cfg, i) == "gqa" else KDA) + BLOCK


def linear_dims(cfg) -> tuple:
    """(heads, head width, taps, low rank) of the linear-attention layers."""
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            cfg["gate_low_rank"])


def layer_shapes(cfg, i: int) -> dict:
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, s = cfg["n_routed_experts"], cfg["n_shared_experts"] * inter
    if layer_kind(cfg, i) == "gqa":
        nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        mixer = {"q": (nh * d, h), "k": (kvh * d, h), "v": (kvh * d, h),
                 "g": (nh * d, h), "o": (h, nh * d)}
    else:
        H, d, taps, r = linear_dims(cfg)
        w = H * d
        mixer = {"q": (w, h), "k": (w, h), "v": (w, h), "o": (h, w),
                 "q_conv": (w, taps), "k_conv": (w, taps), "v_conv": (w, taps),
                 "f_a": (r, h), "f_b": (w, r), "g_a": (r, h), "g_b": (w, r),
                 "b": (H, h), "A_log": (H,), "dt_bias": (w,), "o_norm": (d,)}
    every = {**mixer, "in_norm": (h,), "post_norm": (h,),
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


def decay_grid(kind: str, n: int) -> np.ndarray:
    """The `n` values an `A_log` or a `dt_bias` leaf takes (float64, host)."""
    q = (np.arange(n) + 0.5) / n
    if kind == "A_log":
        return np.log(1.0 + 15.0 * q)
    return np.log(np.expm1(1e-3 + (0.1 - 1e-3) * q))     # inverse softplus


def _permuted(key, index, values, dtype):
    """`values` in an order drawn from the seed."""
    bits = jax.random.bits(jax.random.fold_in(key, index), (len(values),),
                           jnp.uint32)
    return jnp.asarray(values, jnp.float32)[jnp.argsort(bits)].astype(dtype)


def make_leaf(cfg, dtype):
    """(seed, leaf index) -> that leaf, one jitted program a shape and kind."""
    specs = leaf_specs(cfg)
    group = int(cfg["n_routed_experts"])
    if cfg["n_routed_experts_published"] % group:
        raise ValueError("the router's width is not a whole number of shares")
    programs = {}

    def kind_of(name: str) -> str:
        last = name.rsplit(".", 1)[-1]
        if last in ("router_bias", "A_log", "dt_bias"):
            return last
        return "conv" if last.endswith("_conv") else ""

    def f(seed, index: int):
        name, shape = specs[index]
        kind = kind_of(name)
        if (shape, kind) not in programs:
            def draw(s, i, shape=shape, kind=kind):
                if kind == "router_bias":
                    return _router_bias(_key(s), i, shape[0], group, dtype)
                if kind in ("A_log", "dt_bias"):
                    return _permuted(_key(s), i, decay_grid(kind, shape[0]),
                                     dtype)
                leaf = _leaf(_key(s), i, shape, jnp.float32)
                if kind == "conv":
                    leaf = leaf * 2.0 ** (MATRIX_SHIFT - CONV_SHIFT)
                return leaf.astype(dtype)
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
