"""Seeded weights of a dense decoder, made on the device.

One list of leaves (`leaf_specs`) in one order serves the runners and the
reference: leaf `i` is drawn from `fold_in(key(seed), i)` and rounded once
to the configuration's dtype, so the program under test and the
reference (which regenerates a layer at a time and imports nothing of the
program) hold the same values bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("attn_norm", "q", "k", "v", "o", "mlp_norm", "gate", "up", "down")
# A leaf is made from integers alone until one exact scaling, so that any
# program that regenerates it (another jit, another fusion, the CPU) gets the
# same bits: a float32 normal from jax.random differed in its last bit between
# two compiled programs on the v5e (PR 25's first chip run). The draw is the
# sum of a random word's four bytes, centred: an Irwin-Hall bell of standard
# deviation 147.8 on [-510, 510].
_BYTES_STD = 147.8006
MATRIX_SHIFT = 13          # matrices: s * 2**-13, standard deviation 0.01804
NORM_STEP, NORM_SHIFT = 11, 15   # gains: (2**15 + 11 s) * 2**-15 = 1 +- 0.0496 s.d.
INIT_STD = _BYTES_STD * 2.0 ** -MATRIX_SHIFT


def layer_shapes(cfg) -> dict:
    h = cfg["hidden_size"]
    nh = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or h // nh
    kvh, inter = cfg["num_key_value_heads"], cfg["intermediate_size"]
    return {"attn_norm": (h,), "q": (h, nh * d), "k": (h, kvh * d),
            "v": (h, kvh * d), "o": (nh * d, h), "mlp_norm": (h,),
            "gate": (h, inter), "up": (h, inter), "down": (inter, h)}


def leaf_specs(cfg) -> list:
    """[(name, shape)] of every leaf; matrices are [in, out]."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    ls = layer_shapes(cfg)
    out = [("embed", (v, h))]
    for i in range(cfg["num_hidden_layers"]):
        out += [(f"layers.{i}.{n}", ls[n]) for n in LAYER_LEAVES]
    return out + [("final_norm", (h,)), ("head", (h, v))]


def _leaf(key, index, shape, dtype):
    bits = jax.random.bits(jax.random.fold_in(key, index), shape, jnp.uint32)
    s = ((bits & 0xFF) + ((bits >> 8) & 0xFF) + ((bits >> 16) & 0xFF)
         + (bits >> 24)).astype(jnp.int32) - 510
    if len(shape) == 1:      # a gain of exactly 1 would hide a dropped one
        s = (1 << NORM_SHIFT) + NORM_STEP * s
        return (s.astype(jnp.float32) * 2.0 ** -NORM_SHIFT).astype(dtype)
    return (s.astype(jnp.float32) * 2.0 ** -MATRIX_SHIFT).astype(dtype)


def _key(seed):
    return jax.random.key(jnp.asarray(seed, jnp.uint32))


def seed_u32(seed: int):
    """--seed may pass 2**31: fold it into the 32 bits a key is made of."""
    import numpy as np
    return np.uint32(int(seed) % (1 << 32))


def make_all(cfg, dtype):
    """jitted seed -> {name: array} of every leaf, one call."""
    specs = leaf_specs(cfg)

    @jax.jit
    def f(seed):
        key = _key(seed)
        return {n: _leaf(key, i, s, dtype) for i, (n, s) in enumerate(specs)}
    return f


def make_layer(cfg, dtype):
    """jitted (seed, layer index) -> {leaf: array} of one layer."""
    ls = layer_shapes(cfg)

    @jax.jit
    def f(seed, layer):
        key = _key(seed)
        base = 1 + layer * len(LAYER_LEAVES)
        return {n: _leaf(key, base + j, ls[n], dtype)
                for j, n in enumerate(LAYER_LEAVES)}
    return f


def make_ends(cfg, dtype):
    """jitted seed -> (embed, final_norm, head)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    last = 1 + cfg["num_hidden_layers"] * len(LAYER_LEAVES)

    @jax.jit
    def f(seed):
        key = _key(seed)
        return (_leaf(key, 0, (v, h), dtype), _leaf(key, last, (h,), dtype),
                _leaf(key, last + 1, (h, v), dtype))
    return f
