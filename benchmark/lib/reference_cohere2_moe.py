"""The plain reference of the Cohere2-MoE decoder (Command A+): float32
`jax.numpy`, every matmul at `highest` precision, no kernel, no cache, no
sorting of rows. It imports nothing of the program under test: weights come
from `lib.weights_cohere2_moe` and the seed.

The layer, from the public `config.json` (`model_type: cohere2_moe`) and the
family's description; each inference is listed under `assumed` in the
configuration file:

- `n = LayerNorm(x)`: mean subtracted, over `sqrt(var + eps)`, times a weight,
  no bias;
- attention on `n`: 128 query heads on 8 KV heads of 128, no bias, no QK norm,
  scale `1/sqrt(128)`; `layer_types` three `sliding_attention` then one
  `full_attention`. Sliding layers: rope (theta 50000) over the whole head in
  interleaved pairs `(2i, 2i+1)`, key `j` visible to query `i` iff
  `i - window < j <= i`. Full layers: no positional encoding, causal;
- experts on the same `n` (parallel block): `s = sigmoid(n W_r)` over all the
  published experts, in float32 in every pass (the controls too); the 8 largest;
  weights `s_e / sum of the 8`; `E(n) = (silu(n W_g) * (n W_u)) W_d`;
  `m = sum over the chosen experts THAT ARE HELD of w_e E_e(n) + mean of the
  shared experts`: the reference is given the same share of the experts as the
  chip under test (`held`), and what the absent experts would add is left out
  of both;
- `x' = x + a + m`; after the last layer a LayerNorm, then `logits = h E^T *
  logit_scale` with the embedding tied.

Departures: text only; greedy decoding.

It runs a layer at a time, a sequence at a time, attention a block of queries
at a time and the held experts one at a time, so a 15k-token sequence fits
beside one layer's float32 weights. `precision` is "f32" (the reference) or
"fp8" (the control one step below bfloat16: every matmul operand but the
router's rounded to e4m3 with one absmax scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_cohere2_moe as W
from benchmark.lib.reference import _HI, _ein

Q_BLOCK = 256          # queries attended at once
SEQ_BUCKET = 2048      # sequences are padded to a multiple of this


def layer_norm(x, weight, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight


def rope_gptj(x, positions, theta):
    """x [L, heads, D], positions [L]: pairs (2i, 2i+1) rotated."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(d2, dtype=jnp.float32) / d2))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def layer_kind(cfg, i: int) -> str:
    return {"sliding_attention": "window", "full_attention": "full"}[
        cfg["layer_types"][i]]


def attention(q, k, v, window, precision):
    """q [L, KVH, R, D], k/v [L, KVH, D] -> [L, KVH*R*D]; causal, and with
    `window` key j visible to query i iff i - window < j <= i. A block of
    queries at a time against the keys it can see: all of them in a full
    layer, the `block + window - 1` that end with the block in a window
    layer. One loop body for every block (`lax.map`), so a long sequence
    compiles as fast as a short one."""
    l, kvh, rep, d = q.shape
    blk = min(Q_BLOCK, l)
    n_blocks = -(-l // blk)
    span = l if window is None else min(l, blk + window - 1)
    q = jnp.pad(q, ((0, n_blocks * blk - l), (0, 0), (0, 0), (0, 0)))

    def block(q0):
        k0 = jnp.clip(q0 + blk - span, 0, l - span)
        qi = q0 + jnp.arange(blk)[:, None]
        kj = k0 + jnp.arange(span)[None, :]
        ok = kj <= qi
        if window is not None:
            ok = ok & (kj > qi - window)
        kb = jax.lax.dynamic_slice_in_dim(k, k0, span)
        vb = jax.lax.dynamic_slice_in_dim(v, k0, span)
        s = _ein("lgrd,mgd->grlm", jax.lax.dynamic_slice_in_dim(q, q0, blk),
                 kb, precision) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return _ein("grlm,mgd->lgrd", p, vb, precision).reshape(
            blk, kvh * rep * d)

    out = jax.lax.map(block, jnp.arange(n_blocks) * blk)
    return out.reshape(n_blocks * blk, kvh * rep * d)[:l]


def route(n, w_router, top_k, normalize=True):
    """float32 sigmoid scores over all experts -> (weight [L, E] with zeros
    off the top-k, margin [L] between the k-th and the next score)."""
    s = jax.nn.sigmoid(jnp.einsum("lh,eh->le", n, w_router, precision=_HI))
    top, _ = jax.lax.top_k(s, top_k + 1)
    chosen = s >= top[:, top_k - 1:top_k]
    w = jnp.where(chosen, s, 0.0)
    if normalize:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, top[:, top_k - 1] - top[:, top_k]


def experts(lp, n, cfg, held, precision):
    """m [L, H]: the held experts' part of the routed sum plus the mean of the
    shared experts; and the router's margin a position."""
    inter = cfg["intermediate_size"]
    w, margin = route(n, lp["router"], cfg["num_experts_per_tok"],
                      cfg.get("norm_topk_prob", True))

    def one(m, xs):
        gate_up, down, we = xs
        gu = _ein("lh,hf->lf", n, gate_up, precision)
        act = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        return m + we[:, None] * _ein("li,ih->lh", act, down, precision), None

    lo, hi = held
    m, _ = jax.lax.scan(one, jnp.zeros_like(n),
                        (lp["experts_gate_up"], lp["experts_down"],
                         w[:, lo:hi].T))
    s = cfg["num_shared_experts"]
    for j in range(s):
        rows = slice(j * inter, (j + 1) * inter)
        act = jax.nn.silu(_ein("lh,ih->li", n, lp["shared_gate"][rows], precision)) \
            * _ein("lh,ih->li", n, lp["shared_up"][rows], precision)
        m = m + _ein("li,hi->lh", act, lp["shared_down"][:, rows], precision) / s
    return m, margin


def layer_forward(lp, h, cfg, kind, held, precision="f32"):
    """One layer of `kind` ("window" or "full") over h [L, H] (float32),
    positions 0..L-1. Returns (h', margin [L])."""
    l = h.shape[0]
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    n = layer_norm(h, lp["norm"], cfg["layer_norm_eps"])
    q = _ein("lh,dh->ld", n, lp["q"], precision).reshape(l, nh, d)
    k = _ein("lh,dh->ld", n, lp["k"], precision).reshape(l, kvh, d)
    v = _ein("lh,dh->ld", n, lp["v"], precision).reshape(l, kvh, d)
    window = None
    if kind == "window":
        window = cfg["sliding_window"]
        pos = jnp.arange(l)
        q, k = rope_gptj(q, pos, cfg["rope_theta"]), rope_gptj(k, pos, cfg["rope_theta"])
    a = attention(q.reshape(l, kvh, nh // kvh, d), k, v, window, precision)
    a = _ein("ld,hd->lh", a, lp["o"], precision)
    m, margin = experts(lp, n, cfg, held, precision)
    return h + a + m, margin


def head_logits(final_norm, embed, h, cfg, precision="f32"):
    x = layer_norm(h, final_norm.astype(jnp.float32), cfg["layer_norm_eps"])
    return _ein("lh,vh->lv", x, embed.astype(jnp.float32), precision) \
        * cfg.get("logit_scale", 1)


def forward_logits(cfg, layers, embed, final_norm, ids, held, precision="f32"):
    """Whole forward of one sequence from given leaves (tests): ids [L] ->
    logits [L, V]."""
    h = jnp.take(embed, ids, axis=0).astype(jnp.float32)
    for i, lp in enumerate(layers):
        h, _ = layer_forward(lp, h, cfg, layer_kind(cfg, i), held, precision)
    return head_logits(final_norm, embed, h, cfg, precision)


def served_logit_gaps(cfg, seed, sequences, n_prompt, out_pad, dtype,
                      control=None):
    """As `lib.reference.served_logit_gaps`: for each sequence (prompt, then
    the served tokens), at the positions that produced each served token,
    `gap = (best reference logit - reference logit of the served token) / std
    of that position's logits`; with `control` also `control_gap`, the same for
    the token a pass in that precision puts first. Each row also holds
    `margin`: at that position, the least over the layers of the distance
    between the router's k-th and (k+1)-th score in the reference (where it
    is small a top-k choice turns on rounding, and a program that rounds its
    activations to bfloat16 may rightly pick the other expert).

    A sequence is padded to a multiple of `SEQ_BUCKET` (causal attention never
    lets a position see the padding), so few programs are compiled."""
    held = W.experts_held(cfg)
    layer_of = W.make_layer(cfg, dtype)
    embed, final_norm = W.make_ends(cfg, dtype)(seed)
    passes = ["f32"] + ([control] if control else [])

    @functools.partial(jax.jit, static_argnames=("prec", "kind"), donate_argnums=1)
    def step(lp, h, prec, kind):
        return layer_forward(lp, h, cfg, kind, held, prec)

    @functools.partial(jax.jit, static_argnames="prec")
    def gaps(final_norm, embed, h, h_low, idx, served, prec):
        ref = head_logits(final_norm, embed, h[idx], cfg, "f32")
        best, std = jnp.max(ref, -1), jnp.std(ref, -1)
        took = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if prec is None:
            return (best - took) / std, None
        low = head_logits(final_norm, embed, h_low[idx], cfg, prec)
        pick = jnp.argmax(low, -1)
        return ((best - took) / std,
                (best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]) / std)

    hs, margins = {p: [] for p in passes}, []
    for s in sequences:
        ids = np.zeros(-(-len(s) // SEQ_BUCKET) * SEQ_BUCKET, np.int32)
        ids[:len(s)] = s
        h0 = jnp.take(embed, jnp.asarray(ids), axis=0).astype(jnp.float32)
        for p in passes:
            hs[p].append(h0 + 0.0)
        margins.append(None)
    for li in range(cfg["num_hidden_layers"]):
        lp = layer_of(seed, li)
        for p in passes:
            for i in range(len(sequences)):
                hs[p][i], mg = step(lp, hs[p][i], prec=p,
                                    kind=layer_kind(cfg, li))
                if p == "f32":
                    mg = np.asarray(mg)
                    margins[i] = mg if margins[i] is None \
                        else np.minimum(margins[i], mg)
    out = []
    for i, s in enumerate(sequences):
        n_out = len(s) - n_prompt[i]
        # logits at position p predict token p+1
        idx = np.full(out_pad, n_prompt[i] - 1, np.int32)
        idx[:n_out] = np.arange(n_prompt[i] - 1, len(s) - 1)
        served = np.zeros(out_pad, np.int32)
        served[:n_out] = s[n_prompt[i]:]
        g, gc = gaps(final_norm, embed, hs["f32"][i],
                     hs[control][i] if control else None,
                     jnp.asarray(idx), jnp.asarray(served), prec=control)
        row = {"gap": np.asarray(g)[:n_out],
               "margin": margins[i][idx[:n_out]]}
        if control:
            row["control_gap"] = np.asarray(gc)[:n_out]
        out.append(row)
    return out
