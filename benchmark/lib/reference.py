"""The plain reference: a dense GQA + SwiGLU decoder in straightforward
`jax.numpy`, float32, every matmul at `highest` precision, no kernel, no
cache, no batching tricks. It imports nothing of the program under test and
takes nothing the program made: weights come from `lib.weights` and the seed.

Published description followed (Llama / Mistral / Yi `modeling_*.py` on the
Hugging Face hub): pre-norm RMSNorm, rotary embedding in the rotate-half
convention, grouped-query attention with query head j reading KV head
j // (heads / kv_heads), SwiGLU, untied output head, next-token cross entropy
as the mean over all positions but the last, AdamW with decoupled decay.
Departure: none known. A configuration that states bfloat16 parameters with
no float32 master copy is followed in that too: the arithmetic is float32, the
stored parameter is rounded to bfloat16 after each update.

`precision` selects how every matmul is computed, so the same code is the
reference ("f32") and its controls one step of precision down ("bf16" for a
float32 configuration, "fp8" for a bfloat16 one): the control's inputs are
rounded to the lower type (fp8: e4m3 with one absmax scale per operand),
the products accumulate in float32, gradients pass straight through.

It runs a layer at a time and a batch row at a time, so that the float32
copy of a model that fills the chip in bfloat16 still fits beside nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

_HI = jax.lax.Precision.HIGHEST
_E4M3_MAX = 224.0     # under the 240 that 4 IEEE exponent bits reach


def round_to_dtype(x, dtype):
    """x (float32) rounded to the values `dtype` holds, still float32. By
    `reduce_precision`, which XLA keeps: a convert there and back is taken out
    on the TPU (xla_allow_excess_precision), as PR 25's first chip runs showed."""
    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def _round_to(x, precision):
    if precision == "f32":
        return x
    if precision == "bf16":
        q = round_to_dtype(x, jnp.bfloat16)
    elif precision == "fp8":         # e4m3, one absmax scale per operand
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _E4M3_MAX
        q = jax.lax.reduce_precision(x / scale, exponent_bits=4, mantissa_bits=3) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)     # straight-through


def _ein(spec, a, b, precision):
    return jnp.einsum(spec, _round_to(a, precision), _round_to(b, precision),
                      precision=_HI, preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, positions, theta):
    """x [B, L, heads, D], positions [L]: rotate-half rotary embedding."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(d2, dtype=jnp.float32) / d2))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_forward(lp, h, cfg, precision="f32"):
    """One decoder layer over h [B, L, H] (float32), causal, positions 0..L-1."""
    b, l, hid = h.shape
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or hid // nh
    rep = nh // kvh
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    pos = jnp.arange(l)
    x = rms_norm(h, lp["attn_norm"], eps)
    q = rope(_ein("blh,hd->bld", x, lp["q"], precision).reshape(b, l, nh, d), pos, theta)
    k = rope(_ein("blh,hd->bld", x, lp["k"], precision).reshape(b, l, kvh, d), pos, theta)
    v = _ein("blh,hd->bld", x, lp["v"], precision).reshape(b, l, kvh, d)
    q = q.reshape(b, l, kvh, rep, d)
    s = _ein("blgrd,bmgd->bgrlm", q, k, precision) / np.sqrt(d)
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _ein("bgrlm,bmgd->blgrd", p, v, precision).reshape(b, l, nh * d)
    h = h + _ein("bld,dh->blh", o, lp["o"], precision)
    x = rms_norm(h, lp["mlp_norm"], eps)
    ff = jax.nn.silu(_ein("blh,hi->bli", x, lp["gate"], precision)) \
        * _ein("blh,hi->bli", x, lp["up"], precision)
    return h + _ein("bli,ih->blh", ff, lp["down"], precision)


def head_logits(final_norm, head, h, cfg, precision="f32"):
    x = rms_norm(h, final_norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return _ein("blh,hv->blv", x, head.astype(jnp.float32), precision)


def _ce_sum(final_norm, head, h, labels, cfg, precision):
    """Sum over one row block of next-token cross entropy (last position of
    each row has no label)."""
    logits = head_logits(final_norm, head, h, cfg, precision)[:, :-1]
    logz = jax.nn.logsumexp(logits, axis=-1)
    took = jnp.take_along_axis(logits, labels[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(logz - took)


# ---------------------------------------------------------------------------
# serving: logits of given positions of given sequences, a layer at a time
# ---------------------------------------------------------------------------

def served_logit_gaps(cfg, seed, sequences, n_prompt, pad_to, out_pad, dtype,
                      control=None):
    """For each sequence (prompt followed by the tokens that were served), at
    the positions that produced each served token, in the reference's logits:
    `gap[t] = (best logit - logit of the served token) / std of the logits`,
    0 where the served token is the reference's own choice. With `control` (a
    precision) also `control_gap`: the same for the token that a pass in that
    lower precision puts first there.

    Sequences are right-padded to `pad_to` (causal attention never lets a
    position see the padding) and served tokens to `out_pad`, so one set of
    compiled programs serves every run.
    """
    layer_of = W.make_layer(cfg, dtype)
    embed, final_norm, head = W.make_ends(cfg, dtype)(seed)
    passes = ["f32"] + ([control] if control else [])

    @functools.partial(jax.jit, static_argnames="prec", donate_argnums=1)
    def step(lp, h, prec):
        return layer_forward(lp, h, cfg, prec)

    @functools.partial(jax.jit, static_argnames="prec")
    def gaps(final_norm, head, h, h_low, idx, served, prec):
        ref = head_logits(final_norm, head, h[:, idx], cfg, "f32")[0]
        best, std = jnp.max(ref, -1), jnp.std(ref, -1)
        took = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if prec is None:
            return (best - took) / std, None
        low = head_logits(final_norm, head, h_low[:, idx], cfg, prec)[0]
        pick = jnp.argmax(low, -1)
        return ((best - took) / std,
                (best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]) / std)

    ids = np.zeros((len(sequences), pad_to), np.int32)
    for i, s in enumerate(sequences):
        ids[i, :len(s)] = s
    hs = {p: [jnp.take(embed, jnp.asarray(r[None]), axis=0).astype(jnp.float32)
              for r in ids] for p in passes}
    for li in range(cfg["num_hidden_layers"]):
        lp = layer_of(seed, jnp.int32(li))
        for p in passes:
            hs[p] = [step(lp, h, prec=p) for h in hs[p]]
    out = []
    for i, s in enumerate(sequences):
        n_out = len(s) - n_prompt[i]
        # logits at position p predict token p+1: served token t sits at
        # index n_prompt+t of the sequence and was produced at n_prompt+t-1
        idx = np.full(out_pad, n_prompt[i] - 1, np.int32)
        idx[:n_out] = np.arange(n_prompt[i] - 1, len(s) - 1)
        served = np.zeros(out_pad, np.int32)
        served[:n_out] = s[n_prompt[i]:]
        g, gc = gaps(final_norm, head, hs["f32"][i],
                     hs[control][i] if control else None,
                     jnp.asarray(idx), jnp.asarray(served), prec=control)
        row = {"gap": np.asarray(g)[:n_out]}
        if control:
            row["control_gap"] = np.asarray(gc)[:n_out]
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# training: losses, first gradient and parameter change of the first steps
# ---------------------------------------------------------------------------

def _norm(x) -> float:
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def train_steps(cfg, seed, batches, hp, dtype, precision="f32",
                rows_used=None):
    """Follow AdamW training from the seed's weights over `batches` (a list
    of int32 [B, L] id arrays; one step each).

    Returns {"loss": [per step], "grad_norm": {leaf: norm of step 1's
    gradient}, "change_norm": {leaf: norm of (params after the last step -
    initial params)}}. Gradients are formed a layer and a row at a time;
    Adam's state after step 1 is that step's gradient, kept whole, so two
    steps need one float32 copy beside the parameters.

    `rows_used` (a slice) plants the "half of the batch left out" fault: the
    loss and gradient are the mean over those rows alone.
    """
    if len(batches) > 2:
        raise ValueError("the reference follows at most two steps")
    n_layers = cfg["num_hidden_layers"]
    specs = W.leaf_specs(cfg)
    make_all = W.make_all(cfg, dtype)
    params = {k: v.astype(jnp.float32) for k, v in make_all(seed).items()}
    lr, b1, b2 = hp["learning_rate"], hp["beta1"], hp["beta2"]
    eps, wd = hp["epsilon"], hp["weight_decay"]

    def layer_p(p, i):
        return {n: p[f"layers.{i}.{n}"] for n in W.LAYER_LEAVES}

    @jax.jit
    def fwd(lp, h):
        return layer_forward(lp, h, cfg, precision)

    @jax.jit
    def layer_back(lp, h, dh):
        _, vjp = jax.vjp(lambda a, b: layer_forward(a, b, cfg, precision), lp, h)
        return vjp(dh)

    @jax.jit
    def head_back(final_norm, head, h, labels):
        loss, g = jax.value_and_grad(
            lambda a, b, c: _ce_sum(a, b, c, labels, cfg, precision),
            argnums=(0, 1, 2))(final_norm, head, h)
        return loss, g

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(acc, g):
        return jax.tree.map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0,), static_argnames="t")
    def adam(p, g, g_prev, t):
        if t == 1:
            m, v = (1 - b1) * g, (1 - b2) * g * g
        else:
            m = b1 * (1 - b1) * g_prev + (1 - b1) * g
            v = b2 * (1 - b2) * g_prev * g_prev + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p
        # a parameter is a number of the configuration's dtype: the update
        # is formed in float32 and stored rounded to it, as the configuration
        # states (no float32 master copy)
        return round_to_dtype(p - lr * upd, dtype)

    def gradients(p, ids, sink):
        """Loss of `ids` under `p`; every leaf's gradient goes to `sink(name,
        g)` as soon as it is whole, which may then update that leaf: nothing
        later in the backward pass reads it."""
        ids = ids[rows_used] if rows_used is not None else ids
        n_rows, seq = ids.shape
        denom = n_rows * (seq - 1)
        rows = [jnp.asarray(ids[r:r + 1]) for r in range(n_rows)]
        acts = [[jnp.take(p["embed"], r, axis=0)] for r in rows]
        for i in range(n_layers):
            lp = layer_p(p, i)
            for a in acts:
                a.append(fwd(lp, a[-1]))
        loss, dhs, acc = 0.0, [], None
        for r, a in zip(rows, acts):
            ls, (gn, gh, dh) = head_back(p["final_norm"], p["head"], a[-1], r)
            loss += float(ls) / denom
            pair = {"final_norm": gn, "head": gh}
            acc = pair if acc is None else add(acc, pair)
            dhs.append(dh)
        for name, g in acc.items():
            sink(name, g / denom)
        for i in reversed(range(n_layers)):
            lp, acc = layer_p(p, i), None
            for j, a in enumerate(acts):
                dlp, dhs[j] = layer_back(lp, a[i], dhs[j])
                acc = dlp if acc is None else add(acc, dlp)
                a[i + 1] = None
            for n in W.LAYER_LEAVES:
                sink(f"layers.{i}.{n}", acc.pop(n) / denom)
        ge = jnp.zeros_like(p["embed"])
        for r, dh in zip(rows, dhs):
            ge = ge.at[r[0]].add(dh[0])
        sink("embed", ge / denom)
        return loss

    out = {"loss": [], "grad_norm": {}}
    g_prev = {}
    for t, ids in enumerate(batches, start=1):
        def sink(name, g, t=t):
            if t == 1:
                out["grad_norm"][name] = _norm(g)
            params[name] = adam(params[name], g, g_prev.pop(name, None), t=t)
            if t < len(batches):
                g_prev[name] = g
        out["loss"].append(gradients(params, np.asarray(ids), sink))
    first_of = make_all(seed)
    out["change_norm"] = {
        name: _norm(params.pop(name) - first_of.pop(name).astype(jnp.float32))
        for name, _ in specs}
    return out
