"""The plain reference of the Brumby decoder: float32 `jax.numpy`, every matmul
at `highest` precision, no kernel, no state, no chunks, no batching. It imports
nothing of the program under test: weights come from `lib.weights_brumby` and
the seed.

The layer (`x = RMSNorm(h)`, eps `rms_norm_eps`, residuals pre-norm), from the
public `config.json` (`model_type: brumby`: Qwen3-14B's widths) and Manifest
AI's description of power retention ("Symmetric Power Transformers", "Scaling
Context Requires Rethinking Attention"); each inference is listed under
`assumed` in the configuration file:

- `q = W_q x` in `num_attention_heads` heads of `head_dim`, `k = W_k x` and `v
  = W_v x` in `num_key_value_heads` heads; `q` and `k` through their own
  RMSNorm over the head width, then rotated (rotate-half RoPE, `rope_theta`) at
  positions 0, 1, ...; the log decay `gamma = logsigmoid(W_g x + b_g)` a KV
  head, `G` its running sum.
- **The quadratic form**, a block of `QUERY_BLOCK` query rows at a time
  against every key: `o_t = sum_{s<=t} e^{G_t - G_s} (q_t.k_s)^2 v_s / (sum_{s<=t}
  e^{G_t - G_s} (q_t.k_s)^2 + eps)`, query head `i` on KV head `i // r`.
- `h <- h + W_o o`; `h <- h + W_down(silu(W_gate n) * W_up n)` on `n =
  RMSNorm(h)`, a block of `ROW_BLOCK` rows at a time. After the last layer an
  RMSNorm and the untied head.

The STATE the program keeps is computed here only to compare with it, in closed
form and not by a recurrence: `S = sum_{s<=t} e^{G_t - G_s} phi(k_s) v_s^T` and
`z = sum_{s<=t} e^{G_t - G_s} phi(k_s)`, with `phi` the symmetric square in the
layout the configuration's `assumed` states (`feature_pairs`).

`precision` is "f32" (the reference) or "fp8" (the control one step below
bfloat16: every matmul operand rounded to e4m3 with one absmax scale). It runs a
sequence at a time and a layer at a time, so a 28k-token sequence fits beside
one layer's float32 weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_brumby as W
from benchmark.lib.reference import _ein, rms_norm, rope

QUERY_BLOCK = 128      # query rows a block of the quadratic form
ROW_BLOCK = 2048       # rows a block of the feed-forward and the head
MIN_BUCKET = 2048      # sequences are padded to a power of two at least this
EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


def feature_pairs(d: int):
    """`(a, b, coef)` of each row of the state: blocks `j < d / 2` of `d` rows,
    row `a` the pair `(a, (a + j) mod d)`, then `d / 2` rows `(a, a + d / 2)`;
    `coef` sqrt 2 off the diagonal, so that `phi(q).phi(k) = (q.k)^2`."""
    half = d // 2
    a = np.concatenate([np.arange(d)] * half + [np.arange(half)])
    b = np.concatenate([(np.arange(d) + j) % d for j in range(half)]
                       + [np.arange(half) + half])
    c = np.where(a == b, 1.0, math.sqrt(2.0)).astype(np.float32)
    return a, b, c


def phi(x):
    """`[..., d]` -> `[..., D]`: the symmetric square in `feature_pairs`'s
    layout, made of products of rotations: elementwise arithmetic alone, no
    gather of 8,256 from 128 for XLA to lower as it likes."""
    d = x.shape[-1]
    half = d // 2
    r2 = math.sqrt(2.0)
    return jnp.concatenate(
        [x * x] + [r2 * x * jnp.roll(x, -j, -1) for j in range(1, half)]
        + [r2 * x[..., :half] * x[..., half:]], axis=-1)


def retention_rows(lp, n, cfg, mp="f32"):
    """What a layer's retention reads for normed rows `n [L, hidden]` at
    positions 0..L-1: `q [L, heads, d]`, `k, v [L, KV heads, d]`, `gamma [L, KV
    heads]`."""
    L = n.shape[0]
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(L)
    q = rms_norm(_ein("lh,oh->lo", n, lp["q"], mp).reshape(L, nh, d),
                 lp["q_norm"], eps)
    k = rms_norm(_ein("lh,oh->lo", n, lp["k"], mp).reshape(L, kvh, d),
                 lp["k_norm"], eps)
    v = _ein("lh,oh->lo", n, lp["v"], mp).reshape(L, kvh, d)
    q = rope(q[None], pos, cfg["rope_theta"])[0]
    k = rope(k[None], pos, cfg["rope_theta"])[0]
    gamma = jax.nn.log_sigmoid(_ein("lh,gh->lg", n, lp["g"], mp) + lp["g_bias"])
    return q, k, v, gamma


def log_decay_from(gamma, t0):
    """`G_s - G_{t0-1}` for every row `s` (`[L, KV heads]`), summed outward
    from `t0`: backwards over the rows before it, forwards from it. A running
    sum from the sequence's start reaches -1,500 on a head that keeps 15
    tokens over 22k of them, where float32's spacing is 1e-4: the decay of a
    row a few tokens back would be that far off, where the serving program
    sums a chunk at a time."""
    before = jnp.arange(gamma.shape[0])[:, None] < t0
    back = jnp.where(before, gamma, 0.0)
    # -(sum of gamma over s < u < t0), then the sum over t0 <= u <= s
    behind = jnp.cumsum(back[::-1], axis=0)[::-1] - back
    return jnp.where(before, -behind,
                     jnp.cumsum(jnp.where(before, 0.0, gamma), axis=0))


def quadratic(q, k, v, gamma, mp="f32"):
    """The quadratic form over one sequence, a block of query rows at a time:
    `o [L, heads * d]`."""
    L, nh, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(L, kvh, nh // kvh, d)

    def block(t0):
        qb = jax.lax.dynamic_slice_in_dim(qg, t0, QUERY_BLOCK, 0)
        G = log_decay_from(gamma, t0)                         # [L, KV heads]
        Gb = jax.lax.dynamic_slice_in_dim(G, t0, QUERY_BLOCK, 0)
        s = _ein("bhrd,shd->hrbs", qb, k, mp)
        diff = Gb.T[:, :, None] - G.T[:, None, :]             # G_t - G_s
        causal = (t0 + jnp.arange(QUERY_BLOCK))[:, None] >= jnp.arange(L)[None]
        A = s * s * jnp.where(causal, jnp.exp(jnp.minimum(diff, 0.0)),
                              0.0)[:, None]
        num = _ein("hrbs,shv->bhrv", A, v, mp)
        den = jnp.transpose(A.sum(-1), (2, 0, 1))             # [B, KV, r]
        return (num / (den + EPS)[..., None]).reshape(QUERY_BLOCK, nh * d)
    return jax.lax.map(block, jnp.arange(0, L, QUERY_BLOCK)).reshape(L, nh * d)


def state_after(k, v, gamma, at):
    """`(S [KV heads, D, d], z [KV heads, D])` after token `at`, in closed
    form, a block of rows at a time."""
    L, kvh, d = k.shape
    # e^{G_at - G_s}, summed back from `at`
    w = jnp.where(jnp.arange(L)[:, None] <= at,
                  jnp.exp(jnp.minimum(-log_decay_from(gamma, at + 1), 0.0)),
                  0.0)

    def add(carry, t0):
        S, z = carry
        kb, vb, wb = (jax.lax.dynamic_slice_in_dim(x, t0, QUERY_BLOCK, 0)
                      for x in (k, v, w))
        fk = phi(kb) * wb[..., None]                          # [B, KV, D]
        return (S + jnp.einsum("shD,shv->hDv", fk, vb, precision=_HI),
                z + fk.sum(0)), None
    D = d * (d + 1) // 2
    zero = (jnp.zeros((kvh, D, v.shape[-1]), jnp.float32),
            jnp.zeros((kvh, D), jnp.float32))
    return jax.lax.scan(add, zero, jnp.arange(0, L, QUERY_BLOCK))[0]


def layer_forward(lp, h, cfg, precision="f32"):
    """One layer over one sequence `h [L, hidden]` (float32): `(h, the rows
    its retention read)`."""
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    eps = cfg["rms_norm_eps"]
    rows = retention_rows(lp, rms_norm(h, lp["in_norm"], eps), cfg, precision)
    h = h + _ein("lo,ho->lh", quadratic(*rows, precision), lp["o"], precision)

    def mlp(hb):
        n = rms_norm(hb, lp["post_norm"], eps)
        act = jax.nn.silu(_ein("lh,ih->li", n, lp["gate"], precision)) \
            * _ein("lh,ih->li", n, lp["up"], precision)
        return hb + _ein("li,hi->lh", act, lp["down"], precision)
    L = h.shape[0]
    rb = min(ROW_BLOCK, L)
    return jax.lax.map(mlp, h.reshape(L // rb, rb, -1)).reshape(L, -1), rows


def head_logits(final_norm, head, h, cfg, precision="f32"):
    x = rms_norm(h, final_norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return _ein("lh,vh->lv", x, head.astype(jnp.float32), precision)


def forward_logits(cfg, layers, embed, final_norm, head, ids, precision="f32"):
    """Whole forward of one sequence from given leaves (tests): ids [L] ->
    (logits [L, V], {layer: (S, z) after the last token}). The sequence is
    padded to whole query blocks, which its positions never see."""
    n = len(ids)
    padded = jnp.zeros(-(-n // QUERY_BLOCK) * QUERY_BLOCK, jnp.int32)
    h = jnp.take(embed, padded.at[:n].set(ids), axis=0).astype(jnp.float32)
    states = {}
    for i, lp in enumerate(layers):
        h, (_, k, v, gamma) = layer_forward(lp, h, cfg, precision)
        states[i] = state_after(k, v, gamma, n - 1)
    return head_logits(final_norm, head, h[:n], cfg, precision), states


def bucket(n: int) -> int:
    return max(MIN_BUCKET, 1 << (int(n) - 1).bit_length())


def served_logit_gaps(cfg, seed, sequences, n_prompt, out_pad, dtype,
                      control=None, state_of=None):
    """For each sequence (prompt, then the served tokens), at the positions
    that produced each served token, `gap = (best reference logit - reference
    logit of the served token) / std of that position's logits`; with `control`
    ("fp8") also `control_gap`, the same for the token a pass in that precision
    puts first. With `state_of = (i, tokens)`, row `i` also holds `state`:
    every layer's `(S, z)` after the first `tokens` tokens of sequence `i`, and
    `rows`: what the LAST layer's retention read for those tokens in the
    float32 pass (`q, k, v, gamma`, on the device).

    A sequence is padded to a power of two (a causal model's earlier positions
    never see the padding), so few programs are compiled; it runs a sequence at
    a time, each layer's leaves made anew from the seed. Every matmul, those
    XLA makes of other operations too, at `highest`."""
    with jax.default_matmul_precision("highest"):
        return _served_logit_gaps(cfg, seed, sequences, n_prompt, out_pad,
                                  dtype, control, state_of)


def _served_logit_gaps(cfg, seed, sequences, n_prompt, out_pad, dtype,
                       control, state_of):
    layer_of = W.make_layer(cfg, dtype)
    embed, final_norm, head = W.make_ends(cfg, dtype)(seed)
    passes = ["f32"] + ([control] if control else [])

    @functools.partial(jax.jit, static_argnames="prec", donate_argnums=1)
    def step(lp, h, prec):
        return layer_forward(lp, h, cfg, prec)

    @jax.jit
    def snapshot(rows, at):
        return state_after(*rows[1:], at)

    @functools.partial(jax.jit, static_argnames="prec")
    def gaps(final_norm, head, h, h_low, idx, served, prec):
        def block(args):
            i, t = args
            ref = head_logits(final_norm, head, h[i], cfg, "f32")
            best, std = jnp.max(ref, -1), jnp.std(ref, -1)
            took = jnp.take_along_axis(ref, t[:, None], -1)[:, 0]
            if prec is None:
                return (best - took) / std, jnp.zeros_like(best)
            pick = jnp.argmax(head_logits(final_norm, head, h_low[i], cfg,
                                          prec), -1)
            return ((best - took) / std,
                    (best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0])
                    / std)
        nb = idx.shape[0] // 512
        g, gc = jax.lax.map(block, (idx.reshape(nb, 512),
                                    served.reshape(nb, 512)))
        return g.reshape(-1), gc.reshape(-1)

    out = []
    for i, s in enumerate(sequences):
        ids = np.zeros(bucket(len(s)), np.int32)
        ids[:len(s)] = s
        probe = state_of is not None and state_of[0] == i
        hs, states, probe_rows = {}, {}, None
        for p in passes:
            h = jnp.take(embed, jnp.asarray(ids), axis=0).astype(jnp.float32)
            for li in range(cfg["num_hidden_layers"]):
                h, rows = step(layer_of(seed, li), h, prec=p)
                if probe and p == "f32":
                    states[li] = tuple(np.asarray(x) for x in snapshot(
                        rows, jnp.int32(state_of[1] - 1)))
                    if li == cfg["num_hidden_layers"] - 1:
                        probe_rows = tuple(x[:state_of[1]] for x in rows)
                del rows
            hs[p] = h
        n_out = len(s) - n_prompt[i]
        pad = -(-max(out_pad, n_out) // 512) * 512
        # logits at position p predict token p+1
        idx = np.full(pad, n_prompt[i] - 1, np.int32)
        idx[:n_out] = np.arange(n_prompt[i] - 1, len(s) - 1)
        served = np.zeros(pad, np.int32)
        served[:n_out] = s[n_prompt[i]:]
        g, gc = gaps(final_norm, head, hs["f32"], hs.get(control),
                     jnp.asarray(idx), jnp.asarray(served), prec=control)
        row = {"gap": np.asarray(g)[:n_out]}
        if control:
            row["control_gap"] = np.asarray(gc)[:n_out]
        if probe:
            row["state"] = states
            row["rows"] = probe_rows
        out.append(row)
        del hs
    return out


def state_gap(got, ref) -> float:
    """Relative Frobenius distance of a state (`S`, or `(S, z)` taken
    together) from the reference's."""
    got = [np.asarray(x, np.float64) for x in
           (got if isinstance(got, tuple) else (got,))]
    ref = [np.asarray(x, np.float64) for x in
           (ref if isinstance(ref, tuple) else (ref,))]
    num = math.sqrt(sum(np.linalg.norm(g - r) ** 2 for g, r in zip(got, ref)))
    den = math.sqrt(sum(np.linalg.norm(r) ** 2 for r in ref))
    return float(num / max(den, 1e-30))
