"""The plain reference of the Solar Open 2 decoder: float32 `jax.numpy`, every
matmul at `highest` precision, no kernel, no cache, no chunks, no batching. It
imports nothing of the program under test: weights come from
`lib.weights_solar_open2` and the seed.

The layer (`x = RMSNorm(h)`, eps `rms_norm_eps`, residuals pre-norm), from the
public `config.json` (`model_type: solar_open2`) and the published description of
Kimi Delta Attention (arXiv 2510.26692), which the `kda_*` keys name; each
inference is listed under `assumed` in the configuration file:

- **KDA layer** (not in `gqa_layers`; `linear_attn_config`: `num_heads` heads of
  `head_dim` keys and values, a convolution of `short_conv_kernel_size` taps).
  For token `t`: `q~, k~, v~ = W_q x_t, W_k x_t, W_v x_t`; each through its own
  causal depthwise convolution over the sequence (zeros before the first token,
  no bias) and SiLU; in heads: `q = q~ / |q~| * d^-1/2`, `k = k~ / |k~|` (the
  root over `sum + 1e-6`), `v = v~`. Decay a channel `g = -exp(A_log_h) *
  softplus(W_fb (W_fa x_t) + dt_bias)`, `a = exp(g)`; `beta = 2 sigmoid(w_b
  x_t)` (`kda_allow_neg_eigval`). The state `S [d, d]` a head, zero at the
  start, **a `lax.scan` a token**: `S' = Diag(a) S`; `S = S' + beta k (v - S'^T
  k)^T`; `o = S^T q`. Then `o <- RMSNorm_d(o) * sigmoid(W_gb (W_ga x_t))`, `y =
  W_o o`.
- **GQA layer**: `num_attention_heads` query heads on `num_key_value_heads` K/V
  heads, causal softmax at `head_dim^-1/2`, no positions (`use_rope` false), no
  QK norm; `y = W_o (attn * sigmoid(W_g x))` (`use_gqa_gate`).
- `h <- h + y`; on `n = RMSNorm(h)`: `s = sigmoid(W_r n)` over all published
  experts in float32 in every pass, the 8 largest of `s + b`, weights `s` of the
  chosen over their sum (`norm_topk_prob`) times `routed_scaling_factor`; SwiGLU
  experts, of which only those this chip HOLDS are summed (`held`: the same
  share as the program); plus the shared expert; `h <- h + m`. After the last
  layer an RMSNorm and the untied head.

Departures from the paper: none in the mathematics; the state's dtype (float32)
is the configuration's choice. `precision` is "f32" (the reference), "fp8" (the
control one step below bfloat16: every matmul operand but the router's rounded
to e4m3 with one absmax scale) or "state_bf16" (the control one step below the
float32 that the configuration states for the recurrent state: everything as
the reference, the state rounded to bfloat16 after every token).

It runs a layer at a time, a sequence at a time, attention a block of queries
at a time and the held experts one at a time, so a 10k-token sequence fits
beside one layer's float32 weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_solar_open2 as W
from benchmark.lib.reference import _ein, rms_norm, round_to_dtype
from benchmark.lib.reference_cohere2_moe import attention
from benchmark.lib.reference_glm_moe_dsa import experts

SEQ_BUCKET = 2048      # sequences are padded to a multiple of this
L2_EPS = 1e-6


def _matmuls(precision: str) -> str:
    """The precision of a pass's matmuls: the bfloat16-state control rounds
    the state alone."""
    return "f32" if precision == "state_bf16" else precision


def delta_rule(q, k, v, g, beta, state_dtype=None, snap_at=None):
    """The recurrence, a token at a time, from a zero state. `q, k, g [L, H,
    d]`, `v [L, H, dv]`, `beta [L, H]` -> `(o [L, H, dv], the state after token
    `snap_at` (the last by default) [H, d, dv])`."""
    L, H, d = k.shape
    snap_at = L - 1 if snap_at is None else snap_at

    def one(carry, x):
        S, snap = carry
        t, q, k, v, g, beta = x
        S1 = S * jnp.exp(g)[..., None]
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S1, k,
                                            precision=jax.lax.Precision.HIGHEST))
        S2 = S1 + k[..., None] * u[:, None, :]
        if state_dtype is not None:
            S2 = round_to_dtype(S2, state_dtype)
        o = jnp.einsum("hkv,hk->hv", S2, q, precision=jax.lax.Precision.HIGHEST)
        return (S2, jnp.where(t == snap_at, S2, snap)), o

    zero = jnp.zeros((H, d, v.shape[-1]), jnp.float32)
    (_, snap), o = jax.lax.scan(one, (zero, zero),
                                (jnp.arange(L), q, k, v, g, beta))
    return o, snap


def conv_silu(x, w):
    """x [L, C], w [C, taps] (the last tap on the token itself): causal, zeros
    before the first token, then SiLU."""
    L, taps = x.shape[0], w.shape[1]
    seq = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return jax.nn.silu(sum(seq[j:j + L] * w[:, j] for j in range(taps)))


def kda_rows(lp, n, cfg, mp="f32"):
    """What the recurrence of a KDA layer reads for normed rows `n [L, hidden]`:
    `q, k, v, g [L, H, d]` and `beta [L, H]`."""
    H, d, _, _ = W.linear_dims(cfg)
    L = n.shape[0]
    heads = lambda x: x.reshape(L, H, d)
    q, k, v = (heads(conv_silu(_ein("lh,dh->ld", n, lp[p], mp), lp[p + "_conv"]))
               for p in ("q", "k", "v"))
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * d ** -0.5, unit(k)
    raw = _ein("lr,dr->ld", _ein("lh,rh->lr", n, lp["f_a"], mp), lp["f_b"], mp)
    g = -jnp.exp(lp["A_log"])[:, None] * heads(jax.nn.softplus(raw + lp["dt_bias"]))
    beta = jax.nn.sigmoid(_ein("lh,jh->lj", n, lp["b"], mp))
    if cfg.get("kda_allow_neg_eigval", True):
        beta = 2.0 * beta
    return q, k, v, g, beta


def kda_mixer(lp, n, cfg, precision, snap_at=None):
    """`(y [L, hidden], the layer's state after token snap_at)`."""
    H, d, _, _ = W.linear_dims(cfg)
    L = n.shape[0]
    mp = _matmuls(precision)
    q, k, v, g, beta = kda_rows(lp, n, cfg, mp)
    o, snap = delta_rule(q, k, v, g, beta,
                         jnp.bfloat16 if precision == "state_bf16" else None,
                         snap_at)
    o = rms_norm(o, lp["o_norm"], cfg["rms_norm_eps"]).reshape(L, H * d)
    gate = jax.nn.sigmoid(_ein("lr,dr->ld", _ein("lh,rh->lr", n, lp["g_a"], mp),
                               lp["g_b"], mp))
    return _ein("ld,hd->lh", o * gate, lp["o"], mp), snap


def gqa_mixer(lp, n, cfg, precision):
    L = n.shape[0]
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _ein("lh,dh->ld", n, lp["q"], precision).reshape(L, kvh, nh // kvh, d)
    k = _ein("lh,dh->ld", n, lp["k"], precision).reshape(L, kvh, d)
    v = _ein("lh,dh->ld", n, lp["v"], precision).reshape(L, kvh, d)
    a = attention(q, k, v, None, precision)
    if cfg.get("use_gqa_gate", True):
        a = a * jax.nn.sigmoid(_ein("lh,dh->ld", n, lp["g"], precision))
    return _ein("ld,hd->lh", a, lp["o"], precision)


def layer_forward(lp, h, cfg, kind, held, precision="f32", snap_at=None):
    """One layer of `kind` ("kda" or "gqa") over h [L, H] (float32), positions
    0..L-1. Returns (h', router margin [L], the KDA state after token `snap_at`
    or None)."""
    lp = {k: v if k.startswith("experts_") else v.astype(jnp.float32)
          for k, v in lp.items()}
    n = rms_norm(h, lp["in_norm"], cfg["rms_norm_eps"])
    snap = None
    if kind == "kda":
        y, snap = kda_mixer(lp, n, cfg, precision, snap_at)
    else:
        y = gqa_mixer(lp, n, cfg, _matmuls(precision))
    h = h + y
    m, margin = experts(lp, rms_norm(h, lp["post_norm"], cfg["rms_norm_eps"]),
                        cfg, held, _matmuls(precision))
    return h + m, margin, snap


def head_logits(final_norm, head, h, cfg, precision="f32"):
    x = rms_norm(h, final_norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return _ein("lh,vh->lv", x, head.astype(jnp.float32), _matmuls(precision))


def forward_logits(cfg, layers, embed, final_norm, head, ids, held,
                   precision="f32"):
    """Whole forward of one sequence from given leaves (tests): ids [L] ->
    (logits [L, V], {layer: its KDA state after the last token})."""
    h = jnp.take(embed, ids, axis=0).astype(jnp.float32)
    states = {}
    for i, lp in enumerate(layers):
        h, _, snap = layer_forward(lp, h, cfg, W.layer_kind(cfg, i), held,
                                   precision)
        if snap is not None:
            states[i] = snap
    return head_logits(final_norm, head, h, cfg, precision), states


def served_logit_gaps(cfg, seed, sequences, n_prompt, out_pad, dtype,
                      control=None, state_of=None):
    """As `lib.reference_cohere2_moe.served_logit_gaps`: for each sequence
    (prompt, then the served tokens), at the positions that produced each served
    token, `gap = (best reference logit - reference logit of the served token) /
    std of that position's logits`, `margin` the least over the layers of the
    router's distance between its k-th and (k+1)-th biased score; with `control`
    also `control_gap`, the same for the token a pass in that precision puts
    first. With `state_of = (i, tokens)`, row `i` also holds `state`: the KDA
    layers' states after the first `tokens` tokens of sequence `i`, `{layer: [H,
    d, d]}` (and `control_state` in the control's pass), and `rows`: what the
    LAST KDA layer's recurrence read for those tokens in the float32 pass (`q, k,
    v, g [tokens, H, d]`, `beta [tokens, H]`, on the device).

    A sequence is padded to a multiple of `SEQ_BUCKET` (a causal model's earlier
    positions never see the padding), so few programs are compiled."""
    held = W.experts_held(cfg)
    layer_of = W.make_layer(cfg, dtype)
    embed, final_norm, head = W.make_ends(cfg, dtype)(seed)
    passes = ["f32"] + ([control] if control else [])
    n_layers = cfg["num_hidden_layers"]

    @functools.partial(jax.jit, static_argnames=("prec", "kind"), donate_argnums=1)
    def step(lp, h, snap_at, prec, kind):
        return layer_forward(lp, h, cfg, kind, held, prec, snap_at)

    @functools.partial(jax.jit, static_argnames="prec")
    def gaps(final_norm, head, h, h_low, idx, served, prec):
        ref = head_logits(final_norm, head, h[idx], cfg, "f32")
        best, std = jnp.max(ref, -1), jnp.std(ref, -1)
        took = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
        if prec is None:
            return (best - took) / std, None
        low = head_logits(final_norm, head, h_low[idx], cfg, prec)
        pick = jnp.argmax(low, -1)
        return ((best - took) / std,
                (best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]) / std)

    last_kda = max(i for i in range(n_layers) if W.layer_kind(cfg, i) == "kda")

    @jax.jit
    def rows_of(lp, h):
        lp = {k: v.astype(jnp.float32) for k, v in lp.items()
              if not k.startswith("experts_")}
        return kda_rows(lp, rms_norm(h, lp["in_norm"], cfg["rms_norm_eps"]), cfg)

    hs, margins, states = {p: [] for p in passes}, [], {p: {} for p in passes}
    probe_rows = None
    for s in sequences:
        ids = np.zeros(-(-len(s) // SEQ_BUCKET) * SEQ_BUCKET, np.int32)
        ids[:len(s)] = s
        h0 = jnp.take(embed, jnp.asarray(ids), axis=0).astype(jnp.float32)
        for p in passes:
            hs[p].append(h0 + 0.0)
        margins.append(None)
    for li in range(n_layers):
        lp = layer_of(seed, li)
        for p in passes:
            for i in range(len(sequences)):
                at = state_of[1] - 1 if state_of and state_of[0] == i \
                    else len(sequences[i]) - 1
                if li == last_kda and p == "f32" and state_of \
                        and state_of[0] == i:
                    probe_rows = tuple(x[:state_of[1]]
                                       for x in rows_of(lp, hs[p][i]))
                hs[p][i], mg, snap = step(lp, hs[p][i], jnp.int32(at), prec=p,
                                          kind=W.layer_kind(cfg, li))
                if snap is not None and state_of and state_of[0] == i:
                    states[p][li] = np.asarray(snap)
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
        g, gc = gaps(final_norm, head, hs["f32"][i],
                     hs[control][i] if control else None,
                     jnp.asarray(idx), jnp.asarray(served), prec=control)
        row = {"gap": np.asarray(g)[:n_out], "margin": margins[i][idx[:n_out]]}
        if control:
            row["control_gap"] = np.asarray(gc)[:n_out]
        if state_of and state_of[0] == i:
            row["state"] = states["f32"]
            row["rows"] = probe_rows
            if control:
                row["control_state"] = states[control]
        out.append(row)
    return out


def state_gap(got, ref) -> float:
    """Relative Frobenius distance of a state `[H, d, d]` from the reference's."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / max(np.linalg.norm(ref), 1e-30))
