"""The plain reference of the GLM-MoE-DSA decoder (GLM-5.2): float32
`jax.numpy`, every matmul at `highest` precision, no kernel, no cache, no
absorption, no sorting of rows by expert. It imports nothing of the program
under test: weights come from `lib.weights_glm_moe_dsa` and the seed.

The layer, from the public `config.json` (`model_type: glm_moe_dsa`; the family
is DeepSeek-V3.2's, whose indexer and latent attention it keeps); each inference
is listed under `assumed` in the configuration file. `x = RMSNorm(h)`, eps 1e-5,
pre-norm residual blocks, attention then MLP:

- latent attention: `c_q = RMSNorm(x W_qa)`; `q = c_q W_qb` -> 64 heads x (192
  nope + 64 rope); `[c_kv ; k_r] = x W_kva`; `c_kv = RMSNorm(c_kv)`; `k_rope =
  RoPE(k_r)`, one for all heads; `q_rope = RoPE(q_rope)`; theta 8e6, interleaved
  pairs. `[k_nope ; v]_head = c_kv W_kvb` -> 64 x (192 + 256), EXPANDED here as
  the equations read. `score(t, s) = (q_nope . k_nope + q_rope . k_rope) /
  sqrt(256)`, softmax over `s` in `S_t`, `o = sum p v`, `a = concat(o) W_o`;
- indexer (`indexer_types[i] == "full"`): `q^I = c_q W^I_qb` -> 32 heads x 128;
  `k^I = LayerNorm(x W^I_k)` with weight and bias; RoPE on the first 64 of the
  128; `w = x W^I_w * 32^-1/2 * 128^-1/2`; `I(t, s) = sum_j w_j relu(q^I_j .
  k^I(s))`, `s <= t`. `S_t` = the 2048 largest `I(t, .)`, a tie going to the
  lower position, all of `s <= t` while `t < 2048` (`select`, the one place the
  rule is written here);
- `indexer_types[i] == "shared"`: `S_t` of the nearest `full` layer before it;
- feed-forward on `RMSNorm(h + a)`: a SwiGLU of 12288 in a `dense` layer; in a
  `sparse` one `s = sigmoid(x W_r)` in float32 in every pass (the controls too),
  the 8 largest of `s + b`, weights `s_i / sum of the chosen * 2.5`, each expert a
  SwiGLU of 2048, one shared expert added. The reference is given the same share
  of the experts as the chip under test (`held`); what the absent experts would
  add is left out of both;
- final RMSNorm, untied `lm_head`.

Departures: no rotation and no 8-bit quantisation of the indexer's `q` and `k`
(an orthogonal rotation leaves every dot product as it is; the quantisation is an
implementation's); the multi-token-prediction module is left out; text only,
greedy decoding.

It runs a layer at a time, a sequence at a time, a block of queries at a time and
a group of heads at a time, so a 49k-token sequence fits beside one layer's
float32 weights. `precision` is "f32" (the reference) or "fp8" (the control one
step below bfloat16: every matmul operand but the router's rounded to e4m3 with
one absmax scale).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights_glm_moe_dsa as W
from benchmark.lib.reference import _HI, _ein, rms_norm

Q_BLOCK = 64           # queries the indexer scores and selects for at once
ATTN_BLOCK = 128       # queries attended at once
HEAD_GROUP = 8         # heads whose keys and values are expanded at once
SEQ_BUCKET = 2048      # sequences are padded to a multiple of this


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def rope_pairs(x, positions, theta, width=None):
    """x [L, heads, D], positions [L]: pairs (2i, 2i+1) of the first `width`
    columns rotated (all of them by default), the rest passed."""
    width = x.shape[-1] if width is None else width
    d2 = width // 2
    inv = 1.0 / (theta ** (jnp.arange(d2, dtype=jnp.float32) / d2))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0:width:2], x[..., 1:width:2]
    rot = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1).reshape(x.shape[:-1] + (width,))
    return jnp.concatenate([rot, x[..., width:]], axis=-1)


def select(scores, visible, k: int):
    """THE selection rule: of `scores [B, L]` the `k` largest among `visible`, a
    tie going to the lower position (`lax.top_k` lists equal values by rising
    index); every visible position where fewer than `k` are. Returns `(idx [B,
    k'], ok [B, k'], kth [B], nxt [B])` with `k' = min(k, L)`: the positions,
    which of them count, the value of the last that counts and of the first left
    out (`-inf` where nothing is left out)."""
    # -0.0 is 0.0: a sort that orders floats by their bits must not part them
    masked = jnp.where(visible, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    take = min(k + 1, masked.shape[-1])
    top, idx = jax.lax.top_k(masked, take)
    if take <= k:
        top = jnp.pad(top, ((0, 0), (0, 1)), constant_values=-jnp.inf)
        idx = jnp.pad(idx, ((0, 0), (0, 1)))
    ok = jnp.isfinite(top[:, :-1])
    n = jnp.sum(ok, -1)
    kth = jnp.take_along_axis(top, jnp.maximum(n - 1, 0)[:, None], -1)[:, 0]
    return idx[:, :-1], ok, kth, top[:, -1]


def _blocks(l: int, block: int = Q_BLOCK) -> tuple:
    blk = min(block, l)
    return blk, -(-l // blk)


def index_keys(lp, x, cfg, precision):
    """`k^I [L, D]` of one sequence's normed rows `x [L, H]`: the projection,
    the LayerNorm with weight and bias, rope on the first columns."""
    k = layer_norm(_ein("lh,dh->ld", x, lp["index_k"], precision),
                   lp["index_k_norm"], lp["index_k_bias"],
                   cfg.get("index_norm_eps", 1e-6))
    return rope_pairs(k[:, None, :], jnp.arange(x.shape[0]),
                      cfg["rope_parameters"]["rope_theta"],
                      cfg["qk_rope_head_dim"])[:, 0, :]


def indexer(lp, x, c_q, cfg, precision):
    """The selection of every position of one sequence: `(idx [L, k'], ok [L,
    k'], scores-of-note)`, a block of queries at a time. `x [L, H]` normed rows,
    `c_q [L, q_lora_rank]`."""
    l = x.shape[0]
    j, d, rope = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    theta = cfg["rope_parameters"]["rope_theta"]
    pos = jnp.arange(l)
    k = index_keys(lp, x, cfg, precision)
    w = _ein("lh,jh->lj", x, lp["index_w"], precision) * (j ** -0.5 * d ** -0.5)
    blk, n_blocks = _blocks(l)
    pad = n_blocks * blk - l
    c_q = jnp.pad(c_q, ((0, pad), (0, 0)))
    w = jnp.pad(w, ((0, pad), (0, 0)))

    def block(q0):
        rows = q0 + jnp.arange(blk)
        q = _ein("lr,dr->ld", jax.lax.dynamic_slice_in_dim(c_q, q0, blk),
                 lp["index_q_b"], precision).reshape(blk, j, d)
        q = rope_pairs(q, rows, theta, rope)
        s = jnp.maximum(_ein("ljd,md->ljm", q, k, precision), 0.0)
        score = jnp.einsum("ljm,lj->lm", s, jax.lax.dynamic_slice_in_dim(w, q0, blk),
                           precision=_HI)
        visible = pos[None, :] <= rows[:, None]
        idx, ok, kth, nxt = select(score, visible, cfg["index_topk"])
        n = jnp.sum(visible, -1, keepdims=True)     # s.d. of the visible scores
        mean = jnp.sum(jnp.where(visible, score, 0.0), -1, keepdims=True) / n
        var = jnp.sum(jnp.where(visible, jnp.square(score - mean), 0.0), -1,
                      keepdims=True) / n
        return idx, ok, kth, nxt, jnp.sqrt(var[:, 0])

    idx, ok, kth, nxt, std = jax.lax.map(block, jnp.arange(n_blocks) * blk)
    flat = lambda a: a.reshape((n_blocks * blk,) + a.shape[2:])[:l]
    return flat(idx), flat(ok), flat(kth), flat(nxt), flat(std)


def index_scores_at(lp, x, c_q, cfg, rows, cols, precision="f32"):
    """`I(rows[i], cols[i, :])` of one sequence: the indexer's scores of the
    positions `cols [R, K]` for the query rows `rows [R]` (for the comparison
    of a program's selected set with the reference's scores)."""
    j, d, rope = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    theta = cfg["rope_parameters"]["rope_theta"]
    k = index_keys(lp, x, cfg, precision)
    w = _ein("lh,jh->lj", x[rows], lp["index_w"], precision) * (j ** -0.5 * d ** -0.5)
    q = _ein("lr,dr->ld", c_q[rows], lp["index_q_b"], precision).reshape(-1, j, d)
    q = rope_pairs(q, rows, theta, rope)

    def one(args):
        qr, wr, cr = args
        s = jnp.maximum(_ein("jd,kd->jk", qr, k[cr], precision), 0.0)
        return jnp.einsum("jk,j->k", s, wr, precision=_HI)
    return jax.lax.map(one, (q, w, cols))


def attention(lp, c_q, c_kv, k_rope, idx, ok, cfg, precision):
    """`a = concat(o) W_o [L, H]` of one sequence: keys and values expanded from
    `c_kv` a group of heads at a time, a block of queries at a time against
    every position under the selection's mask; each group's heads go through
    their columns of `W_o` as they come (`concat(o)` of a 40k-token sequence
    would be 2.8 GB)."""
    l = c_q.shape[0]
    nh, nope, rope, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                          cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    theta = cfg["rope_parameters"]["rope_theta"]
    grp = min(HEAD_GROUP, nh)
    blk, n_blocks = _blocks(l, ATTN_BLOCK)
    pad = n_blocks * blk - l
    c_q = jnp.pad(c_q, ((0, pad), (0, 0)))
    idx = jnp.pad(idx, ((0, pad), (0, 0)))
    ok = jnp.pad(ok, ((0, pad), (0, 0)))
    groups = nh // grp
    w_q = lp["q_b"].reshape(groups, grp, nope + rope, -1)
    w_kv = lp["kv_b"].reshape(groups, grp, nope + vd, rank)
    w_o = jnp.moveaxis(lp["o"].reshape(-1, groups, grp, vd), 1, 0)

    def group(a, ws):       # one group of heads at a time: a scan, so that
        wq, wkv, wo = ws    # only one group's keys and values are live
        k_nope = _ein("lc,hdc->lhd", c_kv, wkv[:, :nope], precision)
        v = _ein("lc,hvc->lhv", c_kv, wkv[:, nope:], precision)

        def block(q0):
            rows = q0 + jnp.arange(blk)
            q = _ein("lr,hdr->lhd", jax.lax.dynamic_slice_in_dim(c_q, q0, blk),
                     wq, precision)
            q_rope = rope_pairs(q[..., nope:], rows, theta)
            s = (_ein("lhd,mhd->hlm", q[..., :nope], k_nope, precision)
                 + _ein("lhr,mr->hlm", q_rope, k_rope, precision)) \
                / np.sqrt(nope + rope)
            picked = jnp.zeros((blk, l), jnp.int32).at[
                jnp.arange(blk)[:, None],
                jax.lax.dynamic_slice_in_dim(idx, q0, blk)].max(
                jax.lax.dynamic_slice_in_dim(ok, q0, blk).astype(jnp.int32)) > 0
            p = jax.nn.softmax(jnp.where(picked[None], s, -jnp.inf), axis=-1)
            # a padded query row has nothing picked: its row of p is NaN and
            # is cut off below
            return _ein("hlm,mhv->lhv", p, v, precision)

        o = jax.lax.map(block, jnp.arange(n_blocks) * blk)
        return a + _ein("lgv,hgv->lh", o.reshape(n_blocks * blk, grp, vd)[:l],
                        wo, precision), None

    a, _ = jax.lax.scan(group, jnp.zeros((l, lp["o"].shape[0]), jnp.float32),
                        (w_q, w_kv, w_o))
    return a


def route(x, w_router, bias, top_k, normalize, scale):
    """float32 sigmoid scores over all experts, the `top_k` largest of `score +
    bias` -> (weight [L, E] with zeros off the chosen, margin [L] between the
    k-th and the next biased score)."""
    s = jax.nn.sigmoid(jnp.einsum("lh,eh->le", x, w_router, precision=_HI))
    top, _ = jax.lax.top_k(s + bias, top_k + 1)
    chosen = (s + bias) >= top[:, top_k - 1:top_k]
    w = jnp.where(chosen, s, 0.0)
    if normalize:
        w = w / jnp.sum(w, -1, keepdims=True)
    return w * scale, top[:, top_k - 1] - top[:, top_k]


def swiglu(x, gate, up, down, precision):
    act = jax.nn.silu(_ein("lh,ih->li", x, gate, precision)) \
        * _ein("lh,ih->li", x, up, precision)
    return _ein("li,hi->lh", act, down, precision)


def experts(lp, x, cfg, held, precision):
    """A sparse layer's `m [L, H]`: the held experts' part of the routed sum
    plus the shared expert; and the router's margin a position."""
    inter = cfg["moe_intermediate_size"]
    w, margin = route(x, lp["router"], lp["router_bias"],
                      cfg["num_experts_per_tok"], cfg.get("norm_topk_prob", True),
                      cfg.get("routed_scaling_factor", 1.0))

    def one(m, xs):
        gate_up, down, we = xs          # an expert's matrices, cast here
        gu = _ein("lh,hf->lf", x, gate_up.astype(jnp.float32), precision)
        act = jax.nn.silu(gu[:, :inter]) * gu[:, inter:]
        return m + we[:, None] * _ein("li,ih->lh", act,
                                      down.astype(jnp.float32), precision), None

    lo, hi = held
    m, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (lp["experts_gate_up"], lp["experts_down"], w[:, lo:hi].T))
    return m + swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
                      precision), margin


def layer_kinds(cfg, li: int) -> tuple:
    """(`full` or `shared`, `dense` or `sparse`) of layer `li`."""
    return cfg["indexer_types"][li], cfg["mlp_layer_types"][li]


def layer_forward(lp, h, cfg, kinds, held, sel=None, precision="f32",
                  keep_inputs=False):
    """A layer of `kinds` (`layer_kinds`) over one sequence `h [L, H]` (float32),
    positions 0..L-1. `sel` is the selection handed on by the nearest `full`
    layer before a `shared` one. Returns `(h', sel, notes)`: `notes` holds `router_margin [L]`
    (inf in a dense layer) and, on a `full` layer, `kth`, `nxt`, `std [L]` of
    its index scores (with `keep_inputs` also the indexer's inputs `x`, `c_q`,
    for `index_scores_at`). The stacked experts stay in the dtype they came
    in and are cast one at a time."""
    l = h.shape[0]
    eps, rank = cfg["rms_norm_eps"], cfg["kv_lora_rank"]
    theta = cfg["rope_parameters"]["rope_theta"]
    lp = {k: v if k.startswith("experts_") else v.astype(jnp.float32)
          for k, v in lp.items()}
    x = rms_norm(h, lp["in_norm"], eps)
    c_q = rms_norm(_ein("lh,rh->lr", x, lp["q_a"], precision), lp["q_a_norm"], eps)
    kv = _ein("lh,ch->lc", x, lp["kv_a"], precision)
    c_kv = rms_norm(kv[:, :rank], lp["kv_a_norm"], eps)
    k_rope = rope_pairs(kv[:, None, rank:], jnp.arange(l), theta)[:, 0, :]
    notes = {}
    if kinds[0] == "full":
        idx, ok, kth, nxt, std = indexer(lp, x, c_q, cfg, precision)
        sel = (idx, ok)
        notes.update(kth=kth, nxt=nxt, std=std)
        if keep_inputs:
            notes.update(x=x, c_q=c_q)
    h = h + attention(lp, c_q, c_kv, k_rope, sel[0], sel[1], cfg, precision)
    x = rms_norm(h, lp["post_norm"], eps)
    if kinds[1] == "dense":
        m = swiglu(x, lp["gate"], lp["up"], lp["down"], precision)
        notes["router_margin"] = jnp.full((l,), jnp.inf)
    else:
        m, notes["router_margin"] = experts(lp, x, cfg, held, precision)
    return h + m, sel, notes


def head_logits(final_norm, head, h, cfg, precision="f32"):
    x = rms_norm(h, final_norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return _ein("lh,vh->lv", x, head.astype(jnp.float32), precision)


def forward_logits(cfg, layers, embed, final_norm, head, ids, held,
                   precision="f32"):
    """Whole forward of one sequence from given leaves (tests): ids [L] ->
    logits [L, V]."""
    h = jnp.take(embed, ids, axis=0).astype(jnp.float32)
    sel = None
    for i, lp in enumerate(layers):
        h, sel, _ = layer_forward(lp, h, cfg, layer_kinds(cfg, i), held, sel,
                                  precision)
    return head_logits(final_norm, head, h, cfg, precision)


def served_logit_gaps(cfg, seed, sequences, n_prompt, out_pad, dtype,
                      control=None, selections=None):
    """As `lib.reference.served_logit_gaps`: for each sequence (prompt, then the
    served tokens), at the positions that produced each served token, `gap =
    (best reference logit - reference logit of the served token) / std of that
    position's logits`; with `control` also `control_gap`, the same for the token
    a pass in that precision puts first. Each row also holds `margin` (at that
    position, the least over the sparse layers of the distance between the
    router's k-th and (k+1)-th biased score) and `sel_margin` (the least over the
    `full` layers of the distance between the last index score kept and the
    first left out, over the standard deviation of the position's visible
    scores; inf while everything visible is kept).

    `selections` {sequence index: {layer: (idx [n_out, K], n_sel [n_out])}} gives
    a program's selected positions for the served positions of some sequences;
    their rows then hold `sel_short {layer: [n_out, K]}`: for each selected
    position how far its REFERENCE index score lies below the reference's last
    kept one, over the same standard deviation (0 for a position inside the
    reference's set; positions behind `n_sel` read 0), from which the caller
    takes `index_set_overlap` at the widening it allows; and `sel_upstream
    {layer: [n_out, K]}`: for each such (row, selected position) pair the lesser
    of the two positions' router margins over the sparse layers BEFORE that
    layer (inf before the first), which says whether an expert chosen the other
    way on rounding can have moved the pair's index score.

    A sequence is padded to a multiple of `SEQ_BUCKET` (causal attention never
    lets a position see the padding), so few programs are compiled."""
    held = W.experts_held(cfg)
    layer_of = W.make_layer(cfg, dtype)
    embed, final_norm, head = W.make_ends(cfg, dtype)(seed)
    passes = ["f32"] + ([control] if control else [])
    selections = selections or {}

    # one program a kind of layer, a length and a precision: layers of one
    # kind share theirs
    @functools.partial(jax.jit, static_argnames=("prec", "kinds", "keep"),
                       donate_argnums=1)
    def step(lp, h, sel, prec, kinds, keep=False):
        return layer_forward(lp, h, cfg, kinds, held, sel, prec, keep)

    @jax.jit
    def shortfall(lp, x, c_q, rows, cols, n_sel, kth, std):
        lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
        got = index_scores_at(lp, x, c_q, cfg, rows, cols)
        short = jnp.maximum(kth[rows][:, None] - got, 0.0) / std[rows][:, None]
        return jnp.where(jnp.arange(cols.shape[1])[None, :] < n_sel[:, None],
                         short, 0.0)

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

    # a sequence at a time, a layer at a time (each layer's leaves are drawn
    # again for each sequence: a 40k-token sequence's float32 rows and one
    # layer's weights are what the device holds)
    out = []
    for i, s in enumerate(sequences):
        ids = np.zeros(-(-len(s) // SEQ_BUCKET) * SEQ_BUCKET, np.int32)
        ids[:len(s)] = s
        n_out = len(s) - n_prompt[i]
        rows = np.arange(n_prompt[i] - 1, len(s) - 1)
        hs, margin, sel_margin, shorts, upstream = {}, None, None, {}, {}
        for p in passes:
            h = jnp.take(embed, jnp.asarray(ids), axis=0).astype(jnp.float32)
            sel = None
            for li in range(cfg["num_hidden_layers"]):
                keep = p == "f32" and li in selections.get(i, {})
                h, sel, notes = step(layer_of(seed, li), h, sel, prec=p,
                                     kinds=layer_kinds(cfg, li), keep=keep)
                if p != "f32":
                    continue
                if keep:        # the router margins of the layers before
                    before = np.full(len(ids), np.inf, np.float32) \
                        if margin is None else margin
                    cols = selections[i][li][0][:n_out]
                    upstream[li] = np.minimum(before[rows][:, None], before[cols])
                m = np.asarray(notes["router_margin"])
                margin = m if margin is None else np.minimum(margin, m)
                if "kth" in notes:
                    m = np.asarray((notes["kth"] - notes["nxt"]) / notes["std"])
                    sel_margin = m if sel_margin is None \
                        else np.minimum(sel_margin, m)
                if keep:
                    cols, n_sel = selections[i][li]
                    shorts[li] = np.asarray(shortfall(
                        layer_of(seed, li), notes["x"], notes["c_q"],
                        jnp.asarray(rows), jnp.asarray(cols[:n_out]),
                        jnp.asarray(n_sel[:n_out]), notes["kth"], notes["std"]))
                del notes
            hs[p] = h
        # logits at position p predict token p+1
        idx = np.full(out_pad, n_prompt[i] - 1, np.int32)
        idx[:n_out] = rows
        served = np.zeros(out_pad, np.int32)
        served[:n_out] = s[n_prompt[i]:]
        g, gc = gaps(final_norm, head, hs["f32"],
                     hs[control] if control else None,
                     jnp.asarray(idx), jnp.asarray(served), prec=control)
        row = {"gap": np.asarray(g)[:n_out], "margin": margin[idx[:n_out]],
               "sel_margin": sel_margin[idx[:n_out]]}
        if control:
            row["control_gap"] = np.asarray(gc)[:n_out]
        if shorts:
            row["sel_short"], row["sel_upstream"] = shorts, upstream
        out.append(row)
        del hs
    return out
