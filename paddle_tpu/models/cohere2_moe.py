"""Cohere2-MoE (``model_type: cohere2_moe``, Command A+): a decoder whose
block is PARALLEL (attention and experts read one LayerNorm output and are
both added to the residual), whose attention layers are of two kinds in a
fixed period (sliding-window layers with interleaved rope, then one global
layer with no positional encoding), and whose feed-forward is a mixture of
sigmoid-routed experts beside shared experts whose outputs are averaged.

The layer, as this file computes it (``described_as`` and ``config`` of the
public ``config.json``; each inference is listed under ``assumed`` in
``benchmark/configs/command-a-plus-05-2026.ep8.d4.json``):

- ``n = LayerNorm(x)``: mean subtracted, divided by ``sqrt(var + eps)``,
  times a weight, no bias;
- attention on ``n``: ``q = n W_q`` (heads x head_dim), ``k, v`` (KV heads x
  head_dim), no bias, no QK norm, scale ``1/sqrt(head_dim)``. Sliding
  layers: rope over the whole head in interleaved pairs ``(2i, 2i+1)``
  (GPT-J), key ``j`` visible to query ``i`` iff ``i - W < j <= i``. Full
  layers: no positional encoding, plain causal. ``a = concat(heads) W_o``;
- experts on the same ``n``: ``s = sigmoid(n W_r)`` over all experts in
  float32, the ``k`` largest, weights ``s_e / sum of the k``;
  ``E(n) = (silu(n W_g) * (n W_u)) W_d``; ``m = sum_topk w_e E_e(n) +
  mean_s S_s(n)`` over the shared experts;
- ``x' = x + a + m``. After the last layer a LayerNorm, then ``logits =
  h E^T * logit_scale`` with the embedding tied.

**A share of the experts.** ``experts_held=(lo, hi)`` builds the layer as
one chip of an expert-parallel deployment holds it: the router, attention,
shared experts and norms whole, the routed experts ``lo..hi-1`` only. The
routed sum then covers the held experts alone (``incubate/moe_share.py``);
what the absent ones would add is added where they live.

Weights are kept in the layout the serving engine computes in (projections
``[out, in]`` as the published checkpoints store them, experts stacked
``[E, H, 2I]`` / ``[E, I, H]``), so the engine binds these arrays and never
holds a second copy. Text only (no vision tower), greedy decoding.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..incubate import moe_share
from ..nn import initializer as I
from ..nn.layer import Layer

__all__ = ["Cohere2MoeConfig", "Cohere2MoeForCausalLM"]


@dataclass
class Cohere2MoeConfig:
    """Defaults are ``CohereLabs/command-a-plus-05-2026``'s published ones."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096          # one expert's width (routed, shared)
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 4096
    layer_switch: int = 4                  # every 4th layer is a full one
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    tie_word_embeddings: bool = True
    dtype: str = "float32"

    def layer_kind(self, i: int) -> str:
        """``order_of_interleaved_layers: local_attn_first``: sliding
        layers, then the period's last is the full one."""
        return "full" if (i + 1) % self.layer_switch == 0 else "window"

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, sliding_window=8,
                    num_experts=8, num_experts_per_tok=2,
                    num_shared_experts=2, max_position_embeddings=128)
        base.update(kw)
        return Cohere2MoeConfig(**base)


# ---------------------------------------------------------------------------
# the layer's pieces, on arrays
# ---------------------------------------------------------------------------

def layer_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


def rope_interleaved(x, positions, theta):
    """x [..., T, heads, D] rotated at ``positions [..., T]`` in pairs
    ``(2i, 2i+1)`` over the whole head (``rope_gptj``, ``rotary_pct`` 1)."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(d2, dtype=jnp.float32) / d2))
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _mm(h, w):
    """h @ w.T for a projection stored [out, in]."""
    return jax.lax.dot_general(
        h, w, (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(h.dtype)


def qkv(cfg: Cohere2MoeConfig, lp, n, positions, kind: str):
    """q [.., T, heads, D] and k, v [.., T, KV heads, D] of normed rows ``n
    [.., T, H]``; a window layer's q and k rotated at ``positions [.., T]``,
    a full layer's left without positions."""
    lead = n.shape[:-1]
    q = _mm(n, lp["q"]).reshape(*lead, cfg.num_attention_heads, cfg.head_dim)
    k = _mm(n, lp["k"]).reshape(*lead, cfg.num_key_value_heads, cfg.head_dim)
    v = _mm(n, lp["v"]).reshape(*lead, cfg.num_key_value_heads, cfg.head_dim)
    if kind == "window":
        q = rope_interleaved(q, positions, cfg.rope_theta)
        k = rope_interleaved(k, positions, cfg.rope_theta)
    return q, k, v


def experts_block(cfg: Cohere2MoeConfig, lp, n, held, use_kernel=None):
    """``m`` of the layer for rows ``n [T, H]``: the held experts' part of
    the routed sum plus the mean of the shared experts. Returns ``(m,
    counts int32 [3])`` (``moe_share.held_experts_forward``'s counts)."""
    t = n.shape[0]
    with jax.named_scope("paged.moe.route"):
        idx, weight = moe_share.sigmoid_topk_route(
            n, lp["router"], cfg.num_experts_per_tok, cfg.norm_topk_prob)
    with jax.named_scope("paged.moe.experts"):
        routed, counts = moe_share.held_experts_forward(
            n, idx, weight, lp["experts_gate_up"], lp["experts_down"], held,
            moe_share.row_tile(t, cfg.num_experts_per_tok, cfg.num_experts),
            use_kernel=use_kernel)
    with jax.named_scope("paged.moe.shared"):
        # the shared experts side by side are one SwiGLU of their summed
        # width; their mean is its output over their number
        act = (jax.nn.silu(_mm(n, lp["shared_gate"]).astype(jnp.float32))
               * _mm(n, lp["shared_up"]).astype(jnp.float32)).astype(n.dtype)
        shared = _mm(act, lp["shared_down"]).astype(jnp.float32) \
            / cfg.num_shared_experts
    return (routed.astype(jnp.float32) + shared).astype(n.dtype), counts


def _attend_dense(cfg, q, k, v, window: Optional[int]):
    """Plain masked attention over a whole sequence [B, L, heads, D] (the
    model's own forward; serving goes through the paged seam)."""
    b, l, nh, d = q.shape
    kvh = k.shape[2]
    q = q.reshape(b, l, kvh, nh // kvh, d).astype(jnp.float32)
    s = jnp.einsum("blgrd,bmgd->bgrlm", q, k.astype(jnp.float32)) \
        / math.sqrt(d)
    i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    ok = j <= i
    if window is not None:
        ok = ok & (j > i - window)
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bgrlm,bmgd->blgrd", p, v.astype(jnp.float32))
    return o.reshape(b, l, nh * d)


# parameter names of one layer (under ``model.layers.<i>.``) by the short
# names this file and the serving adapter use
LAYER_PARAMS = {
    "norm": "input_layernorm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
    "router": "mlp.gate.weight",
    "experts_gate_up": "mlp.experts.gate_up_proj",
    "experts_down": "mlp.experts.down_proj",
    "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight",
}


def layer_shapes(cfg: Cohere2MoeConfig, n_held: int) -> dict:
    h, d, i = cfg.hidden_size, cfg.head_dim, cfg.intermediate_size
    nh, kvh, s = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.num_shared_experts)
    return {"norm": (h,), "q": (nh * d, h), "k": (kvh * d, h),
            "v": (kvh * d, h), "o": (h, nh * d),
            "router": (cfg.num_experts, h),
            "experts_gate_up": (n_held, h, 2 * i),
            "experts_down": (n_held, i, h),
            "shared_gate": (s * i, h), "shared_up": (s * i, h),
            "shared_down": (h, s * i)}


class _Params(Layer):
    """A bag of named parameters (one decoder layer's, or the ends')."""

    def __init__(self, shapes: dict, std: Optional[float]):
        super().__init__()
        for name, shape in shapes.items():
            init = I.Constant(1.0) if len(shape) == 1 else (
                I.Constant(0.0) if std is None else I.Normal(0.0, std))
            self.add_parameter(
                name,
                self.create_parameter(list(shape), default_initializer=init))


class Cohere2MoeForCausalLM(Layer):
    """The decoder with ``experts_held = (lo, hi)`` of its routed experts
    (all of them by default). ``forward(input_ids)`` is the plain full
    forward (dense masked attention); serving takes ``serve_model()``.
    ``init_std=None`` leaves the matrices zero, for a caller that loads
    every weight next (no random draw of billions of values)."""

    def __init__(self, config: Cohere2MoeConfig,
                 experts_held: Optional[Tuple[int, int]] = None,
                 init_std: Optional[float] = 0.02):
        super().__init__()
        self.config = config
        lo, hi = experts_held or (0, config.num_experts)
        if not 0 <= lo < hi <= config.num_experts:
            raise ValueError(
                f"experts_held {experts_held!r} is not a range of the "
                f"{config.num_experts} experts")
        self.experts_held = (int(lo), int(hi))
        shapes = layer_shapes(config, hi - lo)
        ends = _Params({"embed": (config.vocab_size, config.hidden_size),
                        "final_norm": (config.hidden_size,)}, init_std)
        self.add_sublayer("ends", ends)
        self._layers = []
        for i in range(config.num_hidden_layers):
            bag = _Params(shapes, init_std)
            self.add_sublayer(f"layer_{i}", bag)
            self._layers.append(bag)

    # the published names, whatever the bags are called
    def named_parameters(self, prefix="", include_sublayers=True):
        ends = dict(self.ends._parameters)
        yield "model.embed_tokens.weight", ends["embed"]
        for i, bag in enumerate(self._layers):
            for short, name in LAYER_PARAMS.items():
                yield f"model.layers.{i}.{name}", bag._parameters[short]
        yield "model.norm.weight", ends["final_norm"]

    def forward(self, input_ids):
        cfg = self.config
        params = [p for _, p in self.named_parameters()]
        names = [k for k, _ in self.named_parameters()]

        def f(ids, *arrs):
            p = serve_params(cfg, dict(zip(names, arrs)))
            return full_forward(cfg, p, ids, self.experts_held)
        return apply_op(f, input_ids, *params, op_name="cohere2_moe_forward")

    def serve_model(self):
        """What the paged serving engine asks of a model (``serving.py``,
        the seam between engine and model)."""
        return Cohere2MoeServe(self.config, self.experts_held)


def serve_params(cfg: Cohere2MoeConfig, sd, n_layers: Optional[int] = None,
                 dtype=None) -> dict:
    """name -> array state dict into the pytree the layer functions take.
    Nothing is transposed or copied: the arrays are bound as they are
    (cast only if ``dtype`` differs from what they hold)."""
    def get(name):
        try:
            v = sd[name]
        except KeyError:
            raise ValueError(f"weight state dict is missing {name!r} — not "
                             f"a checkpoint of this model") from None
        v = v._data if hasattr(v, "_data") else v
        return v if dtype is None else jnp.asarray(v, dtype)

    n_layers = n_layers or cfg.num_hidden_layers
    return {"emb": get("model.embed_tokens.weight"),
            "norm": get("model.norm.weight"),
            "layers": [{short: get(f"model.layers.{i}.{name}")
                        for short, name in LAYER_PARAMS.items()}
                       for i in range(n_layers)]}


def full_forward(cfg: Cohere2MoeConfig, params, ids, held):
    """ids [B, L] -> logits [B, L, V], every position, no cache."""
    b, l = ids.shape
    h = jnp.take(params["emb"], ids, axis=0)
    pos = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    for i, lp in enumerate(params["layers"]):
        n = layer_norm(h, lp["norm"], cfg.layer_norm_eps)
        kind = cfg.layer_kind(i)
        q, k, v = qkv(cfg, lp, n, pos, kind)
        window = cfg.sliding_window if kind == "window" else None
        a = _mm(_attend_dense(cfg, q, k, v, window).astype(h.dtype), lp["o"])
        m, _ = experts_block(cfg, lp, n.reshape(b * l, -1), held)
        h = h + a + m.reshape(b, l, -1)
    h = layer_norm(h, params["norm"], cfg.layer_norm_eps)
    return _mm(h, params["emb"]) * cfg.logit_scale


class Cohere2MoeServe:
    """The model's side of the serving seam: its cache spec (a layer's
    kind, its K and V pools by row width, KV heads and head width), its
    parameters in the engine's pytree, and one layer's step over the paged
    cache."""

    n_aux = 3            # moe_rows, moe_experts_hit, moe_max_rows a launch
    aux_names = ("moe_rows", "moe_experts_hit", "moe_max_rows")
    supports_int8 = False
    supports_speculation = False

    def __init__(self, cfg: Cohere2MoeConfig, held: Tuple[int, int]):
        self.cfg = cfg
        self.held = held

    def cache_spec(self, n_layers: int) -> list:
        cfg = self.cfg
        width = cfg.num_key_value_heads * cfg.head_dim
        return [{"kind": cfg.layer_kind(i),
                 "window": cfg.sliding_window
                 if cfg.layer_kind(i) == "window" else None,
                 "pools": {"k": width, "v": width},
                 "kv_heads": cfg.num_key_value_heads,
                 "head_dim": cfg.head_dim,
                 "q_heads": cfg.num_attention_heads}
                for i in range(n_layers)]

    def build_params(self, eng, sd) -> dict:
        return serve_params(self.cfg, sd, eng.n_layers, eng.dtype)

    def embed(self, eng, params, ids):
        return jnp.take(params["emb"], ids, axis=0).astype(eng.dtype)

    def layer(self, eng, li, lp, h, kvl, positions, tables, n_tiles, wmask,
              carry=None):
        """One parallel block over ``h [S, T, H]``: K/V written into the
        layer's pool, attention through the paged seam (a window layer
        with each row's first visible position), experts on the same
        normed rows."""
        cfg = self.cfg
        S, T, H = h.shape
        n = layer_norm(h, lp["norm"], cfg.layer_norm_eps)
        kind = cfg.layer_kind(li)
        q, k, v = qkv(cfg, lp, n, positions, kind)
        # a window layer's rows see from their first visible position on
        lower = positions - (cfg.sliding_window - 1) \
            if kind == "window" else None
        with jax.named_scope("paged.kv_write"):
            kvl = eng._write_kv(kvl, k, v, positions, tables, wmask)
        with jax.named_scope("paged.attn"):
            att = eng._sc.paged_attention(
                q, kvl["k"], kvl["v"], tables, positions,
                block_size=eng.block_size,
                n_rep=cfg.num_attention_heads // cfg.num_key_value_heads,
                n_tiles=n_tiles, use_kernel=eng._pa_kernel, lower=lower)
        a = _mm(att.reshape(S, T, -1), lp["o"])
        m, counts = experts_block(cfg, lp, n.reshape(S * T, H), self.held)
        return h + a + m.reshape(S, T, H), kvl, counts, carry

    def head(self, eng, params, h):
        h = layer_norm(h, params["norm"], self.cfg.layer_norm_eps)
        logits = _mm(h, params["emb"])
        if self.cfg.logit_scale != 1:
            logits = logits * self.cfg.logit_scale
        return logits
