"""GLM-MoE-DSA (``model_type: glm_moe_dsa``, GLM-5.2): a pre-norm decoder
whose attention is LATENT (queries and keys/values go through low-rank
bottlenecks and the cache holds one shared row ``[c_kv ; k_rope]`` a token)
and SPARSE by a learned indexer (a small scorer with a key cache of its own
picks the ``index_topk`` positions a query attends; layers without an
indexer reuse the selection of the nearest indexer layer before them), and
whose feed-forward is dense in the leading layers and then a mixture of
bias-routed sigmoid experts beside one shared expert.

The layer, as this file computes it (the public ``config.json``; each
inference is listed under ``assumed`` in
``benchmark/configs/glm-5.2.ep16.d5.json``). ``x = RMSNorm(h)``:

- latent attention: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> heads x
  (nope + rope); ``[c_kv ; k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``;
  ``k_rope = RoPE(k_r)`` (one for all heads), ``q_rope = RoPE(q_rope)``, in
  interleaved pairs; ``[k_nope ; v]_head = c_kv W_kvb``; ``score(t, s) =
  (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)``, softmax over
  ``s`` in ``S_t``, ``o = sum p v``, ``a = concat(o) W_o``. Serving keeps
  ``[c_kv ; k_rope]`` only and ABSORBS ``W_kvb``: ``q_nope W_kvb,k^T``
  against ``c_kv``, ``sum p c_kv`` through ``W_kvb,v``;
- indexer (``indexer_types[i] == "full"``): ``q^I = c_q W^I_qb`` -> index
  heads x index_head_dim; ``k^I = LayerNorm(x W^I_k)`` (weight and bias);
  RoPE on the first ``qk_rope_head_dim`` of both; ``w = x W^I_w *
  index_n_heads^-1/2 * index_head_dim^-1/2``; ``I(t, s) = sum_j w_j
  relu(q^I_j . k^I(s))``, ``s <= t``; ``S_t`` = the ``index_topk`` largest
  (ties to the lower position; all of ``s <= t`` while fewer are visible);
- ``indexer_types[i] == "shared"``: ``S_t`` of the nearest ``full`` layer
  before it;
- feed-forward on ``RMSNorm(h + a)``: SwiGLU of ``intermediate_size`` in a
  ``dense`` layer; in a ``sparse`` one ``s = sigmoid(x W_r)`` in float32,
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``,
  weights ``s_i / sum of the chosen * routed_scaling_factor``, each expert
  a SwiGLU of ``moe_intermediate_size``, one shared expert added;
- final RMSNorm, untied ``lm_head``. The multi-token-prediction module
  (``num_nextn_predict_layers``) is not built: greedy serving without a
  draft does not run it.

**A share of the experts.** ``experts_held=(lo, hi)`` builds a sparse layer
as one chip of an expert-parallel deployment holds it (``incubate/
moe_share.py``): router, attention, indexer, shared expert and norms
whole, the routed experts ``lo..hi-1`` only.

Weights are kept ``[out, in]`` as the published checkpoints store them,
experts stacked ``[E, H, 2I]`` / ``[E, I, H]``: the engine binds these
arrays. Text only, greedy decoding.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..incubate import moe_share
from ..nn.layer import Layer
from .cohere2_moe import _mm, _Params, rope_interleaved

__all__ = ["GlmMoeDsaConfig", "GlmMoeDsaForCausalLM"]


@dataclass
class GlmMoeDsaConfig:
    """Defaults are ``zai-org/GLM-5.2``'s published ones."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288         # a dense layer's width
    moe_intermediate_size: int = 2048      # one expert's (routed, shared)
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_topk_freq: int = 4
    index_skip_topk_offset: int = 3
    indexer_types: Optional[Tuple[str, ...]] = None    # None: the pattern
    first_k_dense_replace: int = 3
    mlp_layer_types: Optional[Tuple[str, ...]] = None  # None: k dense first
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    index_norm_eps: float = 1e-6           # the indexer's LayerNorm
    rope_theta: float = 8000000.0
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    def indexer_type(self, i: int) -> str:
        """``full``: the layer has an indexer; ``shared``: it attends the
        set of the nearest ``full`` layer before it. Published pattern:
        the first ``index_skip_topk_offset`` layers ``full``, then every
        ``index_topk_freq``-th."""
        if self.indexer_types is not None:
            return self.indexer_types[i]
        off, freq = self.index_skip_topk_offset, self.index_topk_freq
        return "full" if i < off or (i - off) % freq == freq - 1 \
            else "shared"

    def mlp_type(self, i: int) -> str:
        if self.mlp_layer_types is not None:
            return self.mlp_layer_types[i]
        return "dense" if i < self.first_k_dense_replace else "sparse"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """A token's row in the cache: ``[c_kv ; k_rope]``, zero-padded to
        whole 128-lane rows (576 -> 640 at the published widths). A TPU
        stores a bfloat16 row of 576 in 640 either way, or lays the pool
        out block-minor with a token's row strided, which costs a copy of
        the whole pool in and out of every launch (compiled for the v5e:
        ``[NB, bs, 576]`` comes out ``{0,2,1}``)."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    def __post_init__(self):
        if self.indexer_type(0) != "full":
            raise ValueError("layer 0 has no indexer and no layer before "
                             "it to take a selection from")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                    moe_intermediate_size=24, num_hidden_layers=5,
                    num_attention_heads=4, q_lora_rank=16, kv_lora_rank=16,
                    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                    index_n_heads=2, index_head_dim=8, index_topk=8,
                    index_skip_topk_offset=1, first_k_dense_replace=1,
                    n_routed_experts=8, num_experts_per_tok=2,
                    max_position_embeddings=128)
        base.update(kw)
        return GlmMoeDsaConfig(**base)


# ---------------------------------------------------------------------------
# the layer's pieces, on arrays
# ---------------------------------------------------------------------------

def rms_norm(x, weight, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + eps)).astype(x.dtype) * weight


def layer_norm(x, weight, bias, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps)).astype(x.dtype) \
        * weight + bias


def _rope_head(x, positions, theta, width):
    """Interleaved rope over the first ``width`` columns of ``x [..., T,
    heads, D]``; the rest pass."""
    if width == x.shape[-1]:
        return rope_interleaved(x, positions, theta)
    return jnp.concatenate(
        [rope_interleaved(x[..., :width], positions, theta), x[..., width:]],
        axis=-1)


def attention_inputs(cfg: GlmMoeDsaConfig, lp, x, positions):
    """Of normed rows ``x [.., T, H]`` at ``positions [.., T]``: ``c_q [..,
    T, q_lora_rank]``, ``q_nope [.., T, heads, nope]``, ``q_rope [.., T,
    heads, rope]`` (rotated) and the cache row ``[c_kv ; k_rope ; 0] [..,
    T, latent_width]``."""
    lead = x.shape[:-1]
    nh, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim)
    c_q = rms_norm(_mm(x, lp["q_a"]), lp["q_a_norm"], cfg.rms_norm_eps)
    q = _mm(c_q, lp["q_b"]).reshape(*lead, nh, nope + rope)
    q_rope = rope_interleaved(q[..., nope:], positions, cfg.rope_theta)
    kv = _mm(x, lp["kv_a"])
    c_kv = rms_norm(kv[..., :cfg.kv_lora_rank], lp["kv_a_norm"],
                    cfg.rms_norm_eps)
    k_rope = rope_interleaved(kv[..., None, cfg.kv_lora_rank:], positions,
                              cfg.rope_theta)[..., 0, :]
    pad = jnp.zeros(lead + (cfg.latent_width - cfg.kv_lora_rank - rope,),
                    x.dtype)
    return c_q, q[..., :nope], q_rope, jnp.concatenate([c_kv, k_rope, pad], -1)


def indexer_inputs(cfg: GlmMoeDsaConfig, lp, x, c_q, positions):
    """The indexer's ``q^I [.., T, J, D]``, ``k^I [.., T, D]`` (both with
    rope on their first ``qk_rope_head_dim`` columns) and head weights ``w
    [.., T, J]`` float32, already scaled."""
    lead = x.shape[:-1]
    J, D = cfg.index_n_heads, cfg.index_head_dim
    q = _rope_head(_mm(c_q, lp["index_q_b"]).reshape(*lead, J, D), positions,
                   cfg.rope_theta, cfg.qk_rope_head_dim)
    k = layer_norm(_mm(x, lp["index_k"]), lp["index_k_norm"],
                   lp["index_k_bias"], cfg.index_norm_eps)
    k = _rope_head(k[..., None, :], positions, cfg.rope_theta,
                   cfg.qk_rope_head_dim)[..., 0, :]
    w = jax.lax.dot_general(
        x, lp["index_w"], (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * (J ** -0.5 * D ** -0.5)
    return q, k, w


def kv_b_split(cfg: GlmMoeDsaConfig, kv_b):
    """``W_kvb [heads * (nope + v), rank]`` as ``(W_k [heads, nope, rank],
    W_v [heads, v, rank])``."""
    w = kv_b.reshape(cfg.num_attention_heads,
                     cfg.qk_nope_head_dim + cfg.v_head_dim, cfg.kv_lora_rank)
    return w[:, :cfg.qk_nope_head_dim], w[:, cfg.qk_nope_head_dim:]


def swiglu(x, gate, up, down):
    act = (jax.nn.silu(_mm(x, gate).astype(jnp.float32))
           * _mm(x, up).astype(jnp.float32)).astype(x.dtype)
    return _mm(act, down)


def experts_block(cfg: GlmMoeDsaConfig, lp, n, held, use_kernel=None):
    """A sparse layer's feed-forward for rows ``n [T, H]``: the held
    experts' part of the routed sum plus the shared expert. Returns ``(m,
    counts int32 [3])`` (``moe_share.held_experts_forward``'s counts)."""
    t = n.shape[0]
    with jax.named_scope("paged.moe.route"):
        idx, weight = moe_share.sigmoid_topk_route(
            n, lp["router"], cfg.num_experts_per_tok, cfg.norm_topk_prob,
            bias=lp["router_bias"], scale=cfg.routed_scaling_factor)
    with jax.named_scope("paged.moe.experts"):
        routed, counts = moe_share.held_experts_forward(
            n, idx, weight, lp["experts_gate_up"], lp["experts_down"], held,
            moe_share.row_tile(t, cfg.num_experts_per_tok,
                               cfg.n_routed_experts),
            use_kernel=use_kernel)
    with jax.named_scope("paged.moe.shared"):
        shared = swiglu(n, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
    return (routed.astype(jnp.float32)
            + shared.astype(jnp.float32)).astype(n.dtype), counts


def feed_forward(cfg: GlmMoeDsaConfig, li: int, lp, x, held):
    """``(m, counts or None)`` of normed rows ``x [.., H]``."""
    if cfg.mlp_type(li) == "dense":
        with jax.named_scope("paged.mlp"):
            return swiglu(x, lp["gate"], lp["up"], lp["down"]), None
    m, counts = experts_block(cfg, lp, x.reshape(-1, x.shape[-1]), held)
    return m.reshape(x.shape), counts


# parameter names of one layer (under ``model.layers.<i>.``) by the short
# names this file and the serving adapter use; a layer has the groups its
# kinds ask for
ATTN_PARAMS = {
    "in_norm": "input_layernorm.weight",
    "q_a": "self_attn.q_a_proj.weight",
    "q_a_norm": "self_attn.q_a_layernorm.weight",
    "q_b": "self_attn.q_b_proj.weight",
    "kv_a": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_a_norm": "self_attn.kv_a_layernorm.weight",
    "kv_b": "self_attn.kv_b_proj.weight",
    "o": "self_attn.o_proj.weight",
    "post_norm": "post_attention_layernorm.weight",
}
INDEX_PARAMS = {
    "index_q_b": "self_attn.indexer.wq_b.weight",
    "index_k": "self_attn.indexer.wk.weight",
    "index_k_norm": "self_attn.indexer.k_norm.weight",
    "index_k_bias": "self_attn.indexer.k_norm.bias",
    "index_w": "self_attn.indexer.weights_proj.weight",
}
DENSE_PARAMS = {
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight",
}
SPARSE_PARAMS = {
    "router": "mlp.gate.weight",
    "router_bias": "mlp.gate.e_score_correction_bias",
    "experts_gate_up": "mlp.experts.gate_up_proj",
    "experts_down": "mlp.experts.down_proj",
    "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight",
}


def layer_params(cfg: GlmMoeDsaConfig, i: int) -> dict:
    """{short name: published name} of layer ``i``."""
    out = dict(ATTN_PARAMS)
    if cfg.indexer_type(i) == "full":
        out.update(INDEX_PARAMS)
    out.update(DENSE_PARAMS if cfg.mlp_type(i) == "dense" else SPARSE_PARAMS)
    return out


def layer_shapes(cfg: GlmMoeDsaConfig, i: int, n_held: int) -> dict:
    h, nh = cfg.hidden_size, cfg.num_attention_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    out = {"in_norm": (h,), "q_a": (rq, h), "q_a_norm": (rq,),
           "q_b": (nh * cfg.qk_head_dim, rq),
           "kv_a": (rkv + cfg.qk_rope_head_dim, h), "kv_a_norm": (rkv,),
           "kv_b": (nh * (cfg.qk_nope_head_dim + cfg.v_head_dim), rkv),
           "o": (h, nh * cfg.v_head_dim), "post_norm": (h,)}
    if cfg.indexer_type(i) == "full":
        J, D = cfg.index_n_heads, cfg.index_head_dim
        out.update({"index_q_b": (J * D, rq), "index_k": (D, h),
                    "index_k_norm": (D,), "index_k_bias": (D,),
                    "index_w": (J, h)})
    if cfg.mlp_type(i) == "dense":
        inter = cfg.intermediate_size
        out.update({"gate": (inter, h), "up": (inter, h),
                    "down": (h, inter)})
    else:
        inter = cfg.moe_intermediate_size
        s = cfg.n_shared_experts * inter
        out.update({"router": (cfg.n_routed_experts, h),
                    "router_bias": (cfg.n_routed_experts,),
                    "experts_gate_up": (n_held, h, 2 * inter),
                    "experts_down": (n_held, inter, h),
                    "shared_gate": (s, h), "shared_up": (s, h),
                    "shared_down": (h, s)})
    return out


class GlmMoeDsaForCausalLM(Layer):
    """The decoder with ``experts_held = (lo, hi)`` of its routed experts
    (all of them by default). ``forward(input_ids)`` is the plain full
    forward (no cache, attention over expanded keys and values under the
    selection's mask); serving takes ``serve_model()``. ``init_std=None``
    leaves the matrices zero, for a caller that loads every weight next."""

    def __init__(self, config: GlmMoeDsaConfig,
                 experts_held: Optional[Tuple[int, int]] = None,
                 init_std: Optional[float] = 0.02):
        super().__init__()
        self.config = config
        lo, hi = experts_held or (0, config.n_routed_experts)
        if not 0 <= lo < hi <= config.n_routed_experts:
            raise ValueError(
                f"experts_held {experts_held!r} is not a range of the "
                f"{config.n_routed_experts} experts")
        self.experts_held = (int(lo), int(hi))
        ends = _Params({"embed": (config.vocab_size, config.hidden_size),
                        "final_norm": (config.hidden_size,),
                        "head": (config.vocab_size, config.hidden_size)},
                       init_std)
        self.add_sublayer("ends", ends)
        self._layers = []
        for i in range(config.num_hidden_layers):
            bag = _Params(layer_shapes(config, i, hi - lo), init_std)
            for name in ("index_k_bias", "router_bias"):   # a bias starts at 0
                p = bag._parameters.get(name)
                if p is not None:
                    p._data = jnp.zeros_like(p._data)
            self.add_sublayer(f"layer_{i}", bag)
            self._layers.append(bag)

    # the published names, whatever the bags are called
    def named_parameters(self, prefix="", include_sublayers=True):
        ends = dict(self.ends._parameters)
        yield "model.embed_tokens.weight", ends["embed"]
        for i, bag in enumerate(self._layers):
            for short, name in layer_params(self.config, i).items():
                yield f"model.layers.{i}.{name}", bag._parameters[short]
        yield "model.norm.weight", ends["final_norm"]
        yield "lm_head.weight", ends["head"]

    def forward(self, input_ids):
        cfg = self.config
        params = [p for _, p in self.named_parameters()]
        names = [k for k, _ in self.named_parameters()]

        def f(ids, *arrs):
            p = serve_params(cfg, dict(zip(names, arrs)))
            return full_forward(cfg, p, ids, self.experts_held)
        return apply_op(f, input_ids, *params, op_name="glm_moe_dsa_forward")

    def serve_model(self):
        """What the paged serving engine asks of a model (``serving.py``,
        the seam between engine and model)."""
        return GlmMoeDsaServe(self.config, self.experts_held)


def serve_params(cfg: GlmMoeDsaConfig, sd, n_layers: Optional[int] = None,
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
            "head": get("lm_head.weight"),
            "layers": [{short: get(f"model.layers.{i}.{name}")
                        for short, name in layer_params(cfg, i).items()}
                       for i in range(n_layers)]}


def selection_mask(cfg: GlmMoeDsaConfig, lp, x, c_q, pos):
    """``[B, L, L]`` bool: key ``s`` is in query ``t``'s selected set (the
    plain forward's form of the indexer: whole score matrix, ``lax.top_k``,
    whose ties also go to the lower position)."""
    b, l = pos.shape
    q, k, w = indexer_inputs(cfg, lp, x, c_q, pos)
    s = jnp.einsum("btjd,bsd->btjs", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    score = jnp.einsum("btjs,btj->bts", jnp.maximum(s, 0.0), w,
                       precision=jax.lax.Precision.HIGHEST)
    causal = jnp.arange(l)[None, :] <= jnp.arange(l)[:, None]
    score = jnp.where(causal[None], score, -jnp.inf)
    _, idx = jax.lax.top_k(score, min(cfg.index_topk, l))
    picked = jnp.zeros((b, l, l), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(l)[None, :, None],
        idx].set(True)
    return picked & causal[None]


def full_forward(cfg: GlmMoeDsaConfig, params, ids, held):
    """ids [B, L] -> logits [B, L, V], every position, no cache, keys and
    values expanded from the latent as the equations read."""
    b, l = ids.shape
    h = jnp.take(params["emb"], ids, axis=0)
    pos = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    mask = None
    for i, lp in enumerate(params["layers"]):
        x = rms_norm(h, lp["in_norm"], cfg.rms_norm_eps)
        c_q, q_nope, q_rope, row = attention_inputs(cfg, lp, x, pos)
        if cfg.indexer_type(i) == "full":
            mask = selection_mask(cfg, lp, x, c_q, pos)
        w_k, w_v = kv_b_split(cfg, lp["kv_b"])
        c_kv = row[..., :cfg.kv_lora_rank]
        k_rope = row[..., cfg.kv_lora_rank:cfg.kv_lora_rank
                     + cfg.qk_rope_head_dim]
        f32 = jnp.float32
        k_nope = jnp.einsum("bsc,hdc->bshd", c_kv.astype(f32), w_k.astype(f32))
        v = jnp.einsum("bsc,hvc->bshv", c_kv.astype(f32), w_v.astype(f32))
        s = (jnp.einsum("bthd,bshd->bhts", q_nope.astype(f32), k_nope)
             + jnp.einsum("bthr,bsr->bhts", q_rope.astype(f32),
                          k_rope.astype(f32))) / math.sqrt(cfg.qk_head_dim)
        p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhts,bshv->bthv", p, v).reshape(b, l, -1)
        h = h + _mm(o.astype(h.dtype), lp["o"])
        m, _ = feed_forward(
            cfg, i, lp, rms_norm(h, lp["post_norm"], cfg.rms_norm_eps), held)
        h = h + m
    return _mm(rms_norm(h, params["norm"], cfg.rms_norm_eps), params["head"])


class GlmMoeDsaServe:
    """The model's side of the serving seam: its cache spec (every layer a
    ``latent`` pool of ``latent_width`` a token, the
    indexer layers an ``index`` pool of ``index_head_dim`` beside it, all
    under the one ``full`` table), its parameters in the engine's pytree,
    and one layer's step over the paged cache, which hands the selection
    of an indexer layer on to the layers that share it (``carry``)."""

    aux_names = ("moe_rows", "moe_experts_hit", "moe_max_rows",
                 "dsa_selected", "dsa_visible")
    n_aux = len(aux_names)
    supports_int8 = False
    supports_speculation = False
    # a shared prefix block would hold index keys that a selection made
    # for another request reads: not built, so refused
    supports_prefix_sharing = False

    def __init__(self, cfg: GlmMoeDsaConfig, held: Tuple[int, int]):
        self.cfg = cfg
        self.held = held
        self.select_k = cfg.index_topk    # positions a row attends at most

    def cache_spec(self, n_layers: int) -> list:
        cfg = self.cfg
        specs = []
        for i in range(n_layers):
            pools = {"latent": cfg.latent_width}
            if cfg.indexer_type(i) == "full":
                pools["index"] = cfg.index_head_dim
            specs.append({"kind": "full", "window": None, "pools": pools})
        return specs

    def build_params(self, eng, sd) -> dict:
        return serve_params(self.cfg, sd, eng.n_layers, eng.dtype)

    def embed(self, eng, params, ids):
        return jnp.take(params["emb"], ids, axis=0).astype(eng.dtype)

    def layer(self, eng, li, lp, h, kvl, positions, tables, n_tiles, wmask,
              carry=None):
        """One block over ``h [S, T, H]``: the latent row (and the index
        key) written into the layer's pools; on an indexer layer the rows
        scored over the paged index pool and their top-k taken, else the
        carried selection; absorbed attention over the selected rows; the
        feed-forward. ``carry`` is the selection ``[S, T, N]`` bool."""
        cfg = self.cfg
        S, T, H = h.shape
        full = cfg.indexer_type(li) == "full"
        x = rms_norm(h, lp["in_norm"], cfg.rms_norm_eps)
        c_q, q_nope, q_rope, row = attention_inputs(cfg, lp, x, positions)
        rows = {"latent": row}
        if full:
            q_i, k_i, w_i = indexer_inputs(cfg, lp, x, c_q, positions)
            rows["index"] = k_i
        with jax.named_scope("paged.kv_write"):
            kvl = eng._write_rows(kvl, rows, positions, tables, wmask)
        dsa = jnp.zeros((2,), jnp.int32)
        if full:
            with jax.named_scope("paged.index"):
                scores, valid = eng._sc.paged_index_scores(
                    q_i, w_i, kvl["index"], tables, positions,
                    block_size=eng.block_size)
            with jax.named_scope("paged.select"):
                carry, n_sel = eng._sc.select_topk(scores, valid,
                                                   cfg.index_topk)
            # rows attended, and rows a dense walk would attend
            dsa = jnp.stack([jnp.sum(jnp.where(wmask, n_sel, 0)),
                             jnp.sum(jnp.where(wmask, positions + 1, 0))]
                            ).astype(jnp.int32)
        with jax.named_scope("paged.attn"):
            w_k, w_v = kv_b_split(cfg, lp["kv_b"])
            # (results in the activations' dtype: the MXU accumulates in
            # float32 either way, and XLA:CPU has no bf16 x bf16 -> f32 dot
            # batched over a middle axis)
            q_abs = jnp.einsum("sthd,hdc->sthc", q_nope, w_k)
            pad = jnp.zeros(q_rope.shape[:-1] + (
                cfg.latent_width - cfg.kv_lora_rank - cfg.qk_rope_head_dim,),
                q_rope.dtype)
            att = eng._sc.paged_latent_attention(
                jnp.concatenate([q_abs, q_rope, pad], axis=-1),
                kvl["latent"], tables, carry, positions,
                block_size=eng.block_size, rank=cfg.kv_lora_rank,
                scale=1.0 / math.sqrt(cfg.qk_head_dim))
            o = jnp.einsum("sthc,hvc->sthv", att, w_v)
        h = h + _mm(o.reshape(S, T, -1), lp["o"])
        m, counts = feed_forward(
            cfg, li, lp, rms_norm(h, lp["post_norm"], cfg.rms_norm_eps),
            self.held)
        moe = jnp.zeros((3,), jnp.int32) if counts is None else counts
        return h + m, kvl, jnp.concatenate([moe, dsa]), carry

    def head(self, eng, params, h):
        return _mm(rms_norm(h, params["norm"], self.cfg.rms_norm_eps),
                   params["head"])
