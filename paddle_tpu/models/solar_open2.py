"""Solar Open 2 (``model_type: solar_open2``): a decoder whose layers keep
two kinds of memory. Three layers in four are gated delta-rule LINEAR
attention with a decay a channel (Kimi Delta Attention, arXiv 2510.26692:
``linear_attn_config``, the ``kda_*`` keys): a head keeps a fixed-size
matrix state a REQUEST and nothing a token. Every fourth layer
(``gqa_layers``) is softmax attention, grouped-query, with NO positional
encoding (``use_rope`` false) and an output gate (``use_gqa_gate``), over a
paged K/V cache. Every layer's feed-forward is a mixture of sigmoid-routed
experts beside one shared expert.

The layer, as this file computes it (``x = RMSNorm(h)``; each inference is
listed under ``assumed`` in ``benchmark/configs/solar-open2-250b.ep16.d8.json``):

- **KDA layer.** ``q~, k~, v~ = W_q x, W_k x, W_v x``; each through its own
  causal depthwise convolution over the sequence (``short_conv_kernel_size``
  taps, no bias; the tail is the 3 rows before the chunk) and SiLU; in heads
  of ``d``: ``q = q~ / |q~| * d^-1/2``, ``k = k~ / |k~|``, ``v = v~``. Decay
  a channel ``g = -exp(A_log_h) * softplus(W_fb (W_fa x) + dt_bias)``, ``a =
  exp(g)``; ``beta = 2 sigmoid(w_b x)`` (``kda_allow_neg_eigval``: the
  eigenvalue of ``I - beta k k^T`` along ``k`` reaches -1; without it ``beta
  = sigmoid``). State ``S [d, d]`` a head, zero at a request's start: ``S' =
  Diag(a) S``, ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T q``
  (``ops/pallas/kda.py``). Then ``o <- RMSNorm_d(o) * sigmoid(W_gb (W_ga
  x))``, ``y = W_o o``.
- **GQA layer.** ``num_attention_heads`` query heads on
  ``num_key_value_heads`` K/V heads, causal softmax at ``head_dim^-1/2``, no
  positions, no QK norm; ``y = W_o (attn * sigmoid(W_g x))``.
- ``h <- h + y``; ``n = RMSNorm(h)``; scores ``s = sigmoid(W_r n)`` over all
  experts in float32, the ``k`` largest of ``s + b`` chosen, weights ``s`` of
  the chosen over their sum, times ``routed_scaling_factor``; SwiGLU experts;
  plus the shared expert on every row; ``h <- h + m``. After the last layer
  an RMSNorm and an untied head.

Departures from the published description: the state is float32 and the
convolution tail the activations' dtype (the paper leaves both to the
implementation); the L2 norm adds 1e-6 under its root; text only, greedy.

**A share of the experts.** ``experts_held=(lo, hi)`` builds the layer as one
chip of an expert-parallel deployment holds it (``incubate/moe_share.py``):
router, attention, shared expert and norms whole, routed experts
``lo..hi-1`` only; the routed sum covers the held experts alone.

**Serving.** ``serve_model()`` hands the paged engine a cache spec in which
a GQA layer names K and V pools (a row a token, under the block table) and a
KDA layer names a STATE (``{"S": [H, d, d] float32, "conv": [(K-1) 3 H d]}``
a slot, no table, no blocks). A state cannot be truncated or shared by prefix:
speculation, prefix sharing and int8 are refused.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import apply_op
from ..nn.layer import Layer
from ..ops.pallas import kda
from .cohere2_moe import _mm, _Params
from .glm_moe_dsa import experts_block, rms_norm

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM"]

_L2_EPS = 1e-6


@dataclass
class SolarOpen2Config:
    """Defaults are ``upstage/Solar-Open2-250B``'s published ones."""
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gate_low_rank: int = 128                # of the decay and output gates
    moe_intermediate_size: int = 1280
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    use_gqa_gate: bool = True
    kda_allow_neg_eigval: bool = True
    max_position_embeddings: int = 1048576
    tie_word_embeddings: bool = False
    dtype: str = "float32"

    def layer_kind(self, i: int) -> str:
        return "gqa" if i in self.gqa_layers else "kda"

    @property
    def linear_width(self) -> int:
        return self.linear_num_heads * self.linear_head_dim

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    head_dim=16, gqa_layers=(0,), linear_num_heads=2,
                    linear_head_dim=16, gate_low_rank=8,
                    moe_intermediate_size=24, n_routed_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128)
        base.update(kw)
        return SolarOpen2Config(**base)


# ---------------------------------------------------------------------------
# the layer's pieces, on arrays
# ---------------------------------------------------------------------------

def conv_silu(seq, w, rows: int):
    """Causal depthwise convolution then SiLU. ``seq [.., K-1 + rows, C]``
    (the tail, then the rows), ``w [C, K]`` (the last tap on the row itself)
    -> ``[.., rows, C]`` float32."""
    return conv_taps([seq[..., j:j + rows, :] for j in range(w.shape[1])], w)


def conv_taps(taps, w):
    """``silu(sum_j taps[j] * w[:, j])`` in float32: ``taps[j] [.., C]`` is
    what the convolution's tap ``j`` reads for each output row."""
    w32 = w.astype(jnp.float32)
    return jax.nn.silu(sum(t.astype(jnp.float32) * w32[:, j]
                           for j, t in enumerate(taps)))


def kda_inputs(cfg: SolarOpen2Config, lp, x):
    """Of normed rows ``x [.., T, hidden]``: the three projections before
    their convolution ``[.., T, 3 H d]`` (q, k, v side by side), the log
    decay ``g [.., T, H, d]`` (float32, < 0), ``beta [.., T, H]`` (float32)
    and the output gate ``[.., T, H d]`` (float32)."""
    H, d = cfg.linear_num_heads, cfg.linear_head_dim
    with jax.named_scope("paged.kda.proj"):
        pre = jnp.concatenate([_mm(x, lp["q"]), _mm(x, lp["k"]),
                               _mm(x, lp["v"])], axis=-1)
    with jax.named_scope("paged.kda.gate"):
        f32 = jnp.float32
        raw = _mm(_mm(x, lp["f_a"]), lp["f_b"]).astype(f32) \
            + lp["dt_bias"].astype(f32)
        g = -jnp.exp(lp["A_log"].astype(f32))[:, None] \
            * jax.nn.softplus(raw).reshape(*x.shape[:-1], H, d)
        beta = jax.nn.sigmoid(_mm(x, lp["b"]).astype(f32))
        if cfg.kda_allow_neg_eigval:
            beta = 2.0 * beta
        gate = jax.nn.sigmoid(_mm(_mm(x, lp["g_a"]), lp["g_b"]).astype(f32))
    return pre, g, beta, gate


def conv_weights(lp):
    return jnp.concatenate([lp["q_conv"], lp["k_conv"], lp["v_conv"]], axis=0)


def qkv_heads(cfg: SolarOpen2Config, mixed):
    """The convolved rows ``[.., 3 H d]`` float32 as ``q, k, v [.., H, d]``:
    q and k of unit length, q scaled by ``d^-1/2``."""
    H, d = cfg.linear_num_heads, cfg.linear_head_dim
    q, k, v = (mixed[..., i * H * d:(i + 1) * H * d].reshape(
        *mixed.shape[:-1], H, d) for i in range(3))
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, -1, keepdims=True) + _L2_EPS)
    return unit(q) * d ** -0.5, unit(k), v


def kda_output(cfg: SolarOpen2Config, lp, o, gate, dtype):
    """``W_o (RMSNorm_d(o) * gate)`` of ``o [.., H, d]`` float32."""
    with jax.named_scope("paged.kda.out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + cfg.rms_norm_eps) \
            * lp["o_norm"].astype(jnp.float32)
        o = o.reshape(*o.shape[:-2], -1) * gate
        return _mm(o.astype(dtype), lp["o"])


def gqa_qkv(cfg: SolarOpen2Config, lp, x):
    lead = x.shape[:-1]
    nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    return (_mm(x, lp["q"]).reshape(*lead, nh, d),
            _mm(x, lp["k"]).reshape(*lead, kvh, d),
            _mm(x, lp["v"]).reshape(*lead, kvh, d))


def gqa_output(cfg: SolarOpen2Config, lp, x, att):
    """``W_o (att * sigmoid(W_g x))`` of ``att [.., heads * d]``."""
    if cfg.use_gqa_gate:
        with jax.named_scope("paged.gqa_gate"):
            att = (att.astype(jnp.float32) * jax.nn.sigmoid(
                _mm(x, lp["g"]).astype(jnp.float32))).astype(x.dtype)
    return _mm(att.astype(x.dtype), lp["o"])


def _attend_dense(cfg, q, k, v):
    """Plain causal attention over a whole sequence [B, L, heads, D]."""
    b, l, nh, d = q.shape
    kvh = k.shape[2]
    q = q.reshape(b, l, kvh, nh // kvh, d).astype(jnp.float32)
    s = jnp.einsum("blgrd,bmgd->bgrlm", q, k.astype(jnp.float32)) \
        / math.sqrt(d)
    ok = jnp.arange(l)[None, :] <= jnp.arange(l)[:, None]
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bgrlm,bmgd->blgrd", p, v.astype(jnp.float32))
    return o.reshape(b, l, nh * d)


# parameter names of one layer (under ``model.layers.<i>.``) by the short
# names this file and the serving adapter use; a layer has its kind's mixer
KDA_PARAMS = {
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
    "q_conv": "self_attn.q_conv1d.weight",
    "k_conv": "self_attn.k_conv1d.weight",
    "v_conv": "self_attn.v_conv1d.weight",
    "f_a": "self_attn.f_a_proj.weight", "f_b": "self_attn.f_b_proj.weight",
    "g_a": "self_attn.g_a_proj.weight", "g_b": "self_attn.g_b_proj.weight",
    "b": "self_attn.b_proj.weight", "A_log": "self_attn.A_log",
    "dt_bias": "self_attn.dt_bias", "o_norm": "self_attn.o_norm.weight",
}
GQA_PARAMS = {
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "g": "self_attn.g_proj.weight",
    "o": "self_attn.o_proj.weight",
}
BLOCK_PARAMS = {
    "in_norm": "input_layernorm.weight",
    "post_norm": "post_attention_layernorm.weight",
    "router": "mlp.gate.weight",
    "router_bias": "mlp.gate.e_score_correction_bias",
    "experts_gate_up": "mlp.experts.gate_up_proj",
    "experts_down": "mlp.experts.down_proj",
    "shared_gate": "mlp.shared_experts.gate_proj.weight",
    "shared_up": "mlp.shared_experts.up_proj.weight",
    "shared_down": "mlp.shared_experts.down_proj.weight",
}


def layer_params(cfg: SolarOpen2Config, i: int) -> dict:
    """{short name: published name} of layer ``i``."""
    return {**(GQA_PARAMS if cfg.layer_kind(i) == "gqa" else KDA_PARAMS),
            **BLOCK_PARAMS}


def layer_shapes(cfg: SolarOpen2Config, i: int, n_held: int) -> dict:
    h, inter = cfg.hidden_size, cfg.moe_intermediate_size
    s = cfg.n_shared_experts * inter
    if cfg.layer_kind(i) == "gqa":
        nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        out = {"q": (nh * d, h), "k": (kvh * d, h), "v": (kvh * d, h),
               "g": (nh * d, h), "o": (h, nh * d)}
        if not cfg.use_gqa_gate:
            del out["g"]
    else:
        H, d, w = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_width
        r, taps = cfg.gate_low_rank, cfg.short_conv_kernel_size
        out = {"q": (w, h), "k": (w, h), "v": (w, h), "o": (h, w),
               "q_conv": (w, taps), "k_conv": (w, taps), "v_conv": (w, taps),
               "f_a": (r, h), "f_b": (w, r), "g_a": (r, h), "g_b": (w, r),
               "b": (H, h), "A_log": (H,), "dt_bias": (w,), "o_norm": (d,)}
    out.update({"in_norm": (h,), "post_norm": (h,),
                "router": (cfg.n_routed_experts, h),
                "router_bias": (cfg.n_routed_experts,),
                "experts_gate_up": (n_held, h, 2 * inter),
                "experts_down": (n_held, inter, h),
                "shared_gate": (s, h), "shared_up": (s, h),
                "shared_down": (h, s)})
    return out


def default_decay(H: int, width: int):
    """``A_log`` and ``dt_bias`` a fresh model starts from: rates spread
    over 1..16 by head, a softplus of 1e-3..0.1 by channel (a decay a token
    between 0.2 and 0.999)."""
    rate = np.linspace(1.0, 16.0, H)
    dt = np.exp(np.linspace(math.log(1e-3), math.log(0.1), width))
    return np.log(rate), np.log(np.expm1(dt))       # the inverse softplus


class SolarOpen2ForCausalLM(Layer):
    """The decoder with ``experts_held = (lo, hi)`` of its routed experts
    (all of them by default). ``forward(input_ids)`` is the plain full
    forward (dense masked attention, the delta rule from a zero state);
    serving takes ``serve_model()``. ``init_std=None`` leaves the matrices
    zero, for a caller that loads every weight next."""

    def __init__(self, config: SolarOpen2Config,
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
        shapes = {"embed": (config.vocab_size, config.hidden_size),
                  "final_norm": (config.hidden_size,)}
        if not config.tie_word_embeddings:
            shapes["head"] = (config.vocab_size, config.hidden_size)
        self.add_sublayer("ends", _Params(shapes, init_std))
        a_log, dt_bias = default_decay(config.linear_num_heads,
                                       config.linear_width)
        self._layers = []
        for i in range(config.num_hidden_layers):
            bag = _Params(layer_shapes(config, i, hi - lo), init_std)
            for name, value in (("router_bias", 0.0), ("A_log", a_log),
                                ("dt_bias", dt_bias)):
                p = bag._parameters.get(name)
                if p is not None:
                    p._data = jnp.zeros_like(p._data) + jnp.asarray(
                        value, p._data.dtype)
            self.add_sublayer(f"layer_{i}", bag)
            self._layers.append(bag)

    # the published names, whatever the bags are called
    def named_parameters(self, prefix="", include_sublayers=True):
        ends = dict(self.ends._parameters)
        yield "model.embed_tokens.weight", ends["embed"]
        for i, bag in enumerate(self._layers):
            for short, name in layer_params(self.config, i).items():
                if short in bag._parameters:
                    yield f"model.layers.{i}.{name}", bag._parameters[short]
        yield "model.norm.weight", ends["final_norm"]
        if "head" in ends:
            yield "lm_head.weight", ends["head"]

    def forward(self, input_ids):
        cfg = self.config
        params = [p for _, p in self.named_parameters()]
        names = [k for k, _ in self.named_parameters()]

        def f(ids, *arrs):
            p = serve_params(cfg, dict(zip(names, arrs)))
            return full_forward(cfg, p, ids, self.experts_held)
        return apply_op(f, input_ids, *params, op_name="solar_open2_forward")

    def serve_model(self):
        """What the paged serving engine asks of a model (``serving.py``,
        the seam between engine and model)."""
        return SolarOpen2Serve(self.config, self.experts_held)


def serve_params(cfg: SolarOpen2Config, sd, n_layers: Optional[int] = None,
                 dtype=None) -> dict:
    """name -> array state dict into the pytree the layer functions take.
    Nothing is transposed or copied: the arrays are bound as they are (cast
    only if ``dtype`` differs from what they hold)."""
    def get(name):
        try:
            v = sd[name]
        except KeyError:
            raise ValueError(f"weight state dict is missing {name!r} — not "
                             f"a checkpoint of this model") from None
        v = v._data if hasattr(v, "_data") else v
        return v if dtype is None else jnp.asarray(v, dtype)

    n_layers = n_layers or cfg.num_hidden_layers
    emb = get("model.embed_tokens.weight")
    return {"emb": emb, "norm": get("model.norm.weight"),
            "head": emb if cfg.tie_word_embeddings else get("lm_head.weight"),
            "layers": [{short: get(f"model.layers.{i}.{name}")
                        for short, name in layer_params(cfg, i).items()
                        if short != "g" or cfg.use_gqa_gate}
                       for i in range(n_layers)]}


def kda_mixer_dense(cfg: SolarOpen2Config, lp, x):
    """The KDA mixer over whole sequences ``x [B, L, hidden]`` from a zero
    state and a zero tail (the model's own forward)."""
    b, l, _ = x.shape
    H, d = cfg.linear_num_heads, cfg.linear_head_dim
    pre, g, beta, gate = kda_inputs(cfg, lp, x)
    tail = jnp.zeros((b, cfg.short_conv_kernel_size - 1, pre.shape[-1]),
                     pre.dtype)
    q, k, v = qkv_heads(cfg, conv_silu(
        jnp.concatenate([tail, pre], axis=1), conv_weights(lp), l))
    zero = jnp.zeros((1, H, d, d), jnp.float32)
    o = jnp.stack([kda.kda_chunk(zero, 0, True, q[i], k[i], v[i], g[i],
                                 beta[i], use_kernel=False)[0]
                   for i in range(b)])
    return kda_output(cfg, lp, o, gate, x.dtype)


def full_forward(cfg: SolarOpen2Config, params, ids, held):
    """ids [B, L] -> logits [B, L, V], every position, no cache."""
    b, l = ids.shape
    h = jnp.take(params["emb"], ids, axis=0)
    for i, lp in enumerate(params["layers"]):
        x = rms_norm(h, lp["in_norm"], cfg.rms_norm_eps)
        if cfg.layer_kind(i) == "gqa":
            q, k, v = gqa_qkv(cfg, lp, x)
            y = gqa_output(cfg, lp, x, _attend_dense(cfg, q, k, v))
        else:
            y = kda_mixer_dense(cfg, lp, x)
        h = h + y
        n = rms_norm(h, lp["post_norm"], cfg.rms_norm_eps)
        m, _ = experts_block(cfg, lp, n.reshape(b * l, -1), held)
        h = h + m.reshape(b, l, -1)
    return _mm(rms_norm(h, params["norm"], cfg.rms_norm_eps), params["head"])


class SolarOpen2Serve:
    """The model's side of the serving seam: its cache spec (a GQA layer's K
    and V pools under the block table; a KDA layer's state and convolution
    tail a SLOT, kind ``state``: no table, no blocks), its parameters in the
    engine's pytree, and one layer's step."""

    aux_names = ("moe_rows", "moe_experts_hit", "moe_max_rows")
    n_aux = len(aux_names)
    supports_int8 = False
    # a rejected window would have to be rolled back out of a state, which
    # keeps no history; a shared prefix would need the state at its end
    supports_speculation = False
    supports_prefix_sharing = False
    state_subchunk = kda.SUBCHUNK     # rows the spans count a chunk's work in

    def __init__(self, cfg: SolarOpen2Config, held: Tuple[int, int]):
        self.cfg = cfg
        self.held = held

    def cache_spec(self, n_layers: int) -> list:
        cfg = self.cfg
        H, d = cfg.linear_num_heads, cfg.linear_head_dim
        width = cfg.num_key_value_heads * cfg.head_dim
        dt = "bfloat16" if str(cfg.dtype) == "bfloat16" else "float32"
        gqa = {"kind": "full", "window": None,
               "pools": {"k": width, "v": width},
               "kv_heads": cfg.num_key_value_heads,
               "head_dim": cfg.head_dim, "q_heads": cfg.num_attention_heads}
        state = {"kind": "state", "window": None, "pools": {},
                 # the tail's rows side by side: a row a slot, so that the
                 # pool's second-minor dimension is the slots (a [slots, 3,
                 # C] pool is relaid out on the TPU at every launch)
                 "state": {"S": ((H, d, d), "float32"),
                           "conv": (((cfg.short_conv_kernel_size - 1)
                                     * 3 * H * d,), dt)}}
        return [dict(gqa if cfg.layer_kind(i) == "gqa" else state)
                for i in range(n_layers)]

    def build_params(self, eng, sd) -> dict:
        return serve_params(self.cfg, sd, eng.n_layers, eng.dtype)

    def embed(self, eng, params, ids):
        return jnp.take(params["emb"], ids, axis=0).astype(eng.dtype)

    def _kda(self, lp, x, kvl, positions, wmask, slots):
        """The KDA mixer over ``x [S, T, hidden]``. ``slots`` None: a decode
        step, row ``s`` is slot ``s`` and ``T`` is 1; else ``[1]``, the one
        slot whose chunk of ``T`` rows this is. A row with ``wmask`` False
        (padding, a slot that is not active) leaves state and tail as they
        were; a chunk that starts at position 0 reads both as zeros."""
        cfg = self.cfg
        S, T, _ = x.shape
        pool, tails = kvl["S"], kvl["conv"]
        pre, g, beta, gate = kda_inputs(cfg, lp, x)
        keep, C = cfg.short_conv_kernel_size - 1, pre.shape[-1]
        if slots is None:
            if T != 1 or S != pool.shape[0]:
                raise NotImplementedError(
                    "a state layer steps one token for every slot, or one "
                    "slot's chunk: no window of tokens a slot")
            act = wmask[:, 0]
            with jax.named_scope("paged.kda.conv"):
                taps = [tails[:, j * C:(j + 1) * C] for j in range(keep)] \
                    + [pre[:, 0].astype(tails.dtype)]
                q, k, v = qkv_heads(cfg, conv_taps(taps, conv_weights(lp)))
                tails = jnp.where(act[:, None],
                                  jnp.concatenate(taps[1:], axis=1), tails)
            with jax.named_scope("paged.kda.scan"):
                o, pool = kda.kda_step(pool, q, k, v, g[:, 0], beta[:, 0],
                                       act)
            o = o[:, None]
        else:
            if S != 1:
                raise NotImplementedError("a chunk is one slot's")
            slot = slots[0]
            fresh = positions[0, 0] == 0
            nvalid = jnp.sum(wmask[0]).astype(jnp.int32)
            with jax.named_scope("paged.kda.conv"):
                tail = jax.lax.dynamic_index_in_dim(
                    tails, slot, 0, False).reshape(keep, C)
                tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
                seq = jnp.concatenate([tail, pre[0].astype(tail.dtype)], 0)
                q, k, v = qkv_heads(cfg, conv_silu(seq, conv_weights(lp), T))
                # the rows that end with the last valid one
                tails = jax.lax.dynamic_update_index_in_dim(
                    tails, jax.lax.dynamic_slice_in_dim(
                        seq, nvalid, keep, 0).reshape(keep * C), slot, 0)
            with jax.named_scope("paged.kda.scan"):
                live = wmask[0]
                o, pool = kda.kda_chunk(
                    pool, slot, fresh, q, k, v,
                    jnp.where(live[:, None, None], g[0], 0.0),
                    jnp.where(live[:, None], beta[0], 0.0))
            o = o[None]
        y = kda_output(cfg, lp, o, gate, x.dtype)
        return y, dict(kvl, S=pool, conv=tails)

    def layer(self, eng, li, lp, h, kvl, positions, tables, n_tiles, wmask,
              carry=None, slots=None):
        """One block over ``h [S, T, H]``: the layer's mixer (K/V written
        into the pool and attention through the paged seam, or the delta
        rule over the slot's state), then the experts."""
        cfg = self.cfg
        S, T, H = h.shape
        x = rms_norm(h, lp["in_norm"], cfg.rms_norm_eps)
        if cfg.layer_kind(li) == "gqa":
            q, k, v = gqa_qkv(cfg, lp, x)
            with jax.named_scope("paged.kv_write"):
                kvl = eng._write_kv(kvl, k, v, positions, tables, wmask)
            with jax.named_scope("paged.attn"):
                att = eng._sc.paged_attention(
                    q, kvl["k"], kvl["v"], tables, positions,
                    block_size=eng.block_size,
                    n_rep=cfg.num_attention_heads // cfg.num_key_value_heads,
                    n_tiles=n_tiles, use_kernel=eng._pa_kernel)
            y = gqa_output(cfg, lp, x, att.reshape(S, T, -1))
        else:
            y, kvl = self._kda(lp, x, kvl, positions, wmask, slots)
        h = h + y
        n = rms_norm(h, lp["post_norm"], cfg.rms_norm_eps)
        m, counts = experts_block(cfg, lp, n.reshape(S * T, H), self.held)
        return h + m.reshape(S, T, H), kvl, counts, carry

    def head(self, eng, params, h):
        return _mm(rms_norm(h, params["norm"], self.cfg.rms_norm_eps),
                   params["head"])
