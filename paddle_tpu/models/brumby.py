"""Brumby (``model_type: brumby``): Qwen3-14B's widths with every softmax
attention layer replaced by degree-2 POWER RETENTION (Manifest AI, "Symmetric
Power Transformers" and "Scaling Context Requires Rethinking Attention"; their
``retention`` package). A layer keeps a fixed-size state a REQUEST and nothing
a token, so the model serves from a state pool with no K/V pool beside it.

The layer, as this file computes it (``x = RMSNorm(h)``; each inference is
listed under ``assumed`` in ``benchmark/configs/brumby-14b-base.d4.json``):

- ``q = W_q x`` in ``num_attention_heads`` heads of ``head_dim``; ``k = W_k
  x`` and ``v = W_v x`` in ``num_key_value_heads`` heads; no biases
  (``attention_bias`` false). ``q <- RMSNorm_d(q)`` and ``k <- RMSNorm_d(k)``,
  each with its own weight, as Qwen3 does; then RoPE (``rope_theta``, the
  halves rotated) at the token's position.
- The gate: one log decay a KV head a token, ``gamma_t = logsigmoid(W_g x_t +
  b_g)`` (``W_g`` hidden -> KV heads). ``G_t`` is the sum of ``gamma`` up to
  ``t`` within the request.
- Query head ``i`` reads KV head ``h = i // (heads / KV heads)``::

      o_t = sum_{s<=t} e^{G_t - G_s} (q_t.k_s)^2 v_s
            / (sum_{s<=t} e^{G_t - G_s} (q_t.k_s)^2 + eps)

  No scale on ``q.k``: a constant one cancels apart from ``eps``.
- The state form the server runs (``ops/pallas/power_retention.py``): ``S_t
  = e^{gamma_t} S_{t-1} + phi(k_t) v_t^T``, ``z_t = e^{gamma_t} z_{t-1} +
  phi(k_t)``, ``o_t = S_t^T phi(q_t) / (z_t.phi(q_t) + eps)``, with
  ``phi(x)`` the symmetric square of ``x`` (``d (d + 1) / 2`` wide, float32),
  both zero at a request's first chunk.
- ``y = W_o o``; ``h <- h + y``; ``h <- h + W_down(silu(W_gate n) * W_up n)``
  with ``n = RMSNorm(h)``. A final RMSNorm and an untied head close the model.

**Serving.** ``serve_model()`` hands the paged engine a cache spec in which
every layer names a STATE a slot (``{"S": [Hk, D, d], "z": [Hk, D]}``
float32) and no block pool: the engine builds no block table and no
allocator for it and admits by slot. A state cannot be truncated or shared
by prefix: speculation, prefix sharing and int8 are refused.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.autograd import apply_op
from ..nn.layer import Layer
from ..ops.pallas import power_retention as pr
from .cohere2_moe import _mm, _Params
from .glm_moe_dsa import rms_norm
from .llama import _rope_at

__all__ = ["BrumbyConfig", "BrumbyForCausalLM"]


@dataclass
class BrumbyConfig:
    """Defaults are ``manifestai/Brumby-14B-Base``'s published ones."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    retention_eps: float = pr.EPS          # under the normaliser
    dtype: str = "float32"

    @property
    def feature_dim(self) -> int:
        return pr.feature_dim(self.head_dim)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    max_position_embeddings=256)
        base.update(kw)
        return BrumbyConfig(**base)


# parameter names of one layer (under ``model.layers.<i>.``) by the short
# names this file and the serving adapter use
LAYER_PARAMS = {
    "in_norm": "input_layernorm.weight",
    "q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
    "v": "self_attn.v_proj.weight", "o": "self_attn.o_proj.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
    "g": "self_attn.g_proj.weight", "g_bias": "self_attn.g_proj.bias",
    "post_norm": "post_attention_layernorm.weight",
    "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
    "down": "mlp.down_proj.weight",
}


def layer_shapes(cfg: BrumbyConfig) -> dict:
    h, inter, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nh, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
    return {"in_norm": (h,), "q": (nh * d, h), "k": (kvh * d, h),
            "v": (kvh * d, h), "o": (h, nh * d), "q_norm": (d,),
            "k_norm": (d,), "g": (kvh, h), "g_bias": (kvh,),
            "post_norm": (h,), "gate": (inter, h), "up": (inter, h),
            "down": (h, inter)}


def retention_inputs(cfg: BrumbyConfig, lp, x, positions):
    """Of normed rows ``x [S, T, hidden]`` at ``positions [S, T]``: ``q [S, T,
    heads, d]`` and ``k, v [S, T, KV heads, d]`` (q and k normed and
    rotated), and the log decay ``gamma [S, T, KV heads]`` float32 (< 0)."""
    lead = x.shape[:-1]
    nh, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("paged.retention.proj"):
        q = rms_norm(_mm(x, lp["q"]).reshape(*lead, nh, d), lp["q_norm"],
                     cfg.rms_norm_eps)
        k = rms_norm(_mm(x, lp["k"]).reshape(*lead, kvh, d), lp["k_norm"],
                     cfg.rms_norm_eps)
        v = _mm(x, lp["v"]).reshape(*lead, kvh, d)
        q = _rope_at(q, positions, cfg.rope_theta)
        k = _rope_at(k, positions, cfg.rope_theta)
    with jax.named_scope("paged.retention.gate"):
        gamma = jax.nn.log_sigmoid(_mm(x, lp["g"]).astype(jnp.float32)
                                   + lp["g_bias"].astype(jnp.float32))
    return q, k, v, gamma


def retention_output(lp, o, dtype):
    """``W_o o`` of ``o [S, T, heads, d]`` float32."""
    with jax.named_scope("paged.retention.out"):
        return _mm(o.reshape(*o.shape[:-2], -1).astype(dtype), lp["o"])


def mlp(cfg: BrumbyConfig, lp, h):
    """``h + W_down(silu(W_gate n) * W_up n)`` with ``n = RMSNorm(h)``."""
    n = rms_norm(h, lp["post_norm"], cfg.rms_norm_eps)
    with jax.named_scope("paged.mlp"):
        a = jax.nn.silu(_mm(n, lp["gate"]).astype(jnp.float32)) \
            * _mm(n, lp["up"]).astype(jnp.float32)
        return h + _mm(a.astype(h.dtype), lp["down"])


class BrumbyForCausalLM(Layer):
    """The decoder. ``forward(input_ids)`` is the plain full forward (the
    quadratic form of every layer, no state); serving takes
    ``serve_model()``. Every vector (the norms' weights, the gate's bias)
    starts at 1 and every matrix from a normal of ``init_std``;
    ``init_std=None`` leaves the matrices zero, for a caller that loads
    every weight next."""

    def __init__(self, config: BrumbyConfig, init_std=0.02):
        super().__init__()
        self.config = config
        shapes = {"embed": (config.vocab_size, config.hidden_size),
                  "final_norm": (config.hidden_size,)}
        if not config.tie_word_embeddings:
            shapes["head"] = (config.vocab_size, config.hidden_size)
        self.add_sublayer("ends", _Params(shapes, init_std))
        self._layers = []
        for i in range(config.num_hidden_layers):
            bag = _Params(layer_shapes(config), init_std)
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
        if "head" in ends:
            yield "lm_head.weight", ends["head"]

    def forward(self, input_ids):
        cfg = self.config
        named = list(self.named_parameters())
        names = [k for k, _ in named]

        def f(ids, *arrs):
            return full_forward(cfg, serve_params(cfg, dict(zip(names, arrs))),
                                ids)
        return apply_op(f, input_ids, *[p for _, p in named],
                        op_name="brumby_forward")

    def serve_model(self):
        """What the paged serving engine asks of a model (``serving.py``,
        the seam between engine and model)."""
        return BrumbyServe(self.config)


def serve_params(cfg: BrumbyConfig, sd, n_layers=None, dtype=None) -> dict:
    """name -> array state dict into the pytree the layer functions take,
    the arrays bound as they are (cast only if ``dtype`` differs)."""
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
                        for short, name in LAYER_PARAMS.items()}
                       for i in range(n_layers)]}


def full_forward(cfg: BrumbyConfig, params, ids):
    """ids [B, L] -> logits [B, L, V], every position, no state."""
    b, l = ids.shape
    positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32)[None], (b, l))
    h = jnp.take(params["emb"], ids, axis=0)
    for lp in params["layers"]:
        x = rms_norm(h, lp["in_norm"], cfg.rms_norm_eps)
        q, k, v, gamma = retention_inputs(cfg, lp, x, positions)
        o = pr.retention_quadratic(q, k, v, gamma, cfg.retention_eps)
        h = mlp(cfg, lp, h + retention_output(lp, o, h.dtype))
    return _mm(rms_norm(h, params["norm"], cfg.rms_norm_eps), params["head"])


class BrumbyServe:
    """The model's side of the serving seam: a cache spec in which every
    layer keeps a state a SLOT and no block pool, its parameters in the
    engine's pytree, and one layer's step."""

    aux_names = ()
    n_aux = 0
    supports_int8 = False
    # a rejected window would have to be rolled back out of a state, which
    # keeps no history; a shared prefix would need the state at its end
    supports_speculation = False
    supports_prefix_sharing = False
    state_subchunk = pr.ROWS         # rows the spans count a chunk's work in

    def __init__(self, cfg: BrumbyConfig):
        self.cfg = cfg

    def cache_spec(self, n_layers: int) -> list:
        cfg = self.cfg
        kvh, D, d = cfg.num_key_value_heads, cfg.feature_dim, cfg.head_dim
        return [{"kind": "state", "window": None, "pools": {},
                 "state": {"S": ((kvh, D, d), "float32"),
                           "z": ((kvh, D), "float32")}}
                for _ in range(n_layers)]

    def build_params(self, eng, sd) -> dict:
        return serve_params(self.cfg, sd, eng.n_layers, eng.dtype)

    def embed(self, eng, params, ids):
        return jnp.take(params["emb"], ids, axis=0).astype(eng.dtype)

    def layer(self, eng, li, lp, h, kvl, positions, tables, n_tiles, wmask,
              carry=None, slots=None):
        """One block over ``h [S, T, H]``. ``slots`` None: a decode step, row
        ``s`` is slot ``s`` and ``T`` is 1 (a row with ``wmask`` False leaves
        its slot's state as it was); else ``[1]``, the one slot whose chunk
        of ``T`` rows this is (a chunk that starts at position 0 reads the
        state as zeros; padding rows leave it as it was)."""
        cfg = self.cfg
        S, T, _ = h.shape
        x = rms_norm(h, lp["in_norm"], cfg.rms_norm_eps)
        q, k, v, gamma = retention_inputs(cfg, lp, x, positions)
        pool, norm = kvl["S"], kvl["z"]
        if slots is None:
            if T != 1 or S != pool.shape[0]:
                raise NotImplementedError(
                    "a state layer steps one token for every slot, or one "
                    "slot's chunk: no window of tokens a slot")
            with jax.named_scope("paged.retention.step"):
                o, pool, norm = pr.retention_step(
                    pool, norm, q[:, 0], k[:, 0], v[:, 0], gamma[:, 0],
                    wmask[:, 0], eps=cfg.retention_eps)
            o = o[:, None]
        else:
            if S != 1:
                raise NotImplementedError("a chunk is one slot's")
            live = wmask[0]
            with jax.named_scope("paged.retention.chunk"):
                o, pool, norm = pr.retention_chunk(
                    pool, norm, slots[0], positions[0, 0] == 0, q[0],
                    jnp.where(live[:, None, None], k[0], 0),
                    jnp.where(live[:, None, None], v[0], 0),
                    jnp.where(live[:, None], gamma[0], 0.0),
                    eps=cfg.retention_eps)
            o = o[None]
        h = mlp(cfg, lp, h + retention_output(lp, o, h.dtype))
        return h, dict(kvl, S=pool, z=norm), None, carry

    def head(self, eng, params, h):
        return _mm(rms_norm(h, params["norm"], self.cfg.rms_norm_eps),
                   params["head"])
