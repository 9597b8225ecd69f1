"""Llama family: the flagship LM for the framework's headline benchmark.

ref: test/auto_parallel/hybrid_strategy/semi_auto_parallel_llama_model.py
(LlamaAttention/LlamaMLP/LlamaRMSNorm/LlamaForCausalLM and their
shard_tensor placement choices), python/paddle/nn/functional/flash_attention.py
(attention entry). TPU-native design: the decoder stack is ordinary Layer
code; parallelism is *data placement* — `shard_llama` attaches
NamedShardings (GSPMD) to the parameters and one `jax.jit` of the train
step compiles the whole hybrid dp x fsdp x tp program with XLA
collectives over ICI. RoPE/GQA/SwiGLU keep every matmul large and
bfloat16-friendly for the MXU; attention rides the Pallas flash kernel.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..core.autograd import apply_op
from ..nn import functional as F
from ..nn.container import LayerList
from ..nn.layer import Layer
from ..nn.layers_common import Embedding, Linear
from ..nn.layers_conv_norm import RMSNorm
from ..nn import initializer as I

__all__ = [
    "LlamaConfig", "LlamaModel", "LlamaForCausalLM",
    "LlamaPretrainingCriterion", "shard_llama",
]


@dataclass
class LlamaConfig:
    """Defaults are Llama-2 7B (ref: semi_auto_llama.py model config)."""
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32          # < heads => GQA (Llama-2 70B / 3)
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    use_flash_attention: bool = True
    sequence_parallel: bool = False        # shard activations on seq axis
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    # recompute each decoder block in backward (ref: fleet recompute /
    # paddle.distributed.fleet.utils.recompute) = jax.checkpoint
    recompute: bool = False
    # context parallelism (above-parity vs reference, SURVEY §2.2): when a
    # mesh + axis are set, attention runs the ring kernel with K/V blocks
    # rotating over ICI and the sequence sharded across the axis
    cp_mesh: object = None
    cp_axis: str = "sp"

    @staticmethod
    def tiny(**kw):
        """Small config for tests / dry runs."""
        base = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=128)
        base.update(kw)
        return LlamaConfig(**base)


def _rope_cos_sin(seq_len, head_dim, theta, dtype=jnp.float32,
                  position_offset=0):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    pos = jnp.arange(position_offset, position_offset + seq_len,
                     dtype=jnp.float32)
    freqs = jnp.outer(pos, inv_freq)              # [L, D/2]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def _apply_rope(x, cos, sin):
    """x: [B, L, H, D] -> rotated. Pairs (x1, x2) are the two halves, the
    Llama 'rotate_half' convention (ref: semi_auto_parallel_llama_model.py
    apply_rotary_pos_emb)."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    cos = cos[None, :, None, :]
    sin = sin[None, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _causal_flash(mesh, q, k, v):
    """Causal flash attention, [B, L, H, D], under a (possibly sharded)
    train step. GSPMD cannot partition a Mosaic kernel (jax refuses at
    lowering: "wrap the call in a shard_map"), so where parameters live
    on a mesh and the kernel applies, it runs per shard inside shard_map:
    batch over the data-like mesh axes that divide it, heads over the
    model-like ones (the `flash_attention` SPMD rule: no collectives).
    Where the kernel does not apply (CPU mesh tests, untileable shapes)
    the XLA reference runs and GSPMD partitions it by itself."""
    from ..ops.pallas import flash_attention as fa
    b, l, h, d = q.shape
    if mesh is None or mesh.size == 1 or not fa._use_pallas(l, d):
        return fa.flash_attention(q, k, v, True, None)
    from ..distributed._mesh_axes import classify_axes
    from ..distributed.spmd_rules import shard_map_flash_attention

    def dividing(axes, n):
        keep, ways = [], 1
        for a in axes:
            if n % (ways * mesh.shape[a]) == 0:
                keep.append(a)
                ways *= mesh.shape[a]
        return tuple(keep) or None

    batch_axes, head_axes = classify_axes(mesh, None)
    return shard_map_flash_attention(
        mesh, q, k, v, batch_axis=dividing(batch_axes, b),
        head_axis=dividing(head_axes, h), causal=True)


class LlamaAttention(Layer):
    """GQA attention with RoPE; the sdpa is the Pallas flash kernel when
    tiling allows (ref: LlamaAttention in semi_auto_parallel_llama_model.py)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        # the jax Mesh the parameters are sharded over (shard_llama sets
        # it): the flash kernel must then run inside shard_map
        self.mesh = None
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        kv_out = self.num_kv_heads * self.head_dim
        self.q_proj = Linear(self.hidden_size, self.hidden_size,
                             bias_attr=False)
        self.k_proj = Linear(self.hidden_size, kv_out, bias_attr=False)
        self.v_proj = Linear(self.hidden_size, kv_out, bias_attr=False)
        self.o_proj = Linear(self.hidden_size, self.hidden_size,
                             bias_attr=False)

    def forward(self, hidden_states, attention_mask=None, cache=None,
                position_offset=0):
        b, l, _ = hidden_states.shape
        q = self.q_proj(hidden_states).reshape([b, l, self.num_heads,
                                                self.head_dim])
        k = self.k_proj(hidden_states).reshape([b, l, self.num_kv_heads,
                                                self.head_dim])
        v = self.v_proj(hidden_states).reshape([b, l, self.num_kv_heads,
                                                self.head_dim])

        # the whole rope+attend runs through apply_op so eager autograd
        # records one fused node
        cache_in = []
        if cache is not None and cache[0] is not None:
            cache_in = [cache[0], cache[1]]

        def attn_impl(qa, ka, va, *cache_arrs):
            cos, sin = _rope_cos_sin(l, self.head_dim,
                                     self.config.rope_theta,
                                     position_offset=position_offset)
            qa = _apply_rope(qa, cos, sin)
            ka = _apply_rope(ka, cos, sin)
            if cache_arrs:
                ka = jnp.concatenate([cache_arrs[0], ka], axis=1)
                va = jnp.concatenate([cache_arrs[1], va], axis=1)
            rep = self.num_heads // self.num_kv_heads
            new_k, new_v = ka, va
            if rep > 1:
                ka = jnp.repeat(ka, rep, axis=2)
                va = jnp.repeat(va, rep, axis=2)
            from ..ops.pallas.flash_attention import _sdpa_xla
            if (self.config.cp_mesh is not None and not cache_arrs
                    and attention_mask is None):
                from ..distributed.ring_attention import ring_attention
                out = ring_attention(qa, ka, va, self.config.cp_mesh,
                                     self.config.cp_axis, causal=True)
            elif (not cache_arrs and attention_mask is None
                    and self.config.use_flash_attention):
                out = _causal_flash(self.mesh, qa, ka, va)
            else:
                # decode (Lq < Lk) and/or explicit-mask path
                out = _sdpa_xla(qa, ka, va, causal=True,
                                mask=attention_mask)
            return out.reshape(b, l, self.hidden_size), new_k, new_v

        if attention_mask is not None:
            attention_mask = attention_mask._data if isinstance(
                attention_mask, Tensor) else attention_mask
        out, new_k, new_v = apply_op(
            attn_impl, q, k, v, *cache_in, op_name="llama_attention")
        out = self.o_proj(out)
        if cache is not None:
            return out, (new_k, new_v)
        return out


class LlamaMLP(Layer):
    """SwiGLU FFN (ref: LlamaMLP in semi_auto_parallel_llama_model.py)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.gate_proj = Linear(config.hidden_size, config.intermediate_size,
                                bias_attr=False)
        self.up_proj = Linear(config.hidden_size, config.intermediate_size,
                              bias_attr=False)
        self.down_proj = Linear(config.intermediate_size, config.hidden_size,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def forward(self, hidden_states, attention_mask=None, cache=None,
                position_offset=0):
        # the two scopes split a layer's operations (each with its norm
        # and residual add) in an xprof view of the trace; metadata only
        with jax.named_scope("llama.attn"):
            residual = hidden_states
            h = self.input_layernorm(hidden_states)
            if cache is not None:
                h, new_cache = self.self_attn(h, attention_mask, cache,
                                              position_offset)
            else:
                h = self.self_attn(h, attention_mask, None,
                                   position_offset)
            h = residual + h
        with jax.named_scope("llama.mlp"):
            residual = h
            h = self.post_attention_layernorm(h)
            h = self.mlp(h)
            h = residual + h
        if cache is not None:
            return h, new_cache
        return h


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=I.Normal(0.0, 0.02))
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids, attention_mask=None, caches=None,
                position_offset=0):
        with jax.named_scope("llama.embed"):
            h = self.embed_tokens(input_ids)
        if self.config.sequence_parallel:
            h = _seq_constraint(h)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            cache_i = caches[i] if caches is not None else None
            if self.config.recompute and caches is None:
                h = _remat_layer(layer, h, attention_mask, position_offset)
            elif caches is not None:
                h, c = layer(h, attention_mask, cache_i, position_offset)
                new_caches.append(c)
            else:
                h = layer(h, attention_mask, None, position_offset)
            if self.config.sequence_parallel:
                h = _seq_constraint(h)
        h = self.norm(h)
        if caches is not None:
            return h, new_caches
        return h


def _remat_layer(layer, h, attention_mask, position_offset):
    """jax.checkpoint over one decoder block — the TPU-native recompute
    (ref: paddle.distributed.fleet.utils.recompute). The layer's actual
    Parameter objects are passed to apply_op so eager backward routes
    gradients to them."""
    params = [p for _, p in layer.named_parameters()]

    def fn(h_arr, *param_arrs):
        old = [p._data for p in params]
        try:
            for p, a in zip(params, param_arrs):
                p._data = a
            out = layer(Tensor(h_arr), attention_mask, None, position_offset)
            return out._data
        finally:
            for p, o in zip(params, old):
                p._data = o

    return apply_op(jax.checkpoint(fn), h, *params,
                    op_name="remat_decoder_layer")


def _seq_constraint(h):
    """Activation sharding constraint along the sequence axis ('sp' mesh
    axis) — Megatron sequence parallel as pure placement
    (ref: fleet/utils/sequence_parallel_utils.py)."""
    def f(x):
        try:
            from jax.sharding import PartitionSpec as P
            return jax.lax.with_sharding_constraint(
                x, P(None, "sp", None))
        except Exception:
            return x
    return apply_op(f, h, op_name="seq_parallel_constraint")


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  bias_attr=False)

    def _logits(self, h):
        if self.config.tie_word_embeddings:
            # project through the transposed embedding table
            return apply_op(lambda hh, w: hh @ w.T, h,
                            self.llama.embed_tokens.weight,
                            op_name="tied_lm_head")
        return self.lm_head(h)

    def forward(self, input_ids, attention_mask=None, caches=None,
                position_offset=0):
        out = self.llama(input_ids, attention_mask, caches, position_offset)
        h, new_caches = out if caches is not None else (out, None)
        with jax.named_scope("llama.head"):
            logits = self._logits(h)
        return logits if caches is None else (logits, new_caches)

    def serve_model(self):
        """What the paged serving engine asks of a model (``serving.py``,
        the seam between engine and model)."""
        return LlamaServe(self.config)

    def generate(self, input_ids, max_new_tokens=32):
        """Greedy decode with per-layer KV caches (inference parity check,
        not the serving path)."""
        ids = input_ids
        caches = [(None, None)] * self.config.num_hidden_layers
        logits, caches = self.forward(ids, caches=caches)
        for _ in range(max_new_tokens):
            next_id = jnp.argmax(logits._data[:, -1, :], axis=-1)[:, None]
            offset = caches[0][0]._data.shape[1] if isinstance(
                caches[0][0], Tensor) else caches[0][0].shape[1]
            ids = Tensor(jnp.concatenate([ids._data, next_id], axis=1))
            logits, caches = self.forward(
                Tensor(next_id), caches=caches, position_offset=offset)
        return ids


class LlamaPretrainingCriterion(Layer):
    """Causal-LM loss: shifted next-token cross entropy
    (ref: LlamaPretrainingCriterion in semi_auto_parallel_llama_model.py)."""

    def __init__(self, config: Optional[LlamaConfig] = None):
        super().__init__()

    def forward(self, logits, labels):
        def f(lg, lb):
            import jax
            import jax.numpy as jnp

            from ..ops.fused_ce import fused_softmax_ce_mean
            # barrier ties label prep (and any reshard GSPMD inserts for
            # it) into the logits' dependency chain: label-side
            # collectives would otherwise be independent of the model's
            # collective chain and can race it on the XLA:CPU in-process
            # rendezvous (deadlock in the CP dryrun); on TPU the labels
            # are tiny and the barrier costs nothing
            lg, lb = jax.lax.optimization_barrier((lg, lb))
            # shift the LABELS (tiny int array), not the logits: slicing
            # lg[:, :-1] copies the whole [B, L, V] tensor (262 MB at
            # the 1B-scale geometry) and leaves an odd L-1 chunk size;
            # the final position is masked out via ignore_index instead
            shifted = jnp.concatenate(
                [lb[:, 1:], jnp.full((lb.shape[0], 1), -100, lb.dtype)],
                axis=1)
            # the dynamic valid count (inside fused CE) keeps padded
            # batches correct: labels may already carry -100 positions,
            # which must leave the mean's denominator too. Its reduction
            # is serialized behind the barrier above, so it cannot race
            # the model's collective chain.
            return fused_softmax_ce_mean(lg, shifted, ignore_index=-100)
        return apply_op(f, logits, labels, op_name="causal_lm_loss")


# ---------------------------------------------------------------------------
# The serving seam: the decoder layer on arrays, over the engine's paged
# cache (``serving.PagedLlamaDecodeEngine`` asks ``serve_model()`` for it).
# ---------------------------------------------------------------------------

def _quantize_w(w_t):
    """Per-output-channel symmetric int8 of a TRANSPOSED [out, in]
    weight (ref: quantize.py PTQ convert)."""
    w_t = np.asarray(w_t, np.float32)
    step = np.maximum(np.abs(w_t).max(axis=1), 1e-8) / 127.0
    q = np.clip(np.round(w_t / step[:, None]), -127, 127).astype(np.int8)
    return jnp.asarray(q), jnp.asarray(step.astype(np.float32))


# Served weights are stored TRANSPOSED ([out, in]) and contracted against
# their LAST dim: with the natural [in, out] orientation XLA's chosen
# executable layout disagreed with the call-input layout and re-transposed
# the weights every step, a per-call copy no warm-up can amortize because
# jit inputs cannot be layout-pinned across calls.
def _mm(h, w):
    """h @ w (w stored transposed); int8 path = dynamic per-tensor
    act quant + s8*s8->s32 with per-channel scale epilogue
    (quantize._int8_linear_impl math, calibration-free because
    decode activations are visible)."""
    if isinstance(w, tuple):
        w_q, w_step = w
        step = jnp.maximum(jnp.max(jnp.abs(h.astype(jnp.float32))),
                           1e-8) / 127.0
        qh = jnp.clip(jnp.round(h.astype(jnp.float32) / step),
                      -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(
            qh, w_q, (((qh.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * (w_step * step)).astype(
            h.dtype)
    return jax.lax.dot_general(
        h, w, (((h.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(h.dtype)


def _rms(h, w, eps):
    h32 = h.astype(jnp.float32)
    var = jnp.mean(jnp.square(h32), axis=-1, keepdims=True)
    return (h32 * jax.lax.rsqrt(var + eps)).astype(h.dtype) * w


def _rope_at(x, positions, theta):
    """x [S, T, Hd, D] rotated at per-slot absolute positions
    (positions [S, T])."""
    d2 = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, d2, dtype=jnp.float32) / d2))
    freqs = positions.astype(jnp.float32)[..., None] * inv  # [S,T,d2]
    cos = jnp.cos(freqs)[:, :, None, :]
    sin = jnp.sin(freqs)[:, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin],
        axis=-1).astype(x.dtype)


class LlamaServe:
    """The model's side of the serving seam: its cache spec (for each
    layer a kind, ``full`` or ``window`` of ``W`` positions, its pools
    ``k`` and ``v`` by row width, and KV heads x head_dim), its parameters
    as the engine's pytree, and a step a layer ``(h, the layer's pools,
    positions, the kind's block table, carry) -> (h, pools, counts,
    carry)``. From the engine it takes cache services only:
    ``_write_kv``, ``_sc.paged_attention``, ``block_size``, ``n_rep``,
    ``_pa_kernel``, and ``dtype`` / ``n_layers`` / ``int8`` to lay out
    its parameters."""

    n_aux = 0            # small integers a launch hands back with its token
    aux_names = ()
    supports_int8 = True
    supports_speculation = True

    def __init__(self, cfg: LlamaConfig):
        self.cfg = cfg
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads

    def cache_spec(self, n_layers: int) -> list:
        cfg = self.cfg
        width = cfg.num_key_value_heads * self.head_dim
        return [{"kind": "full", "window": None,
                 "pools": {"k": width, "v": width},
                 "kv_heads": cfg.num_key_value_heads,
                 "head_dim": self.head_dim,
                 "q_heads": cfg.num_attention_heads}] * n_layers

    def build_params(self, eng, sd) -> Dict[str, object]:
        """Device param pytree from a name -> array/Tensor state dict:
        dtype cast, TRANSPOSED projections (see ``_mm``), optional int8
        quantization, layer truncation."""
        cfg, dt = self.cfg, eng.dtype

        def get(name):
            try:
                v = sd[name]
            except KeyError:
                raise ValueError(
                    f"weight state dict is missing {name!r} — not a "
                    f"checkpoint of this model") from None
            if hasattr(v, "_data"):
                v = v._data
            return jnp.asarray(v, dt)

        p: Dict[str, object] = {"emb": get("llama.embed_tokens.weight"),
                                "norm": get("llama.norm.weight")}
        if cfg.tie_word_embeddings:
            p["head"] = p["emb"]      # [V, H] is already the
        else:                         # transposed head
            p["head"] = get("lm_head.weight").T
        layers = []
        for i in range(eng.n_layers):
            pre = f"llama.layers.{i}."
            lp = {"in_ln": get(pre + "input_layernorm.weight"),
                  "post_ln": get(pre
                                 + "post_attention_layernorm"
                                   ".weight")}
            for nm in ("q_proj", "k_proj", "v_proj", "o_proj"):
                lp[nm] = get(pre + "self_attn." + nm + ".weight").T
            for nm in ("gate_proj", "up_proj", "down_proj"):
                lp[nm] = get(pre + "mlp." + nm + ".weight").T
            if eng.int8:
                for nm in ("q_proj", "k_proj", "v_proj", "o_proj",
                           "gate_proj", "up_proj", "down_proj"):
                    lp[nm] = _quantize_w(lp[nm])
            layers.append(lp)
        p["layers"] = layers
        if eng.int8:
            p["head"] = _quantize_w(p["head"])
        return p

    def embed(self, eng, params, ids):
        return jnp.take(params["emb"], ids, axis=0).astype(eng.dtype)

    def layer(self, eng, li, lp, h, kvl, positions, tables, n_tiles, wmask,
              carry=None):
        """One decoder layer over [S, T, H] with block-pool K/V writes
        and the tiled streaming attention."""
        cfg = self.cfg
        S, T, H = h.shape
        kvh = cfg.num_key_value_heads
        res = h
        x = _rms(h, lp["in_ln"], cfg.rms_norm_eps)
        q = _mm(x, lp["q_proj"]).reshape(
            S, T, cfg.num_attention_heads, self.head_dim)
        k = _mm(x, lp["k_proj"]).reshape(S, T, kvh, self.head_dim)
        v = _mm(x, lp["v_proj"]).reshape(S, T, kvh, self.head_dim)
        q = _rope_at(q, positions, cfg.rope_theta)
        k = _rope_at(k, positions, cfg.rope_theta)
        with jax.named_scope("paged.kv_write"):
            kvl = eng._write_kv(kvl, k, v, positions, tables, wmask)
        with jax.named_scope("paged.attn"):
            att = eng._sc.paged_attention(
                q, kvl["k"], kvl["v"], tables, positions,
                block_size=eng.block_size, n_rep=eng.n_rep,
                n_tiles=n_tiles, k_scale=kvl.get("ksc"),
                v_scale=kvl.get("vsc"), use_kernel=eng._pa_kernel)
        h = res + _mm(att.reshape(S, T, H), lp["o_proj"])
        with jax.named_scope("paged.mlp"):
            res = h
            x = _rms(h, lp["post_ln"], cfg.rms_norm_eps)
            ff = _mm(jax.nn.silu(
                _mm(x, lp["gate_proj"]).astype(jnp.float32)).astype(
                    x.dtype) * _mm(x, lp["up_proj"]),
                lp["down_proj"])
            return res + ff, kvl, None, carry

    def head(self, eng, params, h):
        return _mm(_rms(h, params["norm"], self.cfg.rms_norm_eps),
                   params["head"])


# ---------------------------------------------------------------------------
# Parallel placement rules (ref: the shard_tensor calls sprinkled through
# semi_auto_parallel_llama_model.py, expressed here as one rule table).
# ---------------------------------------------------------------------------

def shard_llama(model: LlamaForCausalLM, mesh, tp_axis: Optional[str] = "mp",
                fsdp_axis: Optional[str] = None):
    """Attach NamedShardings to every parameter: tensor-parallel column/row
    splits on `tp_axis`, ZeRO-3-style parameter sharding on `fsdp_axis`.

    Mirrors the reference placements: column-parallel weights (q/k/v, gate/up,
    lm_head, embedding hidden dim) shard their OUT dim on tp; row-parallel
    (o_proj, down_proj) shard their IN dim. With weight layout [in, out]:
    column => Shard(1), row => Shard(0). FSDP shards the remaining dim.
    """
    from ..distributed.api import shard_parameter

    for layer in model.sublayers():
        if isinstance(layer, LlamaAttention):
            layer.mesh = mesh.to_jax_mesh()
    for name, p in model.named_parameters():
        if p is None:
            continue
        if any(s in name for s in ("embed_tokens", "q_proj", "k_proj",
                                   "v_proj", "gate_proj", "up_proj",
                                   "lm_head")):
            tp_dim, fsdp_dim = 1, 0               # column parallel
        elif any(s in name for s in ("o_proj", "down_proj")):
            tp_dim, fsdp_dim = 0, 1               # row parallel
        else:                                      # norms
            tp_dim, fsdp_dim = None, None
        shard_parameter(p, mesh, tp_axis, fsdp_axis, tp_dim, fsdp_dim)
    return model
