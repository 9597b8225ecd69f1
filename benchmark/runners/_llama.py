"""What the two Llama-code runners share: the program's model built from a
configuration file and loaded with the benchmark's seeded weights."""
from __future__ import annotations

import gc

# the benchmark's leaf names (lib.weights) -> the program's parameter names
_LAYER = {"attn_norm": "input_layernorm", "mlp_norm": "post_attention_layernorm",
          "q": "self_attn.q_proj", "k": "self_attn.k_proj",
          "v": "self_attn.v_proj", "o": "self_attn.o_proj",
          "gate": "mlp.gate_proj", "up": "mlp.up_proj", "down": "mlp.down_proj"}

# a rehearsal's sizes: the same control flow on a CPU in seconds
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 128,
        "head_dim": 16}


def path_counts() -> dict:
    """`pallas.path_selected_total` as {"kernel:path": n}: which implementation
    every Pallas seam of the program took so far."""
    from paddle_tpu.observability import metrics as om
    c = om.default_registry().get("pallas.path_selected_total")
    return {f"{dict(k).get('kernel')}:{dict(k).get('path')}": int(v)
            for k, v in (c.series() if c is not None else {}).items()}


def program_name(leaf: str) -> str:
    if leaf == "embed":
        return "llama.embed_tokens.weight"
    if leaf == "final_norm":
        return "llama.norm.weight"
    if leaf == "head":
        return "lm_head.weight"
    _, i, part = leaf.split(".")
    return f"llama.layers.{i}.{_LAYER[part]}.weight"


def model_config(cfg: dict, dtype: str = "float32"):
    from paddle_tpu.models import LlamaConfig
    if cfg.get("tie_word_embeddings") or cfg.get("sliding_window"):
        raise ValueError("the Llama-code runners take untied, full-attention "
                         "configurations only")
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        max_position_embeddings=cfg["max_position_embeddings"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        dtype=dtype)


def build_model(cfg: dict, seed_u32, dtype_name: str):
    """`LlamaForCausalLM` at the configuration's sizes, born in its dtype (as
    chip_smoke builds it), every parameter then replaced by the benchmark's
    seeded leaf. The program's own initial values are dropped first, so the
    two sets never sit on the device together."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    from benchmark.lib import weights
    dtype = jnp.dtype(dtype_name)
    paddle.seed(int(seed_u32) % (1 << 31))
    paddle.set_default_dtype(dtype_name)
    try:
        model = LlamaForCausalLM(model_config(cfg, dtype_name))
    finally:
        paddle.set_default_dtype("float32")
    params = dict(model.named_parameters())
    specs = weights.leaf_specs(cfg)
    if sorted(program_name(n) for n, _ in specs) != sorted(params):
        raise RuntimeError("the program's parameters are not the leaves the "
                           "reference is built from")
    for p in params.values():
        if str(p.dtype) != dtype_name:
            raise RuntimeError(f"parameter born as {p.dtype}, not {dtype_name}")
        p._data = jnp.zeros((), dtype)        # drop the program's own values
    gc.collect()
    leaves = weights.make_all(cfg, dtype)(seed_u32)
    for name, shape in specs:
        p = params[program_name(name)]
        p._data = leaves.pop(name)
        if tuple(p._data.shape) != tuple(shape):
            raise RuntimeError(f"leaf {name}: shape {p._data.shape} != {shape}")
    return model
