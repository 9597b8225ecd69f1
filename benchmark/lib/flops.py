"""Operations and bytes from shapes, for a dense GQA + SwiGLU decoder.

Counts what the algorithm needs, not what a program happens to execute:
no embedding gather, no recomputation, the output head once per token that
needs logits, causal attention at half the square. A multiply-add is two
operations. `cfg` is a configuration file's dict (Hugging Face key names).
"""
from __future__ import annotations


def _dims(cfg):
    h = cfg["hidden_size"]
    nh = cfg["num_attention_heads"]
    d = cfg.get("head_dim") or h // nh
    return h, nh, cfg["num_key_value_heads"], d, cfg["intermediate_size"]


def layer_matmul_params(cfg) -> int:
    h, nh, kvh, d, inter = _dims(cfg)
    return h * nh * d + 2 * h * kvh * d + nh * d * h + 3 * h * inter


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg) -> int:
    """Parameters that sit in a matrix multiplication on every token."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) + head_params(cfg)


def total_params(cfg) -> int:
    h = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * h
    embed = cfg["vocab_size"] * h
    tied = cfg.get("tie_word_embeddings", False)
    return matmul_params(cfg) + norms + (0 if tied else embed)


def train_flops_per_token(cfg, seq: int) -> float:
    """Forward + backward of one token in a causal sequence of `seq`:
    6 per matmul parameter, and attention's QK^T and PV (2 matmuls forward,
    4 backward) over the causal half of the square."""
    _, nh, _, d, _ = _dims(cfg)
    attn_fwd = cfg["num_hidden_layers"] * 2 * 2 * nh * d * seq / 2
    return 6 * matmul_params(cfg) + 3 * attn_fwd


def flash_fwd_cost(batch, seq, heads, head_dim, itemsize=2):
    """(operations, bytes) of one causal flash-attention forward call at
    [batch, seq, heads, head_dim] (K and V as the call receives them)."""
    flops = 2 * 2 * batch * heads * seq * seq * head_dim / 2
    tensor = batch * seq * heads * head_dim * itemsize
    return flops, 4 * tensor + batch * heads * seq * 4        # q k v o + lse


def flash_bwd_cost(batch, seq, heads, head_dim, itemsize=2):
    """(operations, bytes) of the backward of that call: five matmuls over
    the causal half (S, dP, dV, dK, dQ) where the forward has two; a split
    into a dq and a dk+dv kernel that forms S and dP twice is not counted
    twice. Reads q k v o do lse, writes dq dk dv."""
    flops = 5 * 2 * batch * heads * seq * seq * head_dim / 2
    tensor = batch * seq * heads * head_dim * itemsize
    return flops, 8 * tensor + 2 * batch * heads * seq * 4


def roofline_seconds(flops, nbytes, peaks) -> tuple:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def serve_flops(cfg, layer_positions, head_tokens: int) -> float:
    """Serving: every token that goes through the layers costs 2 per layer
    matmul parameter plus attention over its context; `layer_positions` is
    an iterable of the (0-based) positions of those tokens. The head is
    applied once per token delivered (`head_tokens`)."""
    _, nh, _, d, _ = _dims(cfg)
    n_layers = cfg["num_hidden_layers"]
    n = 0
    ctx = 0
    for p in layer_positions:
        n += 1
        ctx += p + 1
    return (2 * n_layers * layer_matmul_params(cfg) * n
            + n_layers * 4 * nh * d * ctx + 2 * head_params(cfg) * head_tokens)


def serve_weight_bytes(cfg, itemsize=2) -> int:
    """Bytes of weights one decode or prefill launch must read."""
    return matmul_params(cfg) * itemsize


def kv_bytes_per_token(cfg, itemsize=2) -> int:
    _, _, kvh, d, _ = _dims(cfg)
    return 2 * cfg["num_hidden_layers"] * kvh * d * itemsize
