"""Operations and bytes of the HELD work of a GLM-MoE-DSA decoder that holds a
share of its experts, from shapes and from what the program's launches count.
What the algorithm needs, not what a program happens to execute: the latent
projections, the dense or shared feed-forward and the router once per token; a
routed expert its three matrices once per row that landed on it (`moe_rows`,
counted by the program); the indexer's scores once per (row, visible position)
on the layers that have an indexer; attention once per (row, SELECTED position)
in every layer, in absorbed form (a pair costs the query against the row's 576
numbers and the weighted sum of its 512); the head once per token that needs
logits. The up-projection `W_kvb` is counted once per token (folded into the
query and the output), never per cached position. A multiply-add is two
operations. `cfg` is the configuration file's dict.
"""
from __future__ import annotations


def layer_counts(cfg) -> dict:
    """Layers held here by what they have: all, with an indexer, dense, sparse."""
    n = cfg["num_hidden_layers"]
    full = sum(t == "full" for t in cfg["indexer_types"][:n])
    dense = sum(t == "dense" for t in cfg["mlp_layer_types"][:n])
    return {"layers": n, "full": full, "dense": dense, "sparse": n - dense}


def latent_width(cfg) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attn_params(cfg) -> int:
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv, rope = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * rq + rq * nh * (nope + rope) + h * (rkv + rope)
            + rkv * nh * (nope + vd) + nh * vd * h)


def indexer_params(cfg) -> int:
    j, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * j * d + cfg["hidden_size"] * (d + j)


def dense_mlp_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg) -> int:
    """One routed expert (or the shared one): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_token_params(cfg) -> int:
    """What every token of a sparse layer goes through outside the routed
    experts: the router and the shared expert(s)."""
    return cfg["hidden_size"] * cfg["n_routed_experts_published"] \
        + cfg["n_shared_experts"] * expert_params(cfg)


def token_params(cfg) -> int:
    """Matmul parameters every token goes through, all layers, without the
    routed experts and the head."""
    c = layer_counts(cfg)
    return (c["layers"] * attn_params(cfg) + c["full"] * indexer_params(cfg)
            + c["dense"] * dense_mlp_params(cfg)
            + c["sparse"] * sparse_token_params(cfg))


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg) -> int:
    """Every parameter held here (norm gains and biases included)."""
    c = layer_counts(cfg)
    h = cfg["hidden_size"]
    norms = c["layers"] * (2 * h + cfg["q_lora_rank"] + cfg["kv_lora_rank"]) \
        + c["full"] * 2 * cfg["index_head_dim"] \
        + c["sparse"] * cfg["n_routed_experts_published"] + h
    return (token_params(cfg)
            + c["sparse"] * cfg["n_routed_experts"] * expert_params(cfg)
            + 2 * head_params(cfg) + norms)


def attn_pair_flops(cfg) -> float:
    """One (query row, selected position) pair in one layer, all heads."""
    return 2.0 * cfg["num_attention_heads"] * (latent_width(cfg)
                                               + cfg["kv_lora_rank"])


def index_pair_flops(cfg) -> float:
    """One (query row, visible position) pair on one indexer layer."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]


def launch_flops(cfg, tokens: int, head_tokens: int, selected_pairs: int,
                 visible_pairs: int, moe_rows: int) -> float:
    """One launch: `tokens` rows through every layer, `head_tokens` logit rows,
    attention over `selected_pairs` (row, selected position) pairs in each
    layer, index scores over `visible_pairs` (row, visible position) pairs on
    each indexer layer, and `moe_rows` (row, held expert) pairs summed over the
    layers."""
    c = layer_counts(cfg)
    return (2.0 * token_params(cfg) * tokens
            + c["layers"] * attn_pair_flops(cfg) * selected_pairs
            + c["full"] * index_pair_flops(cfg) * visible_pairs
            + 2.0 * expert_params(cfg) * moe_rows
            + 2.0 * head_params(cfg) * head_tokens)


def launch_weight_bytes(cfg, experts_hit: int, itemsize: int = 2) -> float:
    """Weights one launch must read: everything but the routed experts that
    had no row (`experts_hit`: held experts with a row, summed over layers)."""
    return itemsize * (token_params(cfg) + head_params(cfg)
                       + experts_hit * expert_params(cfg))


def cache_read_bytes(cfg, latent_rows: int, index_keys: int,
                     itemsize: int = 2) -> float:
    """Cache a launch reads: `latent_rows` rows of `[c_kv ; k_rope]` in each
    layer (the selected rows; a chunk's distinct ones), `index_keys` index keys
    on each indexer layer."""
    c = layer_counts(cfg)
    return itemsize * (c["layers"] * latent_width(cfg) * latent_rows
                       + c["full"] * cfg["index_head_dim"] * index_keys)


def experts_cost(cfg, moe_rows: int, experts_hit: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of the routed experts' matmuls of one launch: three
    matrices a counted row; each hit expert's weights once, each row's input,
    hidden pair and output once."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (2.0 * expert_params(cfg) * moe_rows,
            itemsize * (experts_hit * expert_params(cfg)
                        + moe_rows * (2 * h + 3 * inter)))


def latent_attn_cost(cfg, rows: int, selected_pairs: int, latent_rows: int,
                     itemsize: int = 2) -> tuple:
    """(operations, bytes) of the attention over selected rows, all layers, one
    launch: the pairs' two contractions; `latent_rows` cache rows read a layer,
    the absorbed queries read and the latent outputs written."""
    c = layer_counts(cfg)
    nh, w, rank = cfg["num_attention_heads"], latent_width(cfg), cfg["kv_lora_rank"]
    return (c["layers"] * attn_pair_flops(cfg) * selected_pairs,
            itemsize * c["layers"] * (w * latent_rows + rows * nh * (w + rank)))


def index_score_cost(cfg, rows: int, visible_pairs: int, index_keys: int,
                     itemsize: int = 2) -> tuple:
    """(operations, bytes) of the index scores on the indexer layers, one
    launch: the pairs' dot products over the index heads; the keys read once,
    the index queries read, one float32 score a pair written."""
    c = layer_counts(cfg)
    j, d = cfg["index_n_heads"], cfg["index_head_dim"]
    return (c["full"] * index_pair_flops(cfg) * visible_pairs,
            c["full"] * (itemsize * (d * index_keys + rows * j * d)
                         + 4.0 * visible_pairs))
