"""Operations and bytes of the HELD work of a Cohere2-MoE decoder layer that
holds a share of its experts, from shapes and from what the program's launches
count. What the algorithm needs, not what a program happens to execute: a
routed expert costs its three matrices once per row that landed on it (counted
by the program: `moe_rows`), never per row of the launch; attention by layer
kind (a full layer reads every earlier position, a window layer the last
`sliding_window`); the shared experts, the router and the projections once per
token; the tied head once per token that needs logits. A multiply-add is two
operations. `cfg` is the configuration file's dict.
"""
from __future__ import annotations


def _dims(cfg):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["intermediate_size"])


def layer_kinds(cfg) -> tuple:
    """(full layers, window layers) among the layers held here."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    full = sum(k == "full_attention" for k in kinds)
    return full, len(kinds) - full


def attn_params(cfg) -> int:
    h, nh, kvh, d, _ = _dims(cfg)
    return h * nh * d + 2 * h * kvh * d + nh * d * h


def shared_params(cfg) -> int:
    h, _, _, _, inter = _dims(cfg)
    return cfg["num_shared_experts"] * 3 * h * inter


def router_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["num_experts_published"]


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    h, _, _, _, inter = _dims(cfg)
    return 3 * h * inter


def dense_layer_params(cfg) -> int:
    """What every token of a layer goes through outside the routed experts."""
    return attn_params(cfg) + shared_params(cfg) + router_params(cfg)


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def total_params(cfg) -> int:
    layer = dense_layer_params(cfg) + cfg["num_experts"] * expert_params(cfg) \
        + cfg["hidden_size"]
    return cfg["num_hidden_layers"] * layer + head_params(cfg) + cfg["hidden_size"]


def attention_flops(cfg, full_pairs: int, window_pairs: int) -> float:
    """QK^T and PV over the (query, key) pairs a launch attends: `full_pairs`
    in each full layer, `window_pairs` in each window layer."""
    _, nh, _, d, _ = _dims(cfg)
    full, window = layer_kinds(cfg)
    return 4.0 * nh * d * (full * full_pairs + window * window_pairs)


def launch_flops(cfg, tokens: int, head_tokens: int, full_pairs: int,
                 window_pairs: int, moe_rows: int) -> float:
    """One launch: `tokens` rows through every layer, `head_tokens` logit rows,
    attention over the counted pairs, and `moe_rows` (row, held expert) pairs
    summed over the layers."""
    n = cfg["num_hidden_layers"]
    return (2.0 * n * dense_layer_params(cfg) * tokens
            + attention_flops(cfg, full_pairs, window_pairs)
            + 2.0 * expert_params(cfg) * moe_rows
            + 2.0 * head_params(cfg) * head_tokens)


def launch_weight_bytes(cfg, experts_hit: int, itemsize: int = 2) -> float:
    """Weights one launch must read: everything but the routed experts that
    had no row (`experts_hit`: held experts with a row, summed over layers);
    the tied embedding once, as the head."""
    n = cfg["num_hidden_layers"]
    return itemsize * (n * dense_layer_params(cfg) + head_params(cfg)
                       + experts_hit * expert_params(cfg))


def kv_bytes_per_token_layer(cfg, itemsize: int = 2) -> int:
    _, _, kvh, d, _ = _dims(cfg)
    return 2 * kvh * d * itemsize


def kv_read_bytes(cfg, live_tokens: int, window_tokens: int) -> float:
    """K and V a launch reads: `live_tokens` positions in each full layer,
    `window_tokens` in each window layer."""
    full, window = layer_kinds(cfg)
    return kv_bytes_per_token_layer(cfg) * (full * live_tokens
                                            + window * window_tokens)


def experts_cost(cfg, moe_rows: int, experts_hit: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of the routed experts' matmuls of one launch: three
    matrices a counted row; each hit expert's weights once, each row's input,
    hidden pair and output once."""
    h, _, _, _, inter = _dims(cfg)
    flops = 2.0 * expert_params(cfg) * moe_rows
    nbytes = itemsize * (experts_hit * expert_params(cfg)
                         + moe_rows * (2 * h + 3 * inter))
    return flops, nbytes


def paged_attn_cost(cfg, rows: int, full_kv: int, window_kv: int,
                    full_pairs: int, window_pairs: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of the paged-attention kernel over all layers for
    one launch: K and V read by layer kind, queries read and outputs written."""
    _, nh, _, d, _ = _dims(cfg)
    n = cfg["num_hidden_layers"]
    return (attention_flops(cfg, full_pairs, window_pairs),
            kv_read_bytes(cfg, full_kv, window_kv) + 2.0 * n * rows * nh * d * itemsize)
