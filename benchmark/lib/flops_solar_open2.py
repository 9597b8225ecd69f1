"""Operations and bytes of the HELD work of a Solar Open 2 decoder that holds a
share of its experts, from shapes and from what the program's launches count.
What the algorithm needs, not what a program happens to execute: a routed expert
costs its three matrices once per row that landed on it (`moe_rows`); softmax
attention reads every earlier position in the GQA layers only; a KDA layer reads
and writes a slot's whole state once a token in a decode step and once a CHUNK in
prefill, and its delta rule costs `7 d^2` multiply-adds a head a token as a
recurrence; the projections, gates, router and shared expert once per token; the
untied head once per token that needs logits. A multiply-add is two operations.
`cfg` is the configuration file's dict.
"""
from __future__ import annotations

SUBCHUNK = 64           # rows of a sub-chunk of the chunk form


def linear_dims(cfg) -> tuple:
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def layer_kinds(cfg) -> tuple:
    """(GQA layers, KDA layers) among the layers held here."""
    n = cfg["num_hidden_layers"]
    gqa = sum(1 for i in range(n) if i in cfg["gqa_layers"])
    return gqa, n - gqa


def kda_params(cfg) -> int:
    h, r = cfg["hidden_size"], cfg["gate_low_rank"]
    H, d, taps = linear_dims(cfg)
    w = H * d
    return (4 * h * w + 2 * (h * r + r * w) + h * H + 3 * w * taps
            + H + w + d)


def gqa_params(cfg) -> int:
    h, nh, kvh, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    return 3 * h * nh * d + 2 * h * kvh * d      # q, gate, o; k, v


def router_params(cfg) -> int:
    return (cfg["hidden_size"] + 1) * cfg["n_routed_experts_published"]


def expert_params(cfg) -> int:
    """One expert, routed or shared: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg) -> int:
    return cfg["n_shared_experts"] * expert_params(cfg)


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def layer_params(cfg, kind: str) -> int:
    """A layer with its held experts (norms left out of the table's sums are
    counted here: two a layer)."""
    return ((kda_params(cfg) if kind == "kda" else gqa_params(cfg))
            + router_params(cfg) + shared_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg)
            + 2 * cfg["hidden_size"])


def total_params(cfg) -> int:
    gqa, kda = layer_kinds(cfg)
    return (gqa * layer_params(cfg, "gqa") + kda * layer_params(cfg, "kda")
            + 2 * head_params(cfg) + cfg["hidden_size"])


def dense_params(cfg) -> int:
    """What every token goes through outside the routed experts and the head,
    all layers."""
    gqa, kda = layer_kinds(cfg)
    return (gqa * gqa_params(cfg) + kda * kda_params(cfg)
            + (gqa + kda) * (router_params(cfg) + shared_params(cfg)))


def state_bytes_per_slot_layer(cfg) -> int:
    H, d, _ = linear_dims(cfg)
    return H * d * d * 4                       # float32


def delta_rule_flops(cfg, tokens: int) -> float:
    """The recurrence itself, one KDA layer: decay, `S'^T k`, the rank-one
    update and `S^T q` are 7 d^2 multiply-adds a head a token."""
    H, d, _ = linear_dims(cfg)
    return 2.0 * 7 * d * d * H * tokens


def attention_flops(cfg, pairs: int) -> float:
    """QK^T and PV over the (query, key) pairs of ONE GQA layer."""
    return 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs


def launch_flops(cfg, tokens: int, head_tokens: int, pairs: int,
                 moe_rows: int) -> float:
    """One launch: `tokens` rows through every layer (the delta rule in the KDA
    layers), `head_tokens` logit rows, attention over `pairs` (query, key) pairs
    in each GQA layer, `moe_rows` (row, held expert) pairs over the layers."""
    gqa, kda = layer_kinds(cfg)
    return (2.0 * dense_params(cfg) * tokens
            + kda * delta_rule_flops(cfg, tokens)
            + gqa * attention_flops(cfg, pairs)
            + 2.0 * expert_params(cfg) * moe_rows
            + 2.0 * head_params(cfg) * head_tokens)


def launch_weight_bytes(cfg, experts_hit: int, itemsize: int = 2) -> float:
    """Weights one launch must read: everything but the routed experts that had
    no row (`experts_hit`: held experts with a row, summed over layers); the
    untied head once (the embedding is a gather of the launch's rows)."""
    return itemsize * (dense_params(cfg) + head_params(cfg)
                       + experts_hit * expert_params(cfg))


def kv_read_bytes(cfg, live_tokens: int, itemsize: int = 2) -> float:
    """K and V a launch reads: `live_tokens` positions in each GQA layer."""
    gqa, _ = layer_kinds(cfg)
    return (gqa * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize
            * live_tokens)


def state_stream_bytes(cfg, states: int) -> float:
    """`states` slot states read and written once in each KDA layer."""
    _, kda = layer_kinds(cfg)
    return 2.0 * kda * states * state_bytes_per_slot_layer(cfg)


def experts_cost(cfg, moe_rows: int, experts_hit: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of the routed experts' matmuls of one launch."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (2.0 * expert_params(cfg) * moe_rows,
            itemsize * (experts_hit * expert_params(cfg)
                        + moe_rows * (2 * h + 3 * inter)))


def paged_attn_cost(cfg, rows: int, kv: int, pairs: int, itemsize: int = 2) -> tuple:
    """(operations, bytes) of the paged-attention kernel over the GQA layers for
    one launch: K and V read, queries read and outputs written."""
    gqa, _ = layer_kinds(cfg)
    nh, d = cfg["num_attention_heads"], cfg["head_dim"]
    return (gqa * attention_flops(cfg, pairs),
            kv_read_bytes(cfg, kv) + 2.0 * gqa * rows * nh * d * itemsize)


def kda_step_cost(cfg, slots: int) -> tuple:
    """(operations, bytes) of the step kernel over the KDA layers for one decode
    launch: every stepped slot's state read and written, its rows' q, k, decay,
    beta k and beta v read (float32) and its output written."""
    H, d, _ = linear_dims(cfg)
    _, kda = layer_kinds(cfg)
    return (kda * delta_rule_flops(cfg, slots),
            state_stream_bytes(cfg, slots) + kda * slots * H * 6 * d * 4.0)


def kda_chunk_cost(cfg, subchunks: int, chunks: int) -> tuple:
    """(operations, bytes) of the chunk kernel over the KDA layers: what carries
    the state through `subchunks` sub-chunks of `SUBCHUNK` rows (`U = W - Y S`,
    `O = Q S + B U`, `S = g S + K^T U`: three `C x d x d` products and one `C x
    C x d` a head a sub-chunk), its six operands and its output a sub-chunk, and
    the state read and written once a CHUNK."""
    H, d, _ = linear_dims(cfg)
    _, kda = layer_kinds(cfg)
    c = SUBCHUNK
    flops = 2.0 * (3 * c * d * d + c * c * d) * H * subchunks
    nbytes = 4.0 * H * subchunks * (5 * c * d + c * c + d) \
        + 2.0 * chunks * state_bytes_per_slot_layer(cfg)
    return kda * flops, kda * nbytes
