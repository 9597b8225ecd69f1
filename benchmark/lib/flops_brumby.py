"""Operations and bytes of a Brumby decoder (every layer degree-2 power
retention), from shapes and from what the program's launches count. What the
algorithm needs, not what a program happens to execute: the projections, the
gate and the feed-forward once per token through the layers; the untied head
once per token that needs logits; a slot's whole state (`S [KV heads, D, d]`
and `z [KV heads, D]` float32 a layer, `D = d (d + 1) / 2`) read and written
once a token in a decode step and once a CHUNK in prefill; within a chunk the
quadratic form over its causal pairs, `phi(Q) S_0` and `phi(Q).z_0` for its
rows and `phi(K)^T V` into the state. A multiply-add is two operations. `cfg`
is the configuration file's dict.
"""
from __future__ import annotations

ROWS = 128          # rows of the chunk kernel's tile: the spans count in it
F32 = 4


def dims(cfg) -> tuple:
    """(query heads, KV heads, head width, D)."""
    nh, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return nh, kvh, d, d * (d + 1) // 2


def layer_params(cfg) -> int:
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    nh, kvh, d, _ = dims(cfg)
    return (2 * h * nh * d + 2 * h * kvh * d + 3 * h * inter + h * kvh
            + kvh + 2 * h + 2 * d)


def head_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def params(cfg) -> int:
    """Every parameter held here: the layers, the embedding and the head."""
    return cfg["num_hidden_layers"] * layer_params(cfg) + 2 * head_params(cfg)


def state_slot_bytes(cfg) -> int:
    """One slot's state, every layer: `S` and `z` in float32."""
    _, kvh, d, D = dims(cfg)
    return cfg["num_hidden_layers"] * kvh * (D * d + D) * F32


def weight_bytes(cfg, itemsize: int = 2) -> int:
    """Weights a launch streams: every layer's and the head (the embedding is
    read a row a token)."""
    return (cfg["num_hidden_layers"] * layer_params(cfg) + head_params(cfg)) \
        * itemsize


def launch_bytes(cfg, slots: int, chunks: int, itemsize: int = 2) -> int:
    """The least a launch moves: its weights once, and the state of every
    slot it steps (`slots`, a decode launch) or of its chunk's slot (`chunks`,
    a prompt chunk) read and written."""
    return weight_bytes(cfg, itemsize) \
        + 2 * (slots + chunks) * state_slot_bytes(cfg)


def retention_step_cost(cfg, slots: int) -> tuple:
    """(operations, bytes) of the step kernel over `slots` slots, every layer:
    each slot's state read and written, each state row decayed, added to and
    read by the group's queries, and the rows' operands (queries, k, v, decay
    in; numerators and denominators out)."""
    nh, kvh, d, D = dims(cfg)
    n = cfg["num_hidden_layers"] * slots
    flops = n * (2 * kvh * D * d + 2 * nh * D * d + 2 * (kvh + nh) * D)
    nbytes = 2 * slots * state_slot_bytes(cfg) \
        + n * (nh * d + 2 * kvh * d + kvh + 2 * nh * d) * F32
    return float(flops), float(nbytes)


def retention_chunk_cost(cfg, subchunks: int, chunks: int) -> tuple:
    """(operations, bytes) of the chunk kernel over `subchunks` row tiles of
    `ROWS` in `chunks` chunks, every layer: the quadratic form over the causal
    pairs of a chunk's rows (`(q.k)` and the weighted sum of `v`), `phi(Q) S_0`
    and `phi(Q).z_0`, `phi(K)^T V` and the keys' sum into `z`, each state read
    and written once a chunk, and the rows' operands."""
    nh, kvh, d, D = dims(cfg)
    n_layers = cfg["num_hidden_layers"]
    rows = subchunks * ROWS
    per_chunk = rows / max(chunks, 1)
    pairs = chunks * per_chunk * (per_chunk + 1) / 2
    flops = n_layers * (2 * nh * pairs * 2 * d
                        + 2 * rows * nh * D * (d + 1)
                        + 2 * rows * kvh * D * (d + 1))
    nbytes = chunks * state_slot_bytes(cfg) * 2 \
        + n_layers * rows * (nh * d + 2 * kvh * d + kvh + nh * d) * F32
    return float(flops), float(nbytes)


def launch_flops(cfg, rows: int, head_rows: int, slots: int, subchunks: int,
                 chunks: int) -> float:
    """Operations of one launch through the whole model: `rows` rows through
    the layers' matmuls, the head on the `head_rows` that need logits, and the
    retention (a decode step over `slots` slots, or a chunk of `subchunks` row
    tiles)."""
    dense = 2 * (rows * cfg["num_hidden_layers"] * layer_params(cfg)
                 + head_rows * head_params(cfg))
    return dense + retention_step_cost(cfg, slots)[0] \
        + retention_chunk_cost(cfg, subchunks, chunks)[0]
