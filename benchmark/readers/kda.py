"""Readers of the per-layer metrics of a served model whose layers are gated
delta-rule (KDA) linear attention with a state a slot, softmax GQA layers over a
paged K/V pool, and a held share of sparse experts. They read the counts that the
program's `serving.decode` / `serving.prefill` spans carry (`rows`/`tokens`,
`live_tokens`, `state_slots`, `state_subchunks`, `moe_rows`, `moe_experts_hit`,
`moe_max_rows`) and the device time of the kernels' events;
`lib/flops_solar_open2.py` turns counts into operations and bytes. Each returns
None where it finds nothing to read, as on a program without these spans.

The expert counts ride on the span whose fetches bring them (`moe_launches` says
how many launches a span's counts cover): exact in sum over a window, and only
summed here.
"""
from __future__ import annotations

from benchmark.lib import flops_solar_open2 as F
from benchmark.lib import trace_reduce
from benchmark.lib.flops import roofline_seconds
from benchmark.readers.spans import _program

DECODE, PREFILL = "serving.decode", "serving.prefill"


def _launches(obs):
    """One dict a launch span of the window: rows through the layers, rows that
    need logits, K/V positions and (query, key) pairs of a GQA layer, slot
    states a KDA layer steps, sub-chunks and chunks its chunk form takes, and
    the expert counts the span carries (0 where it carries none)."""
    prog = _program(obs)
    if prog is None:
        return None
    out = []
    for _, _, name, st in prog["spans"]:
        if name == DECODE and "state_slots" in st:
            one = {"rows": st["rows"], "head": st["rows"], "kv": st["live_tokens"],
                   "pairs": st["live_tokens"], "states": st["state_slots"],
                   "subchunks": 0, "chunks": 0}
        elif name == PREFILL and "state_subchunks" in st:
            t, s0 = st["tokens"], st["start"]
            one = {"rows": t, "head": 0, "kv": s0 + t,
                   "pairs": t * s0 + t * (t + 1) // 2, "states": 1,
                   "subchunks": st["state_subchunks"], "chunks": 1}
        else:
            continue
        for key in ("moe_rows", "moe_experts_hit", "moe_max_rows"):
            one[key] = st.get(key, 0)
        out.append(one)
    return out or None


def _kernel(obs, pattern):
    """(device seconds, events) of a kernel's events in the traced window."""
    return trace_reduce.op_seconds(obs["trace"], pattern) if obs["trace"] \
        else (0.0, 0)


def _window_s(obs) -> float:
    lo, hi = _program(obs)["window_ns"]
    return (hi - lo) * 1e-9


def mfu(obs):
    """Operations of the held work that the window's launches needed over the
    chip's bf16 peak x the window."""
    launches = _launches(obs)
    if not launches:
        return None
    need = sum(F.launch_flops(obs["config"], l["rows"], l["head"], l["pairs"],
                              l["moe_rows"]) for l in launches)
    return 100.0 * need / (obs["peaks"]["bf16_flops"] * _window_s(obs))


def hbm_stream_share(obs):
    """Bytes the window's launches had to move (weights once a launch less the
    routed experts with no row; the K and V of the GQA layers; every stepped
    slot's state read and written, a chunk's once) over the chip's HBM bandwidth
    x the window."""
    launches = _launches(obs)
    if not launches:
        return None
    cfg = obs["config"]
    nbytes = sum(F.launch_weight_bytes(cfg, l["moe_experts_hit"])
                 + F.kv_read_bytes(cfg, l["kv"])
                 + F.state_stream_bytes(cfg, l["states"]) for l in launches)
    return 100.0 * nbytes / (obs["peaks"]["hbm_bytes_per_s"] * _window_s(obs))


def _roofline(obs, pattern, cost):
    """Least time for what `cost(launch)` says each launch needed (the slower of
    the MXU and HBM, a launch) over the device time of `pattern`'s events."""
    launches = _launches(obs)
    seconds, events = _kernel(obs, pattern)
    if not launches or not events or seconds <= 0:
        return None
    least = sum(roofline_seconds(*cost(l), obs["peaks"])[0] for l in launches)
    return 100.0 * least / seconds if least else None


def kda_step_roofline(obs, pattern):
    """The step kernel: the counted `state_slots`' states read and written in
    each KDA layer, with their rows."""
    cfg = obs["config"]
    return _roofline(obs, pattern, lambda l: F.kda_step_cost(
        cfg, l["states"] if not l["chunks"] else 0))


def kda_chunk_roofline(obs, pattern):
    """The chunk kernel: the counted sub-chunks' operations and operands, the
    state once a chunk."""
    cfg = obs["config"]
    return _roofline(obs, pattern, lambda l: F.kda_chunk_cost(
        cfg, l["subchunks"], l["chunks"]))


def experts_roofline(obs, pattern):
    cfg = obs["config"]
    return _roofline(obs, pattern, lambda l: F.experts_cost(
        cfg, l["moe_rows"], l["moe_experts_hit"]) if l["moe_rows"] else (0.0, 0.0))


def paged_attn_roofline(obs, pattern):
    """The paged kernel over the GQA layers: their K and V, queries, outputs."""
    cfg = obs["config"]
    return _roofline(obs, pattern, lambda l: F.paged_attn_cost(
        cfg, l["rows"], l["kv"], l["pairs"]))


def rows_max_over_mean(obs):
    """Rows of the fullest held expert over the mean rows a held expert, both
    summed over the window's layers and launches: 1 is an even spread."""
    launches = _launches(obs)
    if not launches:
        return None
    rows = sum(l["moe_rows"] for l in launches)
    fullest = sum(l["moe_max_rows"] for l in launches)
    return fullest / (rows / obs["config"]["n_routed_experts"]) if rows else None
