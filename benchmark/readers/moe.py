"""Readers of the per-layer metrics of a served mixture-of-experts model that
holds a share of its experts and mixes window and full attention layers. They
read the counts that the program's `serving.decode` / `serving.prefill` spans
carry (`rows`/`tokens`, `live_tokens`, `window_tokens`, `moe_rows`,
`moe_experts_hit`, `moe_max_rows`), the block gauges the runner sampled, and the
device time of the kernels' events; `lib/flops_cohere2_moe.py` turns counts into
operations and bytes. Each returns None where it finds nothing to read, as on a
program without these spans or counts.

A launch's expert counts are read in a fetch: a prompt chunk that is not its
prompt's last has none, and its counts ride on the next span that fetched
(`moe_launches` says how many launches a span's counts cover). So the expert
counts are exact in sum over a window and are only summed here.
"""
from __future__ import annotations

from benchmark.lib import flops_cohere2_moe as F
from benchmark.lib import trace_reduce
from benchmark.lib.flops import roofline_seconds
from benchmark.readers.spans import _program

DECODE, PREFILL = "serving.decode", "serving.prefill"


def _window_pairs(start: int, tokens: int, window: int) -> int:
    """Sum over the rows `start .. start+tokens-1` of the positions a window
    layer lets each see: min(pos + 1, window)."""
    below = max(min(window - 1 - start, tokens), 0)     # rows with pos + 1 < window
    return below * start + below * (below + 1) // 2 + (tokens - below) * window


def _launches(obs):
    """One dict a launch span of the window: rows through the layers, rows
    that need logits, K/V positions and (query, key) pairs by layer kind, and
    the expert counts the span carries (0 where it carries none)."""
    prog = _program(obs)
    if prog is None:
        return None
    window = obs["config"]["sliding_window"]
    out = []
    for _, _, name, st in prog["spans"]:
        if name == DECODE and "window_tokens" in st:
            one = {"rows": st["rows"], "head": st["rows"],
                   "full_kv": st["live_tokens"], "window_kv": st["window_tokens"],
                   "full_pairs": st["live_tokens"],
                   "window_pairs": st["window_tokens"]}
        elif name == PREFILL and "window_tokens" in st:
            t, s0 = st["tokens"], st["start"]
            one = {"rows": t, "head": 1 if "moe_rows" in st else 0,
                   "full_kv": s0 + t, "window_kv": st["window_tokens"],
                   "full_pairs": t * s0 + t * (t + 1) // 2,
                   "window_pairs": _window_pairs(s0, t, window)}
        else:
            continue
        for key in ("moe_rows", "moe_experts_hit", "moe_max_rows"):
            one[key] = st.get(key, 0)
        out.append(one)
    return out or None


def mfu(obs):
    """Operations of the held work that the window's launches needed over the
    chip's bf16 peak x the window."""
    launches = _launches(obs)
    if not launches:
        return None
    cfg = obs["config"]
    need = sum(F.launch_flops(cfg, l["rows"], l["head"], l["full_pairs"],
                              l["window_pairs"], l["moe_rows"]) for l in launches)
    lo, hi = _program(obs)["window_ns"]
    return 100.0 * need / (obs["peaks"]["bf16_flops"] * (hi - lo) * 1e-9)


def hbm_stream_share(obs):
    """Bytes the window's launches had to read (weights once a launch, less
    the routed experts that had no row; the K and V each layer kind reads) over
    the chip's HBM bandwidth x the window."""
    launches = _launches(obs)
    if not launches:
        return None
    cfg = obs["config"]
    nbytes = sum(F.launch_weight_bytes(cfg, 0)
                 + F.kv_read_bytes(cfg, l["full_kv"], l["window_kv"])
                 for l in launches)
    nbytes += 2 * F.expert_params(cfg) * sum(l["moe_experts_hit"] for l in launches)
    lo, hi = _program(obs)["window_ns"]
    return 100.0 * nbytes / (obs["peaks"]["hbm_bytes_per_s"] * (hi - lo) * 1e-9)


def experts_roofline(obs, pattern):
    """Least time for the counted expert rows and the experts hit (each span's
    counts at the slower of the MXU and HBM) over the device time of the
    expert matmuls' events."""
    launches = _launches(obs)
    seconds, events = trace_reduce.op_seconds(obs["trace"], pattern) \
        if obs["trace"] else (0.0, 0)
    if not launches or not events or seconds <= 0:
        return None
    least = sum(roofline_seconds(*F.experts_cost(
        obs["config"], l["moe_rows"], l["moe_experts_hit"]), obs["peaks"])[0]
        for l in launches if l["moe_rows"])
    return 100.0 * least / seconds if least else None


def paged_attn_roofline(obs, pattern):
    """The paged kernel's share of its roofline with K and V counted by layer
    kind: a full layer reads `live_tokens` (or a chunk's start + tokens), a
    window layer `window_tokens`; each launch at the slower of HBM and the MXU,
    over the kernel's device seconds."""
    launches = _launches(obs)
    seconds, events = trace_reduce.op_seconds(obs["trace"], pattern) \
        if obs["trace"] else (0.0, 0)
    if not launches or not events or seconds <= 0:
        return None
    least = sum(roofline_seconds(*F.paged_attn_cost(
        obs["config"], l["rows"], l["full_kv"], l["window_kv"], l["full_pairs"],
        l["window_pairs"]), obs["peaks"])[0] for l in launches)
    return 100.0 * least / seconds


def rows_max_over_mean(obs):
    """Rows of the fullest held expert over the mean rows a held expert, both
    summed over the window's layers and launches: 1 is an even spread."""
    launches = _launches(obs)
    if not launches:
        return None
    rows = sum(l["moe_rows"] for l in launches)
    fullest = sum(l["moe_max_rows"] for l in launches)
    return fullest / (rows / obs["config"]["num_experts"]) if rows else None


def kv_window_saved_share(obs):
    """Blocks that one table for every layer would hold (each layer the full
    kind's blocks) less the blocks held, over the former; the mean of the
    runner's samples of `serving.kv_blocks_in_use{kind}` in the window."""
    samples = [g for g in obs["run"].get("kv_blocks") or []
               if g.get("full") and "window" in g]
    if not samples:
        return None
    full, window = F.layer_kinds(obs["config"])
    shares = [1.0 - (full * g["full"] + window * g["window"])
              / ((full + window) * g["full"]) for g in samples]
    return 100.0 * sum(shares) / len(shares)
