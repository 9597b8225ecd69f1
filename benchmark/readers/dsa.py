"""Readers of the per-layer metrics of a served decoder with latent attention
over selected rows, a learned indexer and a held share of its experts. They read
the counts that the program's `serving.decode` / `serving.prefill` spans carry
(`rows`/`tokens`, `start`, `live_tokens`, `selected_tokens`, and what a launch's
fetch brings: `moe_rows`, `moe_experts_hit`, `moe_max_rows`, `dsa_selected`,
`dsa_visible`) and the device time of the kernels' events;
`lib/flops_glm_moe_dsa.py` turns counts into operations and bytes. Each returns
None where it finds nothing to read, as on a program without these spans or
counts.

`selected_tokens` and `live_tokens` are counted by the host for the launch a
span ENQUEUES, so they are per launch; the counts a fetch brings ride on the next
span that fetched (`readers/moe.py`), exact in sum over a window and only summed
here.
"""
from __future__ import annotations

from benchmark.lib import flops_glm_moe_dsa as F
from benchmark.lib import trace_reduce
from benchmark.lib.flops import roofline_seconds
from benchmark.readers.spans import _program

DECODE, PREFILL = "serving.decode", "serving.prefill"
FETCHED = ("moe_rows", "moe_experts_hit", "moe_max_rows", "dsa_selected",
           "dsa_visible")


def _launches(obs):
    """One dict a launch span of the window: rows through the layers, rows that
    need logits, (row, selected position) pairs a layer, (row, visible
    position) pairs an indexer layer, latent rows and index keys the launch has
    to read a layer, and the fetched counts the span carries (0 where none)."""
    prog = _program(obs)
    if prog is None:
        return None
    out = []
    for _, _, name, st in prog["spans"]:
        if name == DECODE and "selected_tokens" in st:
            one = {"rows": st["rows"], "head": st["rows"],
                   "selected": st["selected_tokens"],
                   "visible": st["live_tokens"],
                   "latent_rows": st["selected_tokens"],
                   "index_keys": st["live_tokens"]}
        elif name == PREFILL and "selected_tokens" in st:
            t, s0 = st["tokens"], st["start"]
            one = {"rows": t, "head": 0, "selected": st["selected_tokens"],
                   "visible": t * s0 + t * (t + 1) // 2,
                   # a chunk's rows share what they select of one context
                   "latent_rows": min(st["selected_tokens"], s0 + t),
                   "index_keys": s0 + t}
        else:
            continue
        for key in FETCHED:
            one[key] = st.get(key, 0)
        out.append(one)
    return out or None


def _window_s(obs) -> float:
    lo, hi = _program(obs)["window_ns"]
    return (hi - lo) * 1e-9


def mfu(obs):
    """Operations of the held work that the window's launches needed over the
    chip's bf16 peak x the window."""
    launches = _launches(obs)
    if not launches:
        return None
    cfg = obs["config"]
    need = sum(F.launch_flops(cfg, l["rows"], l["head"], l["selected"],
                              l["visible"], l["moe_rows"]) for l in launches)
    return 100.0 * need / (obs["peaks"]["bf16_flops"] * _window_s(obs))


def hbm_stream_share(obs):
    """Bytes the window's launches had to read (weights once a launch, less the
    routed experts that had no row; the selected latent rows of every layer and
    the index keys of the indexer layers) over the chip's HBM bandwidth x the
    window."""
    launches = _launches(obs)
    if not launches:
        return None
    cfg = obs["config"]
    nbytes = sum(F.launch_weight_bytes(cfg, 0)
                 + F.cache_read_bytes(cfg, l["latent_rows"], l["index_keys"])
                 for l in launches)
    nbytes += 2 * F.expert_params(cfg) * sum(l["moe_experts_hit"] for l in launches)
    return 100.0 * nbytes / (obs["peaks"]["hbm_bytes_per_s"] * _window_s(obs))


def _roofline(obs, pattern, cost):
    """Sum over the launches of the least time for `cost(launch)` (each at the
    slower of the MXU and HBM) over the device time of `pattern`'s events."""
    launches = _launches(obs)
    seconds, events = trace_reduce.op_seconds(obs["trace"], pattern) \
        if obs["trace"] else (0.0, 0)
    if not launches or not events or seconds <= 0:
        return None
    least = sum(roofline_seconds(*cost(l), obs["peaks"])[0] for l in launches)
    return 100.0 * least / seconds if least else None


def latent_attn_roofline(obs, pattern):
    """The attention over selected rows at its roofline: the pairs' two
    contractions against the selected rows read once, over the kernel's device
    time."""
    return _roofline(obs, pattern, lambda l: F.latent_attn_cost(
        obs["config"], l["rows"], l["selected"], l["latent_rows"]))


def index_score_roofline(obs, pattern):
    """The indexer's scores at their roofline: the (row, visible position)
    pairs' dot products against the keys read once, over the kernel's device
    time."""
    return _roofline(obs, pattern, lambda l: F.index_score_cost(
        obs["config"], l["rows"], l["visible"], l["index_keys"]))


def experts_roofline(obs, pattern):
    """Least time for the counted expert rows and the experts hit over the
    device time of the expert matmuls' events (`readers/moe.py`'s, with this
    model's sizes)."""
    return _roofline(obs, pattern, lambda l: F.experts_cost(
        obs["config"], l["moe_rows"], l["moe_experts_hit"])
        if l["moe_rows"] else (0.0, 0.0))


def kept_share(obs):
    """Rows attended over rows a dense walk would attend, both counted on the
    device by the indexer layers (`dsa_selected / dsa_visible`, summed over the
    window): how much of the cache attention read."""
    launches = _launches(obs)
    if not launches:
        return None
    visible = sum(l["dsa_visible"] for l in launches)
    return 100.0 * sum(l["dsa_selected"] for l in launches) / visible \
        if visible else None


def rows_max_over_mean(obs):
    """Rows of the fullest held expert over the mean rows a held expert, both
    summed over the window's layers and launches: 1 is an even spread."""
    launches = _launches(obs)
    if not launches:
        return None
    rows = sum(l["moe_rows"] for l in launches)
    fullest = sum(l["moe_max_rows"] for l in launches)
    return fullest / (rows / obs["config"]["n_routed_experts"]) if rows else None
