"""Readers of the per-layer metrics of a served model whose every layer is
degree-2 power retention over a state a slot (no block pool). They read the
counts that the program's `serving.decode` / `serving.prefill` spans carry
(`rows`, `state_slots`; `tokens`, `state_subchunks`) and the device time of the
two kernels' events; `lib/flops_brumby.py` turns counts into operations and
bytes (a slot's state bytes from the configuration, not from the program).
Each returns None where it finds nothing to read, as on a program without these
spans.
"""
from __future__ import annotations

from benchmark.lib import flops_brumby as F
from benchmark.lib import trace_reduce
from benchmark.lib.flops import roofline_seconds
from benchmark.readers.spans import _program

DECODE, PREFILL = "serving.decode", "serving.prefill"


def _launches(obs):
    """One dict a launch span of the window: the rows through the layers, the
    rows that need logits, the slots a decode launch steps, or the row tiles
    of a prompt chunk."""
    prog = _program(obs)
    if prog is None:
        return None
    out = []
    for _, _, name, st in prog["spans"]:
        if name == DECODE and "state_slots" in st:
            out.append({"rows": st["rows"], "head": st["rows"],
                        "states": st["state_slots"], "subchunks": 0,
                        "chunks": 0})
        elif name == PREFILL and "state_subchunks" in st:
            out.append({"rows": st["tokens"], "head": 0, "states": 0,
                        "subchunks": st["state_subchunks"], "chunks": 1})
    return out or None


def _window_s(obs) -> float:
    lo, hi = _program(obs)["window_ns"]
    return (hi - lo) * 1e-9


def mfu(obs):
    """Operations the window's launches needed (`F.launch_flops`) over the
    chip's bf16 peak x the window."""
    launches = _launches(obs)
    if not launches:
        return None
    cfg = obs["config"]
    need = sum(F.launch_flops(cfg, l["rows"], l["head"], l["states"],
                              l["subchunks"], l["chunks"]) for l in launches)
    return 100.0 * need / (obs["peaks"]["bf16_flops"] * _window_s(obs))


def hbm_stream_share(obs):
    """Bytes the window's launches had to move (`F.launch_bytes`: the layers'
    and the head's weights once a launch, every stepped slot's state read and
    written, a chunk's slot once) over the chip's HBM bandwidth x the
    window."""
    launches = _launches(obs)
    if not launches:
        return None
    cfg = obs["config"]
    nbytes = sum(F.launch_bytes(cfg, l["states"], l["chunks"])
                 for l in launches if l["states"] or l["chunks"])
    return 100.0 * nbytes / (obs["peaks"]["hbm_bytes_per_s"] * _window_s(obs))


def _roofline(obs, pattern, cost):
    """Least time for what `cost(launch)` says each launch needed (the slower
    of the MXU at its bf16 peak and HBM, a launch) over the device time of
    `pattern`'s events."""
    launches = _launches(obs)
    seconds, events = trace_reduce.op_seconds(obs["trace"], pattern) \
        if obs["trace"] else (0.0, 0)
    if not launches or not events or seconds <= 0:
        return None
    least = sum(roofline_seconds(*cost(l), obs["peaks"])[0] for l in launches
                if l["states"] or l["chunks"])
    return 100.0 * least / seconds if least else None


def retention_step_roofline(obs, pattern):
    """The step kernel: every layer's state of the slots each decode span
    counted, read and written, with the rows' operands."""
    cfg = obs["config"]
    return _roofline(obs, pattern, lambda l: F.retention_step_cost(
        cfg, l["states"]) if l["states"] else (0.0, 0.0))


def retention_chunk_roofline(obs, pattern):
    """The chunk kernel: the counted row tiles' operations and operands, the
    state once a chunk."""
    cfg = obs["config"]
    return _roofline(obs, pattern, lambda l: F.retention_chunk_cost(
        cfg, l["subchunks"], l["chunks"]) if l["chunks"] else (0.0, 0.0))
