"""Readers of the training role's per-layer metrics."""
from __future__ import annotations

from benchmark.lib import flops, trace_reduce


def host_dispatch_ms(obs):
    """Mean time `DistTrainStep.__call__` holds the host before it returns
    (unblocked), over the window's steps."""
    spans = obs["run"]["spans"]
    return 1e3 * sum(b - a for a, b, _ in spans) / len(spans) if spans else None


def mfu(obs):
    """The whole step's share of the chip's peak: operations the forward and
    backward passes need per token (lib.flops) x tokens/s of this run's window."""
    run = obs["run"]
    per_token = flops.train_flops_per_token(obs["config"], run["seq"])
    return 100.0 * per_token * run["tokens"] / run["window_s"] / obs["peaks"]["bf16_flops"]


def flash_roofline(obs, pattern, direction, events_per_call=1):
    """Share of its roofline that a flash-attention kernel reaches: the least
    time the chip could take for the calls seen, over their summed device time."""
    seconds, events = trace_reduce.op_seconds(obs["trace"], pattern)
    if not events or seconds <= 0:
        return None
    cfg, run = obs["config"], obs["run"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    cost = flops.flash_fwd_cost if direction == "fwd" else flops.flash_bwd_cost
    ops, nbytes = cost(run["batch"], run["seq"], cfg["num_attention_heads"], d)
    least, _ = flops.roofline_seconds(ops, nbytes, obs["peaks"])
    return 100.0 * (events / events_per_call) * least / seconds
