"""Readers of the serving role's per-layer metrics. The program's flight
events arrive as `obs["run"]["timeline"]`: (seconds on the runner's clock,
event name, trace id, attrs), and the window as `obs["run"]["window"]`."""
from __future__ import annotations

import numpy as np

from benchmark.lib import flops


def _per_request(obs):
    by = {}
    for t, name, tid, attrs in obs["run"]["timeline"]:
        by.setdefault(tid, {}).setdefault(name, (t, attrs))
    return by


def _in_window(obs):
    t0, t1 = obs["run"]["window"]
    return {r["trace_id"] for r in obs["run"]["requests"] if t0 <= r["t_submit"] < t1}


def wait_ms_p95(obs, start, end):
    """95th percentile, over the requests submitted in the window, of the time
    from their flight event `start` to their event `end`."""
    keep, waits = _in_window(obs), []
    for tid, evs in _per_request(obs).items():
        if tid in keep and start in evs and end in evs:
            waits.append(evs[end][0] - evs[start][0])
    return 1e3 * float(np.percentile(waits, 95)) if waits else None


def tokens_per_step(obs):
    run = obs["run"]
    return run["tokens_delivered"] / run["steps"] if run["steps"] else None


def _work(obs):
    """What the launched programs of the window had to do, from the flight
    events inside it: tokens through the layers with their positions, tokens
    delivered, launches, and KV positions read. A prompt counts where its
    `prefilled` event falls (its chunks are not journalled one by one)."""
    run = obs["run"]
    t0, t1 = run["window"]
    chunk = run["prefill_chunk"]
    prompt_of = {r["trace_id"]: r["n_prompt"] for r in run["requests"]}
    positions, delivered, kv_read, steps, chunks = [], 0, 0, set(), 0
    for t, name, tid, attrs in run["timeline"]:
        if not t0 <= t < t1:
            continue
        if name == "prefilled":
            p = prompt_of[tid]
            positions.extend(range(p))
            delivered += 1
            n = -(-p // chunk)
            chunks += n
            kv_read += sum(min((j + 1) * chunk, p) for j in range(n))
        elif name == "decode":
            # token k of the request came from a step over token k-1, which
            # sits at position prompt + k - 2 and reads prompt + k - 1 of KV
            k = attrs["tokens"]
            positions.append(prompt_of[tid] + k - 2)
            delivered += 1
            kv_read += prompt_of[tid] + k - 1
            steps.add(attrs["step"])
    return positions, delivered, kv_read, len(steps) + chunks


def mfu(obs):
    """Operations that the prompt and output tokens processed in the window
    need (lib.flops) over the chip's peak x the window."""
    positions, delivered, _, _ = _work(obs)
    if not positions:
        return None
    need = flops.serve_flops(obs["config"], positions, delivered)
    return 100.0 * need / (obs["peaks"]["bf16_flops"] * obs["run"]["window_s"])


def hbm_stream_share(obs):
    """Bytes the launched programs must read (the weights once per decode or
    prefill launch, the live KV of the tokens stepped) over the chip's HBM
    bandwidth x the window."""
    _, _, kv_read, launches = _work(obs)
    if not launches:
        return None
    cfg = obs["config"]
    nbytes = (launches * flops.serve_weight_bytes(cfg)
              + kv_read * flops.kv_bytes_per_token(cfg))
    return 100.0 * nbytes / (obs["peaks"]["hbm_bytes_per_s"] * obs["run"]["window_s"])
