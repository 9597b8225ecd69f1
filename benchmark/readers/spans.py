"""Readers of the program's own spans (`lib/program_spans.py`): what the
device's idle time falls under, the host time of a loop iteration or a step
call, compiles by name, and the paged kernel's roofline from the counts its
launches carry. Each returns None where the trace holds no program span, as
a parent commit's does."""
from __future__ import annotations

import json
import os
import sys

from benchmark.lib import flops, program_spans, trace_reduce

_METRICS = os.path.join(program_spans.ROOT, "benchmark", "metrics")


def _program(obs):
    if not obs["trace"]:
        return None
    return program_spans.load(program_spans.TRACE_DIR, obs["cell"]["chips"])


def _under(name, spans, less=(), only=None):
    return name is not None and name.startswith(tuple(spans)) \
        and not name.endswith(tuple(less)) \
        and (only is None or name.endswith(tuple(only)))


def idle_share(obs, spans, less=(), only=None):
    """Idle time of the window whose innermost program span starts with one
    of `spans`, ends with none of `less` and, where `only` is given, with
    one of `only`; as a share of the window."""
    prog = _program(obs)
    if prog is None:
        return None
    lo, hi = prog["window_ns"]
    ns = sum(v for k, v in prog["idle_ns"].items()
             if _under(k, spans, less, only))
    return 100.0 * ns / (hi - lo)


def idle_other_share(obs, other_than):
    """The window's idle time that none of the metrics `other_than` counts:
    under no program span at all, or under one that belongs to none of them
    (a loop iteration's own time between its children, a parked loop). With
    them it adds up to the device's idle share. The split by span name goes
    to standard error, for PERF.md's breakdown."""
    prog = _program(obs)
    if prog is None:
        return None
    specs = []
    for metric in other_than:
        with open(os.path.join(_METRICS, metric + ".json")) as f:
            specs.append(json.load(f)["args"])
    lo, hi = prog["window_ns"]
    for name, ns in sorted(prog["idle_ns"].items(), key=lambda kv: -kv[1]):
        print(f"spans: idle under {name or 'no program span'}: "
              f"{1e-6 * ns:.3f} ms, {100.0 * ns / (hi - lo):.3f}% of the window",
              file=sys.stderr)
    ns = sum(v for k, v in prog["idle_ns"].items()
             if not any(_under(k, a["spans"], a.get("less", ()), a.get("only"))
                        for a in specs))
    return 100.0 * ns / (hi - lo)


def span_ms(obs, span, less=()):
    """Mean duration of the spans named `span` that lie inside the window,
    less the time of the spans inside them whose name ends with one of
    `less` (a loop iteration without its waits for the device)."""
    prog = _program(obs)
    if prog is None:
        return None
    outer = [(s, e) for s, e, name, _ in prog["spans"] if name == span]
    if not outer:
        return None
    total = sum(e - s for s, e in outer)
    if less:
        cover = trace_reduce.union(outer)
        for s, e, name, _ in prog["spans"]:
            if name.endswith(tuple(less)) and any(a <= s and e <= b for a, b in cover):
                total -= e - s
    return 1e-6 * total / len(outer)


def spans_ms_total(obs, span):
    """Summed duration of the spans named `span` inside the window; 0.0 when
    the program has spans and none of them is this one."""
    prog = _program(obs)
    if prog is None:
        return None
    return 1e-6 * sum(e - s for s, e, name, _ in prog["spans"] if name == span)


def paged_attn_roofline(obs, pattern, decode, prefill):
    """Share of its roofline that the paged-attention kernel reaches: the
    least time the chip could take for what the window's launches name (a
    `decode` span's `rows` query rows over `live_tokens` of KV, a `prefill`
    span's `tokens` rows over `start + tokens`; K and V of every layer read
    once per launch, queries read and outputs written), each launch at the
    slower of HBM and the MXU, over the kernel's device seconds."""
    prog = _program(obs)
    seconds, events = trace_reduce.op_seconds(obs["trace"], pattern) \
        if obs["trace"] else (0.0, 0)
    if prog is None or not events or seconds <= 0:
        return None
    cfg, least, launches = obs["config"], 0.0, 0
    _, nh, _, d, _ = flops._dims(cfg)
    qo_bytes = 2 * cfg["num_hidden_layers"] * nh * d * 2      # q in, out, bf16
    for _, _, name, st in prog["spans"]:
        if name == decode:
            rows, kv_read = st["rows"], st["live_tokens"]
            pairs = kv_read                       # one query row per context
        elif name == prefill and "tokens" in st:
            rows, kv_read = st["tokens"], st["start"] + st["tokens"]
            pairs = rows * st["start"] + rows * (rows + 1) // 2   # causal
        else:
            continue
        ops = cfg["num_hidden_layers"] * 4 * nh * d * pairs
        nbytes = kv_read * flops.kv_bytes_per_token(cfg) + rows * qo_bytes
        least += flops.roofline_seconds(ops, nbytes, obs["peaks"])[0]
        launches += 1
    return 100.0 * least / seconds if launches else None
