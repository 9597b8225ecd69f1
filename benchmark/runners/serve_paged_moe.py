"""Role runner `serve_paged_moe`: a Cohere2-MoE configuration (a share of its
routed experts held, window and full attention layers) in the paged engine
behind `GenerationServer.submit`, under a closed loop of two classes of clients,
on one chip.

The closed loop, the clock, the percentile and the sampling of requests for the
reference are `runners/serve_paged.py`'s; this file builds the other model,
gives each client its class's stream (`lib/traffic_two_class.py`), notes the
block gauges of the two kinds of KV table, checks the expert kernel's path
beside the paged kernel's, and compares with `lib/reference_cohere2_moe.py`.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import reference_cohere2_moe as reference
from benchmark.lib import traffic, traffic_two_class
from benchmark.lib import weights_cohere2_moe as weights
from benchmark.runners._llama import path_counts
from benchmark.runners.serve_paged import (FLIGHT_CAPACITY, _Clients, _p95,
                                           pick_sample)

# A served position is "at a tie" where, in some layer, the reference's 8th and
# 9th router scores lie closer than this: there the choice of experts turns on
# rounding (the program's activations are bfloat16, the reference's float32;
# on the chip a score moved by up to 2e-3 to 4e-3), both choices are right, and
# the logits differ by a whole expert's output. Such positions are compared
# too, under a limit of their own (`served_logit_gap_at_ties`); PERF.md section
# 6 has the readings the margin and both limits were set from.
TIE_MARGIN = 4e-3
MARGINS_READ = (5e-4, 1e-3, 2e-3, 4e-3, 8e-3)     # for tests/control_on_chip.py
MIN_CLEAR = 40      # served positions clear of a tie that a comparison needs

# one prompt per prefill bucket (8 .. 512), one of several chunks, one that
# outgrows the window (the rehearsal's sizes are cut to its max_seq)
WARM_PROMPTS = (5, 12, 24, 40, 100, 200, 400, 1100, 4700)

TINY = {"hidden_size": 64, "intermediate_size": 64, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 128, "num_experts": 4, "num_experts_published": 16,
        "num_experts_per_tok": 2, "num_shared_experts": 2, "sliding_window": 16,
        "experts_held_from": 4}


def _sizes(ctx):
    cfg, mix = dict(ctx.config), dict(ctx.traffic)
    if ctx.rehearsal:
        cfg.update(TINY, serve={"max_slots": 4, "max_seq": 160, "prefill_chunk": 16,
                                "block_size": 4, "num_blocks": {"full": 120, "window": 40}})
        short = dict(mix["classes"][1], clients=3, pool=6, prompt_len=dict(
            mix["classes"][1]["prompt_len"], median=12, min=4, max=30))
        long_ = dict(mix["classes"][0], clients=1, pool=2, prompt_len=dict(
            mix["classes"][0]["prompt_len"], median=60, min=40, max=100))
        mix.update(clients=4, ramp_seconds=0.5, classes=[long_, short],
                   output_len=dict(mix["output_len"], median=8, min=4, max=16))
    return cfg, mix


def program_name(leaf: str) -> str:
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "final_norm":
        return "model.norm.weight"
    from paddle_tpu.models.cohere2_moe import LAYER_PARAMS
    _, i, part = leaf.split(".")
    return f"model.layers.{i}.{LAYER_PARAMS[part]}"


def model_config(cfg: dict, dtype: str):
    from paddle_tpu.models import Cohere2MoeConfig
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    period = cfg["layer_switch"]
    if any((k == "full_attention") != ((i + 1) % period == 0)
           for i, k in enumerate(kinds)):
        raise ValueError("layer_types is not `layer_switch - 1` sliding layers "
                         "then a full one, which is what the program builds")
    return Cohere2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], layer_switch=period,
        rope_theta=cfg["rope_theta"], layer_norm_eps=cfg["layer_norm_eps"],
        num_experts=cfg["num_experts_published"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"], logit_scale=cfg["logit_scale"],
        max_position_embeddings=cfg["max_position_embeddings"], dtype=dtype)


def build_model(cfg: dict, seed_u32, dtype_name: str):
    """`Cohere2MoeForCausalLM` holding the configuration's share of the experts,
    born with empty matrices in its dtype, every parameter then replaced by the
    benchmark's seeded leaf, one at a time: the device never holds a second
    set."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import Cohere2MoeForCausalLM

    dtype = jnp.dtype(dtype_name)
    paddle.set_default_dtype(dtype_name)
    try:
        model = Cohere2MoeForCausalLM(model_config(cfg, dtype_name),
                                      experts_held=weights.experts_held(cfg),
                                      init_std=None)
    finally:
        paddle.set_default_dtype("float32")
    params = dict(model.named_parameters())
    specs = weights.leaf_specs(cfg)
    if sorted(program_name(n) for n, _ in specs) != sorted(params):
        raise RuntimeError("the program's parameters are not the leaves the "
                           "reference is built from")
    leaf = weights.make_leaf(cfg, dtype)
    for index, (name, shape) in enumerate(specs):
        p = params[program_name(name)]
        if str(p.dtype) != dtype_name or tuple(p._data.shape) != tuple(shape):
            raise RuntimeError(f"leaf {name}: the program holds {p._data.shape} "
                               f"{p.dtype}, the reference {shape} {dtype_name}")
        p._data = leaf(seed_u32, index)
    return model


class _ClassClients(_Clients):
    """The closed loop with a stream of requests a class of clients."""

    def __init__(self, srv, streams, classes, sample):
        super().__init__(srv, None, len(classes), sample)
        self.streams, self.classes = streams, classes

    def _send(self, i):
        self.requests = self.streams[self.classes[i]]
        super()._send(i)


def _kv_gauges() -> dict:
    """`serving.kv_blocks_in_use{kind}` as {kind: blocks}; {} where the
    program has no such gauge."""
    from paddle_tpu.observability import metrics as om
    g = om.default_registry().get("serving.kv_blocks_in_use")
    return {dict(k).get("kind"): float(v)
            for k, v in (g.series() if g is not None else {}).items()}


def run(ctx) -> dict:
    import paddle_tpu as paddle
    from paddle_tpu.jit import warmup
    from paddle_tpu.observability import flight
    from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine

    cfg, mix = _sizes(ctx)
    dtype_name = cfg["dtype"]
    seed = weights.seed_u32(ctx.seed)
    vocab = cfg["vocab_size"]
    sizes = cfg["serve"]

    # -- set-up --------------------------------------------------------------
    paddle.set_flags({"FLAGS_flight_recorder_capacity": FLIGHT_CAPACITY})
    paths0 = path_counts()
    model = build_model(cfg, seed, dtype_name)
    ctx.log(f"model built, device holds {ctx.sample_memory() / 1e9:.2f} GB")
    eng = PagedLlamaDecodeEngine(
        model, max_slots=sizes["max_slots"], max_seq=sizes["max_seq"],
        prefill_chunk=sizes["prefill_chunk"], block_size=sizes["block_size"],
        num_blocks=dict(sizes["num_blocks"]))
    ctx.sample_memory()
    del model
    gc.collect()
    srv = GenerationServer(eng)
    ctx.log(f"engine built ({eng.num_blocks} blocks of {eng.block_size}, chunk "
            f"{eng.prefill_chunk_len}, window {eng.window}); peak so far "
            f"{ctx.memory_peak_bytes / 1e9:.2f} GB")

    rng = traffic.rng_for(ctx.seed, 3)
    warm = [srv.submit(rng.integers(0, vocab, n, dtype=np.int32), 4)
            for n in WARM_PROMPTS if n < sizes["max_seq"] - 8]
    for r in warm:
        if not r["done"].wait(1100) or r["error"] is not None:
            raise RuntimeError(f"warm-up request failed: {r['error']!r}")
    ctx.log(f"warm: prefill buckets {sorted(eng._prefills)}, cache {warmup.cache_stats()}")

    kv_samples = []

    def sample():
        g = _kv_gauges()
        if g:
            kv_samples.append((time.perf_counter(), g))
        return ctx.sample_memory()

    clients = _ClassClients(srv, traffic_two_class.streams(mix, vocab, ctx.seed),
                            traffic_two_class.client_classes(mix), sample)
    clients.start()
    clients.run_until(time.perf_counter() + float(mix["ramp_seconds"]))

    # -- the window ----------------------------------------------------------
    if ctx.trace:
        ctx.trace_start()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start

    def counts():
        return {"steps": srv.steps_run, "tokens": srv.tokens_delivered,
                "misses": warmup.cache_stats()["misses"],
                "prefills": len(eng._prefills)}
    count0 = counts()
    clients.run_until(t0 + ctx.window_seconds)
    t1 = time.perf_counter()
    count1 = counts()
    if ctx.trace:
        ctx.trace_stop()
    clients.run_until(t1 + 180.0, resend=False)         # late is late, not lost
    ctx.sample_memory()
    drained = srv.shutdown(drain=True, timeout=180)
    stats = srv.stats()
    paths1 = path_counts()
    ctx.log(f"window {t1 - t0:.3f} s, drained {drained}; stats {stats}")

    # -- what the callers saw -----------------------------------------------
    records = clients.records
    in_window = [r for r in records if t0 <= r["t_submit"] < t1]
    faults = []
    failed = 0
    for r in in_window:
        out = r["req"]["out"]
        bad = (r["req"]["error"] is not None or r["t_done"] is None
               or len(out) != r["max_new"]
               or any(not 0 <= int(t) < vocab for t in out))
        failed += bool(bad)
    if failed:
        faults.append(f"{failed} of {len(in_window)} requests of the window "
                      f"failed, never finished or came back the wrong length")
    for key in ("rejected", "shed", "deadline_rejected", "deadline_expired",
                "crashed", "quarantined", "loop_restarts"):
        if stats.get(key):
            faults.append(f"server stats[{key!r}] = {stats[key]}")
    if not drained:
        faults.append("the server did not drain")
    if flight.dropped():
        faults.append(f"the flight ring dropped {flight.dropped()} events")
    for kernel, other in (("paged_attention", "jnp_walk"),
                          ("expert_rows_matmul", "reference")):
        walk, kern = f"{kernel}:{other}", f"{kernel}:pallas"
        if not ctx.rehearsal and (paths1.get(walk, 0) != paths0.get(walk, 0)
                                  or paths1.get(kern, 0) <= paths0.get(kern, 0)):
            faults.append(f"a serving program left the Pallas {kernel} kernel: "
                          f"{paths0} -> {paths1}")
    pool = stats.get("kv_pool", {}).get("kinds", {})
    for kind, st in pool.items():
        if st["blocks_used"] or st["blocks_reserved"]:
            faults.append(f"the {kind} table leaked: {st} after the drain")

    ttft = [r["t_tokens"][0] - r["t_submit"] for r in in_window if r["t_tokens"]]
    gaps, tokens_in_window = [], 0
    for r in records:
        ts = r["t_tokens"]
        tokens_in_window += sum(1 for t in ts if t0 <= t < t1)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": tokens_in_window / (t1 - t0),
                  "ttft_ms_p95": 1e3 * _p95(ttft) if ttft else None,
                  "token_gap_ms_p95": 1e3 * _p95(gaps) if gaps else None}

    # -- the program's own record, on the runner's clock ---------------------
    events = flight.events(category="serving")
    by_id = {r["trace_id"]: r for r in records}
    offs = [e["ts_us"] * 1e-6 - by_id[e["trace_id"]]["t_submit"] for e in events
            if e["name"] == "submit" and e.get("trace_id") in by_id]
    offset = float(np.median(offs)) if offs else 0.0
    timeline = [(e["ts_us"] * 1e-6 - offset, e["name"], e.get("trace_id"),
                 e.get("attrs") or {}) for e in events
                if e.get("trace_id") in by_id]
    observed = {
        "window": (t0, t1), "window_s": t1 - t0, "timeline": timeline,
        "requests": [{"trace_id": r["trace_id"], "t_submit": r["t_submit"],
                      "n_prompt": r["n_prompt"], "max_new": r["max_new"]}
                     for r in records],
        "steps": count1["steps"] - count0["steps"],
        "tokens_delivered": count1["tokens"] - count0["tokens"],
        "compiles_in_window": (count1["misses"] - count0["misses"])
        + (count1["prefills"] - count0["prefills"]),
        "prefill_chunk": eng.prefill_chunk_len,
        "memory_peak_bytes": ctx.memory_peak_bytes,
        "kv_blocks": [g for t, g in kv_samples if t0 <= t < t1],
        "ttft_n": len(ttft), "gaps_n": len(gaps),
        "ttft_ms_p95": end_to_end["ttft_ms_p95"],
        "token_gap_ms_p95": end_to_end["token_gap_ms_p95"]}
    ctx.log(f"{len(in_window)} requests submitted in the window, "
            f"{tokens_in_window} tokens, {len(gaps)} gaps; peak "
            f"{ctx.memory_peak_bytes / 1e9:.2f} GB")

    # -- free the program, then the reference --------------------------------
    finished = [r for r in records if r["t_done"] is not None
                and t0 <= r["t_done"] < t1 and r["req"]["error"] is None
                and len(r["req"]["out"]) == r["max_new"]]
    sample_reqs = pick_sample(finished, ctx.seed)
    sequences = [np.concatenate([np.asarray(r["req"]["prompt"], np.int32),
                                 np.asarray(r["req"]["out"], np.int32)])
                 for r in sample_reqs]
    n_prompt = [r["n_prompt"] for r in sample_reqs]
    del srv, eng, clients, warm
    for r in records:
        r["req"] = None
    gc.collect()
    compared = {"served_logit_gap": None, "served_logit_gap_at_ties": None}
    if sequences:
        import jax.numpy as jnp
        t_ref = time.perf_counter()
        rows = reference.served_logit_gaps(
            cfg, seed, sequences, n_prompt,
            out_pad=int(mix["output_len"]["max"]), dtype=jnp.dtype(dtype_name),
            control=ctx.control)
        margin = np.concatenate([r["margin"] for r in rows])
        n_tok = len(margin)

        def split(key, at):
            """Largest gap clear of a tie and at one (0 where none is)."""
            gap = np.concatenate([r[key] for r in rows])
            tie = margin < at
            return {"served_logit_gap": float(gap[~tie].max(initial=0.0)),
                    "served_logit_gap_at_ties": float(gap[tie].max(initial=0.0))}
        compared.update(split("gap", TIE_MARGIN))
        near = int((margin < TIE_MARGIN).sum())
        if not ctx.rehearsal and n_tok - near < MIN_CLEAR:
            faults.append(f"only {n_tok - near} of {n_tok} sampled positions are "
                          f"clear of a router tie: too few to compare")
        observed["readings"] = {
            "router_near_tie_share": near / max(n_tok, 1),
            "by_margin": {str(m): dict(split("gap", m), share=float(
                (margin < m).mean())) for m in MARGINS_READ}}
        if ctx.control:
            observed["readings"]["control"] = split("control_gap", TIE_MARGIN)
            observed["readings"]["control_by_margin"] = {
                str(m): split("control_gap", m) for m in MARGINS_READ}
        exact = sum(int((r["gap"] == 0).sum()) for r in rows)
        ctx.log(f"reference: {time.perf_counter() - t_ref:.1f} s over "
                f"{len(rows)} requests (prompts {n_prompt}), {n_tok} served "
                f"tokens, {exact} are its own first choice, {near} at a "
                f"position where some layer's 8th and 9th router scores lie "
                f"within {TIE_MARGIN}")
    else:
        faults.append("the window finished no request to compare")
    return {"attempted": len(in_window), "failed": failed, "faults": faults,
            "compared": compared, "end_to_end": end_to_end, "observed": observed,
            "counts": {"requests": len(in_window), "tokens": tokens_in_window,
                       "steps": observed["steps"],
                       "compiles_in_window": observed["compiles_in_window"],
                       "sampled_requests": len(sequences)}}
