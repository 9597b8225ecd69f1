"""Role runner `serve_paged_dsa`: a GLM-MoE-DSA configuration (latent attention
over the rows a learned indexer selects, a share of the routed experts held) in
the paged engine behind `GenerationServer.submit`, under a closed loop of two
classes of clients, on one chip.

The closed loop, the clock, the percentile and the sampling of requests for the
reference are `runners/serve_paged.py`'s, the two-class clients
`runners/serve_paged_moe.py`'s; this file builds the other model, checks the
paths of the three kernels its launches run, reads the program's SELECTIONS for
the sampled long requests and compares with `lib/reference_glm_moe_dsa.py`:

- `served_logit_gap` / `served_logit_gap_at_ties`: as for the other sparse-expert
  cell, every served token's distance from the reference's first choice, apart
  where some sparse layer's 8th and 9th biased router scores lie within
  `TIE_MARGIN`;
- `index_set_miss` = 1 - `index_set_overlap`: of the positions the program
  selected for the served positions of the sampled long request, the share whose
  REFERENCE index score lies within `WIDEN` (in standard deviations of the
  position's visible scores) of the reference's own 2048th. With random weights
  attention is near uniform, so logits alone would pass a program that attended
  the wrong 2048 rows; this holds the indexer to its mathematics. It is read on
  the FIRST indexer layer, which follows the embedding alone: there the program
  and the reference differ by rounding only. `index_set_miss_deep` is the same
  over the later indexer layers at `WIDEN_DEEP`: their inputs have been through
  sparse layers whose expert choice turns on rounding at most positions, so a
  sixth of their sets differs rightly (PERF.md), and the limit only tells a
  selection from another rule's (the most recent positions read 0.9).

**Where the sets come from.** From the serving decode program's own launches.
The engine's decode program returns, beside each token, the positions its row
attended on each indexer layer (`launch["selected"]`: bitsets, on the device,
fetched by nobody while serving). After the window has drained, the sampled long
request is served again (`served_selections`): its prompt but the last token by
the warm prefill programs, then one launch of the warm decode program a row (the
prompt's last token, then each served token), at [32, 1] with every other slot
live beside it (the other sampled requests, again and again), each slot fed the
token it was fed when served. The pools are those the engine's own chunks and
decode steps wrote; no program is compiled for the check. Every launch's
selections are fetched: the long request's row gives its positions, every
slot's row is held to `min(pos + 1, k)` positions. The share of the replayed
tokens that equal the served ones is a reading. A prompt chunk's own selection
(512 rows of one slot) is not handed back: the chunk programs are those of the
parent of this check to the byte (PERF.md says what an output more costs
there), and stay held by the logits and by tier 1's chunked-prefill tests.

**End-to-end metrics.** `setup_s`, `serve_tokens_per_s`, `ttft_ms_p95`. The gap
between a request's tokens is read as in the other serving cells and logged, but
`token_gap_ms_p95` is no end-to-end metric of this cell: an iteration's time
grows with the context of the prompt chunk it carries (the walk under the mask),
so the gaps' upper twentieth is the last chunks of the window's two or three
longest prompts, some 40 iterations, and the percentile moves by 0.9 ms a chunk
that joins or leaves them: over nine runs it spread by 0.85% against the half
bound of 1%, and the driver's two sets by 1.76 and 1.08% (PERF.md, PR 33).

**Controls.** `ctx.control` `fp8` is the reference one precision below
(`lib/reference_glm_moe_dsa.py`); `recent_deep` plants a wrong rule in the
PROGRAM: its last indexer layer keeps the most recent `index_topk` positions
(`plant_most_recent`), which `index_set_miss_deep` must refuse whatever the
logits say (`tests/control_dsa_on_chip.py`).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import reference_glm_moe_dsa as reference
from benchmark.lib import traffic, traffic_two_class
from benchmark.lib import weights_glm_moe_dsa as weights
from benchmark.runners._llama import path_counts
from benchmark.runners.serve_paged import (FLIGHT_CAPACITY, SAMPLE_REQUESTS,
                                           _p95, pick_sample)
from benchmark.runners.serve_paged_moe import _ClassClients

# A served position is "at a tie" where, in some sparse layer, the reference's
# 8th and 9th biased router scores lie closer than this (limits file).
TIE_MARGIN = 2e-3
MARGINS_READ = (5e-4, 1e-3, 2e-3, 4e-3, 8e-3)     # for tests/control_on_chip.py
MIN_CLEAR = 40      # served positions clear of a tie that a comparison needs
# A selected position counts as the reference's own where its reference index
# score lies within this many standard deviations (of the row's visible scores)
# of the reference's last kept score (limits file).
WIDEN = 0.05
WIDEN_DEEP = 0.2            # the same for the indexer layers after the first
WIDENS_READ = (0.0, 0.01, 0.02, 0.05, 0.1, 0.2)
LONG_REQUESTS = 1   # sampled long requests whose selections are read
LONG_SAMPLED = 1    # long requests the reference reads at most (each costs it
#                     minutes: attention over every position, in float32)
# Where index_topk is small a swapped row is a large part of the softmax (1/16
# in the rehearsal): there a position is at a tie too where the reference's last
# kept and first dropped index scores lie within WIDEN. At the published 2048 a
# swapped row carries 1/2048 of a near-uniform softmax and nearly every long
# position has such a margin: the router's margin alone classes them (PERF.md).
SELECTION_TIES_BELOW_TOPK = 256

# one prompt per prefill bucket (8 .. 512) and one of several chunks
WARM_PROMPTS = (5, 12, 24, 40, 100, 200, 400, 1100)

TINY = {"hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
        "num_hidden_layers": 5, "num_attention_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "index_n_heads": 4, "index_head_dim": 16,
        "index_topk": 16, "vocab_size": 128, "n_routed_experts": 4,
        "n_routed_experts_published": 16, "num_experts_per_tok": 2,
        "experts_held_from": 4}


def _sizes(ctx):
    cfg, mix = dict(ctx.config), dict(ctx.traffic)
    if ctx.rehearsal:
        cfg.update(TINY, serve={"max_slots": 4, "max_seq": 160, "prefill_chunk": 16,
                                "block_size": 4, "num_blocks": 130})
        short = dict(mix["classes"][1], clients=3, pool=6, prompt_len=dict(
            mix["classes"][1]["prompt_len"], median=12, min=4, max=30))
        long_ = dict(mix["classes"][0], clients=1, pool=2, prompt_len=dict(
            mix["classes"][0]["prompt_len"], median=60, min=40, max=100))
        mix.update(clients=4, ramp_seconds=0.5, classes=[long_, short],
                   output_len=dict(mix["output_len"], median=8, min=4, max=16))
    return cfg, mix


def program_name(cfg: dict, leaf: str) -> str:
    if leaf in ("embed", "final_norm", "head"):
        return {"embed": "model.embed_tokens.weight",
                "final_norm": "model.norm.weight", "head": "lm_head.weight"}[leaf]
    from paddle_tpu.models import glm_moe_dsa as M
    _, i, part = leaf.split(".")
    names = {**M.ATTN_PARAMS, **M.INDEX_PARAMS, **M.DENSE_PARAMS, **M.SPARSE_PARAMS}
    return f"model.layers.{i}.{names[part]}"


def model_config(cfg: dict, dtype: str):
    from paddle_tpu.models import GlmMoeDsaConfig
    n = cfg["num_hidden_layers"]
    return GlmMoeDsaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"], num_hidden_layers=n,
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        index_n_heads=cfg["index_n_heads"], index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"],
        indexer_types=tuple(cfg["indexer_types"][:n]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"][:n]),
        first_k_dense_replace=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["n_routed_experts_published"],
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=dtype)


def build_model(cfg: dict, seed_u32, dtype_name: str):
    """`GlmMoeDsaForCausalLM` holding the configuration's share of the experts,
    born with empty matrices in its dtype, every parameter then replaced by the
    benchmark's seeded leaf, one at a time: the device never holds a second
    set."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import GlmMoeDsaForCausalLM

    dtype = jnp.dtype(dtype_name)
    paddle.set_default_dtype(dtype_name)
    try:
        model = GlmMoeDsaForCausalLM(model_config(cfg, dtype_name),
                                     experts_held=weights.experts_held(cfg),
                                     init_std=None)
    finally:
        paddle.set_default_dtype("float32")
    params = dict(model.named_parameters())
    specs = weights.leaf_specs(cfg)
    if sorted(program_name(cfg, n) for n, _ in specs) != sorted(params):
        raise RuntimeError("the program's parameters are not the leaves the "
                           "reference is built from")
    leaf = weights.make_leaf(cfg, dtype)
    for index, (name, shape) in enumerate(specs):
        p = params[program_name(cfg, name)]
        if str(p.dtype) != dtype_name or tuple(p._data.shape) != tuple(shape):
            raise RuntimeError(f"leaf {name}: the program holds {p._data.shape} "
                               f"{p.dtype}, the reference {shape} {dtype_name}")
        p._data = leaf(seed_u32, index)
    return model


def plant_most_recent(eng, layer: int) -> None:
    """The control `recent_deep`: from now on `layer` of the engine's programs
    keeps each row's most recent `k` positions, not its `k` highest index
    scores (everything else of the layer, and every other layer, as it is).
    Call before the first launch is traced."""
    import jax.numpy as jnp
    sc, inner = eng._sc, eng._m.layer

    def most_recent(scores, valid, k):
        at = jnp.broadcast_to(jnp.arange(scores.shape[-1], dtype=scores.dtype),
                              scores.shape)
        return sc.select_topk(at, valid, k)

    class Planted:              # serving_cache with that one rule replaced
        def __getattr__(self, name):
            return most_recent if name == "select_topk" else getattr(sc, name)

    def step(e, li, *args, **kwargs):
        if li != layer:
            return inner(e, li, *args, **kwargs)
        e._sc = Planted()
        try:
            return inner(e, li, *args, **kwargs)
        finally:
            e._sc = sc
    eng._m.layer = step


def served_selections(eng, sequences, n_prompt, which: int, slot: int) -> tuple:
    """What the SERVING decode program selects for the rows that produced
    request `which`'s served tokens: ({layer: (idx [n_out, K], n_sel [n_out])},
    faults, readings). The request is served again in `slot`, every other slot
    live beside it with the other sampled requests (module docstring): each
    prompt but its last token by the engine's warm prefill programs, then one
    warm decode launch a row, each slot fed the token it was fed when served
    (the prompt's last, then the served ones), and every launch's own `selected`
    output read."""
    k, slots = eng.select_k, eng.max_slots
    n_out = len(sequences[which]) - n_prompt[which]
    others = [i for i in range(len(sequences)) if i != which] or [which]
    # slot -> the sequence it replays
    plan = {s: others[j % len(others)] for j, s in enumerate(
        s for s in range(slots) if s != slot)}
    plan[slot] = which
    for s, i in plan.items():
        if not eng.begin_request(s, sequences[i][:n_prompt[i] - 1], max(
                n_out, len(sequences[i]) - n_prompt[i]) + 1):
            raise RuntimeError("no blocks to serve a sampled request again")
        first = None
        while first is None:
            first = eng.prefill_enqueue(s)
        eng.prefill_collect(first)
    out, same, total, crowded = None, 0, 0, 0
    for t in range(n_out):
        # row t reads the token at position n_prompt - 1 + t and makes served
        # token t (a filler past its served tokens goes on with its own)
        for s, i in plan.items():
            at = n_prompt[i] - 1 + t
            if at < len(sequences[i]):
                eng.last_ids[s, 0] = sequences[i][at]
        pos = eng.pos.copy()
        launch = eng.step_enqueue()
        nxt, _ = eng.step_collect(launch)
        for s, i in plan.items():
            if n_prompt[i] + t < len(sequences[i]):
                total += 1
                same += int(nxt[s]) == int(sequences[i][n_prompt[i] + t])
        if out is None:
            out = {li: (np.zeros((n_out, k), np.int32), np.zeros(n_out, np.int32))
                   for li in launch["selected"]}
        for li, words in launch["selected"].items():
            words = np.asarray(words)
            at = eng._sc.bitset_positions(words[slot])
            out[li][0][t, :min(len(at), k)] = at[:k]
            out[li][1][t] = len(at)
            # every live slot keeps min(pos + 1, k) positions
            kept = np.unpackbits(np.ascontiguousarray(words).view(np.uint8),
                                 axis=-1).sum(-1)
            crowded += int((kept != np.minimum(pos + 1, k)).sum())
    for s in plan:
        eng.leave(s)
        eng.release(s)
    faults = [f"{crowded} (launch, layer, slot) selections of the replay did not "
              f"keep min(pos + 1, {k}) positions"] if crowded else []
    return out, faults, {"replay_slots_live": len(plan),
                         "replay_tokens_same_share": same / max(total, 1)}


def overlap(rows: list, widen: float, layer=None, clear_of=None) -> tuple:
    """(share of the selected positions within `widen` of the reference's set,
    the worst row's share) over every row and indexer layer (or `layer` alone)
    of `rows`' `sel_short`, counting the first `n_sel` positions of each row;
    with `clear_of`, only the (row, position) pairs whose router margins before
    that layer (`sel_upstream`) are that wide at least."""
    hit = total = 0
    worst = 1.0
    for r in rows:
        for li, short in r.get("sel_short", {}).items():
            if layer is not None and li != layer:
                continue
            n_sel = r["sel_n"][li]
            counted = np.arange(short.shape[1])[None, :] < n_sel[:, None]
            if clear_of is not None:
                counted &= r["sel_upstream"][li] >= clear_of
            ok = (short <= widen) & counted
            hit += int(ok.sum())
            total += int(counted.sum())
            worst = min(worst, float((ok.sum(1) / np.maximum(counted.sum(1), 1)
                                      )[counted.any(1)].min(initial=1.0)))
    return (hit / total if total else None), worst


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
        num_blocks=sizes["num_blocks"])
    ctx.sample_memory()
    del model
    gc.collect()
    control = ctx.control
    if control == "recent_deep":        # a fault planted in the program
        plant_most_recent(eng, max(li for li, sp in enumerate(eng.cache_spec)
                                   if "index" in sp["pools"]))
        control = None
    srv = GenerationServer(eng)
    ctx.log(f"engine built ({eng.num_blocks} blocks of {eng.block_size}, chunk "
            f"{eng.prefill_chunk_len}, top-{eng.select_k}); peak so far "
            f"{ctx.memory_peak_bytes / 1e9:.2f} GB")

    rng = traffic.rng_for(ctx.seed, 3)
    warm = [srv.submit(rng.integers(0, vocab, n, dtype=np.int32), 4)
            for n in WARM_PROMPTS if n < sizes["max_seq"] - 8]
    for r in warm:
        if not r["done"].wait(1500) or r["error"] is not None:
            raise RuntimeError(f"warm-up request failed: {r['error']!r}")
    ctx.log(f"warm: prefill buckets {sorted(eng._prefills)}, cache {warmup.cache_stats()}")

    clients = _ClassClients(srv, traffic_two_class.streams(mix, vocab, ctx.seed),
                            traffic_two_class.client_classes(mix),
                            ctx.sample_memory)
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
    clients.run_until(t1 + 300.0, resend=False)         # late is late, not lost
    ctx.sample_memory()
    drained = srv.shutdown(drain=True, timeout=300)
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
    for kernel in ("latent_attention", "index_scores", "expert_rows_matmul"):
        other, kern = f"{kernel}:reference", f"{kernel}:pallas"
        if not ctx.rehearsal and (paths1.get(other, 0) != paths0.get(other, 0)
                                  or paths1.get(kern, 0) <= paths0.get(kern, 0)):
            faults.append(f"a serving program left the Pallas {kernel} kernel: "
                          f"{paths0} -> {paths1}")
    pool = stats.get("kv_pool", {})
    if pool.get("blocks_used") or pool.get("blocks_reserved"):
        faults.append(f"the block table leaked: {pool} after the drain")

    ttft = [r["t_tokens"][0] - r["t_submit"] for r in in_window if r["t_tokens"]]
    gaps, tokens_in_window = [], 0
    for r in records:
        ts = r["t_tokens"]
        tokens_in_window += sum(1 for t in ts if t0 <= t < t1)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": tokens_in_window / (t1 - t0),
                  "ttft_ms_p95": 1e3 * _p95(ttft) if ttft else None}
    # read and logged, not an end-to-end metric of this cell (module docstring)
    token_gap_ms_p95 = 1e3 * _p95(gaps) if gaps else None

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
        "ttft_n": len(ttft), "gaps_n": len(gaps),
        "ttft_ms_p95": end_to_end["ttft_ms_p95"],
        "token_gap_ms_p95": token_gap_ms_p95}
    ctx.log(f"{len(in_window)} requests submitted in the window, "
            f"{tokens_in_window} tokens, {len(gaps)} gaps (95th percentile "
            f"{token_gap_ms_p95} ms); peak {ctx.memory_peak_bytes / 1e9:.2f} GB")

    # -- the sample, and the program's selections for its long requests -------
    finished = [r for r in records if r["t_done"] is not None
                and t0 <= r["t_done"] < t1 and r["req"]["error"] is None
                and len(r["req"]["out"]) == r["max_new"]]
    sample_reqs = pick_sample(finished, ctx.seed)
    is_long = lambda r: r["n_prompt"] >= 2 * cfg["index_topk"]
    # a short (traced) window finishes few requests, perhaps no long one: what
    # the drain finished was served by the same programs, so it tops the
    # sample up, the shortest first and one long request at least
    in_sample = {r["trace_id"] for r in finished}
    late = sorted((r for r in records if r["t_done"] is not None
                   and r["trace_id"] not in in_sample
                   and r["req"]["error"] is None
                   and len(r["req"]["out"]) == r["max_new"]),
                  key=lambda r: (r["n_prompt"], r["trace_id"]))
    if sample_reqs and not any(is_long(r) for r in sample_reqs):
        sample_reqs += [r for r in late if is_long(r)][:1]
    # the longest stays; of the other long ones the reference reads one more
    keep, n_long = [], 0
    for r in sample_reqs:
        if not is_long(r) or n_long < LONG_SAMPLED:
            keep.append(r)
            n_long += is_long(r)
    sample_reqs = keep
    in_sample = {r["trace_id"] for r in sample_reqs}
    sample_reqs += [r for r in late if r["trace_id"] not in in_sample
                    and not is_long(r)][
        :max(SAMPLE_REQUESTS - len(sample_reqs), 0)]
    sequences = [np.concatenate([np.asarray(r["req"]["prompt"], np.int32),
                                 np.asarray(r["req"]["out"], np.int32)])
                 for r in sample_reqs]
    n_prompt = [r["n_prompt"] for r in sample_reqs]
    long_ones = [i for i in sorted(range(len(sequences)), key=lambda i: -n_prompt[i])
                 if n_prompt[i] >= 2 * cfg["index_topk"]][:LONG_REQUESTS]
    selections, replay = {}, {}
    if drained:
        t_sel = time.perf_counter()
        for i in long_ones:
            selections[i], bad, replay = served_selections(
                eng, sequences, n_prompt, i, slot=ctx.seed % eng.max_slots)
            faults.extend(bad)
        ctx.log(f"selections of {len(selections)} long requests (prompts "
                f"{[n_prompt[i] for i in selections]}) read from the serving "
                f"programs' own launches in {time.perf_counter() - t_sel:.1f} s: "
                f"{replay}")
    for i, per_layer in selections.items():
        want = np.minimum(np.arange(n_prompt[i], len(sequences[i])),
                          cfg["index_topk"])
        for li, (_, n_sel) in per_layer.items():
            if not np.array_equal(n_sel, want):
                faults.append(f"layer {li} selected {n_sel[:4]}... positions a "
                              f"row where min(pos + 1, k) is {want[:4]}...")

    # -- free the program, then the reference --------------------------------
    # the weights and the pools are deleted by hand: the program's registry of
    # captured steps keeps the engine, and with it 11 GB that the float32
    # reference of a 40k-token request needs
    for leaf in ctx.jax.tree.leaves((eng.params, eng.kvs)):
        leaf.delete()
    del srv, eng, clients, warm
    for r in records:
        r["req"] = None
    gc.collect()
    ctx.log(f"program freed, device holds {ctx.sample_memory() / 1e9:.2f} GB")
    compared = {"served_logit_gap": None, "served_logit_gap_at_ties": None,
                "index_set_miss": None, "index_set_miss_deep": None}
    if sequences:
        import jax.numpy as jnp
        t_ref = time.perf_counter()
        rows = reference.served_logit_gaps(
            cfg, seed, sequences, n_prompt,
            out_pad=int(mix["output_len"]["max"]), dtype=jnp.dtype(dtype_name),
            control=control, selections=selections)
        for i, per_layer in selections.items():
            rows[i]["sel_n"] = {li: n for li, (_, n) in per_layer.items()}
        margin = np.concatenate([r["margin"] for r in rows])
        sel_margin = np.concatenate([r["sel_margin"] for r in rows])
        if cfg["index_topk"] < SELECTION_TIES_BELOW_TOPK:
            # a selection tie counts as a tie of any width (margin 0)
            margin = np.where(sel_margin < WIDEN, 0.0, margin)
        n_tok = len(margin)

        def split(key, at):
            """Largest gap clear of a tie and at one (0 where none is)."""
            gap = np.concatenate([r[key] for r in rows])
            tie = margin < at
            return {"served_logit_gap": float(gap[~tie].max(initial=0.0)),
                    "served_logit_gap_at_ties": float(gap[tie].max(initial=0.0))}
        compared.update(split("gap", TIE_MARGIN))
        index_layers = sorted({li for r in rows for li in r.get("sel_short", {})})
        share, worst = overlap(rows, WIDEN, index_layers[0]) if index_layers \
            else (None, None)
        if share is not None:
            compared["index_set_miss"] = 1.0 - share
            deep = [overlap(rows, WIDEN_DEEP, li)[0] for li in index_layers[1:]]
            compared["index_set_miss_deep"] = 1.0 - min(deep) if deep else 0.0
        elif not long_ones:
            faults.append("the window finished no long request to read the "
                          "selection of")
        near = int((margin < TIE_MARGIN).sum())
        if not ctx.rehearsal and n_tok - near < MIN_CLEAR:
            faults.append(f"only {n_tok - near} of {n_tok} sampled positions are "
                          f"clear of a router tie: too few to compare")
        observed["readings"] = {
            "router_near_tie_share": near / max(n_tok, 1),
            "by_margin": {str(m): dict(split("gap", m), share=float(
                (margin < m).mean())) for m in MARGINS_READ},
            "index_set_overlap": share, "index_set_overlap_worst_row": worst,
            # the witness for what the later indexer layers' misses are: the
            # pairs no expert flipped on rounding can have touched miss less
            "deep_miss_clear_of": {str(m): [
                None if got is None else 1.0 - got for got in (
                    overlap(rows, WIDEN_DEEP, li, clear_of=m)[0]
                    for li in index_layers[1:])] for m in (0.0,) + MARGINS_READ},
            "deep_pairs_clear_share": {str(m): float(np.mean(np.concatenate(
                [(r["sel_upstream"][li] >= m).ravel() for r in rows
                 for li in index_layers[1:] if li in r.get("sel_upstream", {})]
                or [np.zeros(1)]))) for m in MARGINS_READ},
            **replay,
            "overlap_by_widening": {str(w): overlap(rows, w)[0]
                                    for w in WIDENS_READ},
            "overlap_by_layer": {
                str(li): {str(w): overlap(rows, w, li) for w in WIDENS_READ}
                for li in sorted({li for r in rows
                                  for li in r.get("sel_short", {})})},
            "selection_margin_median": float(np.median(
                sel_margin[np.isfinite(sel_margin)])) if np.isfinite(
                    sel_margin).any() else None,
            "selected_rows_read": int(sum(int(n.sum()) for r in rows
                                          for n in r.get("sel_n", {}).values())),
            "gap_past_topk": float(max((r["gap"].max(initial=0.0)
                                        for r, n in zip(rows, n_prompt)
                                        if n >= cfg["index_topk"]), default=0.0))}
        if control:
            observed["readings"]["control"] = split("control_gap", TIE_MARGIN)
            observed["readings"]["control_by_margin"] = {
                str(m): split("control_gap", m) for m in MARGINS_READ}
        exact = sum(int((r["gap"] == 0).sum()) for r in rows)
        ctx.log(f"reference: {time.perf_counter() - t_ref:.1f} s over "
                f"{len(rows)} requests (prompts {n_prompt}), {n_tok} served "
                f"tokens, {exact} are its own first choice, {near} at a router "
                f"tie; index_set_overlap {share} (worst row {worst}) over "
                f"{observed['readings']['selected_rows_read']} selected positions; "
                f"by layer and widening {observed['readings']['overlap_by_layer']}; "
                f"the later indexer layers' miss over the pairs clear of an "
                f"upstream router tie {observed['readings']['deep_miss_clear_of']}"
                f" (their share {observed['readings']['deep_pairs_clear_share']})")
    else:
        faults.append("the window finished no request to compare")
    return {"attempted": len(in_window), "failed": failed, "faults": faults,
            "compared": compared, "end_to_end": end_to_end, "observed": observed,
            "counts": {"requests": len(in_window), "tokens": tokens_in_window,
                       "steps": observed["steps"],
                       "compiles_in_window": observed["compiles_in_window"],
                       "sampled_requests": len(sequences),
                       "long_requests_read": len(selections)}}
