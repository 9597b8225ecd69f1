"""Role runner `serve_paged_kda`: a Solar Open 2 configuration (gated delta-rule
linear-attention layers with a state a slot, one gated GQA layer in four over a
paged K/V pool, a share of the routed experts held) in the paged engine behind
`GenerationServer.submit`, under a closed loop of clients, on one chip.

The closed loop, the clock, the percentile and the sampling of requests for the
reference are `runners/serve_paged.py`'s; this file builds the other model,
checks the paths of the four kernels its launches run, reads the program's STATE
for one sampled request and compares with `lib/reference_solar_open2.py`:

- `served_logit_gap` / `served_logit_gap_at_ties`: as for the other sparse-expert
  cells, every served token's distance from the reference's first choice, apart
  where some layer's 8th and 9th biased router scores lie within `TIE_MARGIN`;
- `state_gap`: the relative Frobenius distance between the LAST KDA layer's
  state of a sampled request, fetched from the program's state pool, and the
  reference's after the same tokens. It holds what the served slot kept to the
  reference over the whole request: a state that was not reset, a stale tail, a
  wrong slot read 1. It cannot see the state's PRECISION: seven layers of
  bfloat16 activations and of experts chosen the other way at a router tie put
  the program's state 4 to 9% from the float32 reference's, where a reference
  with a bfloat16 state lies 2% from it (limits file);
- `recurrence_gap`: the same distance for the recurrence as the serving programs
  run it, fed the reference's own rows (`recurrence_probe`: the reference's `q,
  k, v, g, beta` of that layer and request, the prompt's through
  `kda.kda_chunk(pool, slot, fresh, ...)` in chunks of 512, the served tokens'
  through `kda.kda_step` over every slot with that one active: the two calls
  `SolarOpen2Serve.layer` makes, at the shapes it makes them, IN the engine's
  own state pool of that layer, `[64, 64, 128, 128]` as the engine allocated
  it, which is kept back when the program is freed). With the layers before
  taken out, the chunk kernel, the step kernel and a float32 pool agree with
  the token-by-token reference to 1e-5, and a bfloat16 pool reads 1e-2 (the
  `state_bf16` control: the SAME probe on the same rows in a bfloat16 pool of
  the engine's shape). It is the one limit that sees the state's precision.
  What ties it to the served launches: `tests/test_tpu_compile.py::
  test_the_engine_s_programs_keep_a_float32_state_in_place` compiles the
  engine's decode and chunk programs for the v5e and holds each KDA layer to
  one of these calls with the donated float32 pool aliased in and out and no
  other array of a state's shape, in any dtype, anywhere in the launch; here
  the pool's dtype against the configuration's `serve.state_dtype` and the
  other slots' states (bit for bit after the probe) are `faults`.

**Where the state comes from.** After the window has drained, the sampled
request with the fewest served tokens is served again (`served_state`): its
prompt by the engine's warm prefill programs (chunks of 512 then a bucket), then
one launch of the warm decode program a served token, the slot fed the token it
was fed when served. No program is compiled for the check. The share of the
replayed tokens that equal the served ones is a reading (`replay_tokens_same`).

**End-to-end metrics.** `setup_s` and `serve_tokens_per_s`. Time to first token
and the gap between a request's tokens are read as in the other serving cells and
logged, but `ttft_ms_p95` and `token_gap_ms_p95` are no end-to-end metrics of
this cell: over nine 51-s runs on nine seeds they spread by 3.66% and 1.19%,
over six more by 1.94% and 1.61%, against half bounds of 2.5% and 1% (PERF.md,
PR 35). The iteration's time follows the WEIGHTS' seed (how many of the 20 held
experts a launch's rows reach), TTFT is a prompt's own chunks at one an
iteration, and the gap's 95th percentile is the iteration that carries a chunk.
`serve_tokens_per_s` itself spread by 1.29%, 2.09% and, over all fifteen seeds,
1.09% against a half bound of 1%: a cell at capacity has no other metric to
stand on, so it stays, and PERF.md section 7 says what would steady it.

**Controls** (`tests/control_kda_on_chip.py`): `ctx.control` `fp8` is the
reference with every matmul operand but the router's rounded to e4m3;
`state_bf16` is one step below the float32 the configuration states for the
recurrent state: the reference with the state rounded to bfloat16 after every
token (its `state_gap`, which passes) and `recurrence_probe` in a bfloat16 pool
(its `recurrence_gap`, which does not). Each has to fail at least one limit of
those it reads (`fp8` reads no `recurrence_gap`: the recurrence has no matmul
operand to round).
"""
from __future__ import annotations

import functools
import gc
import time

import numpy as np

from benchmark.lib import reference_solar_open2 as reference
from benchmark.lib import traffic
from benchmark.lib import weights_solar_open2 as weights
from benchmark.runners._llama import path_counts
from benchmark.runners.serve_paged import (FLIGHT_CAPACITY, SAMPLE_REQUESTS,
                                           _Clients, _p95, pick_sample)

# A served position is "at a tie" where, in some layer, the reference's 8th and
# 9th biased router scores lie closer than this (limits file).
TIE_MARGIN = 2e-3
MARGINS_READ = (5e-4, 1e-3, 2e-3, 4e-3, 8e-3)     # for tests/control_kda_on_chip.py
MIN_CLEAR = 40      # served positions clear of a tie that a comparison needs

# one prompt per prefill bucket (8 .. 512) and one of several chunks
WARM_PROMPTS = (5, 12, 24, 40, 100, 200, 400, 1100)

TINY = {"hidden_size": 64, "moe_intermediate_size": 32, "num_hidden_layers": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                               "num_heads": 2, "num_kv_heads": None},
        "gate_low_rank": 8, "vocab_size": 128, "n_routed_experts": 4,
        "n_routed_experts_published": 16, "num_experts_per_tok": 2,
        "experts_held_from": 4}


def _sizes(ctx):
    cfg, mix = dict(ctx.config), dict(ctx.traffic)
    if ctx.rehearsal:
        cfg.update(TINY, serve=dict(cfg["serve"], max_slots=4, max_seq=160,
                                    prefill_chunk=16, block_size=4,
                                    num_blocks=160))
        mix.update(clients=4, ramp_seconds=0.5, pool=8,
                   prompt_len=dict(mix["prompt_len"], median=24, min=4, max=80),
                   output_len=dict(mix["output_len"], median=12, min=4, max=24))
    return cfg, mix


def program_name(cfg: dict, leaf: str) -> str:
    if leaf in ("embed", "final_norm", "head"):
        return {"embed": "model.embed_tokens.weight",
                "final_norm": "model.norm.weight", "head": "lm_head.weight"}[leaf]
    from paddle_tpu.models import solar_open2 as M
    _, i, part = leaf.split(".")
    kind = M.GQA_PARAMS if int(i) in cfg["gqa_layers"] else M.KDA_PARAMS
    return f"model.layers.{i}.{ {**kind, **M.BLOCK_PARAMS}[part]}"


def model_config(cfg: dict, dtype: str):
    from paddle_tpu.models import SolarOpen2Config
    lin = cfg["linear_attn_config"]
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("grouped keys and values in the KDA layers are not "
                         "what the program builds")
    if cfg["first_k_dense_replace"] or cfg["use_rope"] or cfg["kda_use_full_proj"]:
        raise ValueError("a leading dense layer, rope in the GQA layers or "
                         "full-rank gates are not what the program builds")
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        gqa_layers=tuple(cfg["gqa_layers"]), linear_num_heads=lin["num_heads"],
        linear_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        gate_low_rank=cfg["gate_low_rank"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["n_routed_experts_published"],
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"], use_gqa_gate=cfg["use_gqa_gate"],
        kda_allow_neg_eigval=cfg["kda_allow_neg_eigval"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=dtype)


def build_model(cfg: dict, seed_u32, dtype_name: str):
    """`SolarOpen2ForCausalLM` holding the configuration's share of the experts,
    born with empty matrices in its dtype, every parameter then replaced by the
    benchmark's seeded leaf, one at a time: the device never holds a second
    set."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import SolarOpen2ForCausalLM

    dtype = jnp.dtype(dtype_name)
    paddle.set_default_dtype(dtype_name)
    try:
        model = SolarOpen2ForCausalLM(model_config(cfg, dtype_name),
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


def served_state(eng, seq, n_prompt: int, slot: int) -> tuple:
    """What the SERVING programs leave in the KDA layers' states for the request
    `seq` (its prompt, then its served tokens), served again in `slot`: ({layer:
    its state [H, d, d] after every token but the last}, the share of the
    replayed tokens that equal the served ones). The prompt goes through the engine's warm prefill
    programs, then one warm decode launch a served token, the slot fed the token
    it was fed when served."""
    n_out = len(seq) - n_prompt
    if not eng.begin_request(slot, seq[:n_prompt], n_out + 1):
        raise RuntimeError("no blocks to serve a sampled request again")
    first = None
    while first is None:
        first = eng.prefill_enqueue(slot)
    tok, _ = eng.prefill_collect(first)
    same = int(tok == seq[n_prompt])
    for t in range(n_prompt, len(seq) - 1):
        eng.last_ids[slot, 0] = seq[t]
        nxt, _ = eng.step_collect(eng.step_enqueue())
        same += int(nxt[slot]) == int(seq[t + 1])
    state = {li: np.asarray(pool[slot])
             for li, pool in enumerate(eng.kvs["S"]) if pool is not None}
    eng.leave(slot)
    eng.release(slot)
    return state, same / n_out


def recurrence_probe(rows, n_prompt: int, pool, slot: int, chunk: int) -> tuple:
    """The recurrence as the serving programs run it, fed the reference's own
    rows: `rows` (`q, k, v, g [tokens, H, d]`, `beta [tokens, H]`: what the
    reference's last KDA layer read for a sampled request) through the two calls
    of `ops/pallas/kda.py` that `SolarOpen2Serve.layer` makes, at the shapes it
    makes them: `pool` is the engine's OWN state pool of that layer, `[max_slots,
    H, d, d]` as the engine allocated it (donated here as the engine donates it;
    the control passes one of the same shape in bfloat16), `slot` the slot the
    request was served in. The first `n_prompt` rows go in chunks of `chunk` by
    `kda_chunk(pool, slot, fresh, ...)`, the first from a zero state whatever
    the pool holds, the rows past the last valid one with `g = 0, beta = 0` as
    the engine pads a bucket; every later row by `kda_step` over ALL the pool's
    slots with `slot` alone active. Returns (the slot's state `[H, d, d]` after
    the last row, whether every other slot's state is bit for bit what it was).
    Whatever the layers before did to the program's activations is not in it:
    what is left is the recurrence's own arithmetic at the served shapes (the
    chunk kernel against a token at a time, the step kernel over a 64-slot pool
    with its in-place update and skipped slots) and the pool's precision.
    What holds the engine's decode and chunk programs to these two calls, the
    float32 pool aliased in and out and no other array of its shape, is
    `test_the_engine_s_programs_keep_a_float32_state_in_place` in
    `tests/test_tpu_compile.py`."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import kda
    n = rows[1].shape[0]
    NS = pool.shape[0]
    # room for the last chunk's padding, so that no slice is clipped
    rows = tuple(jnp.pad(x, ((0, chunk),) + ((0, 0),) * (x.ndim - 1))
                 for x in rows)
    bits = jax.jit(lambda pool: jnp.sum(jax.lax.bitcast_convert_type(
        pool.astype(jnp.float32), jnp.uint32), axis=(1, 2, 3)))
    before = np.asarray(bits(pool))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def chunked(pool, start, nvalid, *rows):
        live = jnp.arange(chunk) < nvalid
        q, k, v, g, beta = (jax.lax.dynamic_slice_in_dim(x, start, chunk, 0)
                            for x in rows)
        return kda.kda_chunk(pool, jnp.int32(slot), start == 0, q, k, v,
                             jnp.where(live[:, None, None], g, 0.0),
                             jnp.where(live[:, None], beta, 0.0))[1]

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(pool, t, *rows):
        act = jnp.arange(NS) == slot
        one = (jnp.where(act.reshape((NS,) + (1,) * (x.ndim - 1)), x[t][None],
                         0.0) for x in rows)
        return kda.kda_step(pool, *one, act)[1]

    for start in range(0, n_prompt, chunk):
        pool = chunked(pool, jnp.int32(start),
                       jnp.int32(min(chunk, n_prompt - start)), *rows)
    for t in range(n_prompt, n):
        pool = step(pool, jnp.int32(t), *rows)
    others = np.arange(NS) != slot
    same = bool((np.asarray(bits(pool))[others] == before[others]).all())
    return np.asarray(pool[slot].astype(jnp.float32)), same


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
    srv = GenerationServer(eng)
    ctx.log(f"engine built ({eng.num_blocks} blocks of {eng.block_size}, chunk "
            f"{eng.prefill_chunk_len}, state {eng.state_stats()}); peak so far "
            f"{ctx.memory_peak_bytes / 1e9:.2f} GB")

    rng = traffic.rng_for(ctx.seed, 3)
    warm = [srv.submit(rng.integers(0, vocab, n, dtype=np.int32), 4)
            for n in WARM_PROMPTS if n < sizes["max_seq"] - 8]
    for r in warm:
        if not r["done"].wait(1100) or r["error"] is not None:
            raise RuntimeError(f"warm-up request failed: {r['error']!r}")
    ctx.log(f"warm: prefill buckets {sorted(eng._prefills)}, cache {warmup.cache_stats()}")

    clients = _Clients(srv, traffic.requests(mix, vocab, ctx.seed),
                       int(mix["clients"]), ctx.sample_memory)
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
    clients.run_until(t1 + 240.0, resend=False)         # late is late, not lost
    ctx.sample_memory()
    drained = srv.shutdown(drain=True, timeout=240)
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
                          ("expert_rows_matmul", "reference"),
                          ("kda_step", "reference"), ("kda_chunk", "reference")):
        walk, kern = f"{kernel}:{other}", f"{kernel}:pallas"
        if not ctx.rehearsal and (paths1.get(walk, 0) != paths0.get(walk, 0)
                                  or paths1.get(kern, 0) <= paths0.get(kern, 0)):
            faults.append(f"a serving program left the Pallas {kernel} kernel: "
                          f"{paths0} -> {paths1}")
    pool = stats.get("kv_pool", {})
    if pool.get("blocks_used") or pool.get("blocks_reserved") \
            or pool.get("state_slots_in_use"):
        faults.append(f"the cache leaked: {pool} after the drain")

    ttft = [r["t_tokens"][0] - r["t_submit"] for r in in_window if r["t_tokens"]]
    gaps, tokens_in_window = [], 0
    for r in records:
        ts = r["t_tokens"]
        tokens_in_window += sum(1 for t in ts if t0 <= t < t1)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": tokens_in_window / (t1 - t0)}
    # read and logged, not end-to-end metrics of this cell (module docstring)
    ttft_ms_p95 = 1e3 * _p95(ttft) if ttft else None
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
        "ttft_ms_p95": ttft_ms_p95, "token_gap_ms_p95": token_gap_ms_p95}
    ctx.log(f"{len(in_window)} requests submitted in the window, "
            f"{tokens_in_window} tokens; ttft_ms_p95 {ttft_ms_p95} over "
            f"{len(ttft)}, token_gap_ms_p95 {token_gap_ms_p95} over {len(gaps)} "
            f"gaps; peak {ctx.memory_peak_bytes / 1e9:.2f} GB")

    # -- the sample, and the program's state for one of its requests ----------
    ok = lambda r: (r["t_done"] is not None and r["req"]["error"] is None
                    and len(r["req"]["out"]) == r["max_new"])
    finished = [r for r in records if ok(r) and t0 <= r["t_done"] < t1]
    sample_reqs = pick_sample(finished, ctx.seed)
    # a short (traced) window finishes few requests: what the drain finished was
    # served by the same programs, so it tops the sample up, the shortest first
    in_sample = {r["trace_id"] for r in finished}
    late = sorted((r for r in records if ok(r) and r["trace_id"] not in in_sample),
                  key=lambda r: (r["n_prompt"] + r["max_new"], r["trace_id"]))
    if sample_reqs:
        sample_reqs += late[:max(SAMPLE_REQUESTS - len(sample_reqs), 0)]
    sequences = [np.concatenate([np.asarray(r["req"]["prompt"], np.int32),
                                 np.asarray(r["req"]["out"], np.int32)])
                 for r in sample_reqs]
    n_prompt = [r["n_prompt"] for r in sample_reqs]
    last_kda = max(li for li, sp in enumerate(eng.cache_spec) if sp.get("state"))
    held_in = {str(p.dtype) for p in eng.kvs["S"] if p is not None}
    if held_in != {sizes["state_dtype"]}:
        faults.append(f"the engine keeps its recurrent state in {sorted(held_in)}"
                      f", the configuration states {sizes['state_dtype']}")
    chunk = eng.prefill_chunk_len
    slot = ctx.seed % eng.max_slots
    which, state, replay_same = None, None, None
    if drained and sequences:
        t_rep = time.perf_counter()
        which = min(range(len(sequences)),
                    key=lambda i: (len(sequences[i]) - n_prompt[i], i))
        state, replay_same = served_state(
            eng, sequences[which], n_prompt[which], slot=slot)
        ctx.log(f"states of layers {sorted(state)} read for a request of "
                f"{n_prompt[which]} + {len(sequences[which]) - n_prompt[which]} "
                f"tokens served again in {time.perf_counter() - t_rep:.1f} s; "
                f"{replay_same:.4f} of its tokens as served")

    # -- free the program, then the reference --------------------------------
    # (but the last KDA layer's state pool, which the probe below runs in)
    probe_pool = eng.kvs["S"][last_kda]
    for leaf in ctx.jax.tree.leaves((eng.params, eng.kvs)):
        if leaf is not probe_pool:
            leaf.delete()
    del srv, eng, clients, warm
    for r in records:
        r["req"] = None
    gc.collect()
    ctx.log(f"program freed, device holds {ctx.sample_memory() / 1e9:.2f} GB")
    compared = {"served_logit_gap": None, "served_logit_gap_at_ties": None,
                "state_gap": None, "recurrence_gap": None}
    if sequences:
        import jax.numpy as jnp
        t_ref = time.perf_counter()
        rows = reference.served_logit_gaps(
            cfg, seed, sequences, n_prompt,
            out_pad=int(mix["output_len"]["max"]), dtype=jnp.dtype(dtype_name),
            control=ctx.control,
            state_of=None if state is None
            else (which, len(sequences[which]) - 1))
        margin = np.concatenate([r["margin"] for r in rows])
        n_tok = len(margin)

        def split(key, at):
            """Largest gap clear of a tie and at one (0 where none is)."""
            gap = np.concatenate([r[key] for r in rows])
            tie = margin < at
            return {"served_logit_gap": float(gap[~tie].max(initial=0.0)),
                    "served_logit_gap_at_ties": float(gap[tie].max(initial=0.0))}
        compared.update(split("gap", TIE_MARGIN))
        by_layer = {}
        if state is not None:
            by_layer = {li: reference.state_gap(state[li], ref)
                        for li, ref in rows[which]["state"].items()}
            compared["state_gap"] = by_layer[last_kda]
            t_probe = time.perf_counter()
            probe_rows = rows[which].pop("rows")
            shape, kept_in = tuple(probe_pool.shape), str(probe_pool.dtype)
            end, others_same = recurrence_probe(
                probe_rows, n_prompt[which], probe_pool, slot, chunk)
            compared["recurrence_gap"] = reference.state_gap(
                end, rows[which]["state"][last_kda])
            if not others_same:
                faults.append("the recurrence of one slot changed another "
                              "slot's state in the engine's pool")
            ctx.log(f"the serving calls fed the reference's own rows in the "
                    f"engine's pool {shape} {kept_in}, slot {slot}: "
                    f"recurrence_gap {compared['recurrence_gap']:.3e} in "
                    f"{time.perf_counter() - t_probe:.1f} s")
        near = int((margin < TIE_MARGIN).sum())
        if not ctx.rehearsal and n_tok - near < MIN_CLEAR:
            faults.append(f"only {n_tok - near} of {n_tok} sampled positions are "
                          f"clear of a router tie: too few to compare")
        observed["readings"] = {
            "router_near_tie_share": near / max(n_tok, 1),
            "by_margin": {str(m): dict(split("gap", m), share=float(
                (margin < m).mean())) for m in MARGINS_READ},
            "replay_tokens_same": replay_same,
            "state_gap_by_layer": by_layer,
            "state_request": None if which is None else
            [n_prompt[which], len(sequences[which]) - n_prompt[which]]}
        if ctx.control:
            far = low = None
            if state is not None:
                far = reference.state_gap(rows[which]["control_state"][last_kda],
                                          rows[which]["state"][last_kda])
            if state is not None and ctx.control == "state_bf16":
                # the same probe, the same rows, the same shapes: the pool one
                # precision below the one the configuration states
                import jax.numpy as jnp
                low = reference.state_gap(recurrence_probe(
                    probe_rows, n_prompt[which], jnp.zeros(shape, jnp.bfloat16),
                    slot, chunk)[0], rows[which]["state"][last_kda])
            # (the fp8 control rounds matmul operands, of which the recurrence
            # has none to round: it reads no recurrence_gap)
            observed["readings"]["control"] = dict(
                split("control_gap", TIE_MARGIN), state_gap=far,
                recurrence_gap=low,
                state_gap_by_layer={} if state is None else {
                    li: reference.state_gap(rows[which]["control_state"][li], ref)
                    for li, ref in rows[which]["state"].items()})
            observed["readings"]["control_by_margin"] = {
                str(m): split("control_gap", m) for m in MARGINS_READ}
        exact = sum(int((r["gap"] == 0).sum()) for r in rows)
        ctx.log(f"reference: {time.perf_counter() - t_ref:.1f} s over "
                f"{len(rows)} requests (prompts {n_prompt}), {n_tok} served "
                f"tokens, {exact} are its own first choice, {near} at a router "
                f"tie; state_gap {compared['state_gap']}; readings "
                f"{observed['readings']}")
    else:
        faults.append("the window finished no request to compare")
    return {"attempted": len(in_window), "failed": failed, "faults": faults,
            "compared": compared, "end_to_end": end_to_end, "observed": observed,
            "counts": {"requests": len(in_window), "tokens": tokens_in_window,
                       "steps": observed["steps"],
                       "compiles_in_window": observed["compiles_in_window"],
                       "sampled_requests": len(sequences),
                       "state_read": int(state is not None)}}
