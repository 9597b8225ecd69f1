"""Role runner `serve_paged_state`: a Brumby configuration (every layer degree-2
power retention over a state a slot, no block pool) in the paged engine behind
`GenerationServer.submit`, under a closed loop of clients, on one chip.

The closed loop, the clock, the percentile and the sampling of requests for the
reference are `runners/serve_paged.py`'s, the replay of a sampled request
through the warm programs `runners/serve_paged_kda.py`'s; this file builds the
model, checks the paths of the two kernels its launches run and that the engine
built no block table, and compares with `lib/reference_brumby.py`:

- `served_logit_gap`: for 8 requests the window finished (the longest and a
  seeded draw), every served token's distance from the reference's first choice
  (the prompt's last row, then each decode step), in standard deviations of
  that position's logits;
- `state_gap`: the sampled request with the fewest served tokens is served again
  after the drain by the warm programs, and the LAST layer's state of its slot
  (`S` and `z`, fetched from the pools) is held to the reference's after the
  same tokens (relative Frobenius distance). It is there to catch a slot that
  was not reset, a wrong slot and a wrong layout of the state; every run plants
  the three after the replay and logs what each reads (`planted_state_gap`),
  the limits file keeps the readings its limit was set between. It cannot see
  the state's precision, which the layers before blur;
- `recurrence_gap`: the same distance for the recurrence as the serving
  programs run it, fed the reference's own rows of that layer and request
  (`recurrence_probe`): the prompt through `retention_chunk(S, z, slot, fresh,
  ...)` in chunks of 512, then `retention_step` over every slot with that one
  active, the two calls `BrumbyServe.layer` makes, IN the engine's own state
  pools of the last layer (kept back when the program is freed). It is the one
  limit that sees the state's precision: the `state_bf16` control runs the same
  probe in bfloat16 pools.

**End-to-end metrics.** `setup_s`, `serve_tokens_per_s`, and `ttft_ms_p95` /
`token_gap_ms_p95` only where `BENCHMARK.json` lists the cell under them (their
spread over the first runs decides: PERF.md); both are read and logged in every
run.

**Controls** (`tests/control_retention_on_chip.py`): `ctx.control` `fp8` is the
reference with every matmul operand rounded to e4m3 (its `served_logit_gap`);
`state_bf16` is `recurrence_probe` in bfloat16 pools, one step below the float32
the configuration states for the state (its `recurrence_gap`).
"""
from __future__ import annotations

import functools
import gc
import json
import os
import time

import numpy as np

from benchmark.lib import reference_brumby as reference
from benchmark.lib import traffic
from benchmark.lib import weights_brumby as weights
from benchmark.runners._llama import path_counts
from benchmark.runners.serve_paged import (FLIGHT_CAPACITY, SAMPLE_REQUESTS,
                                           _Clients, _p95, pick_sample)
from benchmark.runners.serve_paged_kda import served_state

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# one prompt per prefill bucket (8 .. 512) and one of several chunks
WARM_PROMPTS = (5, 12, 24, 40, 100, 200, 400, 1100)
TAILS = ("ttft_ms_p95", "token_gap_ms_p95")

TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}


def _sizes(ctx):
    cfg, mix = dict(ctx.config), dict(ctx.traffic)
    if ctx.rehearsal:
        cfg.update(TINY, serve=dict(cfg["serve"], max_slots=4, max_seq=256,
                                    prefill_chunk=16))
        mix.update(clients=4, ramp_seconds=0.5, pool=8,
                   prompt_len=dict(mix["prompt_len"], median=24, min=4, max=80),
                   output_len=dict(mix["output_len"], median=12, min=4, max=24))
    return cfg, mix


def program_name(leaf: str) -> str:
    from paddle_tpu.models import brumby as M
    if leaf in ("embed", "final_norm", "head"):
        return {"embed": "model.embed_tokens.weight",
                "final_norm": "model.norm.weight", "head": "lm_head.weight"}[leaf]
    _, i, part = leaf.split(".")
    return f"model.layers.{i}.{M.LAYER_PARAMS[part]}"


def model_config(cfg: dict, dtype: str):
    from paddle_tpu.models import BrumbyConfig
    if cfg["attention_bias"] or cfg["use_sliding_window"] \
            or cfg["rope_scaling"] or cfg["hidden_act"] != "silu":
        raise ValueError("biased projections, a sliding window, scaled rope or "
                         "another activation are not what the program builds")
    return BrumbyConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        max_position_embeddings=cfg["max_position_embeddings"],
        tie_word_embeddings=cfg["tie_word_embeddings"], dtype=dtype)


def build_model(cfg: dict, seed_u32, dtype_name: str):
    """`BrumbyForCausalLM` born with empty matrices in its dtype, every
    parameter then replaced by the benchmark's seeded leaf, one at a time: the
    device never holds a second set."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import BrumbyForCausalLM

    paddle.set_default_dtype(dtype_name)
    try:
        model = BrumbyForCausalLM(model_config(cfg, dtype_name), init_std=None)
    finally:
        paddle.set_default_dtype("float32")
    params = dict(model.named_parameters())
    specs = weights.leaf_specs(cfg)
    if sorted(program_name(n) for n, _ in specs) != sorted(params):
        raise RuntimeError("the program's parameters are not the leaves the "
                           "reference is built from")
    leaf = weights.make_leaf(cfg, jnp.dtype(dtype_name))
    for index, (name, shape) in enumerate(specs):
        p = params[program_name(name)]
        if str(p.dtype) != dtype_name or tuple(p._data.shape) != tuple(shape):
            raise RuntimeError(f"leaf {name}: the program holds {p._data.shape} "
                               f"{p.dtype}, the reference {shape} {dtype_name}")
        p._data = leaf(seed_u32, index)
    return model


def recurrence_probe(rows, n_prompt: int, pools, slot: int, chunk: int,
                     fresh: bool = True) -> tuple:
    """The recurrence as the serving programs run it, fed the reference's own
    rows: `rows` (`q [tokens, heads, d]`, `k, v [tokens, KV heads, d]`, `gamma
    [tokens, KV heads]`: what the reference's last layer read for a sampled
    request) through the two calls of `ops/pallas/power_retention.py` that
    `BrumbyServe.layer` makes: `pools` is the engine's OWN `(S, z)` of that
    layer as it allocated them (donated here as the engine donates them; the
    control passes bfloat16 ones of the same shapes), `slot` the slot the
    request was served in. The first `n_prompt` rows go in chunks of `chunk` by
    `retention_chunk`, the first from a zero state whatever the pools hold
    (`fresh` False plants a slot that was not reset: the first chunk goes on
    from what the slot holds), the rows past the last valid one with `k = v =
    0, gamma = 0` as the engine pads a bucket; every later row by
    `retention_step` over ALL the pools' slots with `slot` alone active.
    Returns (the slot's `(S, z)` after the last row, whether every other slot's
    state is bit for bit what it was, the pools)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import power_retention as pr
    n = rows[1].shape[0]
    S, z = pools
    NS = S.shape[0]
    rows = tuple(jnp.pad(x, ((0, chunk),) + ((0, 0),) * (x.ndim - 1))
                 for x in rows)
    bits = jax.jit(lambda a: jnp.sum(jax.lax.bitcast_convert_type(
        a.astype(jnp.float32), jnp.uint32).reshape(a.shape[0], -1), axis=1))
    before = [np.asarray(bits(a)) for a in (S, z)]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def chunked(S, z, start, nvalid, q, k, v, g):
        live = jnp.arange(chunk) < nvalid
        q, k, v, g = (jax.lax.dynamic_slice_in_dim(x, start, chunk, 0)
                      for x in (q, k, v, g))
        _, S, z = pr.retention_chunk(
            S, z, jnp.int32(slot), jnp.logical_and(fresh, start == 0), q,
            jnp.where(live[:, None, None], k, 0.0),
            jnp.where(live[:, None, None], v, 0.0),
            jnp.where(live[:, None], g, 0.0))
        return S, z

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(S, z, t, *rows):
        act = jnp.arange(NS) == slot
        one = (jnp.where(act.reshape((NS,) + (1,) * (x.ndim - 1)), x[t][None],
                         0.0) for x in rows)
        _, S, z = pr.retention_step(S, z, *one, act)
        return S, z

    for start in range(0, n_prompt, chunk):
        S, z = chunked(S, z, jnp.int32(start),
                       jnp.int32(min(chunk, n_prompt - start)), *rows)
    for t in range(n_prompt, n):
        S, z = step(S, z, jnp.int32(t), *rows)
    others = np.arange(NS) != slot
    same = all(bool((np.asarray(bits(a))[others] == b[others]).all())
               for a, b in zip((S, z), before))
    return (np.asarray(S[slot].astype(jnp.float32)),
            np.asarray(z[slot].astype(jnp.float32))), same, (S, z)


def layout_swapped(state, head_dim: int) -> tuple:
    """`(S, z)` of one layer with the rows of the symmetric square taken in
    another layout: the served rows (`feature_pairs`' order, by the distance
    of a pair) read as the upper triangle row by row. What a program that
    wrote the state in one layout and read it in the other would hold."""
    a, b, _ = reference.feature_pairs(head_dim)
    perm = np.lexsort((np.maximum(a, b), np.minimum(a, b)))
    return tuple(np.take(x, perm, axis=1) for x in state)


def _listed_tails(cell: str) -> set:
    """The tail metrics `BENCHMARK.json` lists this cell under."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return {m["name"] for m in manifest["end_to_end"]
            if m["name"] in TAILS and cell in m.get("workloads", ())}


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
    eng = PagedLlamaDecodeEngine(model, max_slots=sizes["max_slots"],
                                 max_seq=sizes["max_seq"],
                                 prefill_chunk=sizes["prefill_chunk"])
    ctx.sample_memory()
    del model
    gc.collect()
    srv = GenerationServer(eng)
    ctx.log(f"engine built (blocks {eng.num_blocks}, chunk "
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
    clients.run_until(t1 + 600.0, resend=False)         # late is late, not lost
    ctx.sample_memory()
    drained = srv.shutdown(drain=True, timeout=240)
    stats = srv.stats()
    paths1 = path_counts()
    ctx.log(f"window {t1 - t0:.3f} s, drained {drained}; stats {stats}")

    # -- what the callers saw -----------------------------------------------
    records = clients.records
    in_window = [r for r in records if t0 <= r["t_submit"] < t1]
    faults = []
    failed = sum(bool(r["req"]["error"] is not None or r["t_done"] is None
                      or len(r["req"]["out"]) != r["max_new"]
                      or any(not 0 <= int(t) < vocab for t in r["req"]["out"]))
                 for r in in_window)
    if failed:
        faults.append(f"{failed} of {len(in_window)} requests of the window "
                      f"failed, never finished or came back the wrong length")
    faults += [f"server stats[{key!r}] = {stats[key]}" for key in (
        "rejected", "shed", "deadline_rejected", "deadline_expired", "crashed",
        "quarantined", "loop_restarts") if stats.get(key)]
    if not drained:
        faults.append("the server did not drain")
    if flight.dropped():
        faults.append(f"the flight ring dropped {flight.dropped()} events")
    for kernel in ("retention_step", "retention_chunk"):
        walk, kern = f"{kernel}:reference", f"{kernel}:pallas"
        if not ctx.rehearsal and (paths1.get(walk, 0) != paths0.get(walk, 0)
                                  or paths1.get(kern, 0) <= paths0.get(kern, 0)):
            faults.append(f"a serving program left the Pallas {kernel} kernel: "
                          f"{paths0} -> {paths1}")
    if eng.num_blocks or eng._tables_dev() is not None:
        faults.append("the engine built a block table for a model with no pool")
    pool = stats.get("kv_pool", {})
    if pool.get("slots_held") or pool.get("state_slots_in_use"):
        faults.append(f"the cache leaked: {pool} after the drain")

    ttft = [r["t_tokens"][0] - r["t_submit"] for r in in_window if r["t_tokens"]]
    gaps, tokens_in_window = [], 0
    for r in records:
        ts = r["t_tokens"]
        tokens_in_window += sum(1 for t in ts if t0 <= t < t1)
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if t0 <= b < t1)
    tails = {"ttft_ms_p95": 1e3 * _p95(ttft) if ttft else None,
             "token_gap_ms_p95": 1e3 * _p95(gaps) if gaps else None}
    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": tokens_in_window / (t1 - t0),
                  **{k: v for k, v in tails.items()
                     if k in _listed_tails(ctx.cell["name"])}}

    # -- the program's own record, on the runner's clock ---------------------
    events = flight.events(category="serving")
    by_id = {r["trace_id"]: r for r in records}
    offs = [e["ts_us"] * 1e-6 - by_id[e["trace_id"]]["t_submit"] for e in events
            if e["name"] == "submit" and e.get("trace_id") in by_id]
    offset = float(np.median(offs)) if offs else 0.0
    observed = {
        "window": (t0, t1), "window_s": t1 - t0,
        "timeline": [(e["ts_us"] * 1e-6 - offset, e["name"], e.get("trace_id"),
                      e.get("attrs") or {}) for e in events
                     if e.get("trace_id") in by_id],
        "requests": [{"trace_id": r["trace_id"], "t_submit": r["t_submit"],
                      "n_prompt": r["n_prompt"], "max_new": r["max_new"]}
                     for r in records],
        "steps": count1["steps"] - count0["steps"],
        "tokens_delivered": count1["tokens"] - count0["tokens"],
        "compiles_in_window": (count1["misses"] - count0["misses"])
        + (count1["prefills"] - count0["prefills"]),
        "prefill_chunk": eng.prefill_chunk_len,
        "memory_peak_bytes": ctx.memory_peak_bytes,
        "ttft_n": len(ttft), "gaps_n": len(gaps), **tails}
    ctx.log(f"{len(in_window)} requests submitted in the window, "
            f"{tokens_in_window} tokens; tails {tails} over {len(ttft)} first "
            f"tokens and {len(gaps)} gaps; peak "
            f"{ctx.memory_peak_bytes / 1e9:.2f} GB")

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
    last = eng.n_layers - 1
    held_in = {str(p.dtype) for name in ("S", "z") for p in eng.kvs[name]}
    if held_in != {sizes["state_dtype"]}:
        faults.append(f"the engine keeps its state in {sorted(held_in)}, the "
                      f"configuration states {sizes['state_dtype']}")
    chunk = eng.prefill_chunk_len
    slot = ctx.seed % eng.max_slots
    which, state, replay_same = None, None, None
    if drained and sequences:
        t_rep = time.perf_counter()
        which = min(range(len(sequences)),
                    key=lambda i: (len(sequences[i]) - n_prompt[i], i))
        S_all, replay_same = served_state(eng, sequences[which], n_prompt[which],
                                          slot=slot)
        state = {li: (S_all[li], np.asarray(eng.kvs["z"][li][slot]))
                 for li in S_all}
        # a slot beside it holds the state of a request the window served:
        # what a read of the wrong slot would compare
        other = (slot + 1) % eng.max_slots
        neighbour = tuple(np.asarray(eng.kvs[n][last][other])
                          for n in ("S", "z"))
        ctx.log(f"states of layers {sorted(state)} read for a request of "
                f"{n_prompt[which]} + {len(sequences[which]) - n_prompt[which]} "
                f"tokens served again in {time.perf_counter() - t_rep:.1f} s; "
                f"{replay_same:.4f} of its tokens as served")

    # -- free the program, then the reference --------------------------------
    # (but the last layer's state pools, which the probe below runs in)
    probe_pools = (eng.kvs["S"][last], eng.kvs["z"][last])
    for leaf in ctx.jax.tree.leaves((eng.params, eng.kvs)):
        if not any(leaf is p for p in probe_pools):
            leaf.delete()
    del srv, eng, clients, warm
    for r in records:
        r["req"] = None
    gc.collect()
    ctx.log(f"program freed, device holds {ctx.sample_memory() / 1e9:.2f} GB")
    compared = {"served_logit_gap": None, "state_gap": None,
                "recurrence_gap": None}
    if sequences:
        import jax.numpy as jnp
        t_ref = time.perf_counter()
        rows = reference.served_logit_gaps(
            cfg, seed, sequences, n_prompt,
            out_pad=int(mix["output_len"]["max"]), dtype=jnp.dtype(dtype_name),
            control="fp8" if ctx.control == "fp8" else None,
            state_of=None if state is None
            else (which, len(sequences[which]) - 1))
        compared["served_logit_gap"] = float(max(r["gap"].max() for r in rows))
        by_layer, readings = {}, {"replay_tokens_same": replay_same}
        if state is not None:
            want = rows[which]["state"]
            by_layer = {li: reference.state_gap(state[li], want[li])
                        for li in want}
            compared["state_gap"] = by_layer[last]
            t_probe = time.perf_counter()
            probe_rows = rows[which].pop("rows")
            shapes = [tuple(p.shape) for p in probe_pools]
            got, others_same, pools = recurrence_probe(
                probe_rows, n_prompt[which], probe_pools, slot, chunk)
            compared["recurrence_gap"] = reference.state_gap(got, want[last])
            if not others_same:
                faults.append("the recurrence of one slot changed another "
                              "slot's state in the engine's pools")
            # the faults `state_gap` is there to catch, planted at this size:
            # another slot's state, another layout of the rows, and the same
            # calls served into the neighbouring slot with no reset, so that
            # the request goes on from the state another request left there
            no_reset, _, _ = recurrence_probe(
                probe_rows, n_prompt[which], pools, other, chunk, fresh=False)
            readings["planted_state_gap"] = {
                "other_slot": reference.state_gap(neighbour, want[last]),
                "layout": reference.state_gap(
                    layout_swapped(state[last], cfg["head_dim"]), want[last]),
                "not_reset": reference.state_gap(no_reset, want[last])}
            ctx.log(f"the serving calls fed the reference's own rows in the "
                    f"engine's pools {shapes}, slot {slot}: recurrence_gap "
                    f"{compared['recurrence_gap']:.3e} in "
                    f"{time.perf_counter() - t_probe:.1f} s")
            readings.update(state_gap_by_layer=by_layer, state_request=[
                n_prompt[which], len(sequences[which]) - n_prompt[which]])
            if ctx.control == "state_bf16":
                # the same probe, the same rows, the same shapes: the pools one
                # precision below the one the configuration states
                low, _, _ = recurrence_probe(
                    probe_rows, n_prompt[which],
                    tuple(jnp.zeros(s, jnp.bfloat16) for s in shapes), slot,
                    chunk)
                readings["control"] = {
                    "recurrence_gap": reference.state_gap(low, want[last])}
        if ctx.control == "fp8":
            readings["control"] = {"served_logit_gap": float(
                max(r["control_gap"].max() for r in rows))}
        observed["readings"] = readings
        n_tok = sum(len(r["gap"]) for r in rows)
        exact = sum(int((r["gap"] == 0).sum()) for r in rows)
        ctx.log(f"reference: {time.perf_counter() - t_ref:.1f} s over "
                f"{len(rows)} requests (prompts {n_prompt}), {n_tok} served "
                f"tokens, {exact} are its own first choice; compared {compared}; "
                f"readings {readings}")
    else:
        faults.append("the window finished no request to compare")
    return {"attempted": len(in_window), "failed": failed, "faults": faults,
            "compared": compared, "end_to_end": end_to_end, "observed": observed,
            "counts": {"requests": len(in_window), "tokens": tokens_in_window,
                       "steps": observed["steps"],
                       "compiles_in_window": observed["compiles_in_window"],
                       "sampled_requests": len(sequences),
                       "state_read": int(state is not None)}}
