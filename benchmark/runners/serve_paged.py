"""Role runner `serve_paged`: a configuration in `PagedLlamaDecodeEngine`
behind `GenerationServer.submit`, under a closed loop of clients, on one chip.

One thread is every client and the clock: each millisecond it looks at the
requests in flight, stamps the tokens that have arrived since (a caller of
this API sees tokens by reading `req["out"]`), and sends a client's next
request when its last is done. So time to first token and token gaps are the
host's clock as a caller would read it, not the program's own record. The
program's flight events give the per-layer waits and counts.

Set-up builds the model and the engine, warms decode and every prefill bucket,
then runs the closed loop for `ramp_seconds` so that the window opens on a
full, mixed batch and not on 32 prompts queued at once. When the window has
closed the loop drains, the peak memory is read, the server and engine are
freed, and the reference (`lib.reference.served_logit_gaps`) reads a seeded
sample of the requests the window finished, the longest among them.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import reference, traffic, weights
from benchmark.runners import _llama

POLL_S = 0.001
SAMPLE_REQUESTS = 8       # requests the reference reads, the longest included
FLIGHT_CAPACITY = 1 << 20  # the ring must hold the window (default 4096)
# one prompt per prefill bucket (8, 16, 32, 64), one of several chunks
WARM_PROMPTS = (5, 12, 24, 40, 150)


def _sizes(ctx):
    cfg, mix = dict(ctx.config), dict(ctx.traffic)
    if ctx.rehearsal:
        cfg.update(_llama.TINY, max_position_embeddings=128,
                   serve={"max_slots": 4, "max_seq": 128})
        mix.update(clients=4, ramp_seconds=0.5, pool=16,
                   prompt_len=dict(mix["prompt_len"], median=24, min=4, max=80),
                   output_len=dict(mix["output_len"], median=16, min=4, max=32))
    return cfg, mix


class _Clients:
    """The closed loop: `n` clients over one stream of requests."""

    def __init__(self, srv, requests, n, sample):
        self.srv, self.requests, self.n = srv, requests, n
        self.sample = sample
        self.live = [None] * n
        self.records = []

    def _send(self, i):
        r = next(self.requests)
        t = time.perf_counter()
        req = self.srv.submit(r["prompt"], r["max_new"])
        rec = {"req": req, "t_submit": t, "n_prompt": int(r["prompt"].shape[0]),
               "max_new": r["max_new"], "t_tokens": [], "t_done": None,
               "trace_id": req["trace_id"]}
        self.live[i] = rec
        self.records.append(rec)

    def start(self):
        for i in range(self.n):
            self._send(i)

    def poll(self, resend: bool) -> int:
        """One look at every client; returns how many are still in flight."""
        now = time.perf_counter()
        busy = 0
        for i, rec in enumerate(self.live):
            if rec is None:
                continue
            done = rec["req"]["done"].is_set()     # read before the length
            n = len(rec["req"]["out"])
            if n > len(rec["t_tokens"]):
                rec["t_tokens"].extend([now] * (n - len(rec["t_tokens"])))
            if done:
                rec["t_done"] = now
                self.live[i] = None
                if resend:
                    self._send(i)
                    busy += 1
            else:
                busy += 1
        return busy

    def run_until(self, t_end, resend=True):
        last_sample = 0.0
        while True:
            busy = self.poll(resend)
            now = time.perf_counter()
            if now - last_sample > 0.25:
                self.sample()
                last_sample = now
            if (t_end is not None and now >= t_end) or (not resend and busy == 0):
                return
            time.sleep(POLL_S)


def _p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95)) if len(values) else None


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
    paths0 = _llama.path_counts()
    model = _llama.build_model(cfg, seed, dtype_name)
    ctx.log(f"model built, device holds {ctx.sample_memory() / 1e9:.2f} GB")
    eng = PagedLlamaDecodeEngine(model, max_slots=sizes["max_slots"],
                                 max_seq=sizes["max_seq"])
    ctx.sample_memory()
    del model
    gc.collect()
    srv = GenerationServer(eng)
    ctx.log(f"engine built ({eng.num_blocks} blocks of {eng.block_size}, chunk "
            f"{eng.prefill_chunk_len}); peak so far {ctx.memory_peak_bytes / 1e9:.2f} GB")

    rng = traffic.rng_for(ctx.seed, 3)
    warm = [srv.submit(rng.integers(0, vocab, n, dtype=np.int32), 4)
            for n in WARM_PROMPTS if n < sizes["max_seq"] - 8]
    for r in warm:
        if not r["done"].wait(1100) or r["error"] is not None:
            raise RuntimeError(f"warm-up request failed: {r['error']!r}")
    ctx.log(f"warm: prefill buckets {sorted(eng._prefills)}, cache {warmup.cache_stats()}")

    reqs = traffic.requests(mix, vocab, ctx.seed)
    clients = _Clients(srv, reqs, int(mix["clients"]), ctx.sample_memory)
    clients.start()
    clients.run_until(time.perf_counter() + float(mix["ramp_seconds"]))

    # -- the window ----------------------------------------------------------
    if ctx.trace:
        ctx.trace_start()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    count0 = {"steps": srv.steps_run, "tokens": srv.tokens_delivered,
              "misses": warmup.cache_stats()["misses"], "prefills": len(eng._prefills)}
    clients.run_until(t0 + ctx.window_seconds)
    t1 = time.perf_counter()
    count1 = {"steps": srv.steps_run, "tokens": srv.tokens_delivered,
              "misses": warmup.cache_stats()["misses"], "prefills": len(eng._prefills)}
    if ctx.trace:
        ctx.trace_stop()
    clients.run_until(t1 + 120.0, resend=False)         # late is late, not lost
    ctx.sample_memory()
    drained = srv.shutdown(drain=True, timeout=120)
    stats = srv.stats()
    paths1 = _llama.path_counts()
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
    walk, kern = "paged_attention:jnp_walk", "paged_attention:pallas"
    if not ctx.rehearsal and (paths1.get(walk, 0) != paths0.get(walk, 0)
                              or paths1.get(kern, 0) <= paths0.get(kern, 0)):
        faults.append(f"a serving program left the Pallas paged kernel: {paths0} -> {paths1}")

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
    sample = pick_sample(finished, ctx.seed)
    sequences = [np.concatenate([np.asarray(r["req"]["prompt"], np.int32),
                                 np.asarray(r["req"]["out"], np.int32)])
                 for r in sample]
    n_prompt = [r["n_prompt"] for r in sample]
    del srv, eng, clients, warm
    for r in records:
        r["req"] = None
    gc.collect()
    compared = {"served_logit_gap": None}
    if sequences:
        import jax.numpy as jnp
        t_ref = time.perf_counter()
        rows = reference.served_logit_gaps(
            cfg, seed, sequences, n_prompt, pad_to=sizes["max_seq"],
            out_pad=int(mix["output_len"]["max"]), dtype=jnp.dtype(dtype_name),
            control=ctx.control)
        compared["served_logit_gap"] = float(max(r["gap"].max() for r in rows))
        if ctx.control:
            observed["readings"] = {"control": {"served_logit_gap": float(
                max(r["control_gap"].max() for r in rows))}}
        n_tok = sum(len(r["gap"]) for r in rows)
        exact = sum(int((r["gap"] == 0).sum()) for r in rows)
        ctx.log(f"reference: {time.perf_counter() - t_ref:.1f} s over "
                f"{len(rows)} requests, {n_tok} served tokens, {exact} are its "
                f"own first choice")
    else:
        faults.append("the window finished no request to compare")
    return {"attempted": len(in_window), "failed": failed, "faults": faults,
            "compared": compared, "end_to_end": end_to_end, "observed": observed,
            "counts": {"requests": len(in_window), "tokens": tokens_in_window,
                       "steps": observed["steps"],
                       "compiles_in_window": observed["compiles_in_window"],
                       "sampled_requests": len(sequences)}}


def pick_sample(finished: list, seed: int) -> list:
    """The longest finished request and a seeded draw of the others."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (r["n_prompt"] + r["max_new"],
                                            r["trace_id"]))
    longest, rest = order[-1], order[:-1]
    rng = traffic.rng_for(seed, 2)
    take = min(SAMPLE_REQUESTS - 1, len(rest))
    idx = rng.choice(len(rest), size=take, replace=False) if take else []
    return [longest] + [rest[i] for i in sorted(idx)]
