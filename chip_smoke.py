"""Chip smoke: the trainer and the paged server, once, on the TPU.

    python chip_smoke.py               # the real thing; refuses anything but a TPU
    python chip_smoke.py --rehearsal   # tiny sizes on any platform, every line labelled

Drives the package's two main paths through the entry points a user
calls, at the Llama-2-7B widths (hidden 4096, intermediate 11008, 32
heads of 128, vocab 32000), depth cut to 4 layers, bf16, random seeded
weights:

1. trainer — LlamaForCausalLM + LlamaPretrainingCriterion + AdamW
   through DistTrainStep, batch 4 x 2048, two compiling steps + 5 more.
   With >= 4 devices the same trainer runs at 12 layers (too big for one
   chip) through shard_llama(fsdp) on a 4-device mesh.
2. kernels against the repo's own references, on the chip, at the shapes
   the two paths use (flash forward + gradients vs _sdpa_xla; the paged
   kernel vs paged_attention(use_kernel=False)).
3. server — PagedLlamaDecodeEngine(max_slots=8, max_seq=1024) behind
   GenerationServer, 12 requests of several prompt lengths fed through
   submit, 32-64 new tokens each; two served streams are checked token
   by token against the model's own forward pass.

Any failed check raises, so the exit code is non-zero and no result line
is printed. One process holds the chip for the whole run; nothing is
spawned. The last stdout line of a successful run is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``. Wall times,
bytes and cache counts printed on the way are smoke output, not metrics.
"""
from __future__ import annotations

import gc
import json
import re
import sys
import time

REHEARSAL = "--rehearsal" in sys.argv[1:]
_TAG = "REHEARSAL " if REHEARSAL else ""

# -- stated tolerances (bf16 paths against f32 references) -------------------
# Max abs error over the tensor, normalised by the reference's max abs
# value. bf16 keeps 8 significant bits (2^-8 = 0.4%); the kernels round
# the softmax probabilities to bf16 before the PV matmul and the outputs
# once more, and gradients pass through two such matmuls.
# Set at about 3x what the first run on a v5e showed (flash out 3.0e-3,
# dq 4.7e-3, dk 3.5e-3, dv 2.5e-3; paged 3.2e-3 and 2.5e-3).
TOL_FLASH_FWD = 0.01
TOL_FLASH_GRAD = 0.015
TOL_PAGED = 0.01
# Served streams vs the model's own forward: the logit of every served
# token must be within this many standard deviations (of that position's
# logits) of the forward pass's best logit. A wrong token sits ~4 sigma
# down (max of 32000 draws); bf16 noise through 4 layers moved one of 92
# checked tokens off the argmax, by 0.016 sigma, on the first v5e run.
TOL_STREAM_SIGMA = 0.05


def say(msg: str) -> None:
    print(f"{_TAG}{msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"{_TAG}chip_smoke FAILED: {msg}")


class phase:
    """Wall time of one named stretch, printed when it ends."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        self.seconds = time.perf_counter() - self.t0
        if exc_type is None:
            say(f"[time] {self.name}: {self.seconds:.1f} s")
        return False


def mosaic_calls(compiled) -> list:
    """op_name of every Mosaic (Pallas TPU) custom call in a compiled
    program's text, with its first operand's shape."""
    out = []
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="([^"]*)"', line)
        operand = re.search(r"operand_layout_constraints=\{([^}]*\})", line)
        out.append((name.group(1) if name else "?",
                    operand.group(1).split("{")[0] if operand else "?"))
    return out


def norm_err(got, ref) -> float:
    import jax.numpy as jnp
    got = got.astype(jnp.float32)
    ref = ref.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


# ---------------------------------------------------------------------------
# preflight: the device, before any model is built
# ---------------------------------------------------------------------------

def preflight():
    import importlib.metadata as md

    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = "absent"
    say(f"device: platform={device['platform']} "
        f"device_kind={device['kind']} count={device['count']}")
    say(f"versions: {versions}")
    if not REHEARSAL and device["platform"] != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); this script runs on the chip "
              f"only. A CPU dry run is `--rehearsal`.", file=sys.stderr)
        raise SystemExit(2)

    import paddle_tpu  # noqa: F401  (configures the compile cache)
    from paddle_tpu import _native
    from paddle_tpu.jit import warmup
    check(_native.lib is not None,
          "paddle_tpu._native.lib is None: the native runtime did not "
          "build or load from this tree")
    say(f"native runtime: {_native.lib.__file__}")
    say(f"compile cache dir: {warmup.ensure_executable_cache()}")
    return device


def path_counts() -> dict:
    """pallas.path_selected_total as {(kernel, path): n}."""
    from paddle_tpu.observability import metrics as om
    c = om.default_registry().get("pallas.path_selected_total")
    return {(dict(k).get("kernel"), dict(k).get("path")): int(v)
            for k, v in (c.series() if c is not None else {}).items()}


def cache_line(where: str) -> None:
    from paddle_tpu.jit import warmup
    say(f"[cache] after {where}: {warmup.cache_stats()}")


def device_bytes(devices) -> list:
    import paddle_tpu as paddle
    out = []
    for d in devices:
        st = paddle.device.memory_stats(d)
        out.append({"in_use": st["allocated.current"],
                    "peak": st["allocated.peak"],
                    "source": "pjrt" if st["pjrt"] else "tracker"})
    return out


# ---------------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------------

def llama_config(layers: int, dtype: str = "float32"):
    from paddle_tpu.models import LlamaConfig
    if REHEARSAL:
        return LlamaConfig.tiny(num_hidden_layers=min(layers, 2),
                                max_position_embeddings=256, dtype=dtype)
    return LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=layers, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=2048, dtype=dtype)


def build_model(cfg, seed: int):
    """Seeded LlamaForCausalLM whose parameters are bf16 from the start:
    an f32 build of the 12-layer model would not fit the one device every
    parameter is born on before shard_llama spreads it."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM
    paddle.seed(seed)
    paddle.set_default_dtype("bfloat16")
    try:
        return LlamaForCausalLM(cfg)
    finally:
        paddle.set_default_dtype("float32")


def run_trainer(device) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.dist_train import DistTrainStep
    from paddle_tpu.models import LlamaPretrainingCriterion, shard_llama

    four = device["count"] >= 4
    layers = 12 if four else 4
    batch, seq, steps = (4, 32, 5) if REHEARSAL else (4, 2048, 5)
    cfg = llama_config(layers)
    say(f"[trainer] {'4-device fsdp' if four else '1-device'}: "
        f"{layers} layers, hidden {cfg.hidden_size}, batch {batch} x {seq}")

    with phase("trainer: build model + optimizer"):
        model = build_model(cfg, seed=0)
        data_sharding = None
        devices = jax.devices()[:4] if four else jax.devices()[:1]
        if four:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from paddle_tpu.distributed import ProcessMesh
            # ProcessMesh.to_jax_mesh indexes jax.devices() by id
            mesh = ProcessMesh(np.arange(4), dim_names=["fsdp"])
            shard_llama(model, mesh, tp_axis=None, fsdp_axis="fsdp")
            data_sharding = NamedSharding(mesh.to_jax_mesh(),
                                          P("fsdp", None))
        params = list(model.parameters())
        check(all(str(p.dtype) == "bfloat16" for p in params),
              "model parameters are not all bfloat16")
        n_params = sum(int(np.prod(p.shape)) for p in params)
        param_bytes = 2 * n_params
        opt = paddle.optimizer.AdamW(learning_rate=3e-4, parameters=params,
                                     multi_precision=False)
        crit = LlamaPretrainingCriterion()
        step = DistTrainStep(model, lambda lg, lb: crit(lg, lb), opt,
                             data_sharding=data_sharding)
    say(f"[trainer] parameters: {n_params} ({param_bytes / 1e9:.2f} GB bf16)")
    if four:
        for name, p in model.named_parameters():
            on = {s.device for s in p._data.addressable_shards}
            check(len(on) == 4,
                  f"parameter {name} lives on {len(on)} device(s), not 4")
        say("[trainer] every parameter's addressable_shards span 4 devices")

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                   (batch, seq)).astype(np.int32))
    losses = []
    with jax.default_matmul_precision("bfloat16"):
        # the second call compiles too: its inputs are the first call's
        # donated outputs, which jit keys differently from fresh arrays
        with phase("trainer: 2 compiling steps"):
            losses.append(float(step(ids, ids)))
            losses.append(float(step(ids, ids)))
        with phase(f"trainer: {steps} more steps") as t_steps:
            for _ in range(steps):
                losses.append(float(step(ids, ids)))
        say(f"[trainer] step wall time {t_steps.seconds / steps * 1e3:.0f} ms"
            f" (smoke output, each step closed by a loss fetch)")
        say(f"[trainer] losses: {[round(x, 4) for x in losses]}")
        check(all(np.isfinite(x) for x in losses), f"loss not finite: {losses}")
        check(losses[-1] < losses[0],
              f"loss did not fall: {losses[0]} -> {losses[-1]}")
        check(step.stats["compiles"] == 1 and not step.stats["fallbacks"],
              f"train step did not run as one captured program: {step.stats}")

        mem = device_bytes(devices)
        for i, m in enumerate(mem):
            say(f"[trainer] device {i}: bytes_in_use {m['in_use']} "
                f"peak {m['peak']} ({m['source']})")
        share = param_bytes // len(devices)
        check(all(m["peak"] >= share for m in mem),
              f"peak device bytes below the parameters' own {share} bytes: "
              f"{mem}")
        if not REHEARSAL:
            check(all(m["source"] == "pjrt" for m in mem),
                  "memory_stats did not come from PJRT on the TPU")
        if four:
            use = [m["in_use"] for m in mem]
            check(max(use) <= 1.5 * min(use),
                  f"per-device bytes_in_use not within 1.5x: {use}")

        say(f"[trainer] device 0 raw PJRT stats: "
            f"{paddle.device.memory_stats(devices[0])['pjrt']}")
        with phase("trainer: AOT compile of the same step for its text"):
            xla_mem, compiled, _ = step.compile_stats(
                ids, ids, return_compiled=True)
        say(f"[trainer] XLA's analysis of the step, per device: arguments "
            f"{xla_mem.argument_size_in_bytes} temporaries "
            f"{xla_mem.temp_size_in_bytes} bytes")
    calls = mosaic_calls(compiled)
    fwd = [c for c in calls if "_flash_fwd_pallas" in c[0]]
    bwd = [c for c in calls if "_flash_bwd_pallas" in c[0]]
    say(f"[trainer] Mosaic custom calls in the compiled step: {len(calls)} "
        f"(flash forward {len(fwd)}, flash backward {len(bwd)}; "
        f"operand {fwd[0][1] if fwd else '-'})")
    text = compiled.as_text()
    n_coll = {op: len(re.findall(r" %s(?:-start)?\(" % op, text))
              for op in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")}
    say(f"[trainer] collectives in the compiled step: {n_coll}; planned "
        f"gradient buckets {len(step.bucket_plan())}")
    if not REHEARSAL:
        # one forward kernel and two backward kernels (dq; dk+dv) a layer
        check(len(fwd) >= layers and len(bwd) >= 2 * layers,
              f"flash kernels missing from the compiled train step: "
              f"{len(fwd)} forward, {len(bwd)} backward, {layers} layers")
        pc = path_counts()
        check(pc.get(("flash_attention", "pallas"), 0) > 0
              and pc.get(("flash_attention", "xla"), 0) == 0,
              f"flash attention path counters disagree: {pc}")
    cache_line("trainer")


# ---------------------------------------------------------------------------
# phase 2: each kernel on the path against the repo's own reference
# ---------------------------------------------------------------------------

def run_kernel_parity() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu import serving_cache as sc
    from paddle_tpu.ops.pallas.flash_attention import (_sdpa_xla,
                                                       flash_attention)

    # flash attention at the trainer's shape. The f32 reference
    # materialises [H, L, L] logits, so it runs one batch row at a time
    b, l, h, d = (2, 128, 4, 16) if REHEARSAL else (4, 2048, 32, 128)
    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v, g = (jax.random.normal(kk, (b, l, h, d), jnp.bfloat16)
                  for kk in ks)

    @jax.jit
    def flash(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, True, None), q, k, v)
        return (out,) + vjp(g)

    @jax.jit
    def reference(q, k, v, g):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                lambda q, k, v: _sdpa_xla(q, k, v, causal=True), q, k, v)
            return (out,) + vjp(g)

    with phase("kernels: flash forward + backward vs _sdpa_xla"):
        got = flash(q, k, v, g)
        f32 = [x.astype(jnp.float32) for x in (q, k, v, g)]
        rows = [reference(*(x[i:i + 1] for x in f32)) for i in range(b)]
        ref = [jnp.concatenate([r[j] for r in rows]) for j in range(4)]
        errs = {n: norm_err(a, r)
                for n, a, r in zip(("out", "dq", "dk", "dv"), got, ref)}
    say(f"[kernels] flash (b{b},l{l},h{h},d{d}) bf16 causal, normalised max "
        f"error vs f32 _sdpa_xla: " + ", ".join(
            f"{n} {e:.2e}" for n, e in errs.items()))
    check(all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in got),
          "flash attention produced non-finite values")
    check(errs["out"] <= TOL_FLASH_FWD,
          f"flash forward error {errs['out']:.3e} > {TOL_FLASH_FWD}")
    for n in ("dq", "dk", "dv"):
        check(errs[n] <= TOL_FLASH_GRAD,
              f"flash {n} error {errs[n]:.3e} > {TOL_FLASH_GRAD}")

    # paged attention at the server's shapes: the decode step (every
    # slot, one token) and a full prefill chunk (one slot, 64 tokens)
    if REHEARSAL:
        S, H, K, D, bs, MB = 4, 4, 2, 16, 16, 8
    else:
        S, H, K, D, bs, MB = 8, 32, 32, 128, 16, 64
    NB = S * MB
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.standard_normal((NB, bs, K * D)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((NB, bs, K * D)), jnp.bfloat16)
    tables = rng.permutation(NB).reshape(S, MB).astype(np.int32)
    use_kernel = sc.use_kernel_default(D)
    check(use_kernel or REHEARSAL,
          "the paged seam did not choose the Pallas kernel on the TPU")
    for name, s, t in (("decode", S, 1), ("prefill chunk", 1, 64)):
        qq = jnp.asarray(rng.standard_normal((s, t, H, D)), jnp.bfloat16)
        # histories of different lengths, the last one nearly full
        last = rng.integers(t, bs * MB, (s,))
        last[-1] = bs * MB - 1
        pos = (last[:, None] - (t - 1)
               + np.arange(t)[None, :]).astype(np.int32)
        kw = dict(block_size=bs, n_rep=H // K,
                  n_tiles=int(pos.max()) // bs + 1)
        args = (kp, vp, jnp.asarray(tables[:s]), jnp.asarray(pos))
        with phase(f"kernels: paged attention ({name}) vs the jnp walk"):
            got = jax.jit(lambda q, *a: sc.paged_attention(
                q, *a, use_kernel=use_kernel, **kw))(qq, *args)
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda q, *a: sc.paged_attention(
                    q, *a, use_kernel=False, **kw))(
                        qq.astype(jnp.float32),
                        kp.astype(jnp.float32), vp.astype(jnp.float32),
                        *args[2:])
            err = norm_err(got, ref)
        say(f"[kernels] paged attention {name} (S{s},T{t},H{H},KVH{K},"
            f"D{D},bs{bs}) {'kernel' if use_kernel else 'jnp walk'}: "
            f"normalised max error vs f32 walk {err:.2e}")
        check(bool(jnp.isfinite(got.astype(jnp.float32)).all()),
              f"paged attention ({name}) produced non-finite values")
        check(err <= TOL_PAGED,
              f"paged attention ({name}) error {err:.3e} > {TOL_PAGED}")
    cache_line("kernel parity")


# ---------------------------------------------------------------------------
# phase 3: the paged server
# ---------------------------------------------------------------------------

def run_server() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.serving import GenerationServer, PagedLlamaDecodeEngine

    cfg = llama_config(4, dtype="bfloat16")
    slots, max_seq = (4, 256) if REHEARSAL else (8, 1024)
    # several prefill chunks of FLAGS_serving_prefill_chunk = 64, partial
    # last chunks of every bucket, more requests than slots
    prompt_lens = [5, 17, 40, 64, 70, 100, 130, 150, 200, 33, 9, 300]
    new_tokens = [32, 48, 64, 40, 56, 32, 64, 48, 36, 60, 44, 52]
    if REHEARSAL:
        prompt_lens = [min(n, 150) for n in prompt_lens]
        new_tokens = [n // 4 for n in new_tokens]

    paths_before = path_counts()
    with phase("server: build model + engine"):
        model = build_model(cfg, seed=1)
        eng = PagedLlamaDecodeEngine(model, max_slots=slots,
                                     max_seq=max_seq)
        srv = GenerationServer(eng)
    check(eng.prefill_chunk_len == 64, "prefill chunk is not 64")
    check(eng._pa_kernel or REHEARSAL,
          "the engine did not choose the Pallas paged-attention kernel")

    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prompt_lens]
    with phase(f"server: {len(prompts)} requests (compiles included)") as t:
        reqs = [srv.submit(p, n) for p, n in zip(prompts, new_tokens)]
        for i, r in enumerate(reqs):
            check(r["done"].wait(900), f"request {i} did not finish")
    for i, (r, n) in enumerate(zip(reqs, new_tokens)):
        check(r["error"] is None, f"request {i} failed: {r['error']!r}")
        check(len(r["out"]) == n,
              f"request {i}: {len(r['out'])} tokens, asked for {n}")
        check(all(0 <= int(tk) < cfg.vocab_size for tk in r["out"]),
              f"request {i}: token id outside the vocabulary")
    check(srv.shutdown(drain=True, timeout=300), "server did not drain")
    st = srv.stats()
    served = sum(new_tokens)
    say(f"[server] stats: {st}")
    for key in ("rejected", "shed", "deadline_rejected", "deadline_expired",
                "crashed", "quarantined", "loop_restarts", "in_flight",
                "queued"):
        check(st[key] == 0, f"server stats[{key!r}] = {st[key]}")
    check(st["admitted"] == len(prompts) and st["drained"] == 1,
          f"server did not admit and drain every request: {st}")
    check(st["kv_pool"]["blocks_used"] == 0, "KV blocks leaked")
    say(f"[server] served {len(prompts)} requests, {served} tokens, "
        f"{st['steps_run']} decode steps; {served / t.seconds:.0f} tokens/s "
        f"wall with compiles inside (smoke output)")
    check(sorted(eng._prefills) == [8, 16, 32, 64],
          f"prefill buckets exercised: {sorted(eng._prefills)}")

    # the compiled decode and prefill programs carry the paged kernel
    S = eng.max_slots
    tables = jnp.asarray(eng._kv.block_tables)
    with phase("server: AOT compile of decode + prefill for their text"):
        progs = {"decode": eng._decode._jitted.lower(
            eng.params, eng.kvs, jnp.zeros((S, 1), jnp.int32),
            jnp.zeros((S,), jnp.int32), tables,
            jnp.zeros((S,), bool)).compile()}
        i32 = jax.ShapeDtypeStruct((), np.int32)
        for b, fn in sorted(eng._prefills.items()):
            progs[f"prefill[{b}]"] = fn._jitted.lower(
                eng.params, eng.kvs, jnp.zeros((1, b), jnp.int32),
                tables[0], i32, i32, i32).compile()
    for name, prog in progs.items():
        paged = [c for c in mosaic_calls(prog)
                 if "_paged_attention_call" in c[0]]
        say(f"[server] {name}: {len(paged)} paged-attention Mosaic calls")
        check(REHEARSAL or len(paged) >= cfg.num_hidden_layers,
              f"paged kernel missing from compiled {name}: {len(paged)}")
    if not REHEARSAL:
        pc = path_counts()
        walk, kern = (("paged_attention", "jnp_walk"),
                      ("paged_attention", "pallas"))
        check(pc.get(walk, 0) == paths_before.get(walk, 0)
              and pc.get(kern, 0) > paths_before.get(kern, 0),
              f"a serving program took the jnp walk: {paths_before} -> {pc}")

    # two served streams against the model's own forward pass, teacher
    # forced: one multi-chunk prompt with a partial last chunk, one short
    with phase("server: 2 streams vs the model's forward pass"):
        worst = 0.0
        for i in (7, 10):
            full = np.concatenate([prompts[i], np.asarray(reqs[i]["out"],
                                                          np.int32)])
            logits = model(Tensor(jnp.asarray(full[None, :-1])))._data[0]
            logits = logits[len(prompts[i]) - 1:].astype(jnp.float32)
            took = jnp.take_along_axis(
                logits, jnp.asarray(reqs[i]["out"])[:, None], axis=1)[:, 0]
            gap = (jnp.max(logits, axis=1) - took) / jnp.std(logits, axis=1)
            worst = max(worst, float(jnp.max(gap)))
            say(f"[server] request {i} (prompt {len(prompts[i])}, "
                f"{len(reqs[i]['out'])} tokens): served tokens sit at most "
                f"{float(jnp.max(gap)):.3f} sigma below the forward pass's "
                f"best logit; {int(jnp.sum(gap == 0))} are its argmax")
        check(worst <= TOL_STREAM_SIGMA,
              f"a served token is {worst:.3f} sigma below the model's best "
              f"logit (> {TOL_STREAM_SIGMA})")
    cache_line("server")
    return served


def main() -> int:
    t0 = time.perf_counter()
    device = preflight()
    run_trainer(device)
    gc.collect()  # the trainer's parameters and moments go before the rest
    import jax
    say(f"[memory] after the trainer is dropped: "
        f"{[m['in_use'] for m in device_bytes(jax.devices())]}")
    run_kernel_parity()
    run_server()
    say(f"[time] whole run: {time.perf_counter() - t0:.1f} s")
    result = {"ok": True, "device": device}
    if REHEARSAL:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
