"""Benchmarks for the five BASELINE.md workloads.

Default run = the FULL suite, one JSON line per BASELINE workload so the
driver artifact (BENCH_r*.json) captures every bar, not just the
headline. Line 1 is the headline: Llama causal-LM training
tokens/sec/chip — a ~1.17B-param Llama (Llama-2 geometry scaled to one
v5e chip's HBM) in bf16 with bf16 AdamW state through the compiled
whole-train-step path (DistTrainStep: fwd + bwd + optimizer in one XLA
executable, attention on the Pallas flash kernel). Then ResNet-50 img/s,
BERT-base static+fusion MFU, GPT-13B-geometry MFU, ERNIE-MoE dispatch.
``--headline-only`` runs just the Llama line.

MFU uses the standard 6*N_params FLOPs/token estimate, which EXCLUDES
attention score FLOPs (~12*L*h*s extra per token) — reported MFU is
therefore conservative by a few percent at long sequence.

vs_baseline: the reference publishes no numbers (BASELINE.md); for the
transformer workloads the agreed bar is "A100+NCCL MFU" ~0.45, so
vs_baseline = our_MFU / 0.45 with bf16 peak detected per chip. For
ResNet-50 the bar is the public A100 fp16 training rate (~2500 img/s).
For the MoE dispatch vs_baseline = measured useful-FLOPs MFU / 0.40
(absolute expert-FFN utilization bar; the dense one-hot dispatch
oracle's speedup stays in detail.dense_speedup). The dispatch
micro-bench's bar is the stated µs/op budget.

Prints ONE json line per workload:
{"metric", "value", "unit", "vs_baseline", "detail"}.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np


# chip peaks by device_kind substring. An unknown device is an error.
_PEAK_FLOPS = [  # bf16 FLOP/s
    ("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
    ("v4", 275e12), ("v6", 918e12), ("v3", 123e12), ("v2", 46e12),
]
_HBM_BW = [  # bytes/s (Google Cloud documentation, "TPU v5e")
    ("v5 lite", 819e9), ("v5e", 819e9),
]
_BASELINE_MFU = 0.45  # well-tuned A100 Llama pretraining MFU

# device stamp of the workloads that measure in CPU child processes
# whatever this process runs on (hot start, fleet failover)
_CPU_CHILDREN = {"platform": "cpu", "kind": "cpu child processes",
                 "count": 1}

# {"platform", "kind", "count"} of the devices this process measures
# on, as jax reports them; stamped on every metric line. Set by
# _init_device() in the processes that run workloads — the suite parent
# never imports jax (one process per chip) and leaves it None.
_DEVICE = None


def _init_device():
    """Bring the backend up and record what it is. A backend that does
    not initialise raises here, before any workload runs or any metric
    line is printed: there is no other platform to fall back to. The
    CPU smoke is reachable only by JAX_PLATFORMS=cpu from outside, and
    its lines then say ``platform: cpu``."""
    global _DEVICE
    import jax
    devs = jax.devices()
    _DEVICE = {"platform": devs[0].platform,
               "kind": devs[0].device_kind, "count": len(devs)}


def _peak(table, what):
    """The ``table`` entry for the device jax reports. A TPU that is not
    in the table is an error, never another chip's numbers. The CPU
    smoke has no peaks: an infinite one makes every utilization it
    prints 0."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return float("inf")
    kind = dev.device_kind.lower()
    for sub, peak in table:
        if sub in kind:
            return peak
    raise RuntimeError(
        f"no {what} on record for device_kind {dev.device_kind!r} "
        f"({dev.platform}); add it to bench.py with its source")


def _finite(x, ndigits):
    """JSON has no Infinity: the CPU smoke's rooflines print as null."""
    return round(x, ndigits) if x != float("inf") else None


def _peak_flops():
    return _peak(_PEAK_FLOPS, "peak bf16 FLOP/s")


def _hbm_bw():
    return _peak(_HBM_BW, "HBM bandwidth")


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


# Every metric line is ALSO appended to this driver-durable artifact:
# the driver captures only the stdout tail, which truncated round 4's
# eager-dispatch line (it must run first for µs fidelity but then
# scrolls off). A file survives regardless of emission order.
# (ref role: tools/check_op_benchmark_result.py — results as files.)
_ARTIFACT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "BENCH_ALL.json")


def _reset_artifact():
    try:
        with open(_ARTIFACT, "w"):
            pass
    except OSError:
        pass


def _emit(metric, value, unit, vs_baseline, detail, device=None):
    """``device`` overrides the process's own stamp for workloads that
    measure in child processes on another platform."""
    line = json.dumps({
        "metric": metric,
        "value": None if value is None else round(value, 2),
        "unit": unit, "vs_baseline": round(vs_baseline, 4),
        "device": device or _DEVICE,
        "detail": detail,
    })
    print(line, flush=True)
    try:
        with open(_ARTIFACT, "a") as f:
            f.write(line + "\n")
    except OSError:
        pass


def _hbm_detail(step, *args, **kw):
    """peak_hbm_bytes of the compiled train step (args + outputs + temps
    - donation aliases, from XLA's per-device memory analysis via
    TrainStep/DistTrainStep.compile_stats). Best-effort: an analysis
    failure must not kill a bench line.

    Cost note: the AOT lower().compile() here does NOT share the jit
    dispatch cache the timed warmup filled, so each workload pays a
    second XLA compile (outside the timed window). Accepted: the driver
    runs bench once per round and the memory-parity artifact is worth
    the extra minutes; driving the returned Compiled for the timed loop
    instead would bypass __call__'s donation/rng handling."""
    try:
        ma = step.compile_stats(*args, **kw)
        peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        return {"peak_hbm_bytes": int(peak),
                "hbm_temp_bytes": int(ma.temp_size_in_bytes)}
    except Exception as e:  # noqa: BLE001
        return {"peak_hbm_bytes": None,
                "hbm_error": f"{type(e).__name__}: {e}"[:120]}


def bench_llama():
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed.dist_train import DistTrainStep
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    on_tpu = _on_tpu()
    if on_tpu:
        # ~1.2B-param Llama geometry chosen to saturate one v5e chip's HBM
        # (AdamW fp32 state + bf16 params/grads + flash-attention
        # activations); wide layers keep the MXU fed
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=3584, intermediate_size=9728,
            num_hidden_layers=6, num_attention_heads=28,
            num_key_value_heads=28, max_position_embeddings=2048)
        batch, seq, steps = 4, 2048, 10
    else:  # CI smoke path
        cfg = LlamaConfig.tiny()
        batch, seq, steps = 2, 32, 2

    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    # multi_precision=False stores Adam moments in the param dtype (bf16),
    # the reference's own default for AdamW — halves optimizer-state HBM
    # traffic (+14% step time on v5e). bf16 keeps fp32's exponent range,
    # so the moments lose mantissa only, not range.
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters(),
                                 multi_precision=False)
    crit = LlamaPretrainingCriterion()
    step = DistTrainStep(model, lambda lg, lb: crit(lg, lb), opt)

    rng = np.random.default_rng(0)
    import jax.numpy as jnp
    # device-resident feed: per-step host->device uploads would time the
    # host link, not the chip
    ids = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    with jax.default_matmul_precision("bfloat16"):
        # compile + warmup with a full host sync (float(loss): the value
        # cannot arrive before the step that produces it has finished)
        float(step(ids, ids))
        float(step(ids, ids))
        # timed region: steps chain on-device (donated buffers); ONE final
        # loss fetch closes the timing — a fetch per step would put a
        # host round-trip between steps and stall the device queue
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(ids, ids)
        loss = float(loss)
        dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    flops_per_token = 6 * n_params  # standard fwd+bwd estimate
    mfu = tokens_per_sec * flops_per_token / _peak_flops()
    _emit("llama_train_tokens_per_sec_per_chip", tokens_per_sec,
          "tokens/s", mfu / _BASELINE_MFU, {
              "params": n_params, "batch": batch, "seq": seq,
              "mfu": round(mfu, 4), "loss": loss,
              "backend": jax.default_backend(),
              **_hbm_detail(step, ids, ids)})


def bench_llama7b_geometry():
    """BASELINE workload 3's north-star geometry: Llama-2 7B per-layer
    shapes EXACTLY (hidden 4096, intermediate 11008, 32 heads — ref:
    test/auto_parallel/hybrid_strategy/semi_auto_llama.py), depth-scaled
    to one chip's HBM like the GPT-13B row; the full-depth 7B ZeRO-3
    (fsdp) mesh program is validated by the dryrun '7b' regime
    (MULTICHIP json). MFU vs the 0.45 bar — per-layer compute is
    geometry-identical to 7B."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.dist_train import DistTrainStep
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)

    if _on_tpu():
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=4096, intermediate_size=11008,
            num_hidden_layers=4, num_attention_heads=32,
            num_key_value_heads=32, max_position_embeddings=2048)
        batch, seq, steps = 4, 2048, 8
    else:
        cfg = LlamaConfig.tiny(hidden_size=32, intermediate_size=88,
                               num_attention_heads=2,
                               num_key_value_heads=2)
        batch, seq, steps = 2, 16, 2
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=3e-4,
                                 parameters=model.parameters(),
                                 multi_precision=False)
    crit = LlamaPretrainingCriterion()
    step = DistTrainStep(model, lambda lg, lb: crit(lg, lb), opt)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                   (batch, seq)).astype(np.int32))
    with jax.default_matmul_precision("bfloat16"):
        float(step(ids, ids))
        float(step(ids, ids))
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step(ids, ids)
        loss = float(loss)
        dt = time.perf_counter() - t0
    tok = batch * seq * steps / dt
    mfu = tok * 6 * n_params / _peak_flops()
    _emit("llama7b_geometry_tokens_per_sec_per_chip", tok, "tokens/s",
          mfu / _BASELINE_MFU, {
              "params": n_params, "hidden": cfg.hidden_size,
              "intermediate": cfg.intermediate_size,
              "heads": cfg.num_attention_heads,
              "layers_on_chip": cfg.num_hidden_layers,
              "batch": batch, "seq": seq, "mfu": round(mfu, 4),
              "loss": round(loss, 4),
              "mesh_validated_by": "MULTICHIP dryrun '7b' (ZeRO-3 fsdp)",
              "backend": jax.default_backend(),
              **_hbm_detail(step, ids, ids)})


def bench_resnet50():
    """BASELINE workload 1: ResNet-50 training img/s, single chip.
    Bar: public A100 fp16 training ~2500 img/s."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.vision.models import resnet50

    baseline_imgs = 2500.0
    if _on_tpu():
        # 96 chained steps: a long chain amortizes the dispatch
        # pipeline fill and the one closing fetch over many steps
        batch, hw, steps = 128, 224, 96
    else:
        batch, hw, steps = 4, 32, 2
    paddle.seed(0)
    # NHWC end-to-end: TPU-native conv layout (channels in the 128-lane
    # minor dim; BN stats reduce over contiguous dims). Measured vs NCHW
    # on v5e: 1378 -> 2550 img/s together with the custom-VJP batch norm;
    # r5's running-mean-anchored ONE-PASS BN stats (fused into the conv
    # epilogue by XLA — the trace shows (f32[C], f32[C], conv) tuple
    # fusions) lifted 2538 -> 2649.
    model = resnet50(num_classes=1000, data_format="NHWC")
    model.bfloat16()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    crit = paddle.nn.CrossEntropyLoss()
    step = TrainStep(model, lambda out, y: crit(out, y), opt)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (batch, hw, hw, 3)).astype(np.float32) * 0.1, jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 1000, (batch,)).astype(np.int32))
    with jax.default_matmul_precision("bfloat16"):
        float(step(x, y))
        float(step(x, y))
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step(x, y)
        loss = float(loss)
        dt = time.perf_counter() - t0
    imgs = batch * steps / dt
    _emit("resnet50_train_imgs_per_sec", imgs, "imgs/s",
          imgs / baseline_imgs, {
              "batch": batch, "hw": hw, "loss": round(loss, 4),
              "baseline": "A100 fp16 ~2500 img/s",
              "backend": jax.default_backend(),
              **_hbm_detail(step, x, y)})


def bench_llama_decode():
    """Serving decode throughput (the r5 generation-serving path):
    fixed-slot continuous-batching engine, single-token steps advancing
    all slots, device-chained feedback. Decode streams the FULL weight
    set every step, so the honest bar is the weight-streaming roofline
    tokens/s = slots / (weight_bytes / HBM_BW); the bench grades
    against 50% of it (kernel + cache traffic take the rest)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import LlamaDecodeEngine

    if _on_tpu():
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=3584, intermediate_size=9728,
            num_hidden_layers=6, num_attention_heads=28,
            num_key_value_heads=28, max_position_embeddings=2048,
            dtype="bfloat16")
        slots, max_seq, steps = 8, 1024, 192
    else:
        cfg = LlamaConfig.tiny()
        cfg.dtype = "float32"
        slots, max_seq, steps = 2, 64, 4
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    eng = LlamaDecodeEngine(model, max_slots=slots, max_seq=max_seq)
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    weight_bytes = sum(
        int(np.prod(p.shape)) for p in model.parameters()) * itemsize
    # mandatory per-step HBM traffic: the full weight set + every
    # active slot's K/V history (read by the attention dots)
    cache_bytes = (cfg.num_hidden_layers * slots * max_seq *
                   cfg.num_key_value_heads *
                   (cfg.hidden_size // cfg.num_attention_heads) *
                   2 * itemsize)
    rng = np.random.default_rng(0)
    for s in range(slots):
        eng.prefill(s, rng.integers(0, cfg.vocab_size, (16,)))
    # warm with the SAME n as the timed call: decode_steps' token
    # buffer is [slots, n], so a different warm n would leave the
    # timed call to compile its own variant inside the window
    eng.decode_steps(steps)
    t0 = time.perf_counter()
    toks = eng.decode_steps(steps)
    dt = time.perf_counter() - t0
    tok_s = slots * steps / dt
    roofline = slots * _hbm_bw() / (weight_bytes + cache_bytes)
    _emit("llama_decode_tokens_per_sec", tok_s, "tokens/s",
          tok_s / (0.5 * roofline), {
              "slots": slots, "max_seq": max_seq, "steps": steps,
              "params_bytes": int(weight_bytes),
              "kv_cache_bytes": int(cache_bytes),
              "traffic_roofline_tok_s": _finite(roofline, 1),
              "baseline": "50% of the weights+KV-cache streaming "
                          "roofline",
              "sample_tokens": [int(t) for t in toks[0, :4]],
              "backend": jax.default_backend()})


def bench_llama_decode_paged():
    """Paged-KV decode throughput + concurrency at fixed HBM (ISSUE
    11). Same model/slots/max_seq geometry as the dense engine,
    measured back to back: the paged engine's tiled block-table
    attention walks only the ACTIVE history (max(pos)//block_size + 1
    tiles) while the dense step streams all max_seq columns, so paged
    must be >= dense tokens/s. The roofline denominator folds the
    paged cache term as O(active tokens), not O(slots x max_seq) —
    the bar the block pool exists to move. A second line,
    paged_kv_concurrency, admits requests into a pool sized to the
    dense engine's HBM budget until exhaustion: the acceptance is
    >= 2x the dense slot count."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (LlamaDecodeEngine,
                                    PagedLlamaDecodeEngine)

    if _on_tpu():
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=3584, intermediate_size=9728,
            num_hidden_layers=6, num_attention_heads=28,
            num_key_value_heads=28, max_position_embeddings=2048,
            dtype="bfloat16")
        slots, max_seq, steps, prompt_len = 8, 1024, 192, 64
    else:
        cfg = LlamaConfig.tiny()
        cfg.dtype = "float32"
        slots, max_seq, steps, prompt_len = 2, 512, 16, 16
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    itemsize = 2 if cfg.dtype == "bfloat16" else 4
    weight_bytes = sum(
        int(np.prod(p.shape)) for p in model.parameters()) * itemsize
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(slots)]

    def timed_window(eng, budget=None):
        """Best-of-3 decode windows (shared bench hosts are noisy;
        the structural gap — dense streams max_seq columns, paged
        only the active tiles — is what's being measured)."""
        for s in range(slots):
            kw = {} if budget is None else {"budget": budget}
            eng.prefill(s, prompts[s], **kw)
        eng.decode_steps(steps)            # warm: same window shape
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            toks = eng.decode_steps(steps)
            best = min(best, time.perf_counter() - t0)
        return slots * steps / best, toks

    dense_tok_s, _ = timed_window(
        LlamaDecodeEngine(model, max_slots=slots, max_seq=max_seq))
    paged = PagedLlamaDecodeEngine(model, max_slots=slots,
                                   max_seq=max_seq)
    paged_tok_s, toks = timed_window(paged, budget=4 * steps + 2)
    # mandatory per-step traffic with the block pool: weights + the
    # ACTIVE tokens' K/V (what the tiled walk actually streams), not
    # slots x max_seq rows
    active_tokens = paged._kv.active_tokens(paged.pos, paged.active)
    kv_active_bytes = (active_tokens * cfg.num_hidden_layers *
                       cfg.num_key_value_heads *
                       (cfg.hidden_size // cfg.num_attention_heads) *
                       2 * itemsize)
    roofline = slots * _hbm_bw() / (weight_bytes + kv_active_bytes)
    ratio = paged_tok_s / max(dense_tok_s, 1e-9)
    _emit("llama_decode_paged_tokens_per_sec", paged_tok_s, "tokens/s",
          paged_tok_s / (0.5 * roofline), {
              "slots": slots, "max_seq": max_seq, "steps": steps,
              "block_size": paged.block_size,
              "blocks_used": paged._kv.stats()["blocks_used"],
              "active_tokens": active_tokens,
              "kv_active_bytes": int(kv_active_bytes),
              "params_bytes": int(weight_bytes),
              "traffic_roofline_tok_s": _finite(roofline, 1),
              "dense_tokens_per_sec": round(dense_tok_s, 2),
              "paged_vs_dense": round(ratio, 3),
              "baseline": "50% of the weights + ACTIVE-token KV "
                          "streaming roofline",
              "bar": "paged >= dense tokens/s on the same geometry",
              "sample_tokens": [int(t) for t in toks[0, :4]],
              "backend": jax.default_backend()})
    assert ratio >= 1.0, (
        f"paged decode ({paged_tok_s:.1f} tok/s) slower than dense "
        f"({dense_tok_s:.1f} tok/s) on the same geometry")

    # -- concurrency at equal HBM: tiny model, pool == dense budget ------
    tiny = LlamaConfig.tiny()
    tiny.dtype = "float32"
    paddle.seed(0)
    tmodel = LlamaForCausalLM(tiny)
    dense_slots, c_seq, bs = 2, 256, 16
    pool_blocks = dense_slots * c_seq // bs   # == dense HBM budget
    probe = PagedLlamaDecodeEngine(tmodel, max_slots=64,
                                   max_seq=c_seq, block_size=bs,
                                   num_blocks=pool_blocks)
    admitted = 0
    for slot in range(probe.max_slots):
        if not probe.begin_request(slot, [1] * 16, 16):
            break
        admitted += 1
    ratio_c = admitted / dense_slots
    assert ratio_c >= 2.0, (
        f"paged admitted only {admitted} slots vs {dense_slots} dense "
        f"at equal HBM")
    _emit("paged_kv_concurrency", ratio_c, "x", ratio_c / 2.0, {
        "dense_slots": dense_slots, "paged_admitted": admitted,
        "pool_blocks": pool_blocks, "block_size": bs,
        "max_seq": c_seq,
        "request_shape": "16-token prompt + 16-token budget",
        "bar": ">=2x the dense engine's concurrent slots at equal "
               "KV HBM"})


def bench_prefix_sharing_kv():
    """Prefix-sharing KV cache vs the unshared allocator (ISSUE 16):
    64 requests sharing a 256-token prefix (16 blocks at block_size
    16) with 4 unique tail tokens each, served both ways. Three bars:
    streams BIT-equal to the unshared oracle (sharing must be
    invisible in the tokens), served tokens/s >= 1.5x (aliased
    admissions skip 256 of 260 prefill tokens), and admitted slots
    >= 2x on a fixed pool (a shared block is charged once however
    many slots alias it)."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import PagedLlamaDecodeEngine

    cfg = LlamaConfig.tiny()
    cfg.dtype = "float32"
    n_req, prefix_len, tail_len, new_tok = 64, 256, 4, 8
    bs, max_seq = 16, 320
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, cfg.vocab_size, (prefix_len,)).tolist()
    warm_prefix = rng.integers(0, cfg.vocab_size,
                               (prefix_len,)).tolist()
    prompts = [prefix + rng.integers(
        0, cfg.vocab_size, (tail_len,)).tolist() for _ in range(n_req)]

    def build(prefix_cache_on, slots, num_blocks=0):
        prev = paddle.get_flags(["FLAGS_serving_prefix_cache"])
        paddle.set_flags(
            {"FLAGS_serving_prefix_cache": int(prefix_cache_on)})
        try:
            return PagedLlamaDecodeEngine(
                model, max_slots=slots, max_seq=max_seq,
                block_size=bs, num_blocks=num_blocks,
                prefill_chunk=64)
        finally:
            paddle.set_flags(prev)

    def serve_all(eng):
        """Sequential single-slot serve of every request (prefill +
        decode + release) — the wall clock covers the whole request
        lifecycle, which is where prefix reuse pays."""
        eng.generate(warm_prefix + [1] * tail_len,
                     max_new_tokens=new_tok)     # warm both buckets
        streams = []
        t0 = time.perf_counter()
        for p in prompts:
            streams.append(eng.generate(p, max_new_tokens=new_tok))
        dt = time.perf_counter() - t0
        return streams, n_req * new_tok / dt

    off_streams, off_tok_s = serve_all(build(False, slots=2))
    on_eng = build(True, slots=2)
    on_streams, on_tok_s = serve_all(on_eng)
    assert on_streams == off_streams, (
        "prefix-shared streams diverge from the unshared oracle")
    st = on_eng._kv.stats()
    assert st["prefix_hits"] >= n_req - 1, st
    speedup = on_tok_s / max(off_tok_s, 1e-9)

    # -- admissions on a FIXED pool: shared blocks charge once ----------
    pool = 64                     # unshared: 17 blocks/request -> 3 fit
    probe_off = build(False, slots=n_req, num_blocks=pool)
    admitted_off = 0
    for s in range(n_req):
        if not probe_off.begin_request(s, prompts[s], new_tok):
            break
        admitted_off += 1
    probe_on = build(True, slots=n_req, num_blocks=pool)
    probe_on.prefill(0, prompts[0], budget=new_tok)  # seed the tree
    admitted_on = 1
    for s in range(1, n_req):
        if not probe_on.begin_request(s, prompts[s], new_tok):
            break
        admitted_on += 1
    ratio_adm = admitted_on / max(admitted_off, 1)

    _emit("prefix_sharing_kv", speedup, "x", speedup / 1.5, {
        "requests": n_req, "prefix_tokens": prefix_len,
        "tail_tokens": tail_len, "new_tokens": new_tok,
        "block_size": bs,
        "tokens_per_sec_shared": round(on_tok_s, 1),
        "tokens_per_sec_unshared": round(off_tok_s, 1),
        "prefix_hits": st["prefix_hits"],
        "prefix_tokens_reused": st["prefix_tokens_reused"],
        "pool_blocks": pool,
        "admitted_shared": admitted_on,
        "admitted_unshared": admitted_off,
        "admitted_ratio": round(ratio_adm, 2),
        "streams_bit_equal": True,
        "bar": ">=1.5x tokens/s AND >=2x admitted slots vs "
               "FLAGS_serving_prefix_cache=0, streams bit-equal",
        "backend": jax.default_backend()})
    assert speedup >= 1.5, (
        f"prefix sharing served only {speedup:.2f}x the unshared "
        f"tokens/s ({on_tok_s:.1f} vs {off_tok_s:.1f})")
    assert ratio_adm >= 2.0, (
        f"prefix sharing admitted only {admitted_on} slots vs "
        f"{admitted_off} unshared on a {pool}-block pool")


def bench_llama_decode_speculative():
    """Speculative paged decode vs plain paged decode, same geometry
    (ISSUE 12). The draft is the truncated-layer view with the
    target's TAIL residual contributions zeroed (o_proj/down_proj = 0
    — those layers add exactly 0 to the stream), so draft and target
    compute the same function: the repeat-friendly upper bound where
    every window is accepted. What the line grades is the real
    mechanics balance — k cheap draft forwards + ONE batched verify +
    accept/rollback bookkeeping against k plain decode steps (each a
    host round-trip, the continuous-batching server contract on both
    sides). Acceptance/rollback counters ride in detail; bars:
    spec tokens/s >= plain tokens/s AND > 1 committed token per
    target step."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import PagedLlamaDecodeEngine

    if _on_tpu():
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=3584, intermediate_size=9728,
            num_hidden_layers=6, num_attention_heads=28,
            num_key_value_heads=28, max_position_embeddings=2048,
            dtype="bfloat16")
        slots, max_seq, windows, prompt_len = 8, 1024, 24, 64
        spec_k, draft_layers = 4, 3
    else:
        cfg = LlamaConfig.tiny(num_hidden_layers=4)
        cfg.dtype = "float32"
        slots, max_seq, windows, prompt_len = 2, 512, 8, 16
        spec_k, draft_layers = 4, 2
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    if cfg.dtype == "bfloat16":
        model.bfloat16()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (prompt_len,))
               for _ in range(slots)]
    steps = windows * spec_k
    reps = 3                 # best-of: shared bench hosts are noisy
    budget = reps * steps + 2 * spec_k + 8

    def _zero_tail(eng):
        for lp in eng.params["layers"][draft_layers:]:
            lp["o_proj"] = jnp.zeros_like(lp["o_proj"])
            lp["down_proj"] = jnp.zeros_like(lp["down_proj"])

    def _prefill_all(eng):
        for s in range(slots):
            eng.prefill(s, prompts[s], budget=budget)

    # plain per-step paged decode (the pre-spec server loop),
    # best-of-reps against host noise
    plain = PagedLlamaDecodeEngine(model, max_slots=slots,
                                   max_seq=max_seq)
    _zero_tail(plain)
    _prefill_all(plain)
    for _ in range(4):
        plain.step()                       # warm
    plain_dt = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            plain.step()
        plain_dt = min(plain_dt, time.perf_counter() - t0)
    plain_tok_s = slots * steps / plain_dt

    # speculative: k draft proposals + one batched verify per window
    spec = PagedLlamaDecodeEngine(model, max_slots=slots,
                                  max_seq=max_seq)
    _zero_tail(spec)
    spec.attach_draft(spec.make_draft(model, num_layers=draft_layers),
                      spec_tokens=spec_k)
    _prefill_all(spec)
    for _ in range(2):
        spec.spec_step()                   # warm propose + verify
    spec_dt, committed = float("inf"), 0
    for _ in range(reps):
        got = 0
        t0 = time.perf_counter()
        for _ in range(windows):
            _, counts = spec.spec_step()
            got += int(counts[spec.active].sum())
        dt = time.perf_counter() - t0
        if got / dt > committed / spec_dt:   # 0/inf == 0.0 first rep
            spec_dt, committed = dt, got
    spec_tok_s = committed / spec_dt
    per_step = committed / (windows * slots)
    ratio = spec_tok_s / max(plain_tok_s, 1e-9)
    from paddle_tpu.observability import metrics as om
    snap = om.snapshot().get("serving", {})
    proposed = snap.get("spec_proposed_total", 0)
    accepted = snap.get("spec_accepted_total", 0)
    _emit("llama_decode_speculative_tokens_per_sec", spec_tok_s,
          "tokens/s", ratio, {
              "slots": slots, "max_seq": max_seq,
              "spec_tokens": spec_k, "draft_layers": draft_layers,
              "target_layers": cfg.num_hidden_layers,
              "windows": windows,
              "committed_per_target_step": round(per_step, 3),
              "acceptance_rate": round(accepted / max(proposed, 1), 3),
              "rolled_back_blocks":
                  snap.get("spec_rolled_back_total", 0),
              "plain_tokens_per_sec": round(plain_tok_s, 2),
              "spec_vs_plain": round(ratio, 3),
              "draft": "truncated-layer view, tail residual "
                       "contributions zeroed (exact-agreement = the "
                       "repeat-friendly acceptance upper bound)",
              "bar": "spec >= plain tokens/s AND > 1 committed "
                     "token per target step",
              "backend": jax.default_backend()})
    assert per_step > 1.0, (
        f"speculative decode committed only {per_step:.2f} tokens per "
        f"target step (needs > 1 to beat plain stepping)")
    assert ratio >= 1.0, (
        f"speculative decode ({spec_tok_s:.1f} tok/s) slower than "
        f"plain paged decode ({plain_tok_s:.1f} tok/s)")


def bench_paged_attention_paths():
    """The two implementations behind the serving_cache.paged_attention
    seam: PARITY of the Pallas block-table kernel against the jnp tile
    walk (its numerics oracle) on the decode geometry, plus the walk's
    per-call latency. On CPU hosts the kernel runs through the Pallas
    interpreter for the parity check only (interpreter latency is
    meaningless); on a real TPU the kernel path is timed too and its
    speedup rides in detail. Value = jnp-walk µs per decode-step call;
    grade = parity (1.0 when the paths agree to tolerance)."""
    import functools
    import jax
    import jax.numpy as jnp
    from paddle_tpu import serving_cache as sc
    from paddle_tpu.ops.pallas import paged_attention as pk

    rng = np.random.default_rng(0)

    def build(S, T, H, K, D, bs, MB):
        NB = S * MB
        q = jnp.asarray(rng.standard_normal((S, T, H, D)),
                        jnp.float32)
        kp = jnp.asarray(rng.standard_normal((NB, bs, K * D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((NB, bs, K * D)),
                         jnp.float32)
        tables = jnp.asarray(
            rng.permutation(NB).reshape(S, MB).astype(np.int32))
        pos = jnp.asarray(
            rng.integers(bs * (MB - 1), bs * MB - T,
                         (S, 1)).astype(np.int32)
            + np.arange(T, dtype=np.int32)[None, :])
        return q, kp, vp, tables, pos

    # latency: the serving decode-step geometry (full tables walk);
    # head_dim 128 because Mosaic refuses the kernel below that
    # (ops.pallas.paged_attention.kernel_available)
    S, T, H, K, D, bs, MB = 8, 1, 8, 2, 128, 16, 32
    q, kp, vp, tables, pos = build(S, T, H, K, D, bs, MB)
    walk = jax.jit(functools.partial(sc.paged_attention,
                                     block_size=bs, n_rep=H // K,
                                     use_kernel=False))
    walk(q, kp, vp, tables, pos).block_until_ready()   # warm
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        out = walk(q, kp, vp, tables, pos)
    out.block_until_ready()
    walk_us = (time.perf_counter() - t0) / reps * 1e6

    detail = {"geometry": {"slots": S, "q_tokens": T, "heads": H,
                           "kv_heads": K, "head_dim": D,
                           "block_size": bs, "max_blocks": MB},
              "walk_us_per_call": round(walk_us, 1),
              "kernel_on_backend": pk.kernel_available(D),
              "backend": jax.default_backend()}
    # parity on a smaller geometry (the interpreter pays per grid
    # program). Both sides contract f32 at the highest precision, so
    # the CPU parity test's tolerance holds on the chip too
    qs, kps, vps, ts_, ps = build(4, 2, 8, 2, D, 16, 8)
    interp = not pk.kernel_available(D)
    with jax.default_matmul_precision("highest"):
        ref = sc.paged_attention(qs, kps, vps, ts_, ps, block_size=16,
                                 n_rep=4, use_kernel=False)
        got = pk.paged_attention_kernel(qs, kps, vps, ts_, ps,
                                        block_size=16, n_rep=4,
                                        interpret=interp)
    diff = float(jnp.max(jnp.abs(ref - got)))
    parity_ok = diff <= 1e-5
    detail["parity_max_abs_diff"] = diff
    detail["parity_mode"] = "interpret" if interp else "tpu"
    if not interp:
        kern = jax.jit(functools.partial(
            sc.paged_attention, block_size=bs, n_rep=H // K,
            use_kernel=True))
        kern(q, kp, vp, tables, pos).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(reps):
            out = kern(q, kp, vp, tables, pos)
        out.block_until_ready()
        kernel_us = (time.perf_counter() - t0) / reps * 1e6
        detail["kernel_us_per_call"] = round(kernel_us, 1)
        detail["kernel_speedup"] = round(walk_us / kernel_us, 2)
    _emit("paged_attention_paths", walk_us, "us/call",
          1.0 if parity_ok else 0.0, detail)
    assert parity_ok, detail


def bench_bert_base():
    """BASELINE workload 2: BERT-base MLM, static graph + fusion — the
    whole step through one compiled executable (the CINN-fusion analog).
    MFU vs the 0.45 A100 bar."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.jit.api import TrainStep
    from paddle_tpu.models import BertConfig, BertForMaskedLM

    if _on_tpu():
        cfg = BertConfig()  # base: L12 H768 A12
        # 24 chained steps: steady-state rate (short chains pay the
        # dispatch pipeline fill — see the ResNet note).
        # batch 24: the xplane trace showed batch 64 at the 16GB HBM
        # edge — XLA re-materialized every FFN fusion (~21 ms/step of
        # re-execution) and spilled; 24 clears the pressure (measured
        # 113K -> 131K tok/s, MFU 0.46 -> 0.535)
        batch, seq, steps = 24, 512, 24
    else:
        cfg = BertConfig(vocab_size=128, hidden_size=32,
                         num_hidden_layers=2, num_attention_heads=2,
                         intermediate_size=64, max_position_embeddings=64)
        batch, seq, steps = 2, 16, 2
    paddle.seed(0)
    model = BertForMaskedLM(cfg)
    model.bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=False)

    crit = paddle.nn.CrossEntropyLoss()

    def loss_fn(logits, labels):
        # 3-D logits go straight to CrossEntropyLoss, whose big-vocab
        # dispatch routes to the chunked fused CE: the old flatten-to-2D
        # reshape bypassed that routing, so plain CE converted the full
        # [B, L, 30522] logits to f32 (2x 1.2 ms/step in the xplane
        # trace) and XLA materialized a 1.9 GB logits copy (5.9 ms/step)
        return crit(logits, labels)

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                   (batch, seq)).astype(np.int32))
    with jax.default_matmul_precision("bfloat16"):
        float(step(ids, ids))
        float(step(ids, ids))
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step(ids, ids)
        loss = float(loss)
        dt = time.perf_counter() - t0
    tok = batch * seq * steps / dt
    mfu = tok * 6 * n_params / _peak_flops()
    _emit("bert_base_mlm_tokens_per_sec", tok, "tokens/s",
          mfu / _BASELINE_MFU, {
              "params": n_params, "batch": batch, "seq": seq,
              "mfu": round(mfu, 4), "loss": round(loss, 4),
              "backend": jax.default_backend(),
              **_hbm_detail(step, ids, ids)})


def bench_gpt13b_geometry():
    """BASELINE workload 4: GPT-3 13B geometry (hidden 5120, 40 heads),
    depth-scaled to one chip's HBM; the full 13B TP x PP x sharding mesh
    program is validated by dryrun_multichip (MULTICHIP json). MFU vs the
    0.45 bar — per-layer compute is geometry-identical to 13B."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.dist_train import DistTrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if _on_tpu():
        cfg = GPTConfig(vocab_size=50304, hidden_size=5120,
                        num_hidden_layers=3, num_attention_heads=40,
                        intermediate_size=20480,
                        max_position_embeddings=2048)
        batch, seq, steps = 4, 2048, 8
    else:
        cfg = GPTConfig(vocab_size=128, hidden_size=32,
                        num_hidden_layers=2, num_attention_heads=2,
                        intermediate_size=64, max_position_embeddings=64)
        batch, seq, steps = 2, 16, 2
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=False)
    crit = paddle.nn.CrossEntropyLoss()

    def loss_fn(logits, labels):
        return crit(logits.reshape([-1, cfg.vocab_size]),
                    labels.reshape([-1]))

    step = DistTrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                   (batch, seq)).astype(np.int32))
    with jax.default_matmul_precision("bfloat16"):
        float(step(ids, ids))
        float(step(ids, ids))
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step(ids, ids)
        loss = float(loss)
        dt = time.perf_counter() - t0
    tok = batch * seq * steps / dt
    mfu = tok * 6 * n_params / _peak_flops()
    _emit("gpt13b_geometry_tokens_per_sec_per_chip", tok, "tokens/s",
          mfu / _BASELINE_MFU, {
              "params": n_params, "hidden": cfg.hidden_size,
              "heads": cfg.num_attention_heads, "layers_on_chip":
              cfg.num_hidden_layers, "mfu": round(mfu, 4),
              "loss": round(loss, 4),
              "mesh_validated_by": "MULTICHIP dryrun (tp x pp x fsdp)",
              "backend": jax.default_backend(),
              **_hbm_detail(step, ids, ids)})


def bench_moe_dispatch():
    """BASELINE workload 5: ERNIE-MoE expert dispatch throughput.
    vs_baseline is an ABSOLUTE bar: measured MFU over the useful MoE
    FLOPs (gate + dispatched tokens' expert FFNs, fwd+bwd) against 0.40
    — the utilization the reference's CUTLASS fused MoE GEMM exists to
    deliver (ref: phi/kernels/fusion/cutlass/fused_moe_kernel.cu).
    The dense one-hot dispatch oracle (reference global_scatter algebra)
    is kept in detail as dense_oracle_ms/dense_speedup."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.moe import _gshard_dispatch
    from paddle_tpu.incubate.moe_dispatch import moe_forward_indices

    if _on_tpu():
        # 32K tokens: an expert-parallel global batch, and the regime
        # the index path exists for — dense one-hot dispatch/combine
        # einsums are quadratic in T (~T * E*C * H with E*C ~ 2.5T), so
        # tiny-T measurements flatter the dense algebra instead of
        # measuring the scalable path (MoELayer's dispatch_mode="auto"
        # routes small batches to dense for exactly that reason)
        # 24 chained steps: the closing value fetch is one host
        # round-trip, amortized over the chain
        T, E, H, F, steps = 32768, 16, 1024, 4096, 24
    else:
        T, E, H, F, steps = 64, 4, 16, 32, 2
    cap = max(1, int(1.25 * T * 2 / E))
    rng = np.random.default_rng(0)
    # bf16 activations/weights, like every other workload here (and the
    # reference's fp16 CUTLASS MoE GEMM); gate logits stay fp32. The
    # grouped-matmul kernel accumulates in fp32 either way.
    wdt = jnp.bfloat16 if _on_tpu() else jnp.float32
    tokens = jnp.asarray(rng.standard_normal((T, H)).astype(np.float32)
                         * 0.1, wdt)
    gw = jnp.asarray(rng.standard_normal((H, E)).astype(np.float32))
    wi = jnp.asarray(rng.standard_normal((E, H, F)).astype(np.float32)
                     * 0.02, wdt)
    wo = jnp.asarray(rng.standard_normal((E, F, H)).astype(np.float32)
                     * 0.02, wdt)

    def dense_fwd(tk, wi_, wo_):
        logits = tk @ gw
        combine, dispatch, aux = _gshard_dispatch(logits, 2, cap)
        xs = jnp.einsum("tec,th->ech", dispatch.astype(tk.dtype), tk)
        hdn = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", xs, wi_))
        ys = jnp.einsum("ecf,efh->ech", hdn, wo_)
        return jnp.einsum("tec,ech->th", combine.astype(tk.dtype), ys)

    def index_fwd(tk, wi_, wo_):
        return moe_forward_indices(tk, gw, wi_, wo_, 2, cap,
                                   jax.nn.gelu)[0]

    def train(fwd):
        @jax.jit
        def f(tk, wi_, wo_):
            def loss(wi2, wo2):
                out = fwd(tk, wi2, wo2).astype(jnp.float32)
                return jnp.sum(out ** 2)
            l, g = jax.value_and_grad(loss, argnums=(0, 1))(wi_, wo_)
            return l, g
        return f

    def timeit(f):
        l, _ = f(tokens, wi, wo)
        float(l)
        best = float("inf")
        for _ in range(3):  # best-of windows: shared hosts are noisy
            t0 = time.perf_counter()
            l = None
            for _ in range(steps):
                l, _ = f(tokens, wi, wo)
            float(l)
            best = min(best, (time.perf_counter() - t0) / steps)
        return best

    t_dense = timeit(train(dense_fwd))
    t_index = timeit(train(index_fwd))
    tok_s = T / t_index
    # absolute utilization: useful MoE FLOPs = gate matmul + the
    # dispatched tokens' expert FFNs, fwd ~1x + bwd ~2x (dx through
    # combine + dw for wi/wo). Capacity padding is NOT counted useful.
    moe_bar = 0.40
    dispatched = min(T * 2, E * cap)
    flops_fwd = 2 * T * H * E + dispatched * 2 * (2 * H * F)
    mfu = 3 * flops_fwd / t_index / _peak_flops()
    _emit("ernie_moe_dispatch_tokens_per_sec", tok_s, "tokens/s",
          mfu / moe_bar, {
              "tokens": T, "experts": E, "capacity": cap,
              "index_ms": round(t_index * 1e3, 2),
              "dense_oracle_ms": round(t_dense * 1e3, 2),
              "dense_speedup": round(t_dense / t_index, 2),
              "mfu": round(mfu, 4), "mfu_bar": moe_bar,
              "baseline": "absolute expert-FFN utilization bar 0.40 "
                          "(CUTLASS fused MoE GEMM role)",
              "backend": "tpu" if _on_tpu() else "cpu"})


def bench_dispatch_overhead():
    """Eager dispatch µs/op on the cached-hit path (VERDICT r3 item 6;
    ref: the reference's sub-10µs eager hot loop, SURVEY §3.1 +
    test/cpp/eager/performance_tests/benchmark_eager_cuda.cc). Measures
    the grad-recording path — forward through the cached jitted pair +
    GradNode wiring — which was 1.5 ms/op before the fast path. Budget:
    150 µs/op (the raw jnp dispatch floor is reported beside it);
    vs_baseline = budget / measured."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle

    budget_us = 150.0
    # quiesce: this bench runs after the big workloads; pending
    # finalizers/garbage distort µs-level host timing
    import gc
    gc.collect()
    a = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((128, 128))
        .astype(np.float32), stop_gradient=False)
    b = paddle.to_tensor(np.ones((128, 128), np.float32))

    def one():
        return paddle.add(a, b)

    for _ in range(5):
        one()
    jax.block_until_ready(jnp.zeros(()))
    n = 500

    def best_of(fn, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter() - t0) / n)
        return best * 1e6

    us = best_of(one)
    raw = best_of(lambda: jnp.add(a._data, b._data))
    # overhead above the raw-jnp floor is the framework's own cost; the
    # floor itself is environment (host load) and is reported
    # alongside so a loaded run is readable
    _emit("eager_dispatch_overhead_us", us, "us/op", budget_us / us, {
        "path": "grad-recording add, cached jit pair",
        "raw_jnp_dispatch_us": round(raw, 1),
        "overhead_above_floor_us": round(us - raw, 1),
        "budget_us": budget_us,
        "backend": jax.default_backend()})


def bench_metrics_overhead():
    """metrics_overhead: per-dispatch telemetry cost with FLAGS_metrics
    on, as % of the cached-hit eager dispatch time — the always-on
    claim's ≤5% bar, enforced rather than asserted.

    The hot path carries exactly ONE instrument operation per dispatch
    (a guarded counter bump in _op_gate; all per-op attribution is
    snapshot-time collectors), so the graded number multiplies the
    DIRECTLY measured cost of that operation against the measured
    dispatch µs. An end-to-end on/off A/B of the same dispatch loop is
    reported alongside in detail — on this class of shared bench host
    its run-to-run load noise (±15µs/op observed across identical
    configs) cannot resolve the ~0.1µs quantity under test, which is
    why it informs but does not grade."""
    import gc

    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.observability import metrics as om

    gc.collect()
    a = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((128, 128))
        .astype(np.float32), stop_gradient=False)
    b = paddle.to_tensor(np.ones((128, 128), np.float32))

    def one():
        return paddle.add(a, b)

    # fusion OFF: with it on, every 32nd add pays a chain flush inside
    # the timed window and that jitter swamps the per-op number; the
    # plain cached-jit-pair dispatch is the hot path the bar is over
    prev_fusion = paddle.get_flags("FLAGS_eager_fusion")
    prev = paddle.get_flags("FLAGS_metrics")
    paddle.set_flags({"FLAGS_eager_fusion": 0})
    for _ in range(5):
        one()
    jax.block_until_ready(jnp.zeros(()))
    n = 500

    def window():
        t0 = time.perf_counter()
        for _ in range(n):
            one()
        return (time.perf_counter() - t0) / n * 1e6

    # direct cost of the per-dispatch instrument op (the exact code
    # _op_gate runs): guarded attribute bump, loop overhead included
    flag = om.flag_info()
    probe = om.counter("bench.metrics_probe_total")
    m = 200_000

    def inc_window():
        t0 = time.perf_counter()
        for _ in range(m):
            if flag.value:
                probe._v += 1
        return (time.perf_counter() - t0) / m * 1e6

    on_us = off_us = inc_us = float("inf")
    try:
        paddle.set_flags({"FLAGS_metrics": 1})
        for _ in range(5):
            inc_us = min(inc_us, inc_window())
        for _ in range(7):  # interleaved best-of: shared-host load drift
            paddle.set_flags({"FLAGS_metrics": 1})
            on_us = min(on_us, window())
            paddle.set_flags({"FLAGS_metrics": 0})
            off_us = min(off_us, window())
    finally:
        paddle.set_flags(prev)
        paddle.set_flags(prev_fusion)
    overhead_pct = inc_us / off_us * 100.0
    e2e_pct = (on_us - off_us) / off_us * 100.0
    _emit("metrics_overhead", overhead_pct, "%",
          5.0 / max(overhead_pct, 0.01), {
              "per_dispatch_instrument_us": round(inc_us, 4),
              "dispatch_us_per_op": round(off_us, 2),
              "e2e_on_us_per_op": round(on_us, 2),
              "e2e_off_us_per_op": round(off_us, 2),
              "e2e_delta_pct_noisy": round(e2e_pct, 2),
              "bar": "<=5% dispatch overhead with FLAGS_metrics on",
              "path": "grad-recording add, cached jit pair",
              "backend": jax.default_backend()})


def bench_flight_overhead():
    """flight_recorder_overhead: direct per-event append cost of the
    always-on flight recorder with FLAGS_flight_recorder on, as % of
    the cached-hit eager dispatch time — the ≤5% bar metrics_overhead
    set, applied to the black-box journal.

    Like metrics_overhead, the graded number is the DIRECTLY measured
    append cost (clock read + tuple + ring append through the public
    record() path, steady-state with the ring full so eviction cost is
    included) divided by the measured dispatch µs: shared-host e2e A/B
    noise (±15µs/op) cannot resolve a sub-µs quantity, so the e2e
    delta is reported in detail but does not grade. NOTE the hot
    dispatch path records NO event per op (events come from chain
    flushes, syncs and lifecycle edges); per-event-per-dispatch is the
    conservative worst case."""
    import gc

    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.observability import flight

    gc.collect()
    a = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((128, 128))
        .astype(np.float32), stop_gradient=False)
    b = paddle.to_tensor(np.ones((128, 128), np.float32))

    def one():
        return paddle.add(a, b)

    prev_fusion = paddle.get_flags("FLAGS_eager_fusion")
    prev = paddle.get_flags("FLAGS_flight_recorder")
    paddle.set_flags({"FLAGS_eager_fusion": 0})
    for _ in range(5):
        one()
    jax.block_until_ready(jnp.zeros(()))
    n = 500

    def window():
        t0 = time.perf_counter()
        for _ in range(n):
            one()
        return (time.perf_counter() - t0) / n * 1e6

    m = 200_000

    def append_window():
        t0 = time.perf_counter()
        for _ in range(m):
            flight.record("bench", "probe")
        return (time.perf_counter() - t0) / m * 1e6

    on_us = off_us = ev_us = float("inf")
    try:
        paddle.set_flags({"FLAGS_flight_recorder": 1})
        for _ in range(5):
            ev_us = min(ev_us, append_window())
        for _ in range(7):  # interleaved best-of: shared-host drift
            paddle.set_flags({"FLAGS_flight_recorder": 1})
            on_us = min(on_us, window())
            paddle.set_flags({"FLAGS_flight_recorder": 0})
            off_us = min(off_us, window())
    finally:
        paddle.set_flags(prev)
        paddle.set_flags(prev_fusion)
        flight.clear()  # drop the bench probes from the black box
    overhead_pct = ev_us / off_us * 100.0
    e2e_pct = (on_us - off_us) / off_us * 100.0
    _emit("flight_recorder_overhead", overhead_pct, "%",
          5.0 / max(overhead_pct, 0.01), {
              "per_event_append_us": round(ev_us, 4),
              "dispatch_us_per_op": round(off_us, 2),
              "ring_capacity": flight._capacity(),
              "e2e_on_us_per_op": round(on_us, 2),
              "e2e_off_us_per_op": round(off_us, 2),
              "e2e_delta_pct_noisy": round(e2e_pct, 2),
              "bar": "<=5% of dispatch per event with "
                     "FLAGS_flight_recorder on",
              "path": "record() into a full ring, steady state",
              "backend": jax.default_backend()})


def bench_eager_fusion():
    """eager_fusion_speedup: µs/op for a cached 12-op elementwise chain
    on the grad-recording eager path, lazy-eager fusion ON (one jitted
    executable per chain, core/fusion.py) vs OFF (per-op dispatch,
    FLAGS_eager_fusion=0). The fused chain does ONE dispatch and ONE
    memory pass where the unfused path does 12 of each — the locality
    win chain fusion exists for. Bar: >=4x lower µs/op fused."""
    import gc

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.core import fusion

    gc.collect()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((256, 256))
                         .astype(np.float32), stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal((256, 256))
                         .astype(np.float32))

    def chain(t):
        for _ in range(4):
            t = paddle.multiply(t, b)
            t = paddle.add(t, b)
            t = paddle.subtract(t, 0.125)
        return t

    def measure(n=150, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                chain(x).numpy()  # host read closes every chain
            best = min(best, (time.perf_counter() - t0) / n)
        return best * 1e6 / 12.0

    prev = paddle.get_flags("FLAGS_eager_fusion")
    try:
        paddle.set_flags({"FLAGS_eager_fusion": 1})
        for _ in range(20):
            chain(x).numpy()
        s0 = fusion.stats()
        fused_us = measure()
        s1 = fusion.stats()
        paddle.set_flags({"FLAGS_eager_fusion": 0})
        for _ in range(20):
            chain(x).numpy()
        unfused_us = measure()
    finally:
        paddle.set_flags(prev)
    flushes = max(s1["chains_flushed"] - s0["chains_flushed"], 1)
    hit_rate = (s1["cache_hits"] - s0["cache_hits"]) / flushes
    speedup = unfused_us / fused_us
    _emit("eager_fusion_speedup", speedup, "x", speedup / 4.0, {
        "fused_us_per_op": round(fused_us, 1),
        "unfused_us_per_op": round(unfused_us, 1),
        "chain_ops": 12, "shape": [256, 256], "grad_recording": True,
        "steady_state_cache_hit_rate": round(hit_rate, 4),
        "new_compiles_in_timed_window":
            s1["cache_misses"] - s0["cache_misses"],
        "bar": ">=4x lower us/op for the cached 12-op chain",
        "backend": jax.default_backend()})


def bench_reduction_fusion():
    """reduction_fusion_speedup: direct µs/op for (a) a cached
    reduction-TERMINATED chain — 16 elementwise ops + square + mean
    (RED_OPS=18), one fused executable through a host scalar read per
    iteration — and (b) a
    matmul-epilogue chain (x@w + b -> tanh), each vs the identical loop
    under FLAGS_eager_fusion=0 (per-op dispatch). Graded on the DIRECT
    best-of cost ratio of the reduction chain: on this class of shared
    bench host the ±15 µs/op e2e load noise cannot resolve small A/B
    deltas, but the quantity under test here is the whole multiple-x
    dispatch-count collapse, which best-of interleaved windows resolve
    fine. The epilogue ratio is reported in detail but NOT graded: on a
    CPU bench host the 256^3 dot dominates both paths (~1 ms) and
    XLA:CPU trades its library-GEMM fast path when an elementwise
    epilogue fuses into the dot, so the A/B there sits at ~1x inside
    host noise — the epilogue win this measures for regression is the
    TPU MXU/HBM-locality one. Bar: >=3x lower µs/op fused for the
    reduction chain, 100% steady-state cache hits."""
    import gc

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.core import fusion

    gc.collect()
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((256, 256))
                         .astype(np.float32), stop_gradient=False)
    b = paddle.to_tensor(rng.standard_normal((256, 256))
                         .astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((256, 256))
                         .astype(np.float32), stop_gradient=False)
    bias = paddle.to_tensor(rng.standard_normal((256,))
                            .astype(np.float32))

    RED_OPS = 18  # 8x(mul, add) + square + mean

    def _red_build():
        # loss built in its own frame, loss-fn style: the requires-grad
        # intermediates are DEAD by flush time, so the whole chain is
        # one executable (a live named rg intermediate would be a tape
        # edge and cut the program there — eager semantics)
        t = x
        for _ in range(8):
            t = paddle.multiply(t, b)
            t = paddle.add(t, 0.125)
        return paddle.mean(paddle.square(t))

    def red_loss():
        return float(_red_build().numpy())

    EPI_OPS = 3  # matmul + add + tanh

    def epi_step():
        return paddle.tanh(
            paddle.add(paddle.matmul(x, w), bias)).numpy()

    def measure(fn, ops, n=120, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, (time.perf_counter() - t0) / n)
        return best * 1e6 / ops

    prev = paddle.get_flags(["FLAGS_eager_fusion",
                             "FLAGS_eager_fusion_reduce",
                             "FLAGS_eager_fusion_epilogue"])
    try:
        paddle.set_flags({"FLAGS_eager_fusion": 1,
                          "FLAGS_eager_fusion_reduce": 1,
                          "FLAGS_eager_fusion_epilogue": 1})
        for _ in range(20):
            red_loss()
            epi_step()
        s0 = fusion.stats()
        red_fused = measure(red_loss, RED_OPS)
        s1 = fusion.stats()
        epi_fused = measure(epi_step, EPI_OPS)
        paddle.set_flags({"FLAGS_eager_fusion": 0})
        for _ in range(20):
            red_loss()
            epi_step()
        red_unfused = measure(red_loss, RED_OPS)
        epi_unfused = measure(epi_step, EPI_OPS)
    finally:
        paddle.set_flags(prev)
    flushes = max(s1["chains_flushed"] - s0["chains_flushed"], 1)
    hit_rate = (s1["cache_hits"] - s0["cache_hits"]) / flushes
    red_speedup = red_unfused / red_fused
    epi_speedup = epi_unfused / epi_fused
    _emit("reduction_fusion_speedup", red_speedup, "x",
          red_speedup / 3.0, {
              "reduce_chain_ops": RED_OPS,
              "reduce_fused_us_per_op": round(red_fused, 1),
              "reduce_unfused_us_per_op": round(red_unfused, 1),
              "epilogue_chain_ops": EPI_OPS,
              "epilogue_fused_us_per_op": round(epi_fused, 1),
              "epilogue_unfused_us_per_op": round(epi_unfused, 1),
              "epilogue_speedup": round(epi_speedup, 2),
              "shape": [256, 256], "grad_recording": True,
              "steady_state_cache_hit_rate": round(hit_rate, 4),
              "new_compiles_in_timed_window":
                  s1["cache_misses"] - s0["cache_misses"],
              "reductions_fused_in_window":
                  s1["reductions_fused"] - s0["reductions_fused"],
              "bar": ">=3x lower direct us/op for the reduction-"
                     "terminated chain (graded on direct cost; shared-"
                     "host e2e noise ±15us/op documented in detail)",
              "backend": jax.default_backend()})


def bench_fused_optimizer_step():
    """fused_optimizer_step_us: direct per-param cost of one optimizer
    step for a 64-param model — AdamW + global-norm clip + a changing
    (cosine) LR schedule — with the step fused into ONE buffer-donated
    executable (FLAGS_fused_optimizer=1) vs the per-param eager update
    loop (=0, ~10 tiny dispatches per param plus a full clip pass).
    Graded on the directly measured step cost per the ±15µs host-noise
    rule (an e2e train-loop A/B can't resolve the delta on this host);
    bar: >= 3x lower per-param cost fused, with 100% steady-state cache
    hits and <= 1 compile across the whole changing-LR schedule."""
    import gc
    import time as _t

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.observability import metrics as om
    from paddle_tpu.optimizer import fused_step

    gc.collect()
    n_params, shape, steps = 64, (64, 64), 20
    rng = np.random.default_rng(0)
    grads = [rng.normal(size=shape).astype(np.float32) * 1e-3
             for _ in range(n_params)]

    def build():
        ps = [paddle.Parameter(
            np.random.default_rng(i).standard_normal(shape)
            .astype(np.float32)) for i in range(n_params)]
        sched = paddle.optimizer.lr.CosineAnnealingDecay(
            learning_rate=1e-3, T_max=200)
        opt = paddle.optimizer.AdamW(
            learning_rate=sched, parameters=ps,
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
        # grads persist across steps: the plain fused path donates only
        # params + state, so the same grad buffers are reusable
        for p, g in zip(ps, grads):
            p.grad = paddle.to_tensor(g)
        return ps, sched, opt

    def measure(reps=3):
        ps, sched, opt = build()
        for _ in range(3):  # first sighting + compile + one hit
            opt.step()
            sched.step()
        jax.block_until_ready(ps[0]._data)
        best = float("inf")
        for _ in range(reps):
            t0 = _t.perf_counter()
            for _ in range(steps):
                opt.step()
                sched.step()
            jax.block_until_ready(ps[0]._data)
            best = min(best, (_t.perf_counter() - t0) / steps)
        return best * 1e6  # µs per whole step

    prev = paddle.get_flags("FLAGS_fused_optimizer")
    try:
        paddle.set_flags({"FLAGS_fused_optimizer": 1})
        fused_step.clear_cache()
        before = dict(om.snapshot().get("optimizer", {}))
        fused_us = measure()
        after = dict(om.snapshot().get("optimizer", {}))
        paddle.set_flags({"FLAGS_fused_optimizer": 0})
        eager_us = measure()
    finally:
        paddle.set_flags(prev)

    def delta(k):
        return int(after.get(k, 0) - before.get(k, 0))

    compiles = delta("fused_compiles_total")
    hits = delta("cache_hits_total")
    fused_steps = delta("fused_steps_total")
    fused_pp = fused_us / n_params
    eager_pp = eager_us / n_params
    speedup = eager_pp / max(fused_pp, 1e-9)
    # steady state = every step after the first sighting + the compile
    hit_rate = hits / max(fused_steps - 2, 1) * 100.0
    _emit("fused_optimizer_step_us", fused_pp, "us/param", speedup / 3.0, {
        "fused_us_per_param": round(fused_pp, 3),
        "unfused_us_per_param": round(eager_pp, 3),
        "speedup": round(speedup, 1),
        "fused_step_us": round(fused_us, 1),
        "unfused_step_us": round(eager_us, 1),
        "n_params": n_params,
        "compiles_across_changing_lr_schedule": compiles,
        "steady_state_cache_hit_pct": round(hit_rate, 1),
        "donated_bytes_per_step": delta("donated_bytes") // max(
            hits + compiles, 1),
        "optimizer": "AdamW + ClipGradByGlobalNorm + CosineAnnealingDecay",
        "bar": ">=3x lower direct per-param cost, 100% steady-state "
               "hits, <=1 compile across the LR schedule",
        "backend": jax.default_backend()})


def bench_whole_step_capture():
    """whole_step_capture_speedup: steady-state per-step wall time of a
    llama tiny ``Model.fit``-shape train step with SOT whole-step
    capture ON (one cached, donated fwd+bwd+optimizer executable,
    FLAGS_sot_capture=1) vs OFF (per-chain eager fusion + the fused
    optimizer step — today's path). The captured step is ONE dispatch
    where the eager path pays ~8.5µs/op between fused chains
    (BENCH_ALL eager_dispatch_overhead_us — the gap this metric closes;
    this line also lands the dispatch-overhead number BENCH_r05 was
    missing). Asserted: >= 1 captured compile then 100% steady-state
    cache hits. Bar: >= 2x lower per-step wall time captured."""
    import gc
    import time as _t

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.hapi import Model
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.observability import metrics as om

    gc.collect()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (2, 32)).astype(np.int64)

    def build():
        paddle.seed(0)
        net = LlamaForCausalLM(LlamaConfig.tiny())
        m = Model(net)
        m.prepare(optimizer=paddle.optimizer.AdamW(
            learning_rate=1e-3, parameters=net.parameters()),
            loss=LlamaPretrainingCriterion())
        return m

    def measure(m, steps=30, reps=3):
        for _ in range(4):  # sighting + compile + hits
            m.train_batch([ids], [ids])
        # a value transfer is the only trustworthy barrier; the timed
        # loop itself stays fetch-free (the lazy-loss contract)
        float(m.train_batch([ids], [ids])[0])
        best = float("inf")
        last = None
        for _ in range(reps):
            t0 = _t.perf_counter()
            for _ in range(steps):
                last = m.train_batch([ids], [ids])[0]
            float(last)  # one fetch closes the timed window
            best = min(best, (_t.perf_counter() - t0) / steps)
        return best * 1e6

    prev = paddle.get_flags("FLAGS_sot_capture")
    try:
        paddle.set_flags({"FLAGS_sot_capture": 1})
        m = build()
        before = dict(om.snapshot().get("sot", {}))
        captured_us = measure(m)
        after = dict(om.snapshot().get("sot", {}))
        eng_stats = dict(m._captured.stats)
        paddle.set_flags({"FLAGS_sot_capture": 0})
        eager_us = measure(build())
    finally:
        paddle.set_flags(prev)

    def delta(k):
        v = after.get(k, 0)
        b = before.get(k, 0)
        if isinstance(v, dict) or isinstance(b, dict):
            v = sum(v.values()) if isinstance(v, dict) else v
            b = sum(b.values()) if isinstance(b, dict) else b
        return int(v - b)

    compiles = delta("captured_compiles_total")
    captured = delta("captured_steps_total")
    hits = delta("cache_hits_total")
    # steady state = every call after the sighting and the compile
    hit_rate = hits / max(captured - 1, 1) * 100.0
    assert compiles >= 1, "the captured step must compile at least once"
    assert hit_rate >= 99.9, f"steady state must be 100% hits, got " \
                             f"{hit_rate}"
    speedup = eager_us / max(captured_us, 1e-9)
    _emit("whole_step_capture_speedup", speedup, "x", speedup / 2.0, {
        "captured_step_us": round(captured_us, 1),
        "eager_step_us": round(eager_us, 1),
        "captured_compiles": compiles,
        "captured_steps": captured,
        "steady_state_cache_hit_pct": round(hit_rate, 1),
        "guard_misses": delta("guard_misses_total"),
        "fallbacks": eng_stats["fallbacks"],
        "model": "llama tiny (2L/64H) AdamW, batch [2, 32]",
        "bar": ">=2x lower per-step wall time; >=1 compile then 100% "
               "steady-state cache hits",
        "backend": jax.default_backend()})


def bench_amp_captured_step():
    """amp_captured_step_us: steady-state per-step wall time of a llama
    tiny ``Model.fit``-shape AMP/GradScaler train step with whole-step
    capture ON (the ENTIRE iteration — autocast forward, loss scale,
    backward, grad unscale + finite check, device-masked update, scale
    bookkeeping — as ONE donated executable; the PR 10 ``amp``
    fallback residue, now a capture path) vs OFF (eager autocast +
    the fused try_step_scaled path). Asserted: >= 1 captured compile,
    100% steady-state cache hits, ZERO amp-reason fallbacks, and
    captured no slower than eager (>= 1x). Bar: >= 1x."""
    import gc
    import time as _t

    import jax
    import paddle_tpu as paddle
    from paddle_tpu.hapi import Model
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion)
    from paddle_tpu.observability import metrics as om

    gc.collect()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (2, 32)).astype(np.int64)

    def build():
        paddle.seed(0)
        net = LlamaForCausalLM(LlamaConfig.tiny())
        m = Model(net)
        m.prepare(optimizer=paddle.optimizer.AdamW(
            learning_rate=1e-3, parameters=net.parameters()),
            loss=LlamaPretrainingCriterion(),
            amp_configs={"level": "O1", "init_loss_scaling": 1024.0})
        return m

    def measure(m, steps=30, reps=3):
        for _ in range(4):  # sighting + compile + hits
            m.train_batch([ids], [ids])
        float(m.train_batch([ids], [ids])[0])  # barrier
        best = float("inf")
        last = None
        for _ in range(reps):
            t0 = _t.perf_counter()
            for _ in range(steps):
                last = m.train_batch([ids], [ids])[0]
            float(last)  # one fetch closes the timed window
            best = min(best, (_t.perf_counter() - t0) / steps)
        return best * 1e6

    prev = paddle.get_flags("FLAGS_sot_capture")
    try:
        paddle.set_flags({"FLAGS_sot_capture": 1})
        m = build()
        captured_us = measure(m)
        eng_stats = dict(m._captured.stats)
        amp_fallbacks = om.default_registry().get(
            "sot.fallbacks_total").value(reason="amp")
        paddle.set_flags({"FLAGS_sot_capture": 0})
        eager_us = measure(build())
    finally:
        paddle.set_flags(prev)

    assert eng_stats["compiles"] >= 1, eng_stats
    assert eng_stats["fallbacks"] == {}, eng_stats
    assert amp_fallbacks == 0, amp_fallbacks
    hit_rate = eng_stats["cache_hits"] / \
        max(eng_stats["captured_steps"] - 1, 1) * 100.0
    assert hit_rate >= 99.9, eng_stats
    speedup = eager_us / max(captured_us, 1e-9)
    assert speedup >= 1.0, (captured_us, eager_us)
    _emit("amp_captured_step_us", captured_us, "us/step", speedup, {
        "captured_step_us": round(captured_us, 1),
        "eager_amp_step_us": round(eager_us, 1),
        "speedup": round(speedup, 2),
        "captured_compiles": eng_stats["compiles"],
        "steady_state_cache_hit_pct": round(hit_rate, 1),
        "amp_reason_fallbacks": int(amp_fallbacks),
        "scaler": "GradScaler dynamic, init 1024",
        "model": "llama tiny (2L/64H) AdamW O1 bf16, batch [2, 32]",
        "bar": ">= 1x vs eager AMP; >= 1 compile then 100% hits; "
               "0 amp fallbacks",
        "backend": jax.default_backend()})


def _dist_overlap_impl():
    """Worker body for dist_overlap_dryrun (runs under 8 virtual CPU
    devices): both MULTICHIP-validated geometries through the captured
    DistTrainStep with small grad buckets, reporting buckets/step,
    per-bucket bytes, HLO collective sites and captured-vs-epilogue
    (FLAGS_dist_grad_bucket_bytes=0, the pre-T3 program shape)
    compile + step wall time."""
    import re
    import time as _t

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    import paddle_tpu as paddle
    from paddle_tpu.distributed import ProcessMesh
    from paddle_tpu.distributed.dist_train import DistTrainStep
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion,
                                   shard_llama)

    n = len(jax.devices())
    crit = LlamaPretrainingCriterion()
    rng = np.random.default_rng(0)
    out = {"devices": n}

    def run_geometry(label, make, ids):
        geo = {}
        for mode, bucket_bytes in (("bucketed", 16384), ("epilogue", 0)):
            paddle.set_flags(
                {"FLAGS_dist_grad_bucket_bytes": bucket_bytes})
            paddle.seed(0)
            step = make()
            t0 = _t.perf_counter()
            float(step(ids, ids))            # trace + compile + run
            compile_s = _t.perf_counter() - t0
            float(step(ids, ids))            # warm
            t0 = _t.perf_counter()
            loss = None
            for _ in range(5):
                loss = step(ids, ids)
            float(loss)
            step_ms = (_t.perf_counter() - t0) / 5 * 1e3
            geo[mode] = {"compile_s": round(compile_s, 2),
                         "step_ms": round(step_ms, 2)}
            if mode == "bucketed":
                plan = step.bucket_plan()
                _, compiled, _ = step.compile_stats(
                    ids, ids, return_compiled=True)
                n_coll = len(re.findall(
                    r"(all-reduce|reduce-scatter)\(",
                    compiled.as_text()))
                geo["buckets_per_step"] = len(plan)
                geo["per_bucket_bytes"] = [b["bytes"] for b in plan]
                geo["hlo_collective_sites"] = n_coll
        out[label] = geo
        return geo

    # geometry 1: llama 7b-ratio shapes under pure ZeRO-3 (fsdp) —
    # the MULTICHIP dryrun '7b' regime
    flat = ProcessMesh(np.arange(n), dim_names=["fsdp"])

    def make_7b():
        cfg = LlamaConfig.tiny(
            num_hidden_layers=2, hidden_size=64, intermediate_size=172,
            num_attention_heads=4, num_key_value_heads=4,
            vocab_size=128, use_flash_attention=False)
        m = LlamaForCausalLM(cfg)
        shard_llama(m, flat, tp_axis=None, fsdp_axis="fsdp")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        return DistTrainStep(
            m, lambda lg, lb: crit(lg, lb), opt,
            data_sharding=NamedSharding(flat.to_jax_mesh(),
                                        P("fsdp", None)))

    ids7 = rng.integers(0, 128, (n, 16)).astype(np.int32)
    run_geometry("llama7b_fsdp", make_7b, ids7)

    # geometry 2: the gpt13b-style 3-axis mesh (dp x fsdp x tp) the
    # MULTICHIP dryrun validates
    dp, fsdp, mp = max(n // 4, 1), 2 if n % 2 == 0 else 1, \
        2 if n % 4 == 0 else 1
    mesh = ProcessMesh(np.arange(dp * fsdp * mp).reshape(dp, fsdp, mp),
                       dim_names=["dp", "fsdp", "mp"])

    def make_3axis():
        cfg = LlamaConfig.tiny(
            num_hidden_layers=2, hidden_size=16 * mp * fsdp,
            intermediate_size=32 * mp * fsdp,
            num_attention_heads=2 * mp, num_key_value_heads=mp,
            vocab_size=64 * mp, use_flash_attention=False)
        m = LlamaForCausalLM(cfg)
        shard_llama(m, mesh, tp_axis="mp", fsdp_axis="fsdp")
        opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=m.parameters())
        return DistTrainStep(
            m, lambda lg, lb: crit(lg, lb), opt,
            data_sharding=NamedSharding(mesh.to_jax_mesh(),
                                        P("dp", None)))

    ids3 = rng.integers(0, 64 * mp, (2 * dp, 16)).astype(np.int32)
    run_geometry("gpt13b_style_3axis", make_3axis, ids3)
    return out


def bench_dist_overlap_dryrun():
    """dist_overlap_dryrun: structural line for the captured
    distributed step's bucketed compute–collective overlap on the two
    MULTICHIP-validated geometries (llama7b fsdp; gpt13b-style
    dp x fsdp x tp), run in a subprocess with 8 virtual CPU devices
    (the tier-1 mesh harness — overlap WALL-TIME wins need real ICI;
    this line pins the program SHAPE: >= 2 buckets per step, their
    payload bytes, the HLO collective sites, and captured-vs-epilogue
    compile+step cost). Bar: both geometries carry >= 2 buckets."""
    import json as _json
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    xf = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xf:
        env["XLA_FLAGS"] = \
            (xf + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--dist-overlap-worker"],
        env=env, capture_output=True, text=True, timeout=360)
    if r.returncode != 0:
        raise RuntimeError(
            f"overlap worker rc={r.returncode}: {(r.stderr or '')[-400:]}")
    detail = _json.loads(r.stdout.strip().splitlines()[-1])
    b1 = detail["llama7b_fsdp"]["buckets_per_step"]
    b2 = detail["gpt13b_style_3axis"]["buckets_per_step"]
    assert b1 >= 2 and b2 >= 2, (b1, b2)
    detail["bar"] = ">= 2 gradient sync buckets per step on both " \
                    "MULTICHIP geometries; bucketed == epilogue loss " \
                    "(pinned in tests/test_dist_capture.py)"
    _emit("dist_overlap_dryrun", float(min(b1, b2)), "buckets",
          min(b1, b2) / 2.0, detail)


def _hot_start_impl():
    """Worker body for hot_start_time_to_first_step: ONE process boot
    — build a hapi model + captured train steps and a paged decode
    engine, optionally pre-warmed from HS_BUNDLE — timing from before
    model construction to the first captured-step loss fetch + first
    decode tokens. HS_EXPORT additionally exports the warm bundle and
    seals it (prewarm in-process so the AOT-lowered flavors persist
    too). Cache dir arrives as FLAGS_executable_cache_dir in the
    subprocess env."""
    import time as _t

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.hapi import Model
    from paddle_tpu.jit import warmup
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import PagedLlamaDecodeEngine

    bundle = os.environ.get("HS_BUNDLE") or None
    rng = np.random.default_rng(0)
    X = rng.normal(size=(8, 8)).astype(np.float32)
    y = rng.integers(0, 4, 8).astype(np.int64)

    t0 = _t.perf_counter()
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 32), nn.Tanh(), nn.Linear(32, 4))
    m = Model(net)
    m.prepare(optimizer=paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=net.parameters()),
        loss=nn.CrossEntropyLoss(), warm_bundle=bundle)
    loss = None
    for _ in range(3):
        loss = m.train_batch([X], [y])
    float(loss[0])                       # the first-step fetch
    t_train = _t.perf_counter() - t0

    paddle.seed(1)
    lm = LlamaForCausalLM(LlamaConfig.tiny(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, use_flash_attention=False))
    eng = PagedLlamaDecodeEngine(lm, max_slots=2, max_seq=64,
                                 block_size=8, prefill_chunk=16)
    if bundle:
        warmup.prewarm(bundle, engine=eng)
    toks = eng.generate([1, 2, 3, 4], max_new_tokens=4)
    total = _t.perf_counter() - t0

    export = os.environ.get("HS_EXPORT")
    if export:
        warmup.export_bundle(export)
        warmup.prewarm(export, captured=m._captured, engine=eng)
    return {"seconds": round(total, 3),
            "train_seconds": round(t_train, 3),
            "cache": warmup.cache_stats(),
            "captured": dict(m._captured.stats, fallbacks=None),
            "toks": [int(t) for t in toks]}


def bench_hot_start():
    """hot_start_time_to_first_step: cold boot vs pre-warmed boot in
    capped subprocesses sharing ONE executable cache dir. The cold
    worker compiles everything, persists it and exports the warm
    bundle; the warm worker pre-warms from the bundle and must reach
    its first captured train step + first decode tokens with 100%
    persistent-cache hits (misses == 0 asserted) at >= 1x the cold
    wall time (asserted) — the restart-without-compile-storm contract
    (ROADMAP item 5)."""
    import json as _json
    import shutil
    import subprocess
    import sys
    import tempfile

    cache = tempfile.mkdtemp(prefix="hot_start_cache_")
    try:
        bundle = os.path.join(cache, "warm_bundle.json")

        def run(extra):
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       FLAGS_executable_cache_dir=cache, **extra)
            env.pop("FLAGS_warmup_bundle", None)
            # the cold/warm pair needs THIS fresh directory: a cache
            # placed from outside would win over the flag and make the
            # cold boot warm
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--hot-start-worker"],
                env=env, capture_output=True, text=True, timeout=390)
            if r.returncode != 0:
                raise RuntimeError(
                    f"hot-start worker rc={r.returncode}: "
                    f"{(r.stderr or '')[-400:]}")
            return _json.loads(r.stdout.strip().splitlines()[-1])

        cold = run({"HS_EXPORT": bundle})
        warm = run({"HS_BUNDLE": bundle})
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    assert warm["cache"]["misses"] == 0, warm["cache"]
    assert warm["cache"]["hits"] > 0, warm["cache"]
    assert warm["toks"] == cold["toks"], (warm, cold)
    speedup = cold["seconds"] / max(warm["seconds"], 1e-9)
    assert speedup >= 1.0, (cold["seconds"], warm["seconds"])
    _emit("hot_start_time_to_first_step", warm["seconds"], "s",
          speedup, {
              "cold_boot_s": cold["seconds"],
              "warm_boot_s": warm["seconds"],
              "cold_train_s": cold["train_seconds"],
              "warm_train_s": warm["train_seconds"],
              "speedup": round(speedup, 2),
              "cold_compiles": cold["cache"]["writes"],
              "warm_cache": warm["cache"],
              "warm_first_batch_captured":
                  warm["captured"]["eager_steps"] == 0,
              "bar": "warm boot >= 1x cold AND 100% executable-cache "
                     "hits (0 fresh XLA compiles, counters pinned)"},
          device=_CPU_CHILDREN)


def bench_fleet_failover():
    """fleet_failover_recovery_seconds: SIGKILL one of 2 real replica
    processes mid-decode (armed fleet.apply site — the kill lands the
    moment the router applies that replica's first streamed batch) and
    measure (a) kill -> every accepted stream finished (failover
    recovery; the survivors absorb the re-dispatched work) and
    (b) kill -> the replacement replica rejoined AND served tokens,
    A/B: warm resurrection (shared executable cache + warm bundle,
    misses pinned at 0) vs cold (no cache, no bundle: the replacement
    re-compiles before it is useful). vs_baseline = cold time-to-
    serving / warm (the resurrection speedup the warm plane buys)."""
    import shutil
    import signal as _signal
    import tempfile

    from paddle_tpu.serving_fleet import (ReplicaClient, ReplicaHandle,
                                          launch_replica, spawn_fleet)
    from paddle_tpu.utils import fault_injection as fi

    base = {"model": {"kind": "tiny_llama", "seed": 7, "config": dict(
                vocab_size=64, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, use_flash_attention=False)},
            "max_slots": 2, "max_seq": 64, "block_size": 8,
            "prefill_chunk": 8, "supervised": True}
    cache = tempfile.mkdtemp(prefix="fleet_bench_cache_")
    try:
        bundle = os.path.join(cache, "warm.npz")
        # CPU replicas (this process may hold the chip). The warm side
        # needs THIS fresh directory, so a cache placed from outside is
        # cleared (it would win over the flag); the cold side runs with
        # JAX's persistent cache off, so a replacement replica cannot
        # read what its predecessor compiled
        env = {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": "",
               "FLAGS_executable_cache_dir": cache}
        cold_env = {"JAX_PLATFORMS": "cpu",
                    "JAX_ENABLE_COMPILATION_CACHE": "false"}
        # one cold boot seeds the shared cache + seals the bundle
        proc, port, _boot = launch_replica(
            dict(base, prime=[1, 2, 3, 4], export_bundle=bundle),
            env=env)
        ReplicaHandle(0, "127.0.0.1", port, pid=proc.pid,
                      proc=proc).call({"op": "shutdown", "drain": True})
        proc.wait(timeout=120)

        def run(warm):
            cfg = dict(base, warm_bundle=bundle) if warm else dict(base)
            router = spawn_fleet(
                2, cfg, env=(env if warm else cold_env),
                router_kwargs=dict(policy="rr", heartbeat_seconds=0.2,
                                   heartbeat_misses=2,
                                   restart_backoff=0.05,
                                   max_restarts=6))
            try:
                victim = router.replicas[0]
                fi.inject(f"fleet.apply.r{victim.idx}", times=1)
                reqs = [router.submit([i + 1, i + 2, i + 3], 24)
                        for i in range(4)]
                deadline = time.monotonic() + 120
                while victim.proc.poll() is None \
                        and time.monotonic() < deadline:
                    time.sleep(0.002)
                assert victim.proc.poll() is not None, \
                    "armed SIGKILL never fired (streams too short?)"
                t_kill = time.monotonic()
                for r in reqs:
                    assert r["done"].wait(300), "stream stalled"
                    assert r["error"] is None, r["error"]
                recovery = time.monotonic() - t_kill
                while router.stats()["live"] < 2 \
                        and time.monotonic() - t_kill < 300:
                    time.sleep(0.05)
                assert router.stats()["live"] == 2, "no resurrection"
                # "rejoined" means USEFUL: the reborn replica serves
                # tokens (a cold one pays its compiles right here)
                cli = ReplicaClient(victim.host, victim.port,
                                    timeout=300)
                toks = cli.generate([9, 9], 4, timeout=300)
                cli.close()
                assert len(toks) == 4
                tts = time.monotonic() - t_kill
                cache_stats = victim.call(
                    {"op": "cache_stats"})["cache"]
                return recovery, tts, cache_stats, router.stats()
            finally:
                fi.clear()
                router.shutdown(drain=False, timeout=60)

        w_rec, w_tts, w_cache, w_stats = run(warm=True)
        c_rec, c_tts, _c_cache, _ = run(warm=False)
    finally:
        shutil.rmtree(cache, ignore_errors=True)
    assert w_cache["misses"] == 0, w_cache  # warm rejoin: 0 compiles
    speedup = c_tts / max(w_tts, 1e-9)
    assert speedup >= 1.0, (c_tts, w_tts)
    _emit("fleet_failover_recovery_seconds", w_rec, "s", speedup, {
        "warm_recovery_s": round(w_rec, 3),
        "cold_recovery_s": round(c_rec, 3),
        "warm_time_to_serving_s": round(w_tts, 2),
        "cold_time_to_serving_s": round(c_tts, 2),
        "resurrection_speedup": round(speedup, 2),
        "warm_cache": w_cache,
        "failovers": w_stats["failovers"],
        "bar": "every accepted stream survives a replica SIGKILL; "
               "warm resurrection rejoins with 0 fresh XLA compiles "
               "and >= 1x cold time-to-serving"},
          device=_CPU_CHILDREN)


def bench_analysis_selfcheck():
    """analysis_selfcheck: the analysis plane's seeded-bug smoke
    (python -m paddle_tpu.analysis --self-check in-process): one bug
    per analyzer — a lint violation, a host-sync'd fused chain, a
    seeded graph break per PTC rule (the static capture planner), a
    wrong ops.yaml shape spec, a synthetic crash that must leave a
    flight dump with its seeded event, a lock-order inversion — each
    must be detected before anyone trusts a clean report, a capture
    plan or the black box. Bar: all six detector families fire."""
    import time as _t
    from paddle_tpu.analysis.report import self_check
    t0 = _t.perf_counter()
    out = self_check()
    dt = (_t.perf_counter() - t0) * 1e3
    # the PTC detectors are load-bearing for capture planning: require
    # them EXPLICITLY, not just via the aggregate ok
    ptc_fired = bool(out["checks"].get("capture")) and \
        bool(out["checks"].get("shapes"))
    flight_fired = bool(out["checks"].get("flight"))
    ok = out["ok"] and ptc_fired and flight_fired
    _emit("analysis_selfcheck", 1.0 if ok else 0.0, "pass",
          1.0 if ok else 0.0, {
              "checks": {k: ("ok" if v else "FAIL")
                         for k, v in out["checks"].items()},
              "wall_ms": round(dt, 1),
              "detail": out.get("detail", ""),
              "bar": "lint + audit + capture(PTC) + shapes + flight "
                     "+ locks detectors all fire on seeded bugs"})


def bench_checkpoint_roundtrip():
    """checkpoint_roundtrip: durable (sync) vs async save wall time +
    verified restore time for a small model state_dict through
    CheckpointManager (framework/checkpoint.py). The async number is
    the SUBMISSION cost — snapshot-to-host only, serialization/fsync/
    rename on the background thread — which is what a training step
    actually pays (on this bench host the snapshot is a host memcpy, so
    it dominates submission; on TPU the DMA overlaps). Bar: async
    submission <= 2/3 the sync persist."""
    import shutil
    import tempfile

    import paddle_tpu as paddle
    from paddle_tpu.framework.checkpoint import CheckpointManager

    rng = np.random.default_rng(0)
    state = {f"layers.{i}.weight": paddle.to_tensor(
        rng.standard_normal((256, 256)).astype(np.float32))
        for i in range(8)}                      # ~2 MB state_dict
    reps = 5
    roots = [tempfile.mkdtemp(prefix="ckpt_bench_") for _ in range(2)]
    try:
        # best-of per phase: the shared CI hosts are noisy and a mean
        # over a handful of 10-ms saves swings 2x between runs
        m = CheckpointManager(roots[0], keep_n=2)
        m.save(state, step=0)                   # warm (mkdir, caches)
        sync_ms = float("inf")
        for r in range(reps):
            t0 = time.perf_counter()
            m.save(state, step=r + 1)
            sync_ms = min(sync_ms, (time.perf_counter() - t0) * 1e3)

        ma = CheckpointManager(roots[1], keep_n=2, async_save=True)
        ma.save(state, step=0)
        ma.wait()
        submit_ms = float("inf")
        t_all = time.perf_counter()
        for r in range(reps):
            t0 = time.perf_counter()
            ma.save(state, step=r + 1)          # barriers on previous
            submit_ms = min(submit_ms,
                            (time.perf_counter() - t0) * 1e3)
        ma.wait()
        async_total_ms = (time.perf_counter() - t_all) / reps * 1e3

        t0 = time.perf_counter()
        step, restored = m.restore()            # verifies CRC manifest
        restore_ms = (time.perf_counter() - t0) * 1e3
        assert step == reps and len(restored) == len(state)
        nbytes = m.stats()["bytes_written"] // (reps + 1)
    finally:
        for r in roots:
            shutil.rmtree(r, ignore_errors=True)
    speedup = sync_ms / max(submit_ms, 1e-9)
    _emit("checkpoint_roundtrip", sync_ms + restore_ms, "ms",
          speedup / 1.5, {
              "sync_save_ms": round(sync_ms, 2),
              "async_submit_ms": round(submit_ms, 2),
              "async_total_ms": round(async_total_ms, 2),
              "restore_verified_ms": round(restore_ms, 2),
              "async_submit_speedup": round(speedup, 1),
              "checkpoint_bytes": int(nbytes),
              "bar": "async submission <= 2/3 sync persist"})


# The full suite, in emission order. Micro benches first: they need a
# quiet process for µs fidelity (and with per-metric workers, a fresh
# one). Each row: (error-line label, bench fn name).
_SUITE = [
    ("eager_dispatch_overhead_us", "bench_dispatch_overhead"),
    ("metrics_overhead", "bench_metrics_overhead"),
    ("flight_recorder_overhead", "bench_flight_overhead"),
    ("eager_fusion_speedup", "bench_eager_fusion"),
    ("reduction_fusion_speedup", "bench_reduction_fusion"),
    ("fused_optimizer_step_us", "bench_fused_optimizer_step"),
    ("whole_step_capture_speedup", "bench_whole_step_capture"),
    ("amp_captured_step_us", "bench_amp_captured_step"),
    ("dist_overlap_dryrun", "bench_dist_overlap_dryrun"),
    ("hot_start_time_to_first_step", "bench_hot_start"),
    ("fleet_failover_recovery_seconds", "bench_fleet_failover"),
    ("analysis_selfcheck", "bench_analysis_selfcheck"),
    ("bench_llama", "bench_llama"),
    ("bench_llama7b_geometry", "bench_llama7b_geometry"),
    ("bench_resnet50", "bench_resnet50"),
    ("bench_bert_base", "bench_bert_base"),
    ("bench_gpt13b_geometry", "bench_gpt13b_geometry"),
    ("bench_moe_dispatch", "bench_moe_dispatch"),
    ("bench_llama_decode", "bench_llama_decode"),
    ("llama_decode_paged_tokens_per_sec", "bench_llama_decode_paged"),
    ("prefix_sharing_kv", "bench_prefix_sharing_kv"),
    ("llama_decode_speculative_tokens_per_sec",
     "bench_llama_decode_speculative"),
    ("paged_attention_paths", "bench_paged_attention_paths"),
    ("bench_checkpoint_roundtrip", "bench_checkpoint_roundtrip"),
]


def _run_one(fn_name):
    """Worker mode (``--one <fn>``): run a single metric in this
    process. A backend that does not initialise raises before anything
    is printed; a workload that raises prints an error line and the
    process exits 1. Either way the suite parent sees rc != 0."""
    import sys
    label = next((lbl for lbl, fn in _SUITE if fn == fn_name), fn_name)
    _init_device()
    try:
        globals()[fn_name]()
    except Exception as e:  # noqa: BLE001 — name the failure, then fail
        _emit(label, None, "error", 0.0,
              {"error": f"{type(e).__name__}: {e}"[:300]})
        sys.exit(1)


def _run_suite():
    """Suite mode: each metric runs in its OWN capped subprocess, so a
    hung workload costs that metric its cap and the rest still run. The
    parent stays jax-free (a chip belongs to one process at a time). An
    overall budget (PADDLE_TPU_BENCH_BUDGET seconds, 0 disables) skips
    remaining metrics with explicit lines once exhausted. Returns the
    number of metrics that failed, timed out or were skipped; the
    process exits non-zero when it is not 0."""
    import subprocess
    import sys
    _reset_artifact()
    per_cap = float(os.environ.get(
        "PADDLE_TPU_BENCH_METRIC_TIMEOUT", "420"))
    budget = float(os.environ.get("PADDLE_TPU_BENCH_BUDGET", "1740"))
    deadline = (time.time() + budget) if budget > 0 else None
    me = os.path.abspath(__file__)
    failed = 0
    for label, fn_name in _SUITE:
        cap = per_cap
        if deadline is not None:
            remaining = deadline - time.time()
            if remaining <= 10.0:
                failed += 1
                _emit(label, None, "error", 0.0, {
                    "error": "suite budget exhausted; metric skipped",
                    "budget_s": budget})
                continue
            cap = min(cap, remaining)
        try:
            # stdout inherited: the worker's metric lines stream to the
            # driver and append to the shared artifact as they land
            r = subprocess.run([sys.executable, me, "--one", fn_name],
                               timeout=cap)
            if r.returncode != 0:
                failed += 1
                _emit(label, None, "error", 0.0, {
                    "error": f"worker failed rc={r.returncode}"})
        except subprocess.TimeoutExpired:
            failed += 1
            _emit(label, None, "error", 0.0, {
                "error": f"metric exceeded its {cap:.0f}s cap; worker "
                         f"killed, suite continues"})
    return failed


def main(argv=None):
    """Returns the process exit code: non-zero when any workload
    failed."""
    import sys
    argv = sys.argv[1:] if argv is None else argv
    if "--dist-overlap-worker" in argv:
        # bench_dist_overlap_dryrun's subprocess body: the parent set
        # JAX_PLATFORMS=cpu and 8 virtual devices in the environment
        print(json.dumps(_dist_overlap_impl()), flush=True)
        return 0
    if "--hot-start-worker" in argv:
        # bench_hot_start's subprocess body: one boot against the
        # shared executable cache dir (cold exports, warm pre-warms)
        print(json.dumps(_hot_start_impl()), flush=True)
        return 0
    if "--one" in argv:
        _run_one(argv[argv.index("--one") + 1])
        return 0
    if "--headline-only" in argv:
        _init_device()
        bench_llama()
        return 0
    if "--dispatch-only" in argv:
        # quick-iteration smoke path: just the dispatch/fusion/optimizer
        # microbenches, in-process (seconds, not minutes)
        _init_device()
        failed = 0
        for fn in (bench_dispatch_overhead, bench_metrics_overhead,
                   bench_flight_overhead,
                   bench_eager_fusion, bench_reduction_fusion,
                   bench_fused_optimizer_step,
                   bench_whole_step_capture, bench_amp_captured_step,
                   bench_hot_start, bench_analysis_selfcheck):
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — name it, keep going
                failed += 1
                _emit(fn.__name__, None, "error", 0.0,
                      {"error": f"{type(e).__name__}: {e}"[:300]})
        return 1 if failed else 0
    # default (the driver run) = the FULL suite, one JSON line per
    # BASELINE workload, each metric in its own capped subprocess
    return 1 if _run_suite() else 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
