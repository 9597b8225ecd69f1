"""Role runner `train_step`: a configuration through `DistTrainStep`, the
program's one captured, donated train step (forward, flash forward and both
backward kernels, fused cross entropy, AdamW), on one chip.

Set-up builds ONE step object with its state, drives it from the seed through
its first steps by the window's own call and feed (both compiling calls happen
there), notes what `correct` compares, and hands that same object to the
window. The window enqueues steps one ahead: the host blocks on the previous
step's loss while the device runs the current one. When the window has closed
and the peak memory is read, the program's state is freed and the reference
(`lib.reference.train_steps`) follows the first two steps from the same seed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import check, flops, reference, traffic, weights
from benchmark.runners import _llama

STEPS_CHECKED = 2      # the reference follows two steps (it keeps one gradient)
STEPS_WARM = 2         # further steps before the window: no compile is left


def _sizes(ctx):
    cfg, mix = dict(ctx.config), dict(ctx.traffic)
    if ctx.rehearsal:
        cfg.update(_llama.TINY, max_position_embeddings=64)
        mix.update(batch=4, seq=32)
    return cfg, mix


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.distributed.dist_train import DistTrainStep
    from paddle_tpu.jit import warmup
    from paddle_tpu.models import LlamaPretrainingCriterion

    cfg, mix = _sizes(ctx)
    hp = dict(cfg["train"])
    dtype_name = cfg["dtype"]
    dtype = jnp.dtype(dtype_name)
    seed = weights.seed_u32(ctx.seed)
    batch, seq = int(mix["batch"]), int(mix["seq"])
    specs = weights.leaf_specs(cfg)
    prog = {n: _llama.program_name(n) for n, _ in specs}

    def feed(k):
        return traffic.train_batch(mix, cfg["vocab_size"], ctx.seed, k)

    # -- set-up: the one object, its first steps, what is compared -----------
    model = _llama.build_model(cfg, seed, dtype_name)
    params = list(model.parameters())
    opt = paddle.optimizer.AdamW(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"], beta2=hp["beta2"],
        epsilon=hp["epsilon"], weight_decay=hp["weight_decay"],
        parameters=params, multi_precision=bool(hp["multi_precision"]))
    crit = LlamaPretrainingCriterion()
    step = DistTrainStep(model, lambda lg, lb: crit(lg, lb), opt)
    ctx.log(f"built {flops.total_params(cfg) / 1e9:.3f} B parameters, "
            f"batch {batch} x {seq}")

    layer_of, ends_of = weights.make_layer(cfg, dtype), weights.make_ends(cfg, dtype)

    @jax.jit
    def diff_norms(now, first):
        return {n: jnp.sqrt(jnp.sum(jnp.square(
            now[n].astype(jnp.float32) - first[n].astype(jnp.float32))))
            for n in now}

    def change_norms(now):
        """Norm of (live leaf - the seed's leaf), the seed's made anew a layer
        at a time by the functions the reference uses. The two are separate
        arrays of the configuration's dtype: regenerated inside one program
        with the subtraction, XLA would skip the rounding to bfloat16."""
        embed, final_norm, head = ends_of(seed)
        out = diff_norms({n: now[n] for n in ("embed", "final_norm", "head")},
                         {"embed": embed, "final_norm": final_norm, "head": head})
        for i in range(cfg["num_hidden_layers"]):
            first = layer_of(seed, jnp.int32(i))
            names = {n: f"layers.{i}.{n}" for n in weights.LAYER_LEAVES}
            got = diff_norms({n: now[names[n]] for n in names}, first)
            out.update({names[n]: v for n, v in got.items()})
        return {n: float(v) for n, v in out.items()}

    @jax.jit
    def moment_norms(m):
        return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                for n, v in m.items()}

    def live_params():
        return {n: step._params[prog[n]]._data for n in prog}

    def call(k):
        ids = feed(k)
        return step(ids, ids)

    faults = []
    got = {"loss": []}
    roundtrip = max(change_norms(live_params()).values())
    if roundtrip != 0.0:
        faults.append(f"the loaded weights are not the seed's: {roundtrip}")
    for k in range(STEPS_CHECKED):
        got["loss"].append(float(call(k)))
        if k == 0:        # Adam's first moment after one step is (1-beta1) g
            m1 = {n: step._tstates()[prog[n]]["moment1"] for n in prog}
            got["grad_norm"] = {n: float(v) / (1.0 - hp["beta1"])
                                for n, v in moment_norms(m1).items()}
            del m1
    got["change_norm"] = change_norms(live_params())
    for k in range(STEPS_CHECKED, STEPS_CHECKED + STEPS_WARM):
        last = float(call(k))
    ctx.sample_memory()
    compiles0 = (step.stats["compiles"], warmup.cache_stats()["misses"])
    ctx.log(f"set-up steps done, losses {got['loss']} .. {last:.4f}; "
            f"compiles {step.stats['compiles']} cache {warmup.cache_stats()}")

    # -- the window ----------------------------------------------------------
    k = STEPS_CHECKED + STEPS_WARM
    spans = []     # per step: call begins, call returns, previous step is done
    prev = None
    if ctx.trace:
        ctx.trace_start()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    n = 0
    while True:
        with ctx.annotate("bench.make_batch"):
            ids = feed(k)
        a = time.perf_counter()
        with ctx.annotate("bench.call_step"):
            loss = step(ids, ids)
        b = time.perf_counter()
        with ctx.annotate("bench.wait_previous"):
            if prev is not None:
                prev._data.block_until_ready()
        c = time.perf_counter()
        spans.append((a, b, c))
        prev, k, n = loss, k + 1, n + 1
        if n % 8 == 0:
            ctx.sample_memory()
        if c - t0 >= ctx.window_seconds:
            break
    prev._data.block_until_ready()
    t1 = time.perf_counter()
    if ctx.trace:
        ctx.trace_stop()
    ctx.sample_memory()
    final_loss = float(prev)
    compiles1 = (step.stats["compiles"], warmup.cache_stats()["misses"])
    paths = _llama.path_counts()
    if not np.isfinite(final_loss):
        faults.append(f"loss of the last step is {final_loss}")
    if step.stats.get("fallbacks"):
        faults.append(f"the step fell back from capture: {step.stats}")
    if not ctx.rehearsal and (paths.get("flash_attention:pallas", 0) == 0
                              or paths.get("flash_attention:xla", 0) > 0):
        faults.append(f"flash attention did not take the Pallas path: {paths}")
    tokens = n * batch * seq
    waits = sorted(c - a for a, _, c in spans)
    ctx.log(f"window: {n} steps in {t1 - t0:.3f} s; last loss {final_loss:.4f}; "
            f"host loop per step median {1e3 * waits[len(waits) // 2]:.1f} ms, "
            f"longest {1e3 * waits[-1]:.1f} ms")

    # -- free the program, then the reference --------------------------------
    del model, params, opt, crit, step, prev, loss
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.train_steps(cfg, seed, [feed(i) for i in range(STEPS_CHECKED)],
                                hp, dtype)
    ctx.log(f"reference: {time.perf_counter() - t_ref:.1f} s, losses {ref['loss']}")
    compared = compare(got, ref)
    readings = {}
    if ctx.control:      # the control and the planted fault, in the program's place
        first = [feed(i) for i in range(STEPS_CHECKED)]
        readings["control"] = compare(reference.train_steps(
            cfg, seed, first, hp, dtype, precision=ctx.control), ref)
        readings["fault_half_batch"] = compare(reference.train_steps(
            cfg, seed, first, hp, dtype, rows_used=slice(0, batch // 2)), ref)
    observed = {"window_s": t1 - t0, "steps": n, "tokens": tokens,
                "spans": spans, "batch": batch, "seq": seq,
                "compiles_in_window": (compiles1[0] - compiles0[0])
                + (compiles1[1] - compiles0[1]),
                "memory_peak_bytes": ctx.memory_peak_bytes, "readings": readings}
    return {"attempted": n, "failed": 0, "faults": faults, "compared": compared,
            "end_to_end": {"setup_s": setup_s,
                           "train_tokens_per_s": tokens / (t1 - t0)},
            "observed": observed,
            "counts": {"steps": n, "tokens": tokens,
                       "compiles_in_window": observed["compiles_in_window"]}}


def compare(got: dict, ref: dict) -> dict:
    """The numbers of a training cell: each step's loss, the first gradient
    and the parameters' change by the worst leaf (gap of norms, against the
    reference's norm of that leaf or of the median leaf)."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    grad_gap, _ = check.worst_leaf_gap(got["grad_norm"], ref["grad_norm"])
    moved = check.leaves_with_gradient(ref["grad_norm"])
    change_gap, _ = check.worst_leaf_gap(got["change_norm"], ref["change_norm"],
                                         keep=moved)
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "param_change_gap": change_gap}
