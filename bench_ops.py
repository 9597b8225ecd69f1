"""Per-op/kernel perf regression gate (VERDICT r3 item 8).

The reference runs an op-benchmark CI that times kernels and diffs the
results against the develop branch, failing on regressions
(ref: tools/ci_op_benchmark.sh + tools/check_op_benchmark_result.py).
This is the TPU-native equivalent: time the ~25 hot ops/kernels the e2e
benches ride on, write ``BENCH_OPS_r{N}.json``, and diff against the
most recent previous round's file for the same backend — a >10%
slowdown on any op exits non-zero and names the op, so a Pallas tile
change can't hide inside e2e noise.

Usage:
    python bench_ops.py              # time, write, gate vs previous
    python bench_ops.py --no-gate    # time + write only
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
import time

import numpy as np

REPEATS = 5          # median-of to de-noise a shared host
TOLERANCE = 0.10     # >10% slower than previous round fails


def _round_number() -> int:
    """Current round = 1 + highest BENCH_r*.json the driver recorded."""
    rounds = [int(m.group(1)) for f in glob.glob("BENCH_r*.json")
              for m in [re.match(r"BENCH_r(\d+)\.json$",
                                 os.path.basename(f))] if m]
    return (max(rounds) + 1) if rounds else 1


def _previous_file(backend: str):
    """Latest BENCH_OPS_r*.json from an earlier round, same backend."""
    best = None
    for f in glob.glob("BENCH_OPS_r*.json"):
        m = re.match(r"BENCH_OPS_r(\d+)\.json$", os.path.basename(f))
        if not m or int(m.group(1)) >= _round_number():
            continue
        try:
            data = json.load(open(f))
        except Exception:
            continue
        if data.get("backend") != backend:
            continue
        if best is None or int(m.group(1)) > best[0]:
            best = (int(m.group(1)), data)
    return best


def _sync(out):
    """Device barrier: fetch ONE element of the result. The value
    cannot arrive before the work that produces it has finished (same
    methodology as bench.py)."""
    import jax
    leaf = jax.tree.leaves(out)[0]
    return float(leaf.reshape(-1)[0])


def _time_one(fn, args, n: int):
    import jax.numpy as jnp
    out = fn(*args)
    _sync(out)
    # the closing fetch costs one host round-trip; measure it on a
    # fresh trivial value and subtract (a cached buffer would hit the
    # host-side npy cache and under-report)
    rtt = float("inf")
    for i in range(3):
        t0 = time.perf_counter()
        float(jnp.zeros(()) + i)
        rtt = min(rtt, time.perf_counter() - t0)
    # median of repeats, discarding windows that came out below the
    # measured rtt — a min-of-mins once recorded a physically
    # impossible 0.0 ms for a 256MB reduction and poisoned the gate
    samples = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        _sync(out)
        dt = (time.perf_counter() - t0 - rtt) / n
        if dt > 0:
            samples.append(dt)
    if not samples:
        return 0.0
    samples.sort()
    return samples[len(samples) // 2] * 1e3  # ms


def build_specs(on_tpu: bool):
    """(name, n_iters, make() -> (jitted fn, args)) for each hot op.
    Shapes shrink on CPU so the gate logic itself is testable there."""
    import jax
    import jax.numpy as jnp

    S = 1.0 if on_tpu else 0.0  # scale selector
    rng = np.random.default_rng(0)

    def r(*shape, dtype=jnp.bfloat16):
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32) * 0.05, dtype)

    specs = []

    def add(name, n, make):
        specs.append((name, n if on_tpu else 2, make))

    # -- matmul (the MXU floor everything else is judged against)
    def mk_matmul(train, m):
        a, b = r(m, m), r(m, m)
        if not train:
            return jax.jit(lambda x, y: x @ y), (a, b)

        def step(x, y):
            l, g = jax.value_and_grad(
                lambda yy: ((x @ yy).astype(jnp.float32) ** 2).sum())(y)
            return g
        return jax.jit(step), (a, b)

    m0 = 4096 if on_tpu else 128
    add("matmul_fwd_4k", 30, lambda: mk_matmul(False, m0))
    add("matmul_fwdbwd_4k", 20, lambda: mk_matmul(True, m0))

    # -- flash attention (llama/gpt geometry d=128, bert geometry d=64)
    def mk_flash(train, b, h, s, d, causal=True):
        from paddle_tpu.ops.pallas.flash_attention import flash_attention
        q, k, v = r(b, h, s, d), r(b, h, s, d), r(b, h, s, d)
        if not train:
            return jax.jit(
                lambda q, k, v: flash_attention(q, k, v, causal=causal)
            ), (q, k, v)

        def step(q, k, v):
            def loss(q, k, v):
                o = flash_attention(q, k, v, causal=causal)
                return (o.astype(jnp.float32) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return jax.jit(step), (q, k, v)

    if on_tpu:
        add("flash_fwd_d128_s2048", 80, lambda: mk_flash(
            False, 4, 16, 2048, 128))
        add("flash_fwdbwd_d128_s2048", 10, lambda: mk_flash(
            True, 4, 16, 2048, 128))
        add("flash_fwdbwd_d64_s512_bert", 10, lambda: mk_flash(
            True, 16, 12, 512, 64, causal=False))
    else:
        add("flash_fwd_d128_s2048", 2, lambda: mk_flash(
            False, 1, 2, 128, 64))
        add("flash_fwdbwd_d128_s2048", 2, lambda: mk_flash(
            True, 1, 2, 128, 64))
        add("flash_fwdbwd_d64_s512_bert", 2, lambda: mk_flash(
            True, 1, 2, 128, 64, causal=False))

    # -- segmented (varlen) flash
    def mk_flash_seg():
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_segmented)
        # segmented flash takes [B, L, H, D] + seg [B, L]
        b, s, h, d = (2, 2048, 8, 128) if on_tpu else (1, 128, 2, 64)
        q, k, v = r(b, s, h, d), r(b, s, h, d), r(b, s, h, d)
        seg = jnp.asarray(
            np.repeat(np.arange(4), s // 4)[None, :].repeat(b, 0),
            jnp.int32)
        return jax.jit(lambda q, k, v, seg: flash_attention_segmented(
            q, k, v, seg, causal=True)), (q, k, v, seg)

    add("flash_seg_fwd", 60, mk_flash_seg)

    # -- grouped matmul (MoE expert FFN)
    def mk_gmm(train):
        from paddle_tpu.ops.pallas.grouped_matmul import (
            grouped_matmul, tile_expert_ids)
        e = 16 if on_tpu else 4
        t, k, n = (16384, 1024, 4096) if on_tpu else (256, 32, 64)
        # the tuned configuration (K-tiled kernel, fat token tiles):
        # block_t=512 measured 2x over 128 at this geometry
        block_t = 512 if on_tpu else 64
        lhs = r(t, k)
        rhs = r(e, k, n)
        sizes = jnp.full((e,), t // e, jnp.int32)
        # tile_ids passed explicitly: inside jit group_sizes is a tracer
        # and grouped_matmul would fall back to the dense reference —
        # this spec must time the Pallas kernel, like the MoE layer does
        ids = tile_expert_ids(sizes, block_t, t // block_t)
        if not train:
            return jax.jit(
                lambda l, rh, s, i: grouped_matmul(
                    l, rh, s, block_t=block_t, tile_ids=i)
            ), (lhs, rhs, sizes, ids)

        def step(l, rh, s, i):
            def loss(l, rh):
                o = grouped_matmul(l, rh, s, block_t=block_t, tile_ids=i)
                return (o.astype(jnp.float32) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1))(l, rh)
        return jax.jit(step), (lhs, rhs, sizes, ids)

    add("grouped_matmul_fwd", 20, lambda: mk_gmm(False))
    add("grouped_matmul_fwdbwd", 10, lambda: mk_gmm(True))

    # -- chunked big-vocab cross entropy
    def mk_ce():
        from paddle_tpu.ops.fused_ce import fused_softmax_ce_mean
        # chunked CE takes [B, L, V] + labels [B, L]
        t, v = ((4, 2048), 32000) if on_tpu else ((2, 64), 512)
        logits = r(*t, v, dtype=jnp.float32)
        labels = jnp.asarray(rng.integers(0, v, t), jnp.int32)

        def step(lg, lb):
            def loss(lg):
                return fused_softmax_ce_mean(lg, lb)
            return jax.grad(loss)(lg)
        return jax.jit(step), (logits, labels)

    add("chunked_ce_fwdbwd", 10, mk_ce)

    # -- fused transformer pointwise kernels
    def mk_ln_res_dropout():
        from paddle_tpu.core.tensor import Tensor as _T
        from paddle_tpu.incubate.nn.functional import (
            fused_layernorm_residual_dropout)
        t, h = (8192, 4096) if on_tpu else (128, 64)
        x, res = r(t, h), r(t, h)
        w = jnp.ones((h,), jnp.float32)
        b = jnp.zeros((h,), jnp.float32)

        def f(x, res, w, b):
            out, _ = fused_layernorm_residual_dropout(
                _T(x), _T(res), _T(w), _T(b), p=0.0)
            return out._data
        return jax.jit(f), (x, res, w, b)

    add("fused_ln_residual_dropout", 80, mk_ln_res_dropout)

    def mk_rope():
        from paddle_tpu.incubate.nn.functional import (
            fused_rotary_position_embedding)
        from paddle_tpu.core.tensor import Tensor as _T
        b, s, h, d = (4, 2048, 16, 128) if on_tpu else (1, 64, 2, 16)
        q, k = r(b, s, h, d), r(b, s, h, d)

        def f(q, k):
            oq, ok, _ = fused_rotary_position_embedding(
                _T(q), _T(k), use_neox_rotary_style=True)
            return oq._data, ok._data
        return jax.jit(f), (q, k)

    add("fused_rope", 60, mk_rope)

    def mk_bias_gelu():
        t, h, o = (8192, 4096, 4096) if on_tpu else (64, 32, 32)
        x, w, b = r(t, h), r(h, o), r(o)

        def step(x, w, b):
            def loss(w, b):
                y = jax.nn.gelu((x @ w) + b)
                return (y.astype(jnp.float32) ** 2).sum()
            return jax.grad(loss, argnums=(0, 1))(w, b)
        return jax.jit(step), (x, w, b)

    add("linear_bias_gelu_fwdbwd", 20, mk_bias_gelu)

    # -- conv/bn (ResNet hot block, NHWC)
    def mk_conv_block():
        n, hw, cin, cout = (64, 56, 64, 64) if on_tpu else (2, 8, 4, 4)
        x = r(n, hw, hw, cin)
        w1 = r(3, 3, cin, cout)

        def step(x, w1):
            def loss(w1):
                y = jax.lax.conv_general_dilated(
                    x, w1, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))
                y = jax.nn.relu(y)
                return (y.astype(jnp.float32) ** 2).sum()
            return jax.grad(loss)(w1)
        return jax.jit(step), (x, w1)

    add("conv3x3_relu_fwdbwd", 80, mk_conv_block)

    def mk_batchnorm():
        from paddle_tpu.nn.functional.norm import batch_norm
        from paddle_tpu.core.tensor import Tensor as _T
        n, hw, ch = (64, 56, 64) if on_tpu else (2, 8, 4)
        x = r(n, hw, hw, ch, dtype=jnp.float32)
        rm = jnp.zeros((ch,), jnp.float32)
        rv = jnp.ones((ch,), jnp.float32)
        w = jnp.ones((ch,), jnp.float32)
        b = jnp.zeros((ch,), jnp.float32)

        def f(x, rm, rv, w, b):
            out = batch_norm(_T(x), _T(rm), _T(rv), _T(w), _T(b),
                             training=True, data_format="NHWC")
            return out._data
        return jax.jit(f), (x, rm, rv, w, b)

    add("batch_norm_train_nhwc", 80, mk_batchnorm)

    # -- big-vocab embedding gradient (MXU dgrad path)
    def mk_embedding_grad():
        from paddle_tpu.nn.functional.common import _embedding_lookup
        v, h, t = (32000, 4096, 8192) if on_tpu else (512, 32, 128)
        w = r(v, h)
        idx = jnp.asarray(rng.integers(0, v, (t,)), jnp.int32)

        def step(idx, w):
            def loss(w):
                e = _embedding_lookup(idx, w)
                return (e.astype(jnp.float32) ** 2).sum()
            return jax.grad(loss)(w)
        return jax.jit(step), (idx, w)

    add("embedding_dgrad_32kvocab", 10, mk_embedding_grad)

    # -- cheap-hash dropout (the BERT-step regression of r2)
    def mk_dropout():
        from paddle_tpu.nn.functional.common import dropout
        from paddle_tpu.core.tensor import Tensor as _T
        t, h = (8192, 4096) if on_tpu else (128, 64)
        x = r(t, h)
        key = jax.random.key(0)

        def f(x, key):
            from paddle_tpu.core import random as random_mod
            with random_mod.key_stream(key):
                return dropout(_T(x), p=0.1, training=True)._data
        return jax.jit(f), (x, key)

    add("dropout_cheaphash", 100, mk_dropout)

    # -- reductions / softmax (XLA fusion sanity)
    def mk_softmax():
        b, s = (64, 4096) if on_tpu else (8, 128)
        x = r(b, 16, s, dtype=jnp.float32)
        return jax.jit(lambda x: jax.nn.softmax(x, axis=-1)), (x,)

    add("softmax_fp32", 100, mk_softmax)

    def mk_allreduce_sum():
        n = (64 * 1024 * 1024) if on_tpu else 65536
        x = r(n // 1024, 1024, dtype=jnp.float32)
        return jax.jit(lambda x: x.sum()), (x,)

    add("reduce_sum_64M", 100, mk_allreduce_sum)

    return specs


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    import jax
    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    results = {}
    for name, n, make in build_specs(on_tpu):
        try:
            fn, args = make()
            results[name] = round(_time_one(fn, args, n), 4)
            print(f"  {name}: {results[name]:.3f} ms", flush=True)
        except Exception as e:  # noqa: BLE001 — name it, keep timing,
            results[name] = None  # and fail the run at the end
            print(f"  {name}: ERROR {type(e).__name__}: {e}"[:200],
                  flush=True)
    rnd = _round_number()
    out = {"backend": backend, "round": rnd, "tolerance": TOLERANCE,
           "unit": "ms", "ops": results}
    path = f"BENCH_OPS_r{rnd:02d}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")
    errored = [name for name, ms in results.items() if ms is None]
    if errored:
        print(f"FAIL: {len(errored)} op(s) raised: {', '.join(errored)}")
        return 1

    if "--no-gate" in argv:
        return 0
    prev = _previous_file(backend)
    if prev is None:
        print("no previous round to diff against — gate passes trivially")
        return 0
    prev_round, prev_data = prev
    regressions, improved = [], []
    for name, ms in results.items():
        was = prev_data.get("ops", {}).get(name)
        if ms is None or was is None or was < 0.02:
            continue  # absent or below timer resolution: can't gate
        delta = (ms - was) / was
        if delta > TOLERANCE:
            regressions.append((name, was, ms, delta))
        elif delta < -TOLERANCE:
            improved.append((name, was, ms, delta))
    for name, was, ms, delta in improved:
        print(f"IMPROVED {name}: {was:.3f} -> {ms:.3f} ms "
              f"({delta * 100:+.1f}%)")
    if regressions:
        for name, was, ms, delta in regressions:
            print(f"REGRESSION {name}: {was:.3f} -> {ms:.3f} ms "
                  f"({delta * 100:+.1f}%) vs r{prev_round:02d}")
        print(f"FAIL: {len(regressions)} op(s) regressed more than "
              f"{TOLERANCE * 100:.0f}%")
        return 1
    print(f"gate OK vs r{prev_round:02d} "
          f"({len(results)} ops, tol {TOLERANCE * 100:.0f}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
